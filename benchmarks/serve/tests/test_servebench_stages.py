"""Traced runs of a small cell of each mix read the per-layer metrics of the
program's serving stages (``serve.*`` spans timed as ``stage_latency``
events) and of the KV bytes on each ``step_scheduled`` record."""
import pytest

import sbtiny
from servebench import harness

STAGE_METRICS = {
    "queue_wait_p90_ms": "ms",
    "sched_host_ms": "ms",
    "kv_readback_share": "%",
    "page_store_share": "%",
    "kv_upload_mib_per_token": "MiB",
    "kv_readback_mib_per_token": "MiB",
}


@pytest.mark.parametrize("workload", ["qwen3-1.7b.claim_docs", "qwen3-1.7b.chat_unshared"])
def test_a_traced_run_reads_the_stage_metrics(workload, monkeypatch):
    windows = []
    record = harness.window_record

    def keep(*args, **kwargs):
        windows.append(record(*args, **kwargs))
        return windows[-1]

    monkeypatch.setattr(harness, "window_record", keep)
    out = sbtiny.run_tiny(sbtiny.tiny_cell(workload), trace=True)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    for name, unit in STAGE_METRICS.items():
        assert m[name]["unit"] == unit, name
    for name in ("kv_readback_share", "page_store_share"):
        assert 0 <= m[name]["value"] <= 100, name
    assert m["sched_host_ms"]["value"] >= 0
    assert m["kv_upload_mib_per_token"]["value"] > 0
    assert m["kv_readback_mib_per_token"]["value"] > 0
    # every request's queue wait ends before its first token
    (w,) = windows
    ttft_p90_ms = harness.end_to_end(w, 0.0)["ttft_p90_ms"]
    assert 0 <= m["queue_wait_p90_ms"]["value"] <= ttft_p90_ms


def test_a_program_without_the_stages_reads_nothing():
    """On a program that records no stages, byte counts or queue stamps the
    readers find nothing to read, and say so rather than raise."""
    w = harness.Window(
        seconds=1.0,
        rounds=[harness.RoundRecord(reqs=[object()], t_call=0.0, ev_lo=0, ev_hi=0)],
        steps=[[{"step_tokens": 4, "n_decode": 4, "n_feed": 0, "prefill_tokens": 0}]],
        stages={"decode_step": [0.01]},
        dims=None,
        peaks={},
    )
    for name in STAGE_METRICS:
        assert harness.metric_reader(name)(w) is None, name
