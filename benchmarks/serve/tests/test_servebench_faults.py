"""Whole runs of a small cell on the CPU: a sound run is correct, and the
comparison catches each fault a serving cell can have when it is planted
under the timed path -- a decode step that hands back its state unchanged,
and a token altered where it is produced."""
import pytest

import sbtiny


def test_a_sound_run_is_correct_and_reports_its_checks_last():
    out = sbtiny.run_tiny()
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= out["checks"]["logit_gap"]["limit"]


def test_a_traced_run_reads_the_per_layer_metrics():
    out = sbtiny.run_tiny(trace=True)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    for name in ("decode_rows_per_step", "feed_token_share", "prefix_hit_share",
                 "page_upload_share", "restore_share", "decode_step_ms",
                 "device_idle_share", "step_mfu"):
        assert name in m, name
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert m["prefix_hit_share"]["value"] > 50  # documents come from the pool


def _state_unchanged(eng):
    decode = eng._jit_paged_decode

    def step(params, state, toks, poss):
        logits, _new = decode(params, state, toks, poss)
        return logits, state

    eng._jit_paged_decode = step


def _token_altered(eng):
    decode = eng._jit_paged_decode

    def step(params, state, toks, poss):
        logits, new = decode(params, state, toks, poss)
        return -logits, new  # every row's next token becomes its least likely

    eng._jit_paged_decode = step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered], ids=["state_unchanged", "token_altered"])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    out = sbtiny.run_tiny(engine_hook=fault)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]
