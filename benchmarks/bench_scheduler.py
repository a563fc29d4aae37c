"""Mixed-step scheduler bench: decode ITL under concurrent prefill admission.

The unified token-budget scheduler (``repro.serving.scheduler_loop``) claims
zero decode stalls: every live decode row launches on EVERY engine step,
and an in-flight prefill only rides along when its chunk fits the step's
token budget next to the decode rows.  This bench turns that claim into a
latency gate:

  1. **Isolated decode** — ten concurrent streams decode with no admission
     traffic; inter-token latency (ITL) is read off consecutive
     ``step_scheduled`` events (the event is emitted once per engine step,
     after the step's launches complete, so the delta between consecutive
     full-width decode steps IS the wall-clock gap between two tokens of
     every stream).
  2. **Admission burst** — the same ten streams decode while a burst of
     longer prompts (several prefill buckets) is admitted and
     chunk-prefilled mid-stream.  ITL is measured over the steps where a
     prefill chunk was actually in flight (``prefill_tokens > 0`` — the
     contended steps).

Gates (any failure exits non-zero):

  - ``itl_ratio_p99``: decode ITL p99 under concurrent prefill admission
    must be <= 1.5x the isolated decode ITL p99 (best-of-reps on both
    sides, matching the best-of-reps convention of bench_multi_claim —
    one OS hiccup must not fail the gate, and the same treatment on
    numerator and denominator keeps it honest);
  - ``decode_stall_steps_total`` == 0 on every engine — no step with live
    decode rows may ever launch nothing;
  - ``check_step_interleave_order`` green on every engine: the per-request
    event projection under mixed steps is identical to a single-request
    stream (no cross-request reordering);
  - ``check_metrics_reconcile`` + ``validate_event_sequence`` green;
  - every request (decode streams + burst) finishes with its full token
    budget — admission must not starve or truncate anyone.

Also reported (not gated): TTFT percentiles for the burst requests
(``Request.first_token_ts``), step-token occupancy, and the counterfactual
phased-prefill wall time (what the old run-all-prefills-first path would
have inserted in front of the decode streams).

Results merge into ``results/BENCH_serving.json`` under ``"mixed_scheduler"``.

  PYTHONPATH=src python benchmarks/bench_scheduler.py [--fast]
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

from repro.core.analyzer import (
    check_metrics_reconcile,
    check_step_interleave_order,
    validate_event_sequence,
)
from repro.core.native_descriptor import default_engine_factory
from repro.launch.compile_cache import enable_compile_cache

ITL_RATIO_MAX = 1.5
N_STREAMS = 10
STREAM_PLEN = 96
ENGINE_KW = dict(device_blocks=320, cache_len=128, prefill_chunk=8)


def _fail(msg: str) -> None:
    print(f"SCHEDULER GATE FAILED: {msg}")
    sys.exit(1)


def _pct(xs, q):
    """Nearest-rank percentile (same convention as bench_chaos)."""
    s = sorted(xs)
    rank = max(0, min(len(s) - 1, math.ceil(q / 100 * len(s)) - 1))
    return s[rank]


def _pcts_ms(xs) -> dict:
    return {
        **{f"p{q}": round(_pct(xs, q) * 1e3, 4) for q in (50, 95, 99)},
        "count": len(xs),
    }


def _decode_deltas(eng, *, overlap_only: bool) -> list:
    """Wall-clock gaps between consecutive full-width decode steps.

    ``step_scheduled`` is emitted once per step after its launches return,
    with a per-run consecutive ``step`` counter, so the ts delta between
    step k and step k+1 is the inter-token gap of any row that decoded in
    both.  Both endpoints must carry EXACTLY the N_STREAMS decode rows —
    the gate compares like against like (steady-width steps).  Steps where
    a burst row has joined the batch (``n_decode > N_STREAMS``) are batch-
    growth transitions — membership churn every continuous-batching system
    pays, not chunk co-scheduling cost — and are reported separately as
    ``admission_transition_ms`` (see ``_join_deltas``), not gated.  With
    ``overlap_only`` the later endpoint must additionally have carried a
    prefill chunk (``prefill_tokens > 0`` — the contended steps).
    """
    steps = eng.events.named("step_scheduled")
    out = []
    for prev, cur in zip(steps, steps[1:]):
        if cur.payload["step"] != prev.payload["step"] + 1:
            continue
        if prev.payload["n_decode"] != N_STREAMS or cur.payload["n_decode"] != N_STREAMS:
            continue
        if overlap_only and cur.payload["prefill_tokens"] < 1:
            continue
        out.append(cur.ts - prev.ts)
    return out


def _join_deltas(eng) -> list:
    """Step gaps where a burst row sat in the decode batch (batch growth)."""
    steps = eng.events.named("step_scheduled")
    return [
        cur.ts - prev.ts
        for prev, cur in zip(steps, steps[1:])
        if cur.payload["step"] == prev.payload["step"] + 1
        and cur.payload["n_decode"] > N_STREAMS
    ]


def _check_trace(eng, label: str) -> None:
    for name, verdict in (
        ("sequence", validate_event_sequence(eng.events)),
        ("step_interleave_order", check_step_interleave_order(eng.events)),
        ("metrics_reconcile", check_metrics_reconcile(eng.events, eng.metrics)),
    ):
        if not verdict.passed:
            _fail(f"{label}: {name}: {verdict.reasons}")
    if eng.decode_stalls.value() != 0:
        _fail(f"{label}: decode_stall_steps_total = {eng.decode_stalls.value()} (want 0)")


def _submit_streams(eng, *, dec_new: int):
    return [
        eng.submit(
            tuple(range(3000 + 200 * i, 3000 + 200 * i + STREAM_PLEN)),
            max_new_tokens=dec_new,
        )
        for i in range(N_STREAMS)
    ]


def _submit_burst(eng, burst_lens: list):
    return [
        eng.submit(tuple(range(30000 + 500 * i, 30000 + 500 * i + n)), max_new_tokens=1)
        for i, n in enumerate(burst_lens)
    ]


def run_iso_rep(make_engine, *, dec_new: int) -> list:
    eng = make_engine(**ENGINE_KW)
    streams = _submit_streams(eng, dec_new=dec_new)
    eng.run_batch(streams)
    for r in streams:
        if r.status != "finished" or len(r.output_tokens) != dec_new:
            _fail(f"isolated decode stream did not finish: {r.status} {r.error}")
    deltas = _decode_deltas(eng, overlap_only=False)
    _check_trace(eng, "isolated")
    eng.close()
    return deltas


def run_burst_rep(make_engine, *, dec_new: int, burst_lens: list) -> dict:
    eng = make_engine(**ENGINE_KW)
    t0 = time.monotonic()
    streams = _submit_streams(eng, dec_new=dec_new)
    burst = _submit_burst(eng, burst_lens)
    eng.run_batch(streams + burst)
    for r in streams:
        if r.status != "finished" or len(r.output_tokens) != dec_new:
            _fail(f"decode stream starved under admission: {r.status} {r.error}")
    for r, n in zip(burst, burst_lens):
        if r.status != "finished" or len(r.output_tokens) != 1:
            _fail(f"burst prompt (len {n}) did not finish: {r.status} {r.error}")
        if r.first_token_ts is None:
            _fail(f"burst prompt (len {n}) never stamped first_token_ts")
    overlap = _decode_deltas(eng, overlap_only=True)
    all_deltas = _decode_deltas(eng, overlap_only=False)
    joins = _join_deltas(eng)
    if not overlap:
        _fail("no contended steps: prefill never overlapped the decode streams")
    ttfts = [r.first_token_ts - t0 for r in burst]
    prefill_wall = sum(eng.stage_seconds.samples(stage="prefill_chunk"))
    steps = eng.events.named("step_scheduled")
    occupancy = [e.payload["step_tokens"] / e.payload["budget"] for e in steps]
    mixed_steps = sum(
        1 for e in steps if e.payload["n_decode"] >= 1 and e.payload["prefill_tokens"] >= 1
    )
    _check_trace(eng, "burst")
    eng.close()
    return {
        "overlap_deltas": overlap,
        "all_deltas": all_deltas,
        "join_deltas": joins,
        "ttfts": ttfts,
        "phased_prefill_wall_s": prefill_wall,
        "n_steps": len(steps),
        "mixed_steps": mixed_steps,
        "occupancy_max": max(occupancy),
    }


def main() -> None:
    fast = "--fast" in sys.argv
    dec_new = 16 if fast else 32
    burst_lens = [40, 72] if fast else [40, 56, 72, 88]
    reps = 3 if fast else 4

    make_engine = default_engine_factory()
    t_start = time.perf_counter()

    # warmup: compile every launch shape both scenarios will hit (the jitted
    # step functions are cached per model bundle, so fresh measurement
    # engines reuse these executables); the burst run covers the isolated
    # scenario's shapes too — same streams, plus the chunk/transit shapes
    warm = make_engine(**ENGINE_KW)
    warm.run_batch(_submit_streams(warm, dec_new=dec_new) + _submit_burst(warm, burst_lens))
    warm.close()

    iso_reps = [run_iso_rep(make_engine, dec_new=dec_new) for _ in range(reps)]
    burst_reps = [
        run_burst_rep(make_engine, dec_new=dec_new, burst_lens=burst_lens)
        for _ in range(reps)
    ]

    # best-of-reps p99 on both sides: one OS hiccup must not fail the gate,
    # and the same treatment on numerator and denominator keeps it honest
    iso_p99 = min(_pct(d, 99) for d in iso_reps)
    burst_p99 = min(_pct(r["overlap_deltas"], 99) for r in burst_reps)
    ratio = burst_p99 / iso_p99 if iso_p99 > 0 else float("inf")

    iso_pool = [x for d in iso_reps for x in d]
    overlap_pool = [x for r in burst_reps for x in r["overlap_deltas"]]
    all_pool = [x for r in burst_reps for x in r["all_deltas"]]
    join_pool = [x for r in burst_reps for x in r["join_deltas"]]
    ttft_pool = [x for r in burst_reps for x in r["ttfts"]]

    summary = {
        "fast": fast,
        "workload": {
            "decode_streams": {
                "n": N_STREAMS,
                "prompt_len": STREAM_PLEN,
                "max_new_tokens": dec_new,
            },
            "burst_prompt_lens": burst_lens,
            "engine": ENGINE_KW,
            "reps": reps,
        },
        "isolated_itl_ms": _pcts_ms(iso_pool),
        "burst_itl_contended_ms": _pcts_ms(overlap_pool),
        "burst_itl_all_ms": _pcts_ms(all_pool),
        "admission_transition_ms": _pcts_ms(join_pool) if join_pool else None,
        "ttft_ms": _pcts_ms(ttft_pool),
        "itl_p99_best_ms": {
            "isolated": round(iso_p99 * 1e3, 4),
            "contended": round(burst_p99 * 1e3, 4),
        },
        "itl_ratio_p99": round(ratio, 3),
        "mixed_steps_per_rep": [r["mixed_steps"] for r in burst_reps],
        "step_occupancy_max": round(max(r["occupancy_max"] for r in burst_reps), 4),
        "phased_prefill_wall_ms": round(
            max(r["phased_prefill_wall_s"] for r in burst_reps) * 1e3, 2
        ),
        "decode_stall_steps_total": 0,
        "gates": {
            "itl_ratio_p99_max": ITL_RATIO_MAX,
            "zero_decode_stalls": True,
            "step_interleave_order": True,
            "metrics_reconcile": True,
            "all_requests_finished": True,
        },
        "wall_s": round(time.perf_counter() - t_start, 1),
    }

    if ratio > ITL_RATIO_MAX:
        print(json.dumps(summary, indent=1))
        _fail(
            f"decode ITL p99 under admission {burst_p99 * 1e3:.3f}ms is "
            f"{ratio:.2f}x isolated {iso_p99 * 1e3:.3f}ms (> {ITL_RATIO_MAX}x)"
        )

    out_path = Path("results/BENCH_serving.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    merged = json.loads(out_path.read_text()) if out_path.exists() else {}
    merged["mixed_scheduler"] = summary
    out_path.write_text(json.dumps(merged, indent=1))
    print(json.dumps(summary, indent=1))
    print("SCHEDULER BENCH OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
