"""Radix prefix-reuse bench: effective capacity under a prefix-heavy
multi-turn trace.

The workload is the one prefix caching is built for (the serving pattern
arXiv:2506.02634 measures): S chat sessions share one system prompt, and
each session runs T turns where turn t's prompt is the FULL turn t-1
sequence (prompt + generated tokens) plus new user tokens.  With the
pool-wide radix index (serving/kv_cache.py) every turn reuses the system
prompt, the session's earlier turns, AND the readmitted decode tails; with
``prefix_sharing=False`` (request-salted chains — the pre-radix behaviour)
every request folds a private copy of its whole sequence.

Effective capacity is measured as the number of requests served BEFORE the
pool first has to evict under pressure (first ``pressure_eviction`` event):
up to that point every resident sequence is still reusable, so the count is
"how much serving one device-KV budget carries".  The same fixed trace runs
on both engines, sequentially (one ``run`` per turn — identical launch
shapes, so logits are comparable bitwise).

Gates (any failure exits non-zero):

  - ``capacity_ratio``: requests served before first eviction with sharing
    >= 1.5x the sharing-disabled baseline on the same trace and pool;
  - byte-identity: a warm turn-2 prefill over reused pages returns logits
    ``np.array_equal`` to a cold engine prefilling the concatenated prompt
    from scratch — sharing must be a pure capacity optimisation;
  - zero analyzer violations on BOTH capacity engines:
    ``validate_event_sequence``, ``check_step_interleave_order``,
    ``check_metrics_reconcile`` (including the prefix_reuse/page_cow
    counter witnesses), and ``check_shared_page_immutability`` (a shared
    page is never mutated in place while refcount > 1);
  - every trace request finishes (eviction reclaims reusable pages, it
    must never fail live work);
  - the shared engine actually witnesses reuse (``prefix_reuse_hits_total``
    > 0) and the baseline witnesses none.

Results merge into ``results/BENCH_serving.json`` under ``"radix_reuse"``.

  PYTHONPATH=src python benchmarks/bench_radix.py [--fast]
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.core.analyzer import (
    _counter_series,
    check_metrics_reconcile,
    check_shared_page_immutability,
    check_step_interleave_order,
    validate_event_sequence,
)
from repro.core.native_descriptor import default_engine_factory
from repro.launch.compile_cache import enable_compile_cache

CAPACITY_RATIO_MIN = 1.5
ENGINE_KW = dict(device_blocks=48, cache_len=64)
SYSTEM_PROMPT = tuple(range(100, 124))  # 24 tokens = 6 blocks, shared by all
TURN_USER_TOKENS = 8  # block-aligned user turns
TURN_NEW_TOKENS = 4  # decode budget per turn (folds as one full block)


def _fail(msg: str) -> None:
    print(f"RADIX GATE FAILED: {msg}")
    sys.exit(1)


def _session_trace(n_sessions: int, n_turns: int):
    """Per-session turn prompts; turn t is built from the SERVED turn t-1
    sequence at run time, so here we only pre-draw the user tokens."""
    return [
        [
            tuple(range(1000 + 100 * (s * n_turns + t), 1000 + 100 * (s * n_turns + t) + TURN_USER_TOKENS))
            for t in range(n_turns)
        ]
        for s in range(n_sessions)
    ]


def _run_trace(eng, trace) -> dict:
    """Serve every session's turns sequentially; return trace stats."""
    n_served = 0
    reuse_tokens = 0
    for session in trace:
        seq = SYSTEM_PROMPT
        for user_toks in session:
            req = eng.submit(seq + user_toks, max_new_tokens=TURN_NEW_TOKENS)
            eng.run(req)
            if req.status != "finished" or len(req.output_tokens) != TURN_NEW_TOKENS:
                _fail(
                    f"trace request did not finish under pressure: "
                    f"{req.status} ({req.error})"
                )
            n_served += 1
            reuse_tokens += req.cached_tokens
            seq = seq + user_toks + tuple(req.output_tokens)
    evictions = eng.events.named("pressure_eviction")
    cut = evictions[0].seq if evictions else float("inf")
    before = [
        e
        for e in eng.events.named("request_finished")
        if e.payload.get("status") == "FINISHED_OK" and e.seq < cut
    ]
    return {
        "requests": n_served,
        "served_before_eviction": len(before),
        "evictions": len(evictions),
        "reused_tokens": reuse_tokens,
        "pool_used": eng.pool.used,
    }


def _check_trace(eng, label: str) -> None:
    for name, verdict in (
        ("sequence", validate_event_sequence(eng.events)),
        ("step_interleave_order", check_step_interleave_order(eng.events)),
        ("metrics_reconcile", check_metrics_reconcile(eng.events, eng.metrics)),
        ("shared_page_immutability", check_shared_page_immutability(eng.events)),
    ):
        if not verdict.passed:
            _fail(f"{label}: {name}: {verdict.reasons}")
    eng.pool.assert_consistent()


def _counter_total(eng, family: str) -> int:
    return int(sum(_counter_series(eng.metrics.snapshot(), family).values()))


def _byte_identity_probe(make_engine) -> None:
    """Warm turn-2 prefill over reused pages vs a cold engine from scratch."""
    warm = make_engine(**ENGINE_KW)
    t1 = SYSTEM_PROMPT + tuple(range(5000, 5000 + TURN_USER_TOKENS))
    r1 = warm.submit(t1, max_new_tokens=TURN_NEW_TOKENS)
    warm.run(r1)
    t2 = t1 + tuple(r1.output_tokens) + tuple(range(5100, 5100 + TURN_USER_TOKENS))
    lg_warm = warm.prefill_logits(t2)
    if not warm.events.named("prefix_reuse"):
        _fail("byte-identity probe: warm turn-2 admission emitted no prefix_reuse")
    cold = make_engine(**ENGINE_KW)
    lg_cold = cold.prefill_logits(t2)
    if not np.array_equal(lg_warm, lg_cold):
        _fail("warm turn-2 logits over reused pages differ from cold concat serve")
    # the probe requests stay un-decoded -> no terminal events expected
    for eng, label in ((warm, "probe_warm"), (cold, "probe_cold")):
        for name, verdict in (
            ("sequence", validate_event_sequence(eng.events)),
            ("step_interleave_order", check_step_interleave_order(eng.events, require_terminal=False)),
            ("shared_page_immutability", check_shared_page_immutability(eng.events)),
        ):
            if not verdict.passed:
                _fail(f"{label}: {name}: {verdict.reasons}")
        eng.close()


def main() -> None:
    fast = "--fast" in sys.argv[1:]
    n_sessions, n_turns = (6, 2) if fast else (10, 2)
    t_start = time.perf_counter()
    make_engine = default_engine_factory()
    trace = _session_trace(n_sessions, n_turns)

    shared = make_engine(**ENGINE_KW)
    shared_stats = _run_trace(shared, trace)
    _check_trace(shared, "shared")
    reuse_hits = _counter_total(shared, "prefix_reuse_hits_total")
    cow_copies = _counter_total(shared, "cow_copies_total")
    if reuse_hits < 1:
        _fail("shared engine served the multi-turn trace with zero prefix reuse")
    shared.close()

    baseline = make_engine(prefix_sharing=False, **ENGINE_KW)
    base_stats = _run_trace(baseline, trace)
    _check_trace(baseline, "baseline")
    if _counter_total(baseline, "prefix_reuse_hits_total") != 0:
        _fail("sharing-disabled baseline reused a prefix (salting broken)")
    baseline.close()

    _byte_identity_probe(make_engine)

    if base_stats["served_before_eviction"] < 1:
        _fail("baseline served no request before eviction; pool too small for the trace")
    ratio = shared_stats["served_before_eviction"] / base_stats["served_before_eviction"]

    summary = {
        "fast": fast,
        "workload": {
            "sessions": n_sessions,
            "turns_per_session": n_turns,
            "system_prompt_tokens": len(SYSTEM_PROMPT),
            "user_tokens_per_turn": TURN_USER_TOKENS,
            "new_tokens_per_turn": TURN_NEW_TOKENS,
            "engine": ENGINE_KW,
        },
        "shared": shared_stats,
        "baseline": base_stats,
        "prefix_reuse_hits_total": reuse_hits,
        "cow_copies_total": cow_copies,
        "capacity_ratio": round(ratio, 3),
        "gates": {
            "capacity_ratio_min": CAPACITY_RATIO_MIN,
            "byte_identical_warm_vs_cold": True,
            "analyzer_clean": True,
            "all_requests_finished": True,
        },
        "wall_s": round(time.perf_counter() - t_start, 1),
    }

    if ratio < CAPACITY_RATIO_MIN:
        print(json.dumps(summary, indent=1))
        _fail(
            f"effective capacity with sharing {shared_stats['served_before_eviction']} "
            f"is only {ratio:.2f}x baseline {base_stats['served_before_eviction']} "
            f"(< {CAPACITY_RATIO_MIN}x)"
        )

    out_path = Path("results/BENCH_serving.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    merged = json.loads(out_path.read_text()) if out_path.exists() else {}
    merged["radix_reuse"] = summary
    out_path.write_text(json.dumps(merged, indent=1))
    print(json.dumps(summary, indent=1))
    print("RADIX BENCH OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
