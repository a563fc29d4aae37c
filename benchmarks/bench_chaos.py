"""Chaos campaign: randomized-but-seeded fault plans against the serving stack.

The conformance claim under test (paper §7, ROADMAP robustness item): EVERY
injected runtime failure surfaces as an ordered, claim-scoped fail-closed
outcome — never a crash, never a cross-claim blast radius, never an
unattributed refusal.  The campaign drives >= 200 injected faults through
a seeded ``FaultPlan`` (scheduled ``FaultSpec``s only, so the expected
outcome of every round is computable in advance) and gates on:

  - zero crashes (every round's engine calls return; faults become events);
  - zero order violations: ``validate_event_sequence`` plus the chaos
    conformance checks (``check_fail_closed_attribution``,
    ``check_retry_bounded``, ``check_step_interleave_order``) pass on
    every engine's full trace — the scheduler's per-request event
    projection must stay identical to a single-request stream even
    under injected faults;
  - zero cross-claim contamination: bystander requests batched with faulted
    victims all finish with full output (byte-level identity is covered by
    tests/test_chaos.py's paired-engine comparison);
  - exact attribution: each engine's ``fail_closed_total()`` equals the
    schedule-derived expected counter dict EXACTLY — transient faults
    recover via bounded retry and must NOT increment any counter;
  - plan exhaustion: every armed spec was consumed (``armed_remaining == 0``).

Phase 2 exercises tier quarantine on a dedicated engine: three consecutive
permanent-fault restore jobs against disk quarantine the tier
(``tier_quarantined`` boundary event); a fourth disk-resident claim is then
refused with trigger ``tier_quarantined`` WITHOUT touching disk (bytes_read
frozen), while a host-resident claim keeps serving.

Telemetry rides the same gate (PR 7): every engine's metrics registry must
reconcile against its event log (``check_metrics_reconcile`` — counter or
histogram drift from the ordered witnesses is a campaign failure), and the
quarantine-phase engine exports the observability artifacts:
``results/chaos_trace.json`` (Perfetto trace-event JSON covering refused
AND successful claims), ``results/chaos_metrics.prom`` /
``results/chaos_metrics.json`` (Prometheus exposition + snapshot).

Summary (counters, refusal rates, retry histogram, p50/p95/p99 stage
latencies) merges into ``results/BENCH_serving.json`` under
``"chaos_campaign"``.

  PYTHONPATH=src python benchmarks/bench_chaos.py [--fast]
"""
from __future__ import annotations

import json
import math
import random
import sys
import time
from pathlib import Path

from repro.core.analyzer import (
    check_fail_closed_attribution,
    check_metrics_reconcile,
    check_retry_bounded,
    check_step_interleave_order,
    validate_event_sequence,
)
from repro.core.claims import ClaimMode
from repro.core.native_descriptor import default_engine_factory
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.chaos import (
    FaultPlan,
    FaultSpec,
    TRIGGER_CAPACITY,
    TRIGGER_CORRUPTION,
    TRIGGER_PERMANENT,
    TRIGGER_QUARANTINE,
    TRIGGER_TRANSIENT,
    TRIGGER_WORKER_DEATH,
)

SEED = 20260808
ROUNDS_PER_ENGINE = 12  # fresh engine per group keeps the device pool comfortable


def _fail(msg: str) -> None:
    print(f"CHAOS GATE FAILED: {msg}")
    sys.exit(1)


def _check_engine_trace(eng, max_attempts: int, violations: list) -> None:
    for name, verdict in (
        ("sequence", validate_event_sequence(eng.events)),
        ("fail_closed_attribution", check_fail_closed_attribution(eng.events)),
        ("retry_bounded", check_retry_bounded(eng.events, max_attempts)),
        ("metrics_reconcile", check_metrics_reconcile(eng.events, eng.metrics)),
        ("step_interleave_order", check_step_interleave_order(eng.events)),
    ):
        if not verdict.passed:
            violations.append(f"{name}: {verdict.reasons}")


def _collect_latencies(eng, acc: dict) -> None:
    """Pool raw stage/transfer latency samples across engines for the
    campaign-wide percentile export."""
    for stage in ("prefill", "prefill_chunk", "decode_step", "restore"):
        xs = eng.stage_seconds.samples(stage=stage)
        if xs:
            acc.setdefault(stage, []).extend(xs)
    xs = eng.connector._m_transfer.samples()
    if xs:
        acc.setdefault("transfer", []).extend(xs)


def _percentiles_ms(acc: dict) -> dict:
    """Nearest-rank p50/p95/p99 in milliseconds per stage."""
    out = {}
    for stage, xs in sorted(acc.items()):
        s = sorted(xs)
        pcts = {}
        for q in (50, 95, 99):
            rank = max(0, min(len(s) - 1, math.ceil(q / 100 * len(s)) - 1))
            pcts[f"p{q}"] = round(s[rank] * 1e3, 4)
        pcts["count"] = len(s)
        out[stage] = pcts
    return out


def _build_rounds(rng: random.Random, fast: bool):
    """Deterministic round schedule.  Each entry: (kind, tier, repeats,
    bystander).  Scheduled specs only — exact expected-outcome accounting."""
    scale = 5 if fast else 1
    mix = (
        [(TRIGGER_TRANSIENT, None)] * (35 // scale)
        + [(TRIGGER_PERMANENT, None)] * (45 // scale)
        + [(TRIGGER_CORRUPTION, None)] * (35 // scale)
        + [(TRIGGER_WORKER_DEATH, None)] * (25 // scale)
        + [(TRIGGER_CAPACITY, None)] * (25 // scale)
    )
    rng.shuffle(mix)
    rounds = []
    for i, (kind, _) in enumerate(mix):
        tier = "disk" if i % 2 else "host"
        repeats = rng.randint(1, 3)  # <= max_attempts - 1: retry always recovers
        bystander = rng.random() < 0.34
        rounds.append((kind, tier, repeats, bystander))
    return rounds


def run_campaign(make_engine, *, fast: bool, latency_acc: dict) -> dict:
    rng = random.Random(SEED)
    rounds = _build_rounds(rng, fast)

    plan = FaultPlan(seed=SEED)
    expected_total: dict = {}
    violations: list = []
    outcomes = {"recovered": 0, "refused": 0, "finished_bystanders": 0}
    retry_histogram: dict = {}
    n_retries = 0
    base = 10_000

    for group_start in range(0, len(rounds), ROUNDS_PER_ENGINE):
        group = rounds[group_start : group_start + ROUNDS_PER_ENGINE]
        # quarantine off in the mix phase: permanent faults against one tier
        # must stay per-claim outcomes, not tip the tier for later rounds
        eng = make_engine(
            fault_plan=plan, quarantine_after=None, device_blocks=256, cache_len=64
        )
        expected: dict = {}
        for kind, tier, repeats, bystander in group:
            base += 2_000
            if kind == TRIGGER_CAPACITY:
                plan.schedule(FaultSpec(TRIGGER_CAPACITY))
                r = eng.submit(tuple(range(base, base + 8)), max_new_tokens=1)
                eng.run(r)
                if r.status != "refused" or TRIGGER_CAPACITY not in (r.error or ""):
                    _fail(f"capacity round not refused with attribution: {r.status} {r.error}")
                expected[TRIGGER_CAPACITY] = expected.get(TRIGGER_CAPACITY, 0) + 1
                outcomes["refused"] += 1
                continue

            prefix = tuple(range(base, base + 16))  # 4 blocks at block_size=4
            claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
            eng.run(eng.submit(prefix + (base + 900,), max_new_tokens=1))
            if kind == TRIGGER_CORRUPTION:
                # corrupt at rest when the bytes land in the tier (post-checksum)
                plan.schedule(
                    FaultSpec(TRIGGER_CORRUPTION, boundary=tier, claim_id=claim.claim_id)
                )
            if not eng.offload_claim(claim.claim_id, tier=tier):
                _fail(f"offload to {tier} failed in {kind} round")
            boundary = f"{tier}_to_device"
            if kind == TRIGGER_TRANSIENT:
                plan.schedule(
                    FaultSpec(
                        TRIGGER_TRANSIENT,
                        boundary=boundary,
                        claim_id=claim.claim_id,
                        repeats=repeats,
                    )
                )
            elif kind == TRIGGER_PERMANENT:
                plan.schedule(
                    FaultSpec(TRIGGER_PERMANENT, boundary=boundary, claim_id=claim.claim_id)
                )
            elif kind == TRIGGER_WORKER_DEATH:
                plan.schedule(
                    FaultSpec(TRIGGER_WORKER_DEATH, boundary=boundary, claim_id=claim.claim_id)
                )

            reuse = eng.submit(prefix + (base + 901, base + 902), max_new_tokens=1)
            if bystander:
                by = eng.submit(tuple(range(base + 500, base + 512)), max_new_tokens=1)
                eng.run_batch([reuse, by])
                if by.status != "finished" or len(by.output_tokens) != 1:
                    _fail(f"bystander contaminated in {kind} round: {by.status}")
                outcomes["finished_bystanders"] += 1
            else:
                eng.run(reuse)

            if kind == TRIGGER_TRANSIENT:
                if reuse.status != "finished":
                    _fail(f"transient round did not recover: {reuse.status} {reuse.error}")
                if reuse.cached_tokens != len(prefix):
                    _fail(f"transient recovery lost restored tokens: {reuse.cached_tokens}")
                outcomes["recovered"] += 1
            else:
                if reuse.status != "refused":
                    _fail(f"{kind} round not refused: {reuse.status}")
                e13 = [
                    e
                    for e in eng.events.named("scheduler_active_request_refused")
                    if e.request_id == reuse.request_id
                ]
                if not e13 or e13[-1].payload.get("blocking_claim_ids") != [claim.claim_id]:
                    _fail(f"{kind} refusal not attributed to the faulted claim")
                expected[kind] = expected.get(kind, 0) + 1
                outcomes["refused"] += 1

        got = eng.fail_closed_total()
        if got != dict(sorted(expected.items())):
            _fail(f"counter mismatch: got {got}, expected {expected}")
        for k, v in expected.items():
            expected_total[k] = expected_total.get(k, 0) + v
        _check_engine_trace(eng, eng.connector.retry_policy.max_attempts, violations)
        _collect_latencies(eng, latency_acc)
        for att, n in eng.connector.retry_histogram.items():
            retry_histogram[att] = retry_histogram.get(att, 0) + n
        n_retries += eng.connector.queue.retries_performed + sum(
            eng.connector.retry_histogram.values()
        )
        eng.close()

    if plan.armed_remaining:
        _fail(f"{plan.armed_remaining} armed specs never consumed")
    if violations:
        _fail(f"order violations: {violations}")
    return {
        "rounds": len(rounds),
        "injected_faults": dict(sorted(plan.stats.injected.items())),
        "injected_total": plan.stats.total,
        "fail_closed_total": dict(sorted(expected_total.items())),
        "outcomes": outcomes,
        "retry_histogram": {str(k): v for k, v in sorted(retry_histogram.items())},
        "refusal_rate": round(outcomes["refused"] / max(1, len(rounds)), 3),
    }


def run_quarantine_phase(make_engine, *, latency_acc: dict, artifacts_dir=None) -> dict:
    """Dedicated engine: repeated permanent restore failures quarantine disk;
    the engine keeps serving host-resident chains and refuses
    offload-dependent admissions with ``tier_quarantined`` attribution.

    This engine's trace has BOTH refused and successful claims, so it is the
    source of the exported observability artifacts (Perfetto trace +
    Prometheus exposition + metrics snapshot) when ``artifacts_dir`` is set."""
    plan = FaultPlan(seed=SEED + 1)
    eng = make_engine(fault_plan=plan, quarantine_after=3, device_blocks=256, cache_len=64)
    base = 900_000
    claims = []
    for i in range(4):  # A, B, C fault; D rides out the quarantine
        prefix = tuple(range(base + 2_000 * i, base + 2_000 * i + 16))
        c = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
        eng.run(eng.submit(prefix + (base + 900 + i,), max_new_tokens=1))
        if not eng.offload_claim(c.claim_id, tier="disk"):
            _fail("quarantine phase: disk offload failed")
        claims.append((c, prefix))
    host_prefix = tuple(range(base + 50_000, base + 50_016))
    host_claim = eng.accept_claim(host_prefix, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(host_prefix + (base + 999,), max_new_tokens=1))
    if not eng.offload_claim(host_claim.claim_id, tier="host"):
        _fail("quarantine phase: host offload failed")

    for c, prefix in claims[:3]:
        plan.schedule(
            FaultSpec(TRIGGER_PERMANENT, boundary="disk_to_device", claim_id=c.claim_id)
        )
        r = eng.submit(prefix + (1, 2), max_new_tokens=1)
        eng.run(r)
        if r.status != "refused":
            _fail(f"quarantine phase: permanent restore not refused ({r.status})")
    q_events = eng.events.named("tier_quarantined")
    if len(q_events) != 1 or q_events[0].payload.get("tier") != "disk":
        _fail(f"disk not quarantined after 3 failing jobs: {q_events}")

    # the 4th disk-resident claim: refused WITHOUT touching the degraded tier
    reads_before = eng.connector.disk.bytes_read
    c4, p4 = claims[3]
    r4 = eng.submit(p4 + (3, 4), max_new_tokens=1)
    eng.run(r4)
    if r4.status != "refused" or f"tier_quarantined:disk" not in (r4.error or ""):
        _fail(f"quarantined restore not refused with attribution: {r4.status} {r4.error}")
    if eng.connector.disk.bytes_read != reads_before:
        _fail("quarantined tier was read during the refused restore")

    # host-resident chains keep serving through the quarantine
    rh = eng.submit(host_prefix + (5, 6), max_new_tokens=1)
    eng.run(rh)
    if rh.status != "finished" or rh.cached_tokens != len(host_prefix):
        _fail(f"host-resident claim stopped serving under disk quarantine: {rh.status}")

    expected = {TRIGGER_PERMANENT: 3, TRIGGER_QUARANTINE: 1}
    got = eng.fail_closed_total()
    if got != dict(sorted(expected.items())):
        _fail(f"quarantine counters mismatch: got {got}, expected {expected}")
    violations: list = []
    _check_engine_trace(eng, eng.connector.retry_policy.max_attempts, violations)
    if violations:
        _fail(f"quarantine phase order violations: {violations}")
    if plan.armed_remaining:
        _fail("quarantine phase: armed specs never consumed")
    _collect_latencies(eng, latency_acc)
    artifacts = {}
    if artifacts_dir is not None:
        artifacts = _export_artifacts(eng, Path(artifacts_dir))
    eng.close()
    return {
        "injected_faults": dict(sorted(plan.stats.injected.items())),
        "fail_closed_total": got,
        "quarantined_tier": "disk",
        "host_served_through_quarantine": True,
        "disk_untouched_after_quarantine": True,
        **({"artifacts": artifacts} if artifacts else {}),
    }


def _export_artifacts(eng, out_dir: Path) -> dict:
    """Write the observability artifacts for one engine and gate on them:
    the Perfetto trace must validate structurally AND cover at least one
    refused and one successful claim-backed request."""
    from repro.serving.tracing import build_spans, validate_perfetto, write_perfetto

    out_dir.mkdir(parents=True, exist_ok=True)
    trace_path = out_dir / "chaos_trace.json"
    trace = write_perfetto(eng.events, trace_path)
    problems = validate_perfetto(trace)
    if problems:
        _fail(f"exported Perfetto trace invalid: {problems}")
    spans = build_spans(eng.events)
    statuses = {s.args.get("status") for s in spans if s.name == "request"}
    n_refusals = sum(1 for s in spans if s.name == "refusal")
    if "FINISHED_OK" not in statuses or n_refusals == 0:
        _fail(
            f"trace must cover >=1 successful and >=1 refused claim "
            f"(statuses={statuses}, refusals={n_refusals})"
        )
    prom_path = out_dir / "chaos_metrics.prom"
    prom_path.write_text(eng.metrics.prometheus_text())
    snap_path = out_dir / "chaos_metrics.json"
    snap_path.write_text(eng.metrics.to_json())
    return {
        "perfetto_trace": str(trace_path),
        "trace_events": len(trace["traceEvents"]),
        "trace_refusal_spans": n_refusals,
        "prometheus": str(prom_path),
        "metrics_snapshot": str(snap_path),
    }


def main() -> None:
    fast = "--fast" in sys.argv
    make_engine = default_engine_factory()
    latency_acc: dict = {}
    t0 = time.perf_counter()
    campaign = run_campaign(make_engine, fast=fast, latency_acc=latency_acc)
    quarantine = run_quarantine_phase(
        make_engine, latency_acc=latency_acc, artifacts_dir="results"
    )
    wall_s = round(time.perf_counter() - t0, 1)

    total_injected = campaign["injected_total"] + sum(
        quarantine["injected_faults"].values()
    )
    min_faults = 40 if fast else 200
    if total_injected < min_faults:
        _fail(f"only {total_injected} faults injected (< {min_faults})")

    summary = {
        "seed": SEED,
        "fast": fast,
        "wall_s": wall_s,
        "total_injected_faults": total_injected,
        "campaign": campaign,
        "quarantine_phase": quarantine,
        "latency_percentiles_ms": _percentiles_ms(latency_acc),
        "gates": {
            "zero_crashes": True,
            "zero_order_violations": True,
            "zero_cross_claim_contamination": True,
            "exact_counter_attribution": True,
            "metrics_reconcile": True,
            "zero_interleave_violations": True,
            "min_injected_faults": min_faults,
        },
    }
    out_path = Path("results/BENCH_serving.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    merged = json.loads(out_path.read_text()) if out_path.exists() else {}
    merged["chaos_campaign"] = summary
    out_path.write_text(json.dumps(merged, indent=1))
    print(json.dumps(summary, indent=1))
    print("CHAOS CAMPAIGN OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
