"""Connector repetition semantic gates (paper §8.3, Table 8).

131 isolated runs against the claim-native engine over a real JAX model:
  - 131/131 valid event sequences (analyzer-parseable total order);
  - 30/30 positive observation passes (witness path A);
  - 30/30 same-claim failure-outcome passes (witness path B);
  -  0/41 false-positive control passes (ordinary offload without claim,
    unclaimed failure, wrong-claim failure, fallback recompute, generic
    counters);
  - 30 lifecycle runs (demotable / expiring / hard_protected) counted in
    the sequence-validity total.
The paper ran subprocesses around a patched vLLM; here each run is an
isolated engine instance over the native mechanism (DESIGN.md §2).  Timing
and byte diagnostics (Appendix A analogues) are recorded, not gated.
"""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path
from typing import Dict, List

from repro.core.analyzer import (
    check_failure_outcome_path,
    check_no_claim_outcome,
    check_observation_path,
    validate_event_sequence,
)
from repro.core.claims import ClaimMode
from repro.core.events import EventLog
from repro.core.native_descriptor import PREFIX, default_engine_factory
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.offload import FailureInjectionConfig


def _offload_cycle(make_engine, *, fail=False, claim_mode=ClaimMode.OFFLOADABLE):
    eng = make_engine()
    claim = eng.accept_claim(PREFIX, claim_mode)
    r1 = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
    eng.run(r1)
    eng.offload_claim(claim.claim_id, request_id=r1.request_id)
    if fail:
        eng.connector.injection.resident_claim_load_failure = True
        eng.connector.injection.fail_claim_id = claim.claim_id
    r2 = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r2)
    return eng, claim, r2


def run_gates(out_dir: Path = Path("results/connector_gates")) -> Dict[str, str]:
    make_engine = default_engine_factory()
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    rows: List[Dict] = []
    valid_sequences = 0
    total_runs = 0

    def record(kind: str, eng, passed: bool, wall_s: float, analyzer_ns: float):
        nonlocal valid_sequences, total_runs
        total_runs += 1
        seq_ok = validate_event_sequence(eng.events).passed if hasattr(eng, "events") else True
        valid_sequences += seq_ok
        rows.append(
            {
                "kind": kind,
                "passed": passed,
                "sequence_valid": seq_ok,
                "wall_s": round(wall_s, 6),
                "analyzer_ns": int(analyzer_ns),
                "event_bytes": len(eng.events.to_json()) if hasattr(eng, "events") else 0,
            }
        )

    # --- 30 observation passes (path A) ---
    obs_pass = 0
    for _ in range(30):
        t0 = time.perf_counter()
        eng, claim, r2 = _offload_cycle(make_engine, fail=False)
        t1 = time.perf_counter()
        v = check_observation_path(eng.events, claim.claim_id, r2.request_id)
        t2 = time.perf_counter()
        obs_pass += v.passed
        record("observation", eng, v.passed, t1 - t0, (t2 - t1) * 1e9)

    # --- 30 same-claim failure-outcome passes (path B) ---
    fail_pass = 0
    for _ in range(30):
        t0 = time.perf_counter()
        eng, claim, r2 = _offload_cycle(make_engine, fail=True)
        t1 = time.perf_counter()
        v = check_failure_outcome_path(eng.events, claim.claim_id, r2.request_id)
        t2 = time.perf_counter()
        fail_pass += v.passed
        record("claimed_load_failure", eng, v.passed, t1 - t0, (t2 - t1) * 1e9)

    # --- 41 false-positive controls (must NOT pass the failure gate) ---
    control_pass = 0

    def control(kind, eng, claim_id, req_id):
        nonlocal control_pass
        t1 = time.perf_counter()
        v = check_failure_outcome_path(eng.events, claim_id, req_id)
        t2 = time.perf_counter()
        control_pass += v.passed
        record(kind, eng, v.passed, 0.0, (t2 - t1) * 1e9)

    # 10x ordinary offload without claim
    for _ in range(10):
        eng = make_engine()
        r1 = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
        eng.run(r1)
        blocks = eng.pool.lookup_prefix(PREFIX, eng.block_size)
        job = eng.connector.store(blocks, claim_id=None, request_id=r1.request_id)
        eng.connector.complete_job(job)
        r2 = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
        eng.run(r2)
        assert check_no_claim_outcome(eng.events).passed
        control("ordinary_offload_no_claim", eng, "claim-0000", r2.request_id)

    # 10x unclaimed generic failure (separate flag per the paper)
    for _ in range(10):
        eng = make_engine(injection=FailureInjectionConfig(unclaimed_generic_failure=True))
        r1 = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
        eng.run(r1)
        blocks = eng.pool.lookup_prefix(PREFIX, eng.block_size)
        job = eng.connector.store(blocks, claim_id=None, request_id=r1.request_id)
        eng.connector.complete_job(job)
        r2 = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
        eng.run(r2)
        control("unclaimed_failure", eng, "claim-0000", r2.request_id)

    # 10x wrong-claim failure (gate checked for a different accepted claim)
    for _ in range(10):
        eng, claim, r2 = _offload_cycle(make_engine, fail=True)
        other = eng.accept_claim(tuple(range(900, 916)), ClaimMode.OFFLOADABLE)
        control("wrong_claim_failure", eng, other.claim_id, r2.request_id)

    # 5x fallback-recompute replay (request served output after the failure)
    for _ in range(5):
        eng, claim, r2 = _offload_cycle(make_engine, fail=True)
        rows_ev = [e.to_dict() for e in eng.events.events]
        mutated = [
            r for r in copy.deepcopy(rows_ev)
            if not (r["name"] in ("offload_request_finished_pending_jobs", "request_finished")
                    and r.get("request_id") == r2.request_id)
        ]
        mutated.append({"name": "offload_request_finished_no_pending_jobs", "request_id": r2.request_id})
        mutated.append({"name": "request_finished", "request_id": r2.request_id, "status": "FINISHED_OK"})
        log = EventLog.from_dicts(mutated)
        t1 = time.perf_counter()
        v = check_failure_outcome_path(log, claim.claim_id, r2.request_id)
        t2 = time.perf_counter()
        control_pass += v.passed
        record("fallback_recompute", eng, v.passed, 0.0, (t2 - t1) * 1e9)

    # 6x generic-counter replay (transfer counters without scheduler outcome)
    for _ in range(6):
        eng, claim, r2 = _offload_cycle(make_engine, fail=True)
        rows_ev = [e.to_dict() for e in eng.events.events]
        mutated = [
            r for r in copy.deepcopy(rows_ev)
            if r["name"] not in (
                "scheduler_resident_claim_restoration_failed",
                "scheduler_active_request_refused",
                "offload_worker_load_failed",
            )
        ]
        log = EventLog.from_dicts(mutated)
        t1 = time.perf_counter()
        v = check_failure_outcome_path(log, claim.claim_id, r2.request_id)
        t2 = time.perf_counter()
        control_pass += v.passed
        record("generic_counters", eng, v.passed, 0.0, (t2 - t1) * 1e9)

    # --- 30 lifecycle validity runs (demotable / expiring / hard_protected) ---
    from repro.core.native_descriptor import (
        scenario_demotable,
        scenario_expiring,
        scenario_hard_protected,
    )

    lifecycle_ok = 0
    for scen in (scenario_demotable, scenario_expiring, scenario_hard_protected):
        for _ in range(10):
            t0 = time.perf_counter()
            res = scen(make_engine)
            t1 = time.perf_counter()
            ok = all(bool(v) for v in res["gates"].values())
            lifecycle_ok += ok
            total_runs += 1
            valid_sequences += bool(res["gates"].get("order_valid", True))
            rows.append({"kind": f"lifecycle:{scen.__name__}", "passed": ok,
                         "sequence_valid": True, "wall_s": round(t1 - t0, 6),
                         "analyzer_ns": 0, "event_bytes": 0})

    summary = {
        "total_runs": f"{total_runs}",
        "event_sequence_validity": f"{valid_sequences}/{total_runs}",
        "observation_passes": f"{obs_pass}/30",
        "failure_outcome_passes": f"{fail_pass}/30",
        "false_positive_control_passes": f"{control_pass}/41",
        "lifecycle_passes": f"{lifecycle_ok}/30",
    }
    (out_dir / "aggregate.json").write_text(json.dumps({"summary": summary, "runs": rows}, indent=1))
    return summary


if __name__ == "__main__":
    enable_compile_cache()
    print(json.dumps(run_gates(), indent=1))
