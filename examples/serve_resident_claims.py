"""Serving with the full ResidentClaim mode family: fail-closed restoration
failure (witness path B), multi-claim attribution (path C), hard protection,
soft priority under pressure, demotion, expiry, and claim-attributed routing.

  PYTHONPATH=src python examples/serve_resident_claims.py
"""
import jax

from repro.configs import get_config, reduced
from repro.core.analyzer import (
    check_failure_outcome_path,
    check_multi_claim_attribution,
)
from repro.core.claims import ClaimMode, ClaimState
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine
from repro.serving.router import KVAwareRouter


def make_engine(bundle, params, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("device_blocks", 64)
    kw.setdefault("cache_len", 64)
    return ServingEngine(bundle, params, **kw)


def main():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    prefix = tuple(range(10, 26))

    # --- path B: controlled same-claim restoration failure -> fail-closed ---
    print("== witness path B: fail-closed restoration failure ==")
    eng = make_engine(bundle, params)
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(prefix + (30, 31), max_new_tokens=1))
    eng.offload_claim(claim.claim_id)
    eng.connector.injection.resident_claim_load_failure = True
    eng.connector.injection.fail_claim_id = claim.claim_id
    r = eng.submit(prefix + (40, 41), max_new_tokens=4)
    eng.run(r)
    v = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id)
    print(f"request: {r.status} (no output served: {r.output_tokens == []})")
    print(f"claim:   {claim.state.value}")
    print(f"gate:    {v.passed} — {v.reasons[0]}")
    e13 = eng.events.named("scheduler_active_request_refused")[0]
    print(f"refusal: blocking_claim_ids={e13.payload['blocking_claim_ids']}\n")

    # --- path C: multi-claim attribution ---
    print("== witness path C: target-only attribution ==")
    eng = make_engine(bundle, params)
    tp, op = tuple(range(100, 116)), tuple(range(200, 216))
    target = eng.accept_claim(tp, ClaimMode.OFFLOADABLE)
    other = eng.accept_claim(op, ClaimMode.OFFLOADABLE)
    for pfx in (tp, op):
        eng.run(eng.submit(pfx + (5, 6), max_new_tokens=1))
    eng.offload_claim(target.claim_id)
    eng.offload_claim(other.claim_id)
    eng.connector.injection.resident_claim_load_failure = True
    eng.connector.injection.fail_claim_id = target.claim_id
    eng.run(eng.submit(op + (7, 8), max_new_tokens=1))
    eng.run(eng.submit(tp + (7, 8), max_new_tokens=1))
    v = check_multi_claim_attribution(eng.events, target.claim_id, other.claim_id)
    print(f"target={target.state.value}, other={other.state.value}, gate={v.passed}\n")

    # --- hard protection: explicit active/resident conflict action ---
    print("== hard_protected: victim exclusion + refusal with blocking ids ==")
    eng = make_engine(bundle, params, device_blocks=8)
    hard = eng.accept_claim(prefix, ClaimMode.HARD_PROTECTED)
    eng.run(eng.submit(prefix, max_new_tokens=1))
    big = eng.submit(tuple(range(500, 532)), max_new_tokens=4)
    eng.run(big)
    refusal = eng.events.named("scheduler_admission_refused")[0]
    print(f"big request: {big.status}; blocking={refusal.payload['blocking_claim_ids']}; "
          f"protected claim intact: {hard.state == ClaimState.MATERIALIZED}\n")

    # --- soft priority under controlled pressure ---
    print("== soft_priority: eviction order follows priority ==")
    eng = make_engine(bundle, params)
    hi = eng.accept_claim(tuple(range(600, 616)), ClaimMode.SOFT_PRIORITY, priority=5)
    lo = eng.accept_claim(tuple(range(700, 716)), ClaimMode.SOFT_PRIORITY, priority=1)
    for pfx in (tuple(range(600, 616)), tuple(range(700, 716))):
        eng.run(eng.submit(pfx, max_new_tokens=1))
    eng.scheduler.apply_pressure(2)
    first = [e.claim_id for e in eng.events.named("pressure_eviction")[:2]]
    print(f"first losses: {first} (low-priority claim: {lo.claim_id})\n")

    # --- routing with claim attribution ---
    print("== routed_reuse: claim-attributed KV-aware routing ==")
    engines = [make_engine(bundle, params, namespace=f"w{i}") for i in range(2)]
    router = KVAwareRouter(engines)
    rc = router.accept_claim(prefix)
    req1, rec1 = router.submit_and_run(prefix + (30, 31))
    req2, rec2 = router.submit_and_run(prefix + (40, 41))
    reuse = router.events.named("route_reuse_attributed")[-1]
    print(f"claim {rc.claim_id}: placed on worker {rec1.worker}; "
          f"reuse routed to worker {rec2.worker} with hit={reuse.payload['reuse_hit_tokens']} tokens")


if __name__ == "__main__":
    enable_compile_cache()
    main()
