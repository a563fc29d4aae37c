"""Quickstart: accept a ResidentClaim, serve, offload, restore — witness
path A end to end on a real (reduced) model.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs import get_config, reduced
from repro.core.analyzer import check_observation_path, validate_event_sequence
from repro.core.claims import ClaimMode, ClaimState
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine


def main():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    engine = ServingEngine(bundle, params, block_size=4, device_blocks=64, cache_len=64)

    # 1. accept a future-reuse responsibility over a 16-token prefix
    prefix = tuple(range(10, 26))
    claim = engine.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    print(f"accepted {claim.claim_id}: predicate={claim.predicate.name}")

    # 2. first request materializes the claim
    r1 = engine.submit(prefix + (30, 31), max_new_tokens=4)
    engine.run(r1)
    print(f"{r1.request_id}: {r1.status}, output={r1.output_tokens}, claim={claim.state.value}")

    # 3. offload the claimed KV to host
    engine.offload_claim(claim.claim_id, request_id=r1.request_id)
    print(f"offloaded: {claim.state.value}; host blocks={len(engine.host.blocks)}")

    # 4. reuse: restoration is required (and happens) before the prefix serves
    r2 = engine.submit(prefix + (40, 41), max_new_tokens=4)
    engine.run(r2)
    print(f"{r2.request_id}: {r2.status}, restored_tokens={r2.restored_tokens}, claim={claim.state.value}")

    # 5. the analyzer verifies the ordered witness path from the event log
    assert validate_event_sequence(engine.events).passed
    verdict = check_observation_path(engine.events, claim.claim_id, r2.request_id)
    print(f"witness path A: passed={verdict.passed} ({verdict.reasons[0]})")

    print("\nevent log (claim-scoped):")
    for e in engine.events.for_claim(claim.claim_id):
        print(f"  [{e.seq:3d}] {e.name}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
