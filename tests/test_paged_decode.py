"""Paged zero-copy decode: parity with the dense-assembly path, zero-copy
block residency, batched prefill sharing, context beyond the dense cache
ceiling, and fail-closed ordering under paged restore failure.

The tentpole property: decode attends over pool pages through per-request
block tables — no dense per-request cache assembly — and a restored or
promoted block is consumable at its page slot.  The dense path is kept as
``decode_mode="dense"`` and must agree with the paged path to numerical
tolerance across tiers (bf16 KV, different association order => tolerance,
not bitwise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.analyzer import (
    check_failure_outcome_path,
    check_observation_path,
    check_step_interleave_order,
    validate_event_sequence,
)
from repro.core.claims import ClaimMode, ClaimState
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine
from repro.serving.scheduler_loop import StepLoop
from repro.serving.snapshot_engine import SnapshotEngine

PREFIX = tuple(range(10, 26))  # 16 tokens = 4 blocks of 4


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp, mode="paged", **kw):
    bundle, params = bp
    kw.setdefault("block_size", 4)
    kw.setdefault("device_blocks", 64)
    kw.setdefault("cache_len", 64)
    return ServingEngine(bundle, params, decode_mode=mode, **kw)


def _first_logits(eng, tokens, max_new_tokens=2):
    """Serve the request and return its entry (pre-decode) logits."""
    return eng.prefill_logits(tokens, max_new_tokens=max_new_tokens)


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_paged_matches_dense_after_restore(bp, tier):
    """Same logits (within bf16 tolerance) paged vs dense when the claimed
    prefix is offloaded to {tier} and restored — restored pages are consumed
    in place, never assembled into a dense cache."""
    prompt = PREFIX + (40, 41)
    logits = {}
    for mode in ("dense", "paged"):
        eng = make_engine(bp, mode)
        claim = eng.accept_claim(PREFIX, ClaimMode.OFFLOADABLE)
        r1 = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
        eng.run(r1)
        assert eng.offload_claim(claim.claim_id, tier=tier)
        logits[mode] = _first_logits(eng, prompt)
        assert claim.state == ClaimState.RESTORED
    np.testing.assert_allclose(logits["paged"], logits["dense"], atol=3e-2, rtol=3e-2)
    assert logits["paged"].argmax() == logits["dense"].argmax()


def test_paged_matches_dense_fresh_prefill(bp):
    prompt = tuple(range(300, 314))
    lg_d = _first_logits(make_engine(bp, "dense"), prompt)
    lg_p = _first_logits(make_engine(bp, "paged"), prompt)
    np.testing.assert_allclose(lg_p, lg_d, atol=3e-2, rtol=3e-2)


# ------------------------------------------------------------- zero-copy


def test_blocks_are_page_views_not_copies(bp):
    """Device-resident block payloads ARE views of the pool page store, and
    a restore lands the block back into a page slot (no dense slab)."""
    eng = make_engine(bp)
    claim = eng.accept_claim(PREFIX, ClaimMode.OFFLOADABLE)
    r = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
    eng.run(r)
    blocks = eng.pool.lookup_prefix(PREFIX, eng.block_size)
    assert len(blocks) == 4
    for b in blocks:
        assert b.page_index is not None
        assert np.shares_memory(b.k, eng.pool.k_pages), "payload must live IN the page store"
    # offload: the block leaves the device and owns its bytes
    assert eng.offload_claim(claim.claim_id, tier="disk")
    # restore: payload lands in a page slot again, attendable in place
    r2 = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r2)
    assert r2.restored_tokens == len(PREFIX)
    blocks = eng.pool.lookup_prefix(PREFIX, eng.block_size)
    for b in blocks:
        assert b.page_index is not None
        assert np.shares_memory(b.k, eng.pool.k_pages)


def test_shared_prefix_occupies_pages_once(bp):
    """N batch-mates over one prefix share its pages (the batch×context
    lever): pool usage grows by the suffix blocks only."""
    eng = make_engine(bp, device_blocks=64)
    shared = tuple(range(500, 516))  # 4 blocks
    reqs = [eng.submit(shared + (600 + i,) * 4, max_new_tokens=2) for i in range(6)]
    eng.run_batch(reqs)
    assert all(r.status == "finished" for r in reqs)
    # 4 shared blocks + 6 distinct suffix blocks — NOT 6 x 5 — plus the 6
    # readmitted decode-tail partials (2 generated tokens each)
    assert eng.pool.used == 4 + 6 + 6


# ----------------------------------------------------- beyond-dense context


def test_context_beyond_dense_cache_len(bp):
    """Paged decode serves context longer than the dense cache shape: the
    ceiling moves from cache_len to pool pages."""
    bundle, params = bp
    long_prompt = tuple(range(700, 748))  # 48 tokens > cache_len=32
    ref = ServingEngine(bundle, params, block_size=4, device_blocks=64,
                        cache_len=64, decode_mode="dense")
    r_ref = ref.submit(long_prompt, max_new_tokens=3)
    ref.run(r_ref)

    eng = make_engine(bp, "paged", cache_len=32, device_blocks=64)
    r = eng.submit(long_prompt, max_new_tokens=3)
    eng.run(r)
    assert r.status == "finished"
    assert r.output_tokens == r_ref.output_tokens


# -------------------------------------------------------- batched prefill


def test_batched_prefill_shares_one_launch(bp):
    """Same-bucket prompts run ONE shared prefill launch (padded+masked).
    The default prefill graph is the chunk graph (chunked-by-default), so
    the shared launch is one [B, C] chunk per bucket here."""
    eng = make_engine(bp, device_blocks=256)
    calls = []
    orig = eng._jit_prefill_chunk

    def spy(params, state, toks, pos):
        calls.append(tuple(toks.shape))
        return orig(params, state, toks, pos)

    eng._jit_prefill_chunk = spy
    # lengths 12, 11, 12 -> one bucket of 12 (padded), lengths 18 -> its own
    reqs = [
        eng.submit(tuple(range(100, 112)), max_new_tokens=2),
        eng.submit(tuple(range(200, 211)), max_new_tokens=2),
        eng.submit(tuple(range(300, 312)), max_new_tokens=2),
        eng.submit(tuple(range(400, 418)), max_new_tokens=2),
    ]
    eng.run_batch(reqs)
    assert all(r.status == "finished" for r in reqs)
    assert len(calls) == 2, calls  # one per bucket, not one per request
    assert validate_event_sequence(eng.events).passed


def test_padded_prefill_matches_unpadded(bp):
    """A right-padded, masked row reproduces the unpadded prefill logits."""
    eng1 = make_engine(bp, device_blocks=256)
    eng2 = make_engine(bp, device_blocks=256)
    short, long_ = tuple(range(100, 111)), tuple(range(200, 212))
    lg_solo = _first_logits(eng1, short)
    # same prompt prefilled inside a padded bucket with a longer prompt
    r_s = eng2.submit(short, max_new_tokens=2)
    r_l = eng2.submit(long_, max_new_tokens=2)
    shapes, entry = [], {}
    orig = eng2._jit_prefill_chunk

    def spy(params, state, toks, pos):
        shapes.append(tuple(toks.shape))
        return orig(params, state, toks, pos)

    eng2._jit_prefill_chunk = spy
    StepLoop(
        eng2, [r_s, r_l],
        on_entry=lambda req, lg: entry.setdefault(req.request_id, np.asarray(lg, np.float32)),
    ).run()
    assert r_s.status == r_l.status == "finished"
    assert shapes and all(s[0] == 4 for s in shapes), shapes  # one padded bucket
    lg_bucket = entry[r_s.request_id]  # row of the shared launches
    np.testing.assert_allclose(lg_bucket, lg_solo, atol=3e-2, rtol=3e-2)


def _spy_launches(eng):
    """Record the name of every model launch the engine makes."""
    names = []
    for attr in ("_jit_prefill", "_jit_decode", "_jit_paged_decode", "_jit_prefill_chunk"):
        orig = getattr(eng, attr)

        def spy(*a, _orig=orig, _name=attr):
            names.append(_name)
            return _orig(*a)

        setattr(eng, attr, spy)
    return names


def test_prefill_logits_is_the_step_loop_entry(bp):
    """``prefill_logits`` is the step loop's entry: its argmax is the first
    token ``run`` serves on a fresh engine, cold and after an
    offload/restore, and it launches only the step loop's two programs."""
    prompt = PREFIX + (40, 41, 42)
    cold = make_engine(bp)
    launches = _spy_launches(cold)
    lg_cold = cold.prefill_logits(prompt)
    assert set(launches) == {"_jit_paged_decode", "_jit_prefill_chunk"}, launches
    fresh = make_engine(bp)
    want = fresh.run(fresh.submit(prompt, max_new_tokens=2)).output_tokens[0]
    assert int(lg_cold.argmax()) == want

    eng = make_engine(bp)
    claim = eng.accept_claim(PREFIX, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(PREFIX + (30, 31), max_new_tokens=1))
    assert eng.offload_claim(claim.claim_id, tier="host")
    launches = _spy_launches(eng)
    lg_restored = eng.prefill_logits(prompt)
    assert claim.state == ClaimState.RESTORED
    assert set(launches) == {"_jit_paged_decode"}, launches
    assert int(lg_restored.argmax()) == want
    # a refused request has no entry logits: prefill_logits raises
    assert eng.offload_claim(claim.claim_id, tier="host")
    eng.connector.injection.resident_claim_load_failure = True
    eng.connector.injection.fail_claim_id = claim.claim_id
    with pytest.raises(RuntimeError, match="refused"):
        eng.prefill_logits(prompt)
    assert check_step_interleave_order(eng.events).passed


def test_exact_prefix_hit_still_materializes(bp):
    """Regression: a claim accepted AFTER its prefix became resident must
    still materialize when an exact-prefix request replays through the
    paged tail (the named observation point applies to replays too)."""
    eng = make_engine(bp)
    eng.run(eng.submit(PREFIX, max_new_tokens=1))  # prefix resident, no claim yet
    claim = eng.accept_claim(PREFIX, ClaimMode.BEST_EFFORT)
    eng.run(eng.submit(PREFIX, max_new_tokens=1))  # exact-prefix replay
    assert claim.state == ClaimState.MATERIALIZED
    mats = [e for e in eng.events.named("claim_materialized") if e.claim_id == claim.claim_id]
    assert mats and mats[0].payload["observation_point"] == "prefill_complete"


def test_tiny_pool_continuation_refuses_not_crashes(bp):
    """Regression: with a pool too small to hold a request's prefix AND its
    new blocks, the chain pin makes the allocation fail closed (refusal
    with allocation attribution) instead of evicting pages the request's
    own block table attends."""
    bundle, params = bp
    eng = ServingEngine(bundle, params, block_size=4, device_blocks=2,
                        cache_len=64, decode_mode="paged")
    r1 = eng.submit(tuple(range(100, 108)), max_new_tokens=1)  # fills the pool
    eng.run(r1)
    assert r1.status == "finished"
    r2 = eng.submit(tuple(range(100, 112)), max_new_tokens=1)  # prefix + 1 block
    eng.run(r2)  # must not crash the batch
    assert r2.status == "refused"
    fin = [e for e in eng.events.named("request_finished") if e.request_id == r2.request_id]
    assert fin and fin[0].payload["status"] == "REFUSED_ADMISSION"
    # the surviving resident prefix is intact and unpinned
    blocks = eng.pool.lookup_prefix(tuple(range(100, 108)), 4)
    assert len(blocks) == 2 and all(b.ref == 0 for b in blocks)


# ------------------------------------------- fail-closed under paged decode


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_paged_restore_failure_fail_closed(bp, tier):
    """Same-claim restore failure at the {tier}->device boundary keeps the
    full ordered fail-closed path with paged decode: E11 -> E12 ->
    E13(blocking_claim_ids) -> E14 before terminal handling, no output."""
    eng = make_engine(bp)
    claim = eng.accept_claim(PREFIX, ClaimMode.OFFLOADABLE)
    r1 = eng.submit(PREFIX + (30, 31), max_new_tokens=1)
    eng.run(r1)
    assert eng.offload_claim(claim.claim_id, tier=tier)
    eng.connector.injection.resident_claim_load_failure = True
    eng.connector.injection.fail_claim_id = claim.claim_id

    r2 = eng.submit(PREFIX + (40, 41), max_new_tokens=2)
    eng.run(r2)
    assert r2.status == "refused"
    assert r2.output_tokens == []  # fail closed: pages never reached decode
    assert claim.state == ClaimState.RESTORATION_FAILED
    assert validate_event_sequence(eng.events).passed
    v = check_failure_outcome_path(eng.events, claim.claim_id, r2.request_id, source_tier=tier)
    assert v.passed, v.reasons


def test_paged_batch_failure_isolation(bp):
    """Within one paged batch, a same-claim restore failure refuses only the
    affected request; batch-mates decode over their pages untouched."""
    eng = make_engine(bp, device_blocks=256)
    tp, op = tuple(range(800, 816)), tuple(range(900, 916))
    target = eng.accept_claim(tp, ClaimMode.OFFLOADABLE)
    other = eng.accept_claim(op, ClaimMode.OFFLOADABLE)
    for pfx in (tp, op):
        eng.run(eng.submit(pfx + (5, 6), max_new_tokens=1))
    eng.offload_claim(target.claim_id)
    eng.offload_claim(other.claim_id, tier="disk")
    eng.connector.injection.resident_claim_load_failure = True
    eng.connector.injection.fail_claim_id = target.claim_id

    r_t = eng.submit(tp + (7, 8), max_new_tokens=2)
    r_o = eng.submit(op + (7, 8), max_new_tokens=2)
    eng.run_batch([r_t, r_o])
    assert r_t.status == "refused" and r_t.output_tokens == []
    assert r_o.status == "finished" and r_o.restored_tokens == len(op)
    assert target.state == ClaimState.RESTORATION_FAILED
    assert other.state == ClaimState.RESTORED
    v = check_observation_path(eng.events, other.claim_id, r_o.request_id)
    assert v.passed, v.reasons


# ----------------------------------------------- snapshot batched decode


def test_snapshot_serve_batch(bp):
    """Recurrent-state snapshot serving decodes a whole batch with states
    stacked on the batch axis through the shared greedy loop."""
    cfg = reduced(get_config("xlstm-350m"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    prefix = tuple(range(10, 22))

    eng = SnapshotEngine(bundle, params)
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    eng.materialize_claim(claim.claim_id)
    eng.offload_claim(claim.claim_id)

    prompts = [prefix + (30 + i, 31 + i) for i in range(3)]
    reqs = eng.serve_batch(prompts, max_new_tokens=3)
    assert [r.status for r in reqs] == ["finished"] * 3
    # the claim restored once, then every batch-mate reused it device-side
    assert reqs[0].restored_tokens == len(prefix)
    assert all(r.cached_tokens == len(prefix) for r in reqs)
    assert all(len(r.output_tokens) == 3 for r in reqs)
    assert eng.events.named("batch_scheduled")
    assert validate_event_sequence(eng.events).passed
