"""Chunked paged prefill: O(chunk) prefill memory over the page store.

The tentpole property: with ``prefill_chunk=C`` the engine never
materializes the monolithic [L, B, S, KV, Dh] prefill KV buffer — the
prompt runs chunk-by-chunk, each launch attending already-written pool
pages (carried block tables) plus the in-flight chunk (causal), with
completed blocks landing in page slots between launches.  Prompt length is
therefore bounded by pool pages (the claim substrate), not by the prefill
launch — and the fail-closed, claim-scoped lifecycle survives unchanged:
a mid-prefill store failure refuses with allocation attribution, chains
stay pinned across chunks, and claims still materialize at
``prefill_complete``.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.analyzer import validate_event_sequence
from repro.core.claims import ClaimMode, ClaimState
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PoolExhausted


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp, **kw):
    bundle, params = bp
    kw.setdefault("block_size", 4)
    kw.setdefault("device_blocks", 64)
    kw.setdefault("cache_len", 64)
    return ServingEngine(bundle, params, **kw)


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_matches_full_prefill(bp, chunk):
    """Chunked prefill reproduces the full-prompt logits of the dense
    engine's monolithic prefill launch across chunk sizes (chunk attention
    over pages + causal-within-chunk composes to exact causal attention
    over the whole prompt).  This cross-graph comparison is
    tolerance-based — structural equality lives within the chunk graph
    (see the structural-parity tests below)."""
    prompt = tuple(range(300, 340))  # 40 tokens, bs=4 -> 10 blocks
    lg_full = make_engine(bp, decode_mode="dense").prefill_logits(prompt)
    lg_chunk = make_engine(bp, prefill_chunk=chunk).prefill_logits(prompt)
    np.testing.assert_allclose(lg_chunk, lg_full, atol=3e-2, rtol=3e-2)
    assert lg_chunk.argmax() == lg_full.argmax()


def test_chunked_matches_full_prefill_unaligned(bp):
    """A prompt that ends mid-block feeds its trailing partial block
    through the paged tail and still matches the full-prompt launch."""
    prompt = tuple(range(500, 537))  # 37 tokens: 9 full blocks + 1 partial
    lg_full = make_engine(bp, decode_mode="dense").prefill_logits(prompt)
    lg_chunk = make_engine(bp, prefill_chunk=16).prefill_logits(prompt)
    np.testing.assert_allclose(lg_chunk, lg_full, atol=3e-2, rtol=3e-2)
    assert lg_chunk.argmax() == lg_full.argmax()


# -------------------------------------------------- structural parity
# The default prefill graph is the chunk graph for EVERY chunk size
# (including one chunk covering the whole prompt), so parity within it is
# BITWISE — np.array_equal, no tolerance, no argmax-on-margin lottery.
# This is the property that makes chunked-by-default safe.


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunk_size_structural_invariance(bp, chunk):
    """Every chunk size produces bitwise-identical prefill logits to the
    default (chunk=64 > prompt covers the whole prompt in ONE launch —
    the chunked path's own 'full prefill')."""
    prompt = tuple(range(300, 340))  # 40 tokens
    lg_default = make_engine(bp).prefill_logits(prompt)
    lg = make_engine(bp, prefill_chunk=chunk).prefill_logits(prompt)
    assert np.array_equal(lg, lg_default), (
        f"chunk={chunk} diverges bitwise from the default chunk graph"
    )


def test_restored_vs_cold_structural_parity(bp):
    """Restored-vs-cold logits equality is structural: a block-aligned
    prompt served cold and served through offload->restore runs the SAME
    feed executable over bitwise-identical page bytes."""
    prompt = tuple(range(600, 640))  # 40 tokens, block-aligned
    lg_cold = make_engine(bp).prefill_logits(prompt)
    eng = make_engine(bp)
    claim = eng.accept_claim(prompt, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(prompt, max_new_tokens=1))
    assert claim.state == ClaimState.MATERIALIZED
    assert eng.offload_claim(claim.claim_id, tier="disk")
    lg_restored = eng.prefill_logits(prompt)
    assert np.array_equal(lg_cold, lg_restored), (
        "restored continuation diverges bitwise from cold prefill"
    )


# ------------------------------------------- O(chunk) memory / admission


def test_chunk_launch_never_sees_full_prompt(bp):
    """The O(chunk) property, pinned structurally: every prefill launch
    of a long prompt carries exactly chunk_len token positions."""
    eng = make_engine(bp, prefill_chunk=16)
    chunk_shapes = []
    orig_chunk = eng._jit_prefill_chunk

    def spy_chunk(params, state, tokens, pos):
        chunk_shapes.append(tokens.shape)
        return orig_chunk(params, state, tokens, pos)

    eng._jit_prefill_chunk = spy_chunk
    r = eng.submit(tuple(range(100, 148)), max_new_tokens=2)  # 48 tokens
    eng.run(r)
    assert r.status == "finished"
    assert chunk_shapes == [(1, 16)] * 3, chunk_shapes


def test_prompt_beyond_dense_cache_len_admitted_via_pages(bp):
    """A prompt far beyond the dense cache shape is admitted and served:
    the ceiling is pool pages, with peak prefill KV one chunk."""
    bundle, params = bp
    long_prompt = tuple(range(0, 200))  # 200 tokens >> cache_len=32
    eng = ServingEngine(
        bundle, params, block_size=4, device_blocks=64, cache_len=32,
        decode_mode="paged", prefill_chunk=32,
    )
    r = eng.submit(long_prompt, max_new_tokens=3)
    eng.run(r)
    assert r.status == "finished" and len(r.output_tokens) == 3
    # 50 prompt blocks + the readmitted decode-tail partial (3 tokens)
    assert eng.pool.used == len(long_prompt) // 4 + 1
    # chain fully unpinned after the request completes
    blocks = eng.pool.lookup_prefix(long_prompt, 4)
    assert len(blocks) == 50 and all(b.ref == 0 for b in blocks)
    assert validate_event_sequence(eng.events).passed
    # logits parity with the dense engine's full-prompt launch, whose
    # cache is made large enough for the prompt
    lg_full = ServingEngine(
        bundle, params, block_size=4, device_blocks=64, cache_len=256,
        decode_mode="dense",
    ).prefill_logits(long_prompt)
    lg_chunk = ServingEngine(
        bundle, params, block_size=4, device_blocks=64, cache_len=32,
        prefill_chunk=32,
    ).prefill_logits(long_prompt)
    np.testing.assert_allclose(lg_chunk, lg_full, atol=3e-2, rtol=3e-2)
    assert lg_chunk.argmax() == lg_full.argmax()


def test_prefill_chunk_zero_is_rejected(bp):
    """The chunk graph is the only prefill graph: a chunk size of zero or
    below is refused at construction, naming the option."""
    for chunk in (0, -4):
        with pytest.raises(ValueError, match="prefill_chunk"):
            make_engine(bp, prefill_chunk=chunk)


def test_dense_mode_refuses_beyond_cache_shape(bp):
    """Regression for the silent-truncation hazard the chunked path
    escapes: the dense-assembly engine now fails CLOSED on prompts that
    cannot fit its cache shape instead of corrupting KV."""
    bundle, params = bp
    eng = ServingEngine(
        bundle, params, block_size=4, device_blocks=64, cache_len=32,
        decode_mode="dense",
    )
    r = eng.submit(tuple(range(0, 40)), max_new_tokens=2)
    eng.run(r)
    assert r.status == "refused" and "dense_cache_overflow" in r.error
    fin = [e for e in eng.events.named("request_finished") if e.request_id == r.request_id]
    assert fin and fin[0].payload["status"] == "REFUSED_ADMISSION"


# ---------------------------------------------- fail-closed mid-prefill


def test_mid_prefill_store_failure_fails_closed(bp):
    """An injected store failure in a LATER chunk (the first chunk's blocks
    are already page-resident and pinned) yields the ordered claim-scoped
    refusal: allocation attribution, REFUSED_ADMISSION terminal, every pin
    unwound, no output tokens."""
    eng = make_engine(bp, prefill_chunk=16)
    calls = {"n": 0}
    orig = eng.pool.add_block

    def failing_add_block(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 6:  # second chunk (chunk=16 -> 4 blocks per chunk)
            raise PoolExhausted("injected mid-prefill store failure", ["claim-x"])
        return orig(*a, **kw)

    eng.pool.add_block = failing_add_block
    r = eng.submit(tuple(range(900, 940)), max_new_tokens=2)
    eng.run(r)
    assert r.status == "refused" and r.output_tokens == []
    assert calls["n"] >= 6, "failure must land mid-prefill, after chunk 1 stored"
    refusals = [
        e for e in eng.events.named("scheduler_admission_refused")
        if e.request_id == r.request_id
    ]
    assert refusals and refusals[0].payload["stage"] == "allocation"
    assert refusals[0].payload["blocking_claim_ids"] == ["claim-x"]
    fin = [e for e in eng.events.named("request_finished") if e.request_id == r.request_id]
    assert fin and fin[0].payload["status"] == "REFUSED_ADMISSION"
    # the unwound chain leaves nothing pinned; surviving blocks are reusable
    assert all(b.ref == 0 for b in eng.pool.blocks.values())
    assert validate_event_sequence(eng.events).passed


def test_mid_prefill_failure_isolated_within_bucket(bp):
    """A mid-prefill pool exhaustion refuses only the starved bucket-mate;
    the row whose chain was already pinned finishes decode untouched."""
    bundle, params = bp
    # 10 blocks capacity; two 24-token prompts (6 blocks each) in one bucket
    eng = ServingEngine(
        bundle, params, block_size=4, device_blocks=10, cache_len=64,
        prefill_chunk=8,
    )
    r1 = eng.submit(tuple(range(100, 124)), max_new_tokens=2)
    r2 = eng.submit(tuple(range(200, 224)), max_new_tokens=2)
    eng.run_batch([r1, r2])
    statuses = sorted([r1.status, r2.status])
    assert statuses == ["finished", "refused"], statuses
    ok = r1 if r1.status == "finished" else r2
    assert len(ok.output_tokens) == 2
    assert all(b.ref == 0 for b in eng.pool.blocks.values())
    assert validate_event_sequence(eng.events).passed


# ---------------------------------------------------- claims + batching


def test_chunked_prefill_materializes_claim(bp):
    """prefill_complete stays the named observation point: a claim over an
    early prefix (covered entirely by the FIRST chunk) materializes after
    chunked prefill with metadata bound to the chunk-stored blocks."""
    eng = make_engine(bp, prefill_chunk=16)
    prefix = tuple(range(700, 716))  # 16 tokens = first chunk exactly
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    r = eng.submit(prefix + tuple(range(800, 824)), max_new_tokens=1)  # 40 total
    eng.run(r)
    assert r.status == "finished"
    assert claim.state == ClaimState.MATERIALIZED
    mats = [e for e in eng.events.named("claim_materialized") if e.claim_id == claim.claim_id]
    assert mats and mats[0].payload["observation_point"] == "prefill_complete"
    # the claim's blocks carry its id — bound when the first chunk stored them
    blocks = eng.pool.lookup_prefix(prefix, 4)
    assert len(blocks) == 4
    assert all(claim.claim_id in b.claim_ids for b in blocks)


def test_chunked_offload_restore_roundtrip(bp):
    """Chunk-stored pages survive the full claim lifecycle: offload to
    disk, restore-before-reuse, exact-prefix continuation."""
    eng = make_engine(bp, prefill_chunk=16)
    prefix = tuple(range(40, 72))  # 32 tokens, chunked into 2 launches
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(prefix + (30, 31), max_new_tokens=1))
    assert claim.state == ClaimState.MATERIALIZED
    assert eng.offload_claim(claim.claim_id, tier="disk")
    r2 = eng.submit(prefix + (40, 41), max_new_tokens=2)
    eng.run(r2)
    assert r2.status == "finished"
    assert r2.restored_tokens == len(prefix)
    assert claim.state == ClaimState.RESTORED
    assert validate_event_sequence(eng.events).passed


def test_chunked_composes_with_bucket_sharing(bp):
    """Same-bucket prompts share ONE chunk-launch sequence: the whole
    bucket rides each [B, C] launch, not one chunk loop per request."""
    eng = make_engine(bp, prefill_chunk=16, device_blocks=256)
    launches = []
    orig = eng._jit_prefill_chunk

    def spy(params, state, tokens, pos):
        launches.append(tuple(tokens.shape))
        return orig(params, state, tokens, pos)

    eng._jit_prefill_chunk = spy
    # three same-bucket prompts (len 40) + one its own bucket (len 24)
    reqs = [
        eng.submit(tuple(range(100, 140)), max_new_tokens=2),
        eng.submit(tuple(range(200, 240)), max_new_tokens=2),
        eng.submit(tuple(range(300, 340)), max_new_tokens=2),
        eng.submit(tuple(range(400, 424)), max_new_tokens=2),
    ]
    eng.run_batch(reqs)
    assert all(r.status == "finished" for r in reqs)
    # bucket 40 -> pad 48 = 3 chunks of 16; the singleton bucket 24 launches
    # unpadded [1, C] (pad 32 = 2 chunks) -- no wasted rows for a lone prompt
    assert launches == [(4, 16)] * 3 + [(1, 16)] * 2, launches
    # shared-prefix dedup still applies across the bucket
    assert validate_event_sequence(eng.events).passed


def test_chunked_batch_tokens_match_full_path(bp):
    """End-to-end continuous batching emits the same greedy tokens for
    chunk 16, the default, and one chunk covering the whole prompt."""
    bundle, params = bp
    prompts = [tuple(range(100 + i, 140 + i)) for i in range(3)]

    def run_all(**kw):
        eng = ServingEngine(
            bundle, params, block_size=4, device_blocks=128, cache_len=64, **kw
        )
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.run_batch(reqs)
        assert all(r.status == "finished" for r in reqs)
        return [r.output_tokens for r in reqs]

    # 40-token prompts: chunk 64 prefills each in ONE launch
    assert run_all(prefill_chunk=16) == run_all() == run_all(prefill_chunk=64)
