"""The device mirror of the page pool (``ServingEngine._device_pages``).

After a page write the mirror is brought up to date by copying only the
dirty page slots and scattering them into it; the whole pool is uploaded
only for the first build, a shape change, or a dirty set whose padded
count reaches the capacity.  Whatever the path, the mirror equals the
host pool bitwise on every page that is not free.
"""
import math

import jax
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import get_config, reduced
from repro.core.analyzer import check_metrics_reconcile
from repro.core.claims import ClaimMode
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine

DOC = tuple(range(300, 340))  # 10 blocks of 4: two prefill chunks


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp, device_blocks=96):
    bundle, params = bp
    return ServingEngine(
        bundle, params, block_size=4, device_blocks=device_blocks, cache_len=64, host_blocks=64
    )


def counts(eng):
    return (
        eng.mirror_full_uploads.value(),
        eng.mirror_scatter_syncs.value(),
        eng.mirror_pages_scattered.value(),
    )


def assert_mirror_matches(eng, pages):
    """The mirror equals the host pool bitwise on every page not free."""
    pool = eng.pool
    if pool.k_pages is None:
        return
    used = sorted(set(range(pool.k_pages.shape[2])) - set(pool._free_pages))
    for dev, host in zip(pages, (pool.k_pages, pool.v_pages)):
        assert dev.shape == host.shape
        got = np.asarray(dev)[:, :, used]
        np.testing.assert_array_equal(got.view(np.uint16), host[:, :, used].view(np.uint16))


def write_pages(eng, n, rng, tag):
    """Store ``n`` fresh blocks of random KV (a full pool evicts)."""
    cfg = eng.cfg
    shape = (cfg.num_layers, eng.block_size, cfg.num_kv_heads, cfg.resolved_head_dim)
    for i in range(n):
        k = rng.standard_normal(shape).astype(jax.numpy.bfloat16)
        v = rng.standard_normal(shape).astype(jax.numpy.bfloat16)
        eng.pool.add_block((i,) * eng.block_size, f"{tag}-{i}", k, v, np.arange(eng.block_size))


def test_a_claim_cycle_keeps_the_mirror_exact_and_scatters(bp):
    """Chunked prefill, a claim restore, a feed fold and a retirement
    readmit: after every mirror call the mirror matches the pool, and
    only the first build uploads the whole pool."""
    eng = make_engine(bp)
    calls = {"_device_pages": 0, "_fold_sequence_blocks": 0, "_readmit_decode_tail": 0}

    def spy(attr):
        fn = getattr(eng, attr)

        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            calls[attr] += 1
            if attr == "_device_pages":
                assert_mirror_matches(eng, out)
            return out

        setattr(eng, attr, wrapper)

    claim = eng.accept_claim(DOC, ClaimMode.OFFLOADABLE)
    for attr in calls:
        spy(attr)
    first = [eng.submit(DOC, max_new_tokens=3)]
    eng.run_batch(first)
    assert eng.offload_claim(claim.claim_id)
    second = [
        eng.submit(DOC + (7, 8, 9, 10, 11), max_new_tokens=4),
        eng.submit(tuple(range(500, 530)), max_new_tokens=5),
    ]
    eng.run_batch(second)
    third = [eng.submit(DOC + (7, 8, 9, 10, 11, 12), max_new_tokens=3)]
    eng.run_batch(third)
    for r in first + second + third:
        assert r.status == "finished", (r.request_id, r.error)
    assert second[0].restored_tokens > 0
    assert third[0].cached_tokens > 0
    assert calls["_fold_sequence_blocks"] > 0 and calls["_readmit_decode_tail"] > 0
    full, scatters, pages = counts(eng)
    assert full == 1
    assert scatters > 0 and pages >= scatters
    assert check_metrics_reconcile(eng.events, eng.metrics).passed
    # a counter that moves without its witness event fails reconciliation
    eng.mirror_pages_scattered.inc()
    assert not check_metrics_reconcile(eng.events, eng.metrics).passed


@pytest.mark.parametrize("n, padded", [(1, 1), (3, 4), (5, 8), (94, None)])
def test_dirty_pages_are_padded_to_a_power_of_two(bp, n, padded):
    """n dirty pages travel as the next power of two; a count whose
    padding reaches the capacity (96) falls back to the whole pool."""
    eng = make_engine(bp)
    rng = np.random.default_rng(n)
    write_pages(eng, 1, rng, "first")
    assert_mirror_matches(eng, eng._device_pages())
    write_pages(eng, 1, rng, "second")
    old = eng._device_pages()
    assert_mirror_matches(eng, old)
    assert counts(eng) == (1, 1, 1)
    # a scattered mirror is a buffer of its own (an upload may alias the
    # host pool where the device is the host's memory)
    old_bytes = [np.asarray(a).copy() for a in old]
    eng._take_kv_bytes()
    write_pages(eng, n, rng, "more")
    pages = eng._device_pages()
    assert_mirror_matches(eng, pages)
    pool = eng.pool
    page_bytes = pool.k_pages[:, :, 0].nbytes
    moved = eng._take_kv_bytes()["upload_bytes"]
    if padded is None:
        assert counts(eng) == (2, 1, 1)
        assert moved == 2 * pool.k_pages.nbytes
    else:
        assert counts(eng) == (1, 2, 1 + n)
        assert moved == 2 * padded * page_bytes
    # out of place: whoever holds the old mirror keeps its bytes
    for a, before in zip(old, old_bytes):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16), before.view(np.uint16))
    # nothing written since: the same arrays come back, nothing is copied
    again = eng._device_pages()
    assert again[0] is pages[0] and again[1] is pages[1]
    assert eng._take_kv_bytes()["upload_bytes"] == 0


def test_frees_alone_re_key_the_mirror(bp):
    eng = make_engine(bp)
    write_pages(eng, 4, np.random.default_rng(0), "a")
    pages = eng._device_pages()
    before = counts(eng)
    eng.pool.remove(next(iter(eng.pool.blocks)))
    again = eng._device_pages()
    assert again[0] is pages[0] and again[1] is pages[1]
    assert counts(eng) == before


def test_scatter_programs_stay_one_per_power_of_two(bp, monkeypatch):
    """Varying dirty counts build at most ceil(log2(capacity)) + 1 scatter
    programs (a capacity no other test uses, so the jit cache starts
    without this pool's shapes)."""
    capacity = 40
    eng = make_engine(bp, device_blocks=capacity)
    seen = set()
    scatter = engine_mod._scatter_pages

    def recording(km, vm, idx, kd, vd):
        seen.add((km.shape, idx.shape, kd.shape))
        return scatter(km, vm, idx, kd, vd)

    monkeypatch.setattr(engine_mod, "_scatter_pages", recording)
    rng = np.random.default_rng(7)
    write_pages(eng, capacity, rng, "fill")
    eng._device_pages()
    programs0 = scatter._cache_size()
    for step, n in enumerate([1, 2, 3, 5, 7, 9, 12, 17, 20, 31, 33, 6, 1, 4, 25, 19]):
        write_pages(eng, n, rng, f"s{step}")
        assert_mirror_matches(eng, eng._device_pages())
    bound = math.ceil(math.log2(capacity)) + 1
    assert len(seen) <= bound
    assert scatter._cache_size() - programs0 <= bound
    full, scatters, pages = counts(eng)
    assert full == 2 and scatters == 15  # the first build; 33 pads to 64 >= 40
    assert pages == sum([1, 2, 3, 5, 7, 9, 12, 17, 20, 31, 6, 1, 4, 25, 19])
