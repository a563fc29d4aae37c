"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py
pure-jnp oracles, plus cross-checks against the model-layer chunked flash
implementation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOLS = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


# ---------------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,Sq,Sk,D,causal,window,softcap",
    [
        (1, 4, 4, 32, 32, 16, True, 0, 0.0),
        (2, 4, 2, 64, 64, 32, True, 0, 0.0),     # GQA
        (1, 2, 1, 48, 48, 16, True, 16, 0.0),    # sliding window
        (1, 2, 2, 32, 32, 16, True, 0, 30.0),    # grok softcap
        (2, 2, 2, 40, 72, 16, False, 0, 0.0),    # non-causal, ragged blocks
        (1, 8, 8, 128, 128, 64, True, 0, 0.0),   # MXU-aligned tile
    ],
)
def test_flash_attention_matches_ref(dtype, B, H, KV, Sq, Sk, D, causal, window, softcap):
    rng = np.random.default_rng(0)
    q = _rand(rng, (B, H, Sq, D), dtype)
    k = _rand(rng, (B, KV, Sk, D), dtype)
    v = _rand(rng, (B, KV, Sk, D), dtype)
    out = ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, block_q=16, block_k=16
    )
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


def test_flash_attention_matches_model_layer():
    """The Pallas kernel, the naive oracle and the model's chunked-scan
    reference must agree on the same inputs."""
    from repro.models.layers import attention_prefill

    rng = np.random.default_rng(1)
    B, H, KV, S, D = 2, 4, 2, 64, 16
    q = _rand(rng, (B, H, S, D), jnp.float32)
    k = _rand(rng, (B, KV, S, D), jnp.float32)
    v = _rand(rng, (B, KV, S, D), jnp.float32)
    out_kernel = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_model = attention_prefill(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        q_positions=pos,
        kv_positions=pos,
        causal=True,
        q_chunk=16,
        kv_chunk=16,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out_kernel), np.asarray(out_model), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------- paged attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,KV,G,D,page,P,N",
    [
        (2, 2, 2, 16, 8, 4, 16),
        (1, 4, 1, 32, 16, 3, 8),
        (3, 1, 8, 64, 8, 5, 32),
    ],
)
def test_paged_attention_matches_ref(dtype, B, KV, G, D, page, P, N):
    rng = np.random.default_rng(2)
    q = _rand(rng, (B, KV, G, D), dtype)
    k_pages = _rand(rng, (KV, N, page, D), dtype)
    v_pages = _rand(rng, (KV, N, page, D), dtype)
    block_tables = jnp.asarray(rng.integers(0, N, (B, P)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, P * page + 1, (B,)), jnp.int32)
    out = ops.paged_attention(q, k_pages, v_pages, block_tables, lengths)
    expect = ref.paged_attention_ref(q, k_pages, v_pages, block_tables, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


def test_paged_attention_matches_dense_decode():
    """Paged decode == the model layer's dense-cache decode on shared data."""
    from repro.models.layers import attention_decode

    rng = np.random.default_rng(3)
    B, KV, G, D, page, P = 2, 2, 2, 16, 8, 4
    S = page * P
    H = KV * G
    # build a dense cache, then page it out
    k_dense = _rand(rng, (B, S, KV, D), jnp.float32)
    v_dense = _rand(rng, (B, S, KV, D), jnp.float32)
    lengths = jnp.asarray([S, S // 2], jnp.int32)
    q = _rand(rng, (B, 1, H, D), jnp.float32)

    # paged layout: page n of sequence b lives at page id b*P + n
    k_pages = k_dense.reshape(B, P, page, KV, D).transpose(3, 0, 1, 2, 4).reshape(KV, B * P, page, D)
    v_pages = v_dense.reshape(B, P, page, KV, D).transpose(3, 0, 1, 2, 4).reshape(KV, B * P, page, D)
    block_tables = jnp.asarray([[b * P + n for n in range(P)] for b in range(B)], jnp.int32)

    out_paged = ops.paged_attention(
        q[:, 0].reshape(B, KV, G, D), k_pages, v_pages, block_tables, lengths
    )
    kv_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_dense = attention_decode(
        q, k_dense, v_dense, kv_positions=kv_positions, cur_pos=lengths - 1
    )
    np.testing.assert_allclose(
        np.asarray(out_paged).reshape(B, H, D),
        np.asarray(out_dense).reshape(B, H, D),
        rtol=1e-5,
        atol=1e-5,
    )


# ------------------------------------------------- paged decode (serving entry)
def _decode_case(*values, id=None):
    """One row of the paged-decode sweep; without ``id`` it is named as
    pytest names a plain row."""
    return pytest.param(*values, id=id or "-".join(str(v) for v in values[:9]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,KV,G,D,page,P,N,T,window,softcap,prefix",
    [
        _decode_case(2, 2, 2, 16, 4, 4, 16, 8, 0, 0.0, None),
        _decode_case(1, 4, 1, 32, 8, 3, 8, 4, 0, 0.0, None),
        _decode_case(3, 1, 4, 16, 4, 5, 32, 8, 12, 0.0, None),  # sliding window
        # MHA as deepseek-7b has it (KV 32, G 1), at a small head dim
        _decode_case(2, 32, 1, 16, 8, 5, 24, 8, 0, 0.0, None, id="mha_kv32_g1"),
        _decode_case(2, 2, 4, 32, 4, 6, 16, 8, 0, 0.0, None, id="gqa_g4"),
        # qwen3 widths: 8 pages a step in bf16, 4 in f32, so P = 13 leaves a
        # short last block; rows end on a page, mid-page, and hold no page
        _decode_case(3, 8, 2, 128, 8, 13, 40, 16, 0, 0.0, (104, 77, 0), id="qwen3_widths_p13"),
        # deepseek widths: 2 pages a step in bf16, 1 in f32
        _decode_case(2, 32, 1, 128, 8, 5, 12, 8, 0, 0.0, (40, 21), id="mha_widths_p5"),
        _decode_case(4, 2, 2, 16, 4, 6, 16, 8, 0, 0.0, (0, 7, 24, 1), id="empty_and_midpage_rows"),
        _decode_case(3, 2, 2, 16, 4, 6, 16, 8, 10, 30.0, (20, 0, 13), id="window_softcap"),
    ],
)
def test_paged_decode_attention_matches_ref(dtype, B, KV, G, D, page, P, N, T, window, softcap, prefix):
    """The batched serving entry point (block-table prefix + in-flight tail)
    against the dense-gather oracle.  Where ``prefix`` gives the rows'
    prefix lengths, the table's padding past them points at page 0."""
    rng = np.random.default_rng(5)
    q = _rand(rng, (B, KV, G, D), dtype)
    k_pages = _rand(rng, (KV, N, page, D), dtype)
    v_pages = _rand(rng, (KV, N, page, D), dtype)
    k_tail = _rand(rng, (B, KV, T, D), dtype)
    v_tail = _rand(rng, (B, KV, T, D), dtype)
    block_tables = np.asarray(rng.integers(0, N, (B, P)), np.int32)
    if prefix is None:
        prefix_len = jnp.asarray(rng.integers(1, P * page + 1, (B,)), jnp.int32)
    else:
        prefix_len = jnp.asarray(prefix, jnp.int32)
        for b, n in enumerate(prefix):
            block_tables[b, -(-n // page):] = 0
    block_tables = jnp.asarray(block_tables)
    t_used = rng.integers(1, T + 1, (B,))
    tail_pos = np.full((B, T), -1, np.int32)
    for b in range(B):
        tail_pos[b, : t_used[b]] = int(prefix_len[b]) + np.arange(t_used[b])
    cur_pos = jnp.asarray(np.asarray(prefix_len) + t_used - 1, jnp.int32)
    args = (q, k_pages, v_pages, block_tables, prefix_len, k_tail, v_tail,
            jnp.asarray(tail_pos), cur_pos)
    out = ops.paged_decode_attention(*args, window=window, softcap=softcap)
    expect = ref.paged_decode_attention_ref(*args, window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


@pytest.mark.parametrize(
    "KV,page,D,itemsize,P,ppb",
    [
        (8, 8, 128, 2, 256, 8),  # qwen3-1.7b: 16 KiB a page
        (32, 8, 128, 2, 256, 2),  # deepseek-7b (MHA): 64 KiB a page
        (8, 8, 128, 4, 256, 4),  # a float32 pool halves the pages
        (8, 8, 128, 2, 5, 5),  # capped by the table width
        (2, 4, 16, 2, 0, 1),  # an empty table
    ],
)
def test_pages_per_block_from_shapes(KV, page, D, itemsize, P, ppb):
    from repro.kernels.paged_attention import pages_per_block

    assert pages_per_block(KV, page, D, itemsize, P) == ppb


def test_paged_decode_attention_reads_stacked_pool_by_layer():
    """A stacked [L, KV, N, page, D] pool read with ``layer`` gives what
    that layer's slice gives."""
    rng = np.random.default_rng(8)
    L, B, KV, G, D, page, P, N, T = 3, 2, 2, 2, 16, 4, 4, 8, 8
    k_pages = _rand(rng, (L, KV, N, page, D), jnp.float32)
    v_pages = _rand(rng, (L, KV, N, page, D), jnp.float32)
    tail_pos = np.full((B, T), -1, np.int32)
    tail_pos[:, :2] = np.asarray([[13, 14], [9, 10]])
    args = (
        jnp.asarray(rng.integers(0, N, (B, P)), jnp.int32), jnp.asarray([13, 9], jnp.int32),
        _rand(rng, (B, KV, T, D), jnp.float32), _rand(rng, (B, KV, T, D), jnp.float32),
        jnp.asarray(tail_pos), jnp.asarray([14, 10], jnp.int32),
    )
    q = _rand(rng, (B, KV, G, D), jnp.float32)
    for layer in range(L):
        stacked = ops.paged_decode_attention(q, k_pages, v_pages, *args, layer=jnp.int32(layer))
        sliced = ops.paged_decode_attention(q, k_pages[layer], v_pages[layer], *args)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))


def test_paged_decode_attention_matches_model_helper():
    """Kernel == the model layer's jnp paged-decode formulation (the CPU
    lowering the engine actually runs)."""
    from repro.models.layers import paged_attention_decode

    rng = np.random.default_rng(6)
    B, KV, G, D, page, P, N, T = 2, 2, 2, 16, 4, 3, 8, 8
    H = KV * G
    q = _rand(rng, (B, KV, G, D), jnp.float32)
    k_pages = _rand(rng, (KV, N, page, D), jnp.float32)
    v_pages = _rand(rng, (KV, N, page, D), jnp.float32)
    k_tail = _rand(rng, (B, KV, T, D), jnp.float32)
    v_tail = _rand(rng, (B, KV, T, D), jnp.float32)
    block_tables = jnp.asarray(rng.integers(0, N, (B, P)), jnp.int32)
    prefix_len = jnp.asarray([P * page, P * page - 2], jnp.int32)
    tail_pos = np.full((B, T), -1, np.int32)
    tail_pos[:, :3] = np.asarray(prefix_len)[:, None] + np.arange(3)
    cur_pos = prefix_len + 2
    out_kernel = ops.paged_decode_attention(
        q, k_pages, v_pages, block_tables, prefix_len, k_tail, v_tail,
        jnp.asarray(tail_pos), cur_pos,
    )
    out_model = paged_attention_decode(
        q.reshape(B, 1, H, D),
        k_pages, v_pages, block_tables, prefix_len,
        jnp.transpose(k_tail, (0, 2, 1, 3)),
        jnp.transpose(v_tail, (0, 2, 1, 3)),
        jnp.asarray(tail_pos), cur_pos,
    )
    np.testing.assert_allclose(
        np.asarray(out_kernel).reshape(B, H, D),
        np.asarray(out_model).reshape(B, H, D),
        rtol=1e-5, atol=1e-5,
    )


# ----------------------------------------------- chunked prefill (serving entry)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,KV,G,D,page,P,N,C,window,softcap",
    [
        (2, 2, 2, 16, 4, 4, 16, 8, 0, 0.0),
        (1, 4, 1, 32, 8, 3, 8, 16, 0, 0.0),
        (3, 1, 4, 16, 4, 5, 32, 8, 12, 0.0),  # sliding window
        (1, 2, 2, 16, 4, 3, 8, 8, 0, 20.0),   # softcap
    ],
)
def test_paged_prefill_attention_matches_ref(dtype, B, KV, G, D, page, P, N, C, window, softcap):
    """The chunked-prefill entry point (chunk queries over block-table
    prefix + causal within the chunk) against the dense-gather oracle."""
    rng = np.random.default_rng(7)
    q = _rand(rng, (B, KV, G, C, D), dtype)
    k_pages = _rand(rng, (KV, N, page, D), dtype)
    v_pages = _rand(rng, (KV, N, page, D), dtype)
    k_chunk = _rand(rng, (B, KV, C, D), dtype)
    v_chunk = _rand(rng, (B, KV, C, D), dtype)
    block_tables = jnp.asarray(rng.integers(0, N, (B, P)), jnp.int32)
    # block-aligned prefixes, including an empty one (first chunk of a prompt)
    prefix_len = jnp.asarray(
        rng.integers(0, P + 1, (B,)) * page, jnp.int32
    )
    args = (q, k_pages, v_pages, block_tables, prefix_len, k_chunk, v_chunk)
    out = ops.paged_prefill_attention(*args, window=window, softcap=softcap)
    expect = ref.paged_prefill_attention_ref(*args, window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


def test_paged_prefill_attention_matches_model_helper():
    """Kernel == the model layer's jnp chunked-prefill formulation (the CPU
    lowering the engine actually runs)."""
    from repro.models.layers import paged_attention_prefill

    rng = np.random.default_rng(8)
    B, KV, G, D, page, P, N, C = 2, 2, 2, 16, 4, 3, 8, 8
    H = KV * G
    q = _rand(rng, (B, KV, G, C, D), jnp.float32)
    k_pages = _rand(rng, (KV, N, page, D), jnp.float32)
    v_pages = _rand(rng, (KV, N, page, D), jnp.float32)
    k_chunk = _rand(rng, (B, KV, C, D), jnp.float32)
    v_chunk = _rand(rng, (B, KV, C, D), jnp.float32)
    block_tables = jnp.asarray(rng.integers(0, N, (B, P)), jnp.int32)
    prefix_len = jnp.asarray([P * page, page], jnp.int32)
    q_positions = prefix_len[:, None] + jnp.arange(C)[None, :]
    out_kernel = ops.paged_prefill_attention(
        q, k_pages, v_pages, block_tables, prefix_len, k_chunk, v_chunk
    )
    out_model = paged_attention_prefill(
        q.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D),
        k_pages, v_pages, block_tables, prefix_len,
        jnp.transpose(k_chunk, (0, 2, 1, 3)),
        jnp.transpose(v_chunk, (0, 2, 1, 3)),
        q_positions,
    )
    np.testing.assert_allclose(
        np.asarray(out_kernel).transpose(0, 3, 1, 2, 4).reshape(B, C, H, D),
        np.asarray(out_model),
        rtol=1e-5, atol=1e-5,
    )


def test_paged_prefill_composes_to_full_causal():
    """Running a sequence chunk-by-chunk (prefix pages + causal chunk)
    reproduces one full causal flash attention over the whole sequence —
    the identity chunked prefill rests on."""
    rng = np.random.default_rng(9)
    B, KV, G, D, page, C = 1, 2, 2, 16, 4, 8
    S = 4 * C  # 4 chunks
    H = KV * G
    q_all = _rand(rng, (B, H, S, D), jnp.float32)
    k_all = _rand(rng, (B, KV, S, D), jnp.float32)
    v_all = _rand(rng, (B, KV, S, D), jnp.float32)
    full = ref.flash_attention_ref(q_all, k_all, v_all, causal=True)  # [B, H, S, D]

    P = S // page
    k_pages = np.zeros((KV, P, page, D), np.float32)
    v_pages = np.zeros((KV, P, page, D), np.float32)
    outs = []
    for lo in range(0, S, C):
        q = q_all[:, :, lo : lo + C].reshape(B, KV, G, C, D)
        kc = k_all[:, :, lo : lo + C]
        vc = v_all[:, :, lo : lo + C]
        bt = jnp.asarray([[i for i in range(P)]], jnp.int32)
        out = ops.paged_prefill_attention(
            q, jnp.asarray(k_pages), jnp.asarray(v_pages), bt,
            jnp.asarray([lo], jnp.int32), kc, vc,
        )
        outs.append(np.asarray(out))  # [B, KV, G, C, D]
        # land the chunk's pages before the next chunk, like the engine
        for b0 in range(lo // page, (lo + C) // page):
            k_pages[:, b0] = np.asarray(k_all[0, :, b0 * page : (b0 + 1) * page])
            v_pages[:, b0] = np.asarray(v_all[0, :, b0 * page : (b0 + 1) * page])
    got = np.concatenate(outs, axis=3).reshape(B, KV, G, S, D).reshape(B, H, S, D)
    np.testing.assert_allclose(got, np.asarray(full), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- kv block copy
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_kv_block_copy_matches_ref(dtype):
    rng = np.random.default_rng(4)
    N, page, KV, D = 16, 8, 2, 32
    if dtype == jnp.int32:
        src = jnp.asarray(rng.integers(0, 100, (N, page, KV, D)), dtype)
    else:
        src = _rand(rng, (N, page, KV, D), dtype)
    idx = jnp.asarray(rng.permutation(N)[:5], jnp.int32)
    out = ops.kv_block_copy(src, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.kv_block_copy_ref(src, idx)))


def test_gather_payloads_surfaces_kernel_failure(monkeypatch):
    """Uniform page payloads go through the copy kernel, and a kernel
    failure reaches the caller — no silent numpy copy in its place."""
    from repro.kernels.kv_block_copy import gather_payloads

    rng = np.random.default_rng(10)
    pages = [rng.normal(size=(2, 4, 2, 8)).astype(np.float32) for _ in range(3)]
    got = gather_payloads(pages)
    for a, b in zip(got, pages):
        np.testing.assert_array_equal(a, b)
        assert a is not b

    def broken_copy(*_a, **_kw):
        raise RuntimeError("copy kernel failed")

    monkeypatch.setattr(ops, "kv_block_copy", broken_copy)
    with pytest.raises(RuntimeError, match="copy kernel failed"):
        gather_payloads(pages)


@pytest.mark.parametrize(
    "shapes",
    [
        [(64,), (64,)],  # packed 1-D state snapshots
        [(2, 4, 2, 8), (2, 4, 2, 4)],  # mixed shapes
        [(2, 0, 2, 8)],  # empty
    ],
)
def test_gather_payloads_non_pages_take_plain_copy(monkeypatch, shapes):
    """Payloads that are not uniform pages are copied without a launch."""
    from repro.kernels.kv_block_copy import gather_payloads

    def no_launch(*_a, **_kw):
        raise AssertionError("non-page payloads must not launch the kernel")

    monkeypatch.setattr(ops, "kv_block_copy", no_launch)
    arrays = [np.arange(int(np.prod(s)), dtype=np.float32).reshape(s) for s in shapes]
    got = gather_payloads(arrays)
    for a, b in zip(got, arrays):
        np.testing.assert_array_equal(a, b)
        assert a is not b

# Property tests (hypothesis) live in tests/test_hypothesis_properties.py so
# this module always collects even when hypothesis is absent.
