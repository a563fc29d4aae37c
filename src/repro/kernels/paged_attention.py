"""Pallas TPU paged attention (decode): the serving hot-spot the paper's
ResidentClaims govern.

One decode step attends a [G, D] query group (GQA q-heads of one kv head)
over that sequence's KV pages, located through a *block table* — the same
block table the claim-native engine maintains (serving/kv_cache.py).  Pages
stream HBM->VMEM via a scalar-prefetched index map (``block_tables`` and
``lengths`` are prefetch operands, so Mosaic can schedule page DMA ahead of
compute); online softmax state lives in VMEM scratch across the page grid
axis; pages past the sequence length are skipped with ``pl.when``.

Memory-bound by design: the roofline term that dominates decode is KV bytes
per token, which is why restore-before-reuse (claim restoration) is the
latency-critical path this kernel pairs with (kernels/kv_block_copy.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _paged_kernel(
    bt_ref,  # [B, P] scalar prefetch: block tables
    len_ref,  # [B] scalar prefetch: sequence lengths
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, 1, page, D]
    v_ref,
    o_ref,  # [1, 1, G, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
    num_pages: int,
    softcap: float,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [G, page]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            pexp.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == num_pages - 1)
    def _finalize():
        o_ref[0, 0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q,
    k_pages,
    v_pages,
    block_tables,
    lengths,
    *,
    softcap: float = 0.0,
    interpret: bool = False,
):
    """Decode-step attention over paged KV.

    q:            [B, KV, G, D]  (GQA query groups)
    k/v_pages:    [KV, N_pages, page_size, D]  (the device block pool)
    block_tables: [B, P] int32  page ids per sequence (padded arbitrarily)
    lengths:      [B] int32     valid tokens per sequence
    -> [B, KV, G, D]
    """
    B, KV, G, D = q.shape
    page_size = k_pages.shape[2]
    P = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _paged_kernel,
        sm_scale=sm_scale,
        page_size=page_size,
        num_pages=P,
        softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, P),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln: (kv, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln: (kv, bt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# batched serving entry point: block-table prefix + in-flight tail
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    bt_ref,  # [B, P+1] scalar prefetch: block tables (last column padding)
    len_ref,  # [B] scalar prefetch: prefix tokens addressed via the table
    cpos_ref,  # [B] scalar prefetch: current query positions
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, 1, page, D]
    v_ref,
    kt_ref,  # [1, 1, T, D]  in-flight tail
    vt_ref,
    tp_ref,  # [1, 1, T] int32  absolute tail positions (-1 = empty)
    o_ref,  # [1, 1, G, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
    num_pages: int,
    softcap: float,
    window: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    cur_pos = cpos_ref[b]

    def _online_update(s, v):
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            pexp.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(jnp.logical_and(p < num_pages, p * page_size < length))
    def _pages():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [G, page]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < length
        if window:
            valid &= cur_pos - k_pos < window
        s = jnp.where(valid, s, NEG_INF)
        _online_update(s, v)

    @pl.when(p == num_pages)
    def _tail_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        kt = kt_ref[0, 0].astype(jnp.float32)  # [T, D]
        vt = vt_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [G, T]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        tp = tp_ref[0]  # [1, T]
        valid = (tp >= 0) & (tp <= cur_pos)
        if window:
            valid &= cur_pos - tp < window
        s = jnp.where(valid, s, NEG_INF)
        _online_update(s, vt)
        o_ref[0, 0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# chunked prefill entry point: chunk queries over block-table prefix + itself
# ---------------------------------------------------------------------------


def _paged_prefill_kernel(
    bt_ref,  # [B, P+1] scalar prefetch: block tables (last column padding)
    len_ref,  # [B] scalar prefetch: prefix tokens addressed via the table
    q_ref,  # [1, 1, G*C, D]  chunk queries (G query groups x C positions)
    k_ref,  # [1, 1, page, D]
    v_ref,
    kc_ref,  # [1, 1, C, D]  the chunk's own keys
    vc_ref,
    o_ref,  # [1, 1, G*C, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
    num_pages: int,
    chunk: int,
    softcap: float,
    window: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    def _online_update(s, v):
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            pexp.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # row r of the flattened [G*C] query block sits at chunk offset r % C,
    # i.e. absolute position length + (r % C) — the engine's chunk contract
    @pl.when(jnp.logical_and(p < num_pages, p * page_size < length))
    def _pages():
        q = q_ref[0, 0].astype(jnp.float32)  # [G*C, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [G*C, page]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # prefix positions all precede every chunk query: no causal term
        valid = k_pos < length
        if window:
            c_q = jnp.remainder(jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), chunk)
            valid &= (length + c_q) - k_pos < window
        s = jnp.where(valid, s, NEG_INF)
        _online_update(s, v)

    @pl.when(p == num_pages)
    def _chunk_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32)  # [G*C, D]
        kc = kc_ref[0, 0].astype(jnp.float32)  # [C, D]
        vc = vc_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [G*C, C]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        c_q = jnp.remainder(jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), chunk)
        c_k = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = c_k <= c_q  # causal within the chunk
        if window:
            valid &= c_q - c_k < window
        s = jnp.where(valid, s, NEG_INF)
        _online_update(s, vc)
        o_ref[0, 0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def paged_prefill_attention_pallas(
    q,
    k_pages,
    v_pages,
    block_tables,
    prefix_len,
    k_chunk,
    v_chunk,
    *,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
):
    """Chunked-prefill attention over paged prefix KV plus the in-flight
    chunk — the O(chunk) prefill entry point.

    One chunk of C query positions attends the pages already written for
    its sequence (streamed HBM->VMEM via the scalar-prefetched block table,
    exactly like decode) plus the chunk's own keys, causal within the
    chunk.  The full-length [S] KV buffer of a monolithic prefill never
    exists: peak prefill KV is the chunk plus the page pool the tokens land
    in anyway.

    Queries are assumed to sit at absolute positions ``prefix_len[b] + c``
    (the serving engine feeds block-aligned chunks, so a chunk starts
    exactly where its paged prefix ends).

    q:            [B, KV, G, C, D]  (GQA query groups x chunk positions)
    k/v_pages:    [KV, N_pages, page_size, D]  (the device page pool)
    block_tables: [B, P] int32   page ids per sequence
    prefix_len:   [B] int32      tokens addressed via the block table
    k/v_chunk:    [B, KV, C, D]  the chunk's own keys/values
    -> [B, KV, G, C, D]
    """
    B, KV, G, C, D = q.shape
    page_size = k_pages.shape[2]
    P = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(D)
    q_r = q.reshape(B, KV, G * C, D)

    # one padding column so the page index map stays in bounds on the chunk step
    bt = jnp.concatenate(
        [block_tables.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1
    )

    kernel = functools.partial(
        _paged_prefill_kernel,
        sm_scale=sm_scale,
        page_size=page_size,
        num_pages=P,
        chunk=C,
        softcap=softcap,
        window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, P + 1),
        in_specs=[
            pl.BlockSpec((1, 1, G * C, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln: (kv, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln: (kv, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, C, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, C, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G * C, D), lambda b, kv, p, bt, ln: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * C, D), jnp.float32),
            pltpu.VMEM((G * C, LANES), jnp.float32),
            pltpu.VMEM((G * C, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G * C, D), q.dtype),
        interpret=interpret,
    )(
        bt,
        prefix_len.astype(jnp.int32),
        q_r,
        k_pages,
        v_pages,
        k_chunk,
        v_chunk,
    )
    return out.reshape(B, KV, G, C, D)


def paged_decode_attention_pallas(
    q,
    k_pages,
    v_pages,
    block_tables,
    prefix_len,
    k_tail,
    v_tail,
    tail_pos,
    cur_pos,
    *,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
):
    """Batched decode-step attention over paged prefix KV plus a dense tail —
    the serving engine's zero-copy decode entry point.

    The prefix pages stay in place in the device pool and stream HBM->VMEM
    via the scalar-prefetched block table; the tail (trailing partial block
    + already-decoded tokens) rides along as one extra grid step, so a
    request's ENTIRE context is attended without assembling a dense cache.

    q:            [B, KV, G, D]  (GQA query groups)
    k/v_pages:    [KV, N_pages, page_size, D]  (the device page pool)
    block_tables: [B, P] int32   page ids per sequence
    prefix_len:   [B] int32      tokens addressed via the block table
    k/v_tail:     [B, KV, T, D]  in-flight tail
    tail_pos:     [B, T] int32   absolute tail positions (-1 = empty)
    cur_pos:      [B] int32      query token position
    -> [B, KV, G, D]
    """
    B, KV, G, D = q.shape
    page_size = k_pages.shape[2]
    P = block_tables.shape[1]
    T = k_tail.shape[2]
    sm_scale = 1.0 / math.sqrt(D)

    # one padding column so the page index map stays in bounds on the tail step
    bt = jnp.concatenate(
        [block_tables.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1
    )

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=sm_scale,
        page_size=page_size,
        num_pages=P,
        softcap=softcap,
        window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, P + 1),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, kv, p, bt, ln, cp: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln, cp: (kv, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page_size, D), lambda b, kv, p, bt, ln, cp: (kv, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, kv, p, bt, ln, cp: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, kv, p, bt, ln, cp: (b, kv, 0, 0)),
            # [B, 1, T]: a (1, 1, T) block keeps its last two dims equal to
            # the array's, as Mosaic's tiling rule requires for any B (a
            # (1, T) block over [B, T] compiles only when B == 1)
            pl.BlockSpec((1, 1, T), lambda b, kv, p, bt, ln, cp: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, kv, p, bt, ln, cp: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(
        bt,
        prefix_len.astype(jnp.int32),
        cur_pos.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
        k_tail,
        v_tail,
        tail_pos.astype(jnp.int32).reshape(B, 1, T),
    )
