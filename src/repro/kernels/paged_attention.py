"""Pallas TPU paged attention (decode): the serving hot-spot the paper's
ResidentClaims govern.

One decode step attends each sequence's GQA query groups over that
sequence's KV pages, located through a *block table* — the same block
table the claim-native engine maintains (serving/kv_cache.py).

The serving entry point (``paged_decode_attention_pallas``) leaves the pool
in HBM and copies pages itself: a grid step covers a run of ``ppb``
consecutive block-table entries of one row, one DMA per page carrying all
KV heads, the next run's copies in flight while this run is attended
(two VMEM slots), and only pages below the row's prefix are copied.  The
online-softmax state, batched over the KV heads, lives in VMEM scratch
across the row's steps.  The older decode-only ``paged_attention_pallas``
and the chunked prefill kernel still stream one page of one KV head per
grid step through a scalar-prefetched index map.

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

# one grid step's K block (ppb pages x all KV heads) is sized to reach this
BLOCK_BYTES = 128 * 1024
# the tail is attended in slices of this many positions (a lane-aligned
# slice of tail_pos), which bounds the float32 copies of its K and V
TAIL_CHUNK = 128


def pages_per_block(kv_heads: int, page_size: int, head_dim: int, itemsize: int, num_pages: int) -> int:
    """Block-table entries one grid step of the decode kernel covers: the
    smallest power of two whose K block (``ppb x KV x page x D x itemsize``
    bytes) reaches ``BLOCK_BYTES``, capped by the table width ``num_pages``.

    qwen3-1.7b (KV 8, page 8, D 128, bf16: 16 KiB a page) gets 8, an MHA
    model with KV 32 (64 KiB a page) gets 2.  The pool buffers take
    2 slots x (K and V) x that block: 512 KiB of VMEM at both.
    """
    per_page = kv_heads * page_size * head_dim * itemsize
    ppb = 1
    while ppb * per_page < BLOCK_BYTES:
        ppb *= 2
    return max(1, min(ppb, num_pages))


def _online_update(acc_ref, m_ref, l_ref, s, v):
    """Fold one block of scores ``s`` [KV, G, t] and values ``v`` [KV, t, D]
    into the float32 online-softmax state."""
    m_prev = m_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pexp, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _scores(q, k, sm_scale, softcap):
    """s[kv, g, t] = q[kv, g, :] . k[kv, t, :], scaled and soft-capped."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * sm_scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _paged_decode_kernel(
    layer_ref,  # [1] scalar prefetch: the pool's layer
    bt_ref,  # [B * P] scalar prefetch: block tables, row-major
    len_ref,  # [B] scalar prefetch: prefix tokens addressed via the table
    cpos_ref,  # [B] scalar prefetch: current query positions
    q_ref,  # [1, KV, G, D]
    k_hbm,  # [L, KV, N, page, D]  the stacked pool, left in HBM
    v_hbm,
    kt_ref,  # [1, KV, T, D]  in-flight tail
    vt_ref,
    tp_ref,  # [1, 1, T] int32  absolute tail positions (-1 = empty)
    o_ref,  # [1, KV, G, D]
    k_buf,  # [2, KV, ppb, page, D]  two slots of copied pages
    v_buf,
    k_sem,  # DMA [2]: one per slot
    v_sem,
    acc_ref,  # [KV, G, D] f32
    m_ref,  # [KV, G, LANES] f32
    l_ref,
    *,
    sm_scale: float,
    page_size: int,
    num_pages: int,
    ppb: int,
    softcap: float,
    window: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    num_blocks = pl.num_programs(1) - 1  # the last step is the tail
    KV, _, D = acc_ref.shape
    block_tokens = ppb * page_size
    layer = layer_ref[0]

    def pages_of(row):
        """Pages that hold the row's prefix tokens."""
        return jnp.minimum((len_ref[row] + page_size - 1) // page_size, num_pages)

    def blocks_of(row):
        return (pages_of(row) + ppb - 1) // ppb

    def each_copy(row, blk, slot, act):
        """Apply ``act`` to the copy of every page of block ``blk`` of
        ``row`` below its prefix: all KV heads of one page per copy."""
        n = pages_of(row)
        for j in range(ppb):
            p = blk * ppb + j

            @pl.when(p < n)
            def _():
                page = bt_ref[row * num_pages + p]
                act(pltpu.make_async_copy(k_hbm.at[layer, :, page], k_buf.at[slot, :, j], k_sem.at[slot]))
                act(pltpu.make_async_copy(v_hbm.at[layer, :, page], v_buf.at[slot, :, j], v_sem.at[slot]))

    def start(row, blk, slot):
        each_copy(row, blk, slot, lambda c: c.start())

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a row's first block is started by the previous row's tail step
    @pl.when(jnp.logical_and(b == 0, i == 0))
    def _first():
        start(0, 0, 0)

    length = len_ref[b]
    cur_pos = cpos_ref[b]

    @pl.when(i < blocks_of(b))
    def _pages():
        slot = i % 2

        @pl.when(i + 1 < blocks_of(b))
        def _():
            start(b, i + 1, 1 - slot)

        each_copy(b, i, slot, lambda c: c.wait())
        q = q_ref[0].astype(jnp.float32)  # [KV, G, D]
        k = k_buf[slot].astype(jnp.float32).reshape(KV, block_tokens, D)
        v = v_buf[slot].astype(jnp.float32).reshape(KV, block_tokens, D)
        # pages past the prefix were not copied: their slot holds stale or
        # uninitialised bytes, kept out of the sum by zeroed values
        v_pos = i * block_tokens + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(v_pos < length, v, 0.0)
        s = _scores(q, k, sm_scale, softcap)  # [KV, G, ppb*page]
        k_pos = i * block_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = k_pos < length
        if window:
            valid &= cur_pos - k_pos < window
        _online_update(acc_ref, m_ref, l_ref, jnp.where(valid, s, NEG_INF), v)

    @pl.when(i == num_blocks)
    def _tail_and_finalize():
        @pl.when(b + 1 < pl.num_programs(0))
        def _():
            start(b + 1, 0, 0)

        q = q_ref[0].astype(jnp.float32)  # [KV, G, D]
        T = kt_ref.shape[2]
        for t0 in range(0, T, TAIL_CHUNK):
            t1 = min(T, t0 + TAIL_CHUNK)
            kt = kt_ref[0, :, t0:t1].astype(jnp.float32)  # [KV, t, D]
            vt = vt_ref[0, :, t0:t1].astype(jnp.float32)
            s = _scores(q, kt, sm_scale, softcap)  # [KV, G, t]
            tp = tp_ref[:, :, t0:t1]  # [1, 1, t]
            valid = (tp >= 0) & (tp <= cur_pos)
            if window:
                valid &= cur_pos - tp < window
            _online_update(acc_ref, m_ref, l_ref, jnp.where(valid, s, NEG_INF), vt)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


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
    its sequence (streamed HBM->VMEM one page of one KV head per grid step
    via the scalar-prefetched block table) plus the chunk's own keys,
    causal within the chunk.  The full-length [S] KV buffer of a monolithic prefill never
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
    layer=None,
    interpret: bool = False,
):
    """Batched decode-step attention over paged prefix KV plus a dense tail —
    the serving engine's zero-copy decode entry point.

    The prefix pages stay in place in the device pool (HBM).  The grid is
    ``(B, ceil(P / ppb) + 1)``: step ``i`` of row ``b`` copies block-table
    entries ``i*ppb .. i*ppb+ppb-1`` below the row's prefix into VMEM, one
    DMA per page carrying all KV heads, while the next block's copies are
    already in flight (two slots), and attends all KV heads at once.  Steps
    past the prefix start no copy.  The tail (trailing partial block +
    already-decoded tokens) is the last step, which also starts the next
    row's first copies, so a request's ENTIRE context is attended without
    assembling a dense cache.

    q:            [B, KV, G, D]  (GQA query groups)
    k/v_pages:    [KV, N_pages, page_size, D]  (the device page pool), or
                  [L, KV, N_pages, page_size, D] with ``layer`` (an int32
                  scalar, may be traced) naming the layer to read: the
                  stacked pool is read in place, no layer slice is copied
    block_tables: [B, P] int32   page ids per sequence
    prefix_len:   [B] int32      tokens addressed via the block table
    k/v_tail:     [B, KV, T, D]  in-flight tail
    tail_pos:     [B, T] int32   absolute tail positions (-1 = empty)
    cur_pos:      [B] int32      query token position
    -> [B, KV, G, D]
    """
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    B, KV, G, D = q.shape
    page_size = k_pages.shape[3]
    P = block_tables.shape[1]
    T = k_tail.shape[2]
    sm_scale = 1.0 / math.sqrt(D)
    ppb = pages_per_block(KV, page_size, D, k_pages.dtype.itemsize, P)
    num_blocks = -(-P // ppb)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=sm_scale,
        page_size=page_size,
        num_pages=P,
        ppb=ppb,
        softcap=softcap,
        window=window,
    )
    row = lambda b, i, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, num_blocks + 1),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, KV, T, D), row),
            pl.BlockSpec((1, KV, T, D), row),
            # [B, 1, T]: a (1, 1, T) block keeps its last two dims equal to
            # the array's, as Mosaic's tiling rule requires for any B (a
            # (1, T) block over [B, T] compiles only when B == 1)
            pl.BlockSpec((1, 1, T), lambda b, i, *_: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, D), row),
        scratch_shapes=[
            pltpu.VMEM((2, KV, ppb, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, KV, ppb, page_size, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((KV, G, D), jnp.float32),
            pltpu.VMEM((KV, G, LANES), jnp.float32),
            pltpu.VMEM((KV, G, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        # a row's copies are started on the step before they are read,
        # across rows too, so both axes run in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        # a table of width 0 still needs one (never read) entry in SMEM
        block_tables.astype(jnp.int32).reshape(-1) if P else jnp.zeros((1,), jnp.int32),
        prefix_len.astype(jnp.int32),
        cur_pos.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
        k_tail,
        v_tail,
        tail_pos.astype(jnp.int32).reshape(B, 1, T),
    )
