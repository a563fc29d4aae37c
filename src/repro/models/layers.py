"""Shared model building blocks (pure JAX, functional, pytree params).

Conventions
-----------
- Params are nested dicts of jnp arrays; layer stacks carry a leading ``L``
  axis and are consumed with ``jax.lax.scan`` (small HLO, fast compile — this
  matters when lowering 314B-param configs against 512 host devices).
- Activations are bf16; softmax/normalization statistics are fp32.
- Attention is written chunked (online softmax over KV blocks) so a 32k
  prefill never materializes an [S, S] score matrix.  The same math is the
  oracle for the Pallas flash kernel (kernels/ref.py uses the naive quadratic
  form on small shapes to cross-check both).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

DEFAULT_DTYPE = jnp.bfloat16
NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


# ---------------------------------------------------------------------------
# activation sharding (SP: sequence over 'model' between layers)
# ---------------------------------------------------------------------------


# Attention sharding mode (perf lever, EXPERIMENTS.md §Perf):
#   "chunked_seq" — baseline: activations stay sequence-sharded through
#       attention; GSPMD re-gathers each KV chunk per q-chunk scan step
#       (measured: the dominant collective term on every prefill cell).
#   "gather_kv"   — K/V gathered ONCE per layer; q stays sequence-sharded;
#       scores/outputs need no further communication.
#   "heads"       — K/V/Q head-sharded over 'model' (Megatron SP<->TP
#       transition); requires num_kv_heads % model == 0 (falls back to
#       gather_kv otherwise).
_ATTN_SHARDING = "gather_kv"


def set_attn_sharding(mode: str) -> None:
    global _ATTN_SHARDING
    assert mode in ("chunked_seq", "gather_kv", "heads")
    globals()["_ATTN_SHARDING"] = mode


def get_attn_sharding() -> str:
    return _ATTN_SHARDING


def _mesh_axes(mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None
    return dp, tp


def constrain_attention_qkv(q, k, v, mesh):
    """Apply the selected attention sharding layout (no-op without mesh).

    q: [B, Sq, H, D]; k, v: [B, Skv, KV, D].
    """
    if mesh is None or _ATTN_SHARDING == "chunked_seq":
        return q, k, v
    from jax.sharding import PartitionSpec as P

    dp, tp = _mesh_axes(mesh)
    if tp is None:
        return q, k, v
    tp_n = mesh.shape[tp]
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    b_ok = q.shape[0] % dp_n == 0
    bspec = dp if b_ok else None
    wsc = jax.lax.with_sharding_constraint

    mode = _ATTN_SHARDING
    if mode == "heads" and k.shape[2] % tp_n != 0:
        mode = "gather_kv"
    if mode == "heads":
        q = wsc(q, P(bspec, None, tp, None))
        k = wsc(k, P(bspec, None, tp, None))
        v = wsc(v, P(bspec, None, tp, None))
    else:  # gather_kv: one K/V gather per layer, q stays seq-sharded
        seq_ok = q.shape[1] % tp_n == 0 and q.shape[1] > 1
        q = wsc(q, P(bspec, tp if seq_ok else None, None, None))
        k = wsc(k, P(bspec, None, None, None))
        v = wsc(v, P(bspec, None, None, None))
    return q, k, v


def constrain_activations(x, mesh, *, seq_dim: Optional[int] = 1):
    """Layer-boundary sharding constraint for [B, S, d]-like activations.

    Batch -> data axes; sequence -> 'model' (Megatron-style sequence
    parallelism: divides the remat stash by the model-axis size).  Dims that
    do not divide fall back to replication.  No-op without a mesh.
    """
    if mesh is None:
        return x
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    tp = "model" if "model" in mesh.axis_names else None
    tp_n = mesh.shape[tp] if tp else 1

    spec = [None] * x.ndim
    if x.shape[0] % dp_n == 0 and dp:
        spec[0] = dp
    if seq_dim is not None and seq_dim < x.ndim and tp and x.shape[seq_dim] % tp_n == 0 and x.shape[seq_dim] > 1:
        spec[seq_dim] = tp
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, in_dim: int, out_dim: int, dtype=DEFAULT_DTYPE):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype=DEFAULT_DTYPE):
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def make_norm(cfg_norm: str, rng, dim: int):
    if cfg_norm == "rmsnorm":
        return {"w": jnp.ones((dim,), DEFAULT_DTYPE)}
    return {"w": jnp.ones((dim,), DEFAULT_DTYPE), "b": jnp.zeros((dim,), DEFAULT_DTYPE)}


def apply_norm(cfg_norm: str, p, x):
    if cfg_norm == "rmsnorm":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [..., S] (int)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [D/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [..., S, 1, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def pick_chunk(S: int, target: int = 128) -> int:
    """Largest divisor of S that is <= target (for two-level scans)."""
    if S <= target:
        return S
    for c in range(target, 0, -1):
        if S % c == 0:
            return c
    return 1


def chunked_recurrent_scan(step, init, xs, *, chunk: int = 128):
    """Two-level (binomial) checkpointed scan over the token axis.

    A flat ``lax.scan`` over S tokens saves per-step residuals for backward —
    O(S x state) memory, which is what breaks 4k-token training of the
    recurrent blocks (mLSTM carries a [B, nh, dh, dh] matrix per step).
    Scanning chunks-of-tokens with a rematted inner scan bounds the stash to
    O(S/chunk x state + chunk x residuals).
    xs: pytree with leading dim S; returns (carry, ys) like lax.scan.
    """
    S = jax.tree.leaves(xs)[0].shape[0]
    c = pick_chunk(S, chunk)
    n = S // c
    xs_c = jax.tree.map(lambda a: a.reshape((n, c) + a.shape[1:]), xs)

    @partial(jax.checkpoint, prevent_cse=False)
    def outer(carry, xc):
        return jax.lax.scan(step, carry, xc)

    carry, ys = jax.lax.scan(outer, init, xs_c)
    ys = jax.tree.map(lambda a: a.reshape((S,) + a.shape[2:]), ys)
    return carry, ys


def sinusoidal_positions(length: int, dim: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-math.log(10000.0) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(DEFAULT_DTYPE)


# ---------------------------------------------------------------------------
# attention core (GQA, chunked online softmax)
# ---------------------------------------------------------------------------


def _scores(q, k, scale: float, softcap: float):
    """q [..., G, Sq, D], k [..., Sk, D] -> scores fp32 [..., G, Sq, Sk].

    The QK dot runs in the operand dtype and only the (small) score tensor is
    upcast.  Requesting an f32 dot here makes the CPU host backend legalize
    by converting the cache operand to f32 — a conversion XLA then hoists out
    of the layer scan as a full-cache f32 replica (measured +16 GB/dev on
    grok decode).  On the TPU target the Pallas kernels accumulate in f32
    natively (kernels/flash_attention.py, kernels/paged_attention.py).
    """
    s = jnp.einsum("...gqd,...kd->...gqk", q, k).astype(jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


def attention_prefill(
    q,
    k,
    v,
    *,
    q_positions,
    kv_positions,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
):
    """Chunked flash attention reference (pure jnp).

    q: [B, Sq, H, D]; k, v: [B, Sk, KV, D]; positions: [B, S*].
    GQA is computed without repeating KV: q is reshaped to [B, KV, G, Sq, D].
    Returns [B, Sq, H, D].
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to multiples; padded kv positions get masked out via -1 sentinel
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)), constant_values=-1)

    Sq_p, Skv_p = Sq + pq, Skv + pk
    nq, nk = Sq_p // q_chunk, Skv_p // kv_chunk

    # [B, KV, G, nq, cq, D]
    qg = q.reshape(B, nq, q_chunk, KV, G, D).transpose(0, 3, 4, 1, 2, 5)
    kg = k.reshape(B, nk, kv_chunk, KV, D).transpose(0, 3, 1, 2, 4)  # [B, KV, nk, ck, D]
    vg = v.reshape(B, nk, kv_chunk, KV, D).transpose(0, 3, 1, 2, 4)
    qpos = q_positions.reshape(B, nq, q_chunk)
    kpos = kv_positions.reshape(B, nk, kv_chunk)

    @partial(jax.checkpoint, prevent_cse=False)  # flash backward: recompute
    def q_block(carry, qi):                      # score blocks, never save S^2
        qb = qg[:, :, :, qi]  # [B, KV, G, cq, D]
        qp = qpos[:, qi]  # [B, cq]

        def kv_block(state, ki):
            m, l, acc = state
            kb, vb = kg[:, :, ki], vg[:, :, ki]  # [B, KV, ck, D]
            kp = kpos[:, ki]  # [B, ck]
            s = _scores(qb, kb, scale, softcap)  # [B, KV, G, cq, ck]
            mask = kp[:, None, None, None, :] >= 0
            if causal:
                mask &= qp[:, None, None, :, None] >= kp[:, None, None, None, :]
            if window:
                mask &= qp[:, None, None, :, None] - kp[:, None, None, None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("...qk,...kd->...qd", p.astype(vb.dtype), vb[:, :, None])
            acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, G, q_chunk), jnp.float32),
            jnp.zeros((B, KV, G, q_chunk, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_block, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return carry, out  # [B, KV, G, cq, D]

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))  # [nq, B, KV, G, cq, D]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_p, H, D)
    return out[:, :Sq].astype(q.dtype)


def paged_attention_decode(
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
    window: int = 0,
    softcap: float = 0.0,
    layer=None,
):
    """Single-step decode attention over PAGED prefix KV plus a dense tail.

    The prefix lives in the device page pool and is addressed through
    per-request block tables — no dense per-request cache is assembled; the
    tail holds the in-flight tokens (partial trailing block + decoded
    tokens) that are not yet page-resident.

    q:            [B, 1, H, D]
    k/v_pages:    [KV, N, page, D]   (this layer's slice of the pool), or
                  [L, KV, N, page, D] (the stacked pool) with ``layer`` the
                  index of this layer
    block_tables: [B, P] int32       page ids per request (padding masked
                                     by prefix_len)
    prefix_len:   [B] int32          tokens addressed via the block table
    k/v_tail:     [B, T, KV, D]      in-flight tail (this layer)
    tail_pos:     [B, T] int32       absolute tail positions (-1 = empty)
    cur_pos:      [B] int32          query token position
    Returns [B, 1, H, D].

    On the TPU target this lowers to the Pallas paged-attention decode
    kernel (kernels/paged_attention.paged_decode_attention_pallas): each of
    its grid steps DMAs a run of a row's block-table pages, all KV heads
    of a page per copy, from the pool in HBM into VMEM, the next run's
    copies in flight while this one is attended.  This jnp formulation is
    the same math expressed with an explicit page gather.
    """
    B = q.shape[0]
    page = k_pages.shape[-2]
    P = block_tables.shape[1]
    if jax.default_backend() == "tpu":
        # copy pages HBM->VMEM by the block table inside the kernel — the
        # pool is read strictly in place, nothing is gathered densely
        from repro.kernels.ops import paged_decode_attention

        KV = k_pages.shape[-4]
        G = q.shape[2] // KV
        out = paged_decode_attention(
            q[:, 0].reshape(B, KV, G, q.shape[3]),
            k_pages,
            v_pages,
            block_tables,
            prefix_len,
            jnp.transpose(k_tail, (0, 2, 1, 3)),
            jnp.transpose(v_tail, (0, 2, 1, 3)),
            tail_pos,
            cur_pos,
            softcap=softcap,
            window=window,
            layer=layer,
        )
        return out.reshape(B, 1, q.shape[2], q.shape[3])
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    # gather the referenced pages: [KV, B, P, page, D] -> [B, P*page, KV, D]
    kd = jnp.transpose(k_pages[:, block_tables], (1, 2, 3, 0, 4)).reshape(
        B, P * page, k_pages.shape[0], k_pages.shape[3]
    )
    vd = jnp.transpose(v_pages[:, block_tables], (1, 2, 3, 0, 4)).reshape(
        B, P * page, v_pages.shape[0], v_pages.shape[3]
    )
    # prefix positions are the leading prefix by construction; mask slots
    # beyond prefix_len (block-table padding and partial last pages)
    ppos = jnp.broadcast_to(jnp.arange(P * page, dtype=jnp.int32)[None], (B, P * page))
    ppos = jnp.where(ppos < prefix_len[:, None], ppos, -1)
    k_all = jnp.concatenate([kd, k_tail], axis=1)
    v_all = jnp.concatenate([vd, v_tail], axis=1)
    pos_all = jnp.concatenate([ppos, tail_pos], axis=1)
    return attention_decode(
        q, k_all, v_all, kv_positions=pos_all, cur_pos=cur_pos,
        window=window, softcap=softcap,
    )


def paged_attention_prefill(
    q,
    k_pages,
    v_pages,
    block_tables,
    prefix_len,
    k_chunk,
    v_chunk,
    q_positions,
    *,
    window: int = 0,
    softcap: float = 0.0,
):
    """Chunk-of-queries prefill attention over PAGED prefix KV plus the
    chunk itself — the O(chunk) prefill counterpart of
    ``paged_attention_decode``.

    The already-prefilled prefix lives in the device page pool and is
    addressed through per-request block tables (full attention — every
    prefix position precedes every chunk query); the chunk's own keys are
    attended causally.  Chunk queries sit at absolute positions
    ``prefix_len[b] + c`` (the engine feeds block-aligned chunks), which is
    what ``q_positions`` must carry — the Pallas kernel derives positions
    from ``prefix_len`` directly.

    q:            [B, C, H, D]       chunk queries
    k/v_pages:    [KV, N, page, D]   (this layer's slice of the pool)
    block_tables: [B, P] int32       page ids per request
    prefix_len:   [B] int32          tokens addressed via the block table
    k/v_chunk:    [B, C, KV, D]      the chunk's own keys/values
    q_positions:  [B, C] int32       absolute chunk positions
    Returns [B, C, H, D].

    On the TPU target this lowers to the Pallas chunked-prefill kernel
    (kernels/paged_attention.paged_prefill_attention_pallas), which streams
    prefix pages HBM->VMEM via the scalar-prefetched block table; this jnp
    formulation is the same math with an explicit page gather (the gather
    is transient — the full-length KV of a monolithic prefill is never
    collected).
    """
    B, C, H, D = q.shape
    KV = k_pages.shape[0]
    G = H // KV
    page = k_pages.shape[2]
    P = block_tables.shape[1]
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import paged_prefill_attention

        qg = q.reshape(B, C, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B, KV, G, C, D]
        out = paged_prefill_attention(
            qg,
            k_pages,
            v_pages,
            block_tables,
            prefix_len,
            jnp.transpose(k_chunk, (0, 2, 1, 3)),
            jnp.transpose(v_chunk, (0, 2, 1, 3)),
            softcap=softcap,
            window=window,
        )
        return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D)
    # gather the referenced pages: [KV, B, P, page, D] -> [B, P*page, KV, D]
    kd = jnp.transpose(k_pages[:, block_tables], (1, 2, 3, 0, 4)).reshape(
        B, P * page, KV, k_pages.shape[3]
    )
    vd = jnp.transpose(v_pages[:, block_tables], (1, 2, 3, 0, 4)).reshape(
        B, P * page, KV, v_pages.shape[3]
    )
    ppos = jnp.broadcast_to(jnp.arange(P * page, dtype=jnp.int32)[None], (B, P * page))
    ppos = jnp.where(ppos < prefix_len[:, None], ppos, -1)
    k_all = jnp.concatenate([kd, k_chunk], axis=1)  # [B, S, KV, D]
    v_all = jnp.concatenate([vd, v_chunk], axis=1)
    pos_all = jnp.concatenate([ppos, q_positions.astype(jnp.int32)], axis=1)
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, C, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B, KV, G, C, D]
    kb = k_all.transpose(0, 2, 1, 3)  # [B, KV, S, D]
    vb = v_all.transpose(0, 2, 1, 3)
    s = _scores(qg, kb, scale, softcap)  # [B, KV, G, C, S]
    valid = (pos_all[:, None, :] >= 0) & (
        pos_all[:, None, :] <= q_positions[:, :, None]
    )  # [B, C, S]
    if window:
        valid &= q_positions[:, :, None] - pos_all[:, None, :] < window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("...cs,...sd->...cd", p.astype(vb.dtype), vb[:, :, None])
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D).astype(q.dtype)


def attn_paged_prefill_layer(
    p, cfg, x, k_pages, v_pages, block_tables, prefix_len, positions, *, use_rope=True
):
    """One chunk of paged prefill: computes the chunk's (k, v) and attends
    prefix pages (in place, via the block table) plus the chunk causally.

    x: [B, C, d]; k/v_pages: [KV, N, page, Dh]; positions: [B, C] absolute
    chunk positions (= prefix_len + arange(C)).
    Returns (out [B, C, d], (k, v) [B, C, KV, Dh]) for the engine to land
    in pool pages — the only KV this chunk ever materializes.
    """
    B, C, _ = x.shape
    q, k, v = attn_qkv(p, cfg, x, positions, use_rope=use_rope)
    out = paged_attention_prefill(
        q, k_pages, v_pages, block_tables, prefix_len, k, v, positions,
        window=cfg.sliding_window, softcap=cfg.attn_logit_softcap,
    )
    out = out.reshape(B, C, -1) @ p["wo"]
    return out, (k, v)


def attn_paged_decode_layer(
    p, cfg, x, k_pages, v_pages, block_tables, prefix_len,
    tail_k, tail_v, tail_pos, cur_pos, tail_slot, *, layer=None, use_rope=True
):
    """One-token decode over paged prefix KV: writes the new (k, v) into the
    tail at ``tail_slot`` and attends pages + tail in place.

    x: [B, 1, d]; k/v_pages: [KV, N, page, Dh], or the stacked
    [L, KV, N, page, Dh] pool with ``layer`` its index; tail_k/v:
    [B, T, KV, Dh]; tail_pos: [B, T] (already updated with cur_pos at
    tail_slot).  Returns (out [B, 1, d], new_tail_k, new_tail_v).
    """
    B = x.shape[0]
    q, k, v = attn_qkv(p, cfg, x, cur_pos[:, None], use_rope=use_rope)
    with jax.named_scope("tail_write"):
        new_tk = slot_update(tail_k, k, tail_slot)
        new_tv = slot_update(tail_v, v, tail_slot)
        new_tk, new_tv = jax.lax.optimization_barrier((new_tk, new_tv))
    out = paged_attention_decode(
        q, k_pages, v_pages, block_tables, prefix_len,
        new_tk, new_tv, tail_pos, cur_pos,
        window=cfg.sliding_window, softcap=cfg.attn_logit_softcap, layer=layer,
    )
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, new_tk, new_tv


def attention_decode(q, k_cache, v_cache, *, kv_positions, cur_pos, window: int = 0, softcap: float = 0.0):
    """Single-step decode attention against a dense (or ring) KV cache.

    q: [B, 1, H, D]; caches: [B, S_cache, KV, D]; kv_positions: [B, S_cache]
    absolute positions of cache entries (-1 for unwritten slots);
    cur_pos: [B] current absolute position of the query token.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, 1, KV, G, D).transpose(0, 2, 3, 1, 4)  # [B, KV, G, 1, D]
    kb = k_cache.transpose(0, 2, 1, 3)  # [B, KV, S, D]
    vb = v_cache.transpose(0, 2, 1, 3)
    s = _scores(qg, kb, scale, softcap)  # [B, KV, G, 1, S]
    valid = kv_positions >= 0
    valid &= kv_positions <= cur_pos[:, None]
    if window:
        valid &= cur_pos[:, None] - kv_positions < window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("...qk,...kd->...qd", p.astype(vb.dtype), vb[:, :, None])
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention layer (projections + rope + qk-norm + cache plumbing)
# ---------------------------------------------------------------------------


def attn_init(rng, cfg, bias: bool = False):
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, H * Dh),
        "wk": dense_init(ks[1], d, KV * Dh),
        "wv": dense_init(ks[2], d, KV * Dh),
        "wo": dense_init(ks[3], H * Dh, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), DEFAULT_DTYPE)
        p["k_norm"] = jnp.ones((Dh,), DEFAULT_DTYPE)
    return p


def attn_qkv(p, cfg, x, positions, *, use_rope: bool = True):
    B, S, _ = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, KV, Dh)
    v = (x @ p["wv"]).reshape(B, S, KV, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_prefill_sharded(q, k, v, *, q_positions, kv_positions, mesh, **kw):
    """Sequence-parallel flash attention via shard_map.

    q stays sequence-sharded over 'model'; k/v are gathered ONCE per layer
    (the in_specs force exactly one all-gather); inside the shard_map the
    q-chunk scan slices purely local data, so no per-chunk re-gather can be
    inserted (the baseline's dominant collective, EXPERIMENTS.md §Perf).
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    dp, tp = _mesh_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    tp_n = mesh.shape[tp]
    bspec = dp if q.shape[0] % dp_n == 0 else None
    sspec = tp if q.shape[1] % tp_n == 0 and q.shape[1] > 1 else None

    def body(q_loc, k_rep, v_rep, qp_loc, kp_rep):
        return attention_prefill(
            q_loc, k_rep, v_rep, q_positions=qp_loc, kv_positions=kp_rep, **kw
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, sspec, None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
            P(bspec, sspec),
            P(bspec, None),
        ),
        out_specs=P(bspec, sspec, None, None),
        check_rep=False,
    )(q, k, v, q_positions, kv_positions)


def attn_prefill_layer(p, cfg, x, positions, *, causal=True, use_rope=True, kv_override=None, mesh=None):
    """Full attention layer at prefill; returns (out, (k, v)) for cache init."""
    q, k, v = attn_qkv(p, cfg, x, positions, use_rope=use_rope)
    q, k, v = constrain_attention_qkv(q, k, v, mesh)
    if kv_override is not None:  # cross attention consumes precomputed kv
        k, v = kv_override
        kv_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (x.shape[0], k.shape[1]))
    else:
        kv_pos = positions
    kwargs = dict(
        causal=causal, window=cfg.sliding_window, softcap=cfg.attn_logit_softcap
    )
    if mesh is not None and get_attn_sharding() == "gather_kv" and "model" in mesh.axis_names:
        out = attention_prefill_sharded(
            q, k, v, q_positions=positions, kv_positions=kv_pos, mesh=mesh, **kwargs
        )
    else:
        out = attention_prefill(q, k, v, q_positions=positions, kv_positions=kv_pos, **kwargs)
    out = out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]
    return out, (k, v)


def decode_slot(cfg, S_cache: int, cur_pos):
    """Cache slot written by the current decode step (ring for SWA)."""
    if cfg.sliding_window and S_cache <= cfg.sliding_window:
        return cur_pos % S_cache  # ring buffer
    return jnp.minimum(cur_pos, S_cache - 1)


def slot_update(cache, value, slot):
    """Write ``value`` [B, 1, ...] at per-row ``slot`` into [B, S, ...].

    Expressed as a broadcast-select rather than a scatter: a scatter into the
    sequence-sharded cache makes GSPMD all-gather the whole cache per layer
    (measured: 17 GB/layer on grok decode); the select is elementwise and
    keeps the sequence shards local.  The Pallas paged-attention path writes
    in place per page and avoids even the select's full rewrite.
    """
    S = cache.shape[1]
    hit = jnp.arange(S)[None, :] == slot[:, None]  # [B, S]
    hit = hit.reshape(hit.shape + (1,) * (cache.ndim - 2))
    return jnp.where(hit, value.astype(cache.dtype), cache)


def attn_decode_layer(p, cfg, x, cache_k, cache_v, kv_positions, cur_pos, slot, *, use_rope=True):
    """One-token decode; writes (k, v) at ``slot`` and attends over the cache.

    x: [B, 1, d]; cache_*: [B, S_cache, KV, Dh]; kv_positions: [B, S_cache]
    (already updated with cur_pos at slot); cur_pos, slot: [B].
    Returns (out [B, 1, d], new_k, new_v).
    """
    B = x.shape[0]
    q, k, v = attn_qkv(p, cfg, x, cur_pos[:, None], use_rope=use_rope)
    new_k = slot_update(cache_k, k, slot)
    new_v = slot_update(cache_v, v, slot)
    # Barrier: stops the CPU host backend's bf16-dot f32-legalization convert
    # from being reassociated through the update and hoisted out of the layer
    # scan as a full f32 cache replica (+16 GB/dev measured on grok decode).
    # No-op on the real TPU target.
    new_k, new_v = jax.lax.optimization_barrier((new_k, new_v))
    out = attention_decode(
        q,
        new_k,
        new_v,
        kv_positions=kv_positions,
        cur_pos=cur_pos,
        window=cfg.sliding_window,
        softcap=cfg.attn_logit_softcap,
    )
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, new_k, new_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(rng, d: int, ff: int, activation: str):
    ks = jax.random.split(rng, 3)
    if activation == "silu":  # SwiGLU
        return {
            "w_gate": dense_init(ks[0], d, ff),
            "w_up": dense_init(ks[1], d, ff),
            "w_down": dense_init(ks[2], ff, d),
        }
    return {"w_up": dense_init(ks[0], d, ff), "w_down": dense_init(ks[1], ff, d)}


def mlp_apply(p, x, activation: str):
    if activation == "silu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]


# ---------------------------------------------------------------------------
# losses (vocab-sharded friendly, seq-chunked)
# ---------------------------------------------------------------------------


def chunked_cross_entropy(x, w_unembed, labels, *, chunk: int = 512):
    """Mean token cross-entropy without materializing [B, S, V] at once.

    x: [B, S, d] final hidden states; w_unembed: [d, V]; labels: [B, S].
    The max/sum reductions over V and the one-hot label pick lower to cheap
    all-reduces when V is sharded over the model axis.
    """
    B, S, _ = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // chunk
    xs = x.reshape(B, n, chunk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @partial(jax.checkpoint, prevent_cse=False)  # never save [B, c, V] logits
    def body(carry, inp):
        xc, lc = inp
        logits = (xc @ w_unembed).astype(jnp.float32)  # [B, c, V]
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        onehot = jax.nn.one_hot(lc, logits.shape[-1], dtype=jnp.float32)
        correct = jnp.sum(logits * onehot, axis=-1)
        valid = (lc >= 0).astype(jnp.float32)
        return (carry[0] + jnp.sum((lse - correct) * valid), carry[1] + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ls))
    return tot / jnp.maximum(cnt, 1.0)
