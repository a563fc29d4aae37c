"""Dense / MoE / VLM decoder-only transformer LM.

Covers qwen3-1.7b, h2o-danube-1.8b (SWA), deepseek-7b, stablelm-12b,
phi-3-vision-4.2b (stub patch-embedding prefix), grok-1-314b and arctic-480b
(MoE, optionally with Arctic's dense residual MLP).

Layers are stacked on a leading ``L`` axis and consumed with ``lax.scan`` so
the lowered HLO is O(1) in depth; the scan body is ``jax.checkpoint``-ed for
training (full remat, the baseline activation policy).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models.layers import (
    DEFAULT_DTYPE,
    apply_norm,
    attn_decode_layer,
    attn_init,
    attn_prefill_layer,
    chunked_cross_entropy,
    constrain_activations,
    decode_slot,
    slot_update,
    embed_init,
    make_norm,
    mlp_apply,
    mlp_init,
)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(cfg, rng) -> Dict[str, Any]:
    k_embed, k_layers, k_head = jax.random.split(rng, 3)

    def layer_init(k):
        ks = jax.random.split(k, 4)
        p = {
            "ln1": make_norm(cfg.norm, ks[0], cfg.d_model),
            "attn": attn_init(ks[1], cfg),
            "ln2": make_norm(cfg.norm, ks[2], cfg.d_model),
        }
        if cfg.moe.num_experts:
            p["moe"] = moe_lib.moe_init(ks[3], cfg)
            if cfg.moe.dense_residual:
                p["mlp"] = mlp_init(jax.random.fold_in(ks[3], 1), cfg.d_model, cfg.d_ff, cfg.activation)
        else:
            p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.activation)
        return p

    params = {
        "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model),
        "layers": jax.vmap(layer_init)(jax.random.split(k_layers, cfg.num_layers)),
        "final_norm": make_norm(cfg.norm, k_head, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
        ).astype(DEFAULT_DTYPE)
    return params


def unembed(cfg, params):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# forward (shared by train and prefill)
# ---------------------------------------------------------------------------


def _moe_block(lp, cfg, h, mesh, moe_strategy):
    B, S, d = h.shape
    h2 = h.reshape(B * S, d)
    if mesh is None:
        m, aux = moe_lib.moe_apply_local(lp["moe"], h2, cfg)
    else:
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        m, aux = moe_lib.moe_apply_sharded(
            lp["moe"], h2, cfg, mesh, dp_axes=dp, tp_axis="model", strategy=moe_strategy
        )
    m = m.reshape(B, S, d)
    if cfg.moe.dense_residual:
        m = m + mlp_apply(lp["mlp"], h, cfg.activation)
    return m, aux


def forward_hidden(
    params,
    cfg,
    x,
    positions,
    *,
    mesh=None,
    moe_strategy: str = "auto",
    collect_cache: bool = False,
    remat: bool = False,
):
    """Run the layer stack. x: [B, S, d] embedded inputs.

    Returns (hidden [B, S, d], aux_loss, cache_kv or None).
    cache_kv: (k, v) stacked [L, B, S, KV, Dh].
    """

    def body(carry, lp):
        x, aux = carry
        x = constrain_activations(x, mesh)
        h = apply_norm(cfg.norm, lp["ln1"], x)
        a, (k_, v_) = attn_prefill_layer(lp["attn"], cfg, h, positions, mesh=mesh)
        x = x + a
        h = apply_norm(cfg.norm, lp["ln2"], x)
        if cfg.moe.num_experts:
            m, aux_l = _moe_block(lp, cfg, h, mesh, moe_strategy)
            aux = aux + aux_l
        else:
            m = mlp_apply(lp["mlp"], h, cfg.activation)
        x = x + m
        x = constrain_activations(x, mesh)
        if collect_cache:
            ys = (
                constrain_activations(k_, mesh),
                constrain_activations(v_, mesh),
            )
        else:
            ys = None
        return (x, aux), ys

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), cache = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    return x, aux, cache


def embed_tokens(params, cfg, tokens, extra_embeds=None):
    """Token embedding; VLM/audio archs prepend stub frontend embeddings."""
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def loss_fn(params, cfg, batch, *, mesh=None, moe_strategy="auto", aux_coef: float = 0.01):
    """Next-token LM loss.  batch: {tokens [B,S], (patch_embeds [B,P,d])}."""
    tokens = batch["tokens"]
    extra = batch.get("patch_embeds")
    B, S = tokens.shape
    P_len = extra.shape[1] if extra is not None else 0
    x = embed_tokens(params, cfg, tokens, extra)
    positions = jnp.broadcast_to(jnp.arange(S + P_len)[None], (B, S + P_len))
    x, aux, _ = forward_hidden(
        params, cfg, x, positions, mesh=mesh, moe_strategy=moe_strategy, remat=True
    )
    x = apply_norm(cfg.norm, params["final_norm"], x)
    # predict token t+1 from position t; frontend positions carry no labels
    labels = jnp.concatenate([tokens[:, 1:], jnp.full((B, 1), -1, tokens.dtype)], axis=1)
    if P_len:
        labels = jnp.concatenate([jnp.full((B, P_len), -1, tokens.dtype), labels], axis=1)
    ce = chunked_cross_entropy(x, unembed(cfg, params), labels)
    return ce + aux_coef * aux


def quantize_kv(x):
    """Per-token absmax int8 over head_dim.  x: [..., Dh]."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-8)[..., None])
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale, dtype=DEFAULT_DTYPE):
    return (q.astype(dtype) * scale.astype(dtype)[..., None]).astype(dtype)


def make_cache(cfg, batch: int, cache_len: int, dtype=DEFAULT_DTYPE):
    L, KV, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    Sc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    cache = {"pos": jnp.full((batch, Sc), -1, jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        cache.update(
            k=jnp.zeros((L, batch, Sc, KV, Dh), jnp.int8),
            v=jnp.zeros((L, batch, Sc, KV, Dh), jnp.int8),
            k_scale=jnp.zeros((L, batch, Sc, KV), jnp.bfloat16),
            v_scale=jnp.zeros((L, batch, Sc, KV), jnp.bfloat16),
        )
    else:
        cache.update(
            k=jnp.zeros((L, batch, Sc, KV, Dh), dtype),
            v=jnp.zeros((L, batch, Sc, KV, Dh), dtype),
        )
    return cache


def prefill(params, cfg, batch, cache_len: int, *, mesh=None, moe_strategy="auto"):
    """Prefill; returns (last-position logits [B, V], cache)."""
    tokens = batch["tokens"]
    extra = batch.get("patch_embeds")
    B, S = tokens.shape
    P_len = extra.shape[1] if extra is not None else 0
    St = S + P_len
    x = embed_tokens(params, cfg, tokens, extra)
    positions = jnp.broadcast_to(jnp.arange(St)[None], (B, St))
    x, _, (ck, cv) = forward_hidden(
        params, cfg, x, positions, mesh=mesh, moe_strategy=moe_strategy, collect_cache=True
    )
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = (x[:, -1] @ unembed(cfg, params)).astype(jnp.float32)

    cache = make_cache(cfg, B, cache_len)
    Sc = cache["k"].shape[2]
    keep = min(Sc, St)
    # write the trailing `keep` positions of the prefill KV into the cache
    if cfg.kv_cache_dtype == "int8":
        qk, sk = quantize_kv(ck[:, :, St - keep :])
        qv, sv = quantize_kv(cv[:, :, St - keep :])
        cache["k"] = cache["k"].at[:, :, :keep].set(qk)
        cache["v"] = cache["v"].at[:, :, :keep].set(qv)
        cache["k_scale"] = cache["k_scale"].at[:, :, :keep].set(sk)
        cache["v_scale"] = cache["v_scale"].at[:, :, :keep].set(sv)
    else:
        cache["k"] = cache["k"].at[:, :, :keep].set(ck[:, :, St - keep :])
        cache["v"] = cache["v"].at[:, :, :keep].set(cv[:, :, St - keep :])
    cache["pos"] = cache["pos"].at[:, :keep].set(positions[:, St - keep :])
    return logits, cache


def prefill_chunk(params, cfg, state, tokens, positions, *, mesh=None, moe_strategy="auto"):
    """One chunk of chunked paged prefill — the O(chunk) serving path.

    ``state``:
      k_pages/v_pages [L, KV, N, page, Dh]  the device page pool (read-only)
      block_tables    [B, P] int32          pages of the ALREADY-PREFILLED
                                            prefix (earlier chunks)
      prefix_len      [B] int32             tokens addressed via the table
    tokens: [B, C] the chunk's token ids; positions: [B, C] absolute
    positions (= prefix_len + arange(C) — chunks are block-aligned, so a
    chunk starts exactly where its paged prefix ends).

    Returns the chunk's collected KV ``(ck, cv)`` stacked [L, B, C, KV, Dh]
    — the ONLY KV this launch materializes.  The engine lands each
    completed block directly in a pool page slot and carries the grown
    block table into the next chunk, so peak prefill memory is O(chunk).
    Attention is causal within the chunk and full over the prefix pages
    (every prefix position precedes every chunk query), which composes to
    exact causal attention over the whole prompt.

    Entry state for decode (tail KV + pre-decode logits) intentionally
    does NOT come from this launch: the step loop feeds the trailing
    tokens through the same paged decode launch continuations use, keeping
    cold-vs-restored parity structural (see serving/scheduler_loop.Row).
    """
    from repro.models.layers import attn_paged_prefill_layer

    with jax.named_scope("embed"):
        x = embed_tokens(params, cfg, tokens)  # [B, C, d]
    bt = state["block_tables"]
    plen = state["prefix_len"]

    def body(carry, xs):
        x, = carry
        lp, kp, vp = xs
        x = constrain_activations(x, mesh)
        with jax.named_scope("attention"):
            h = apply_norm(cfg.norm, lp["ln1"], x)
            a, (k_, v_) = attn_paged_prefill_layer(
                lp["attn"], cfg, h, kp, vp, bt, plen, positions
            )
            x = x + a
        with jax.named_scope("mlp"):
            h = apply_norm(cfg.norm, lp["ln2"], x)
            if cfg.moe.num_experts:
                m, _ = _moe_block(lp, cfg, h, mesh, moe_strategy)
            else:
                m = mlp_apply(lp["mlp"], h, cfg.activation)
            x = x + m
        x = constrain_activations(x, mesh)
        return (x,), (constrain_activations(k_, mesh), constrain_activations(v_, mesh))

    (x,), (ck, cv) = jax.lax.scan(
        body, (x,), (params["layers"], state["k_pages"], state["v_pages"])
    )
    return ck, cv


def paged_decode_step(params, cfg, state, tokens, cur_pos, *, mesh=None, moe_strategy="auto"):
    """One decode step over paged prefix KV — the zero-copy serving path.

    ``state``:
      k_pages/v_pages [L, KV, N, page, Dh]  the device page pool (read-only)
      block_tables    [B, P] int32          per-request page ids
      prefix_len      [B] int32             tokens addressed via the table
      k_tail/v_tail   [L, B, T, KV, Dh]     in-flight tail (written here)
      tail_pos        [B, T] int32          absolute tail positions (-1 empty)
    tokens, cur_pos: [B].  Returns (logits [B, V], {k_tail, v_tail,
    tail_pos}): the updated tail only.

    The page pool is never rewritten: a step only appends one (k, v) row to
    the tail at ``cur_pos - prefix_len`` and attends pages + tail in place.
    The pool, ``block_tables`` and ``prefix_len`` are read-only inputs and
    never leave the program: under a non-donating ``jax.jit`` an input
    passed back out is a fresh output buffer, so returning the pool would
    copy both whole pools on every launch.  The caller keeps them.

    On TPU the batch rides the paged-attention kernel's grid.  On the host
    CPU backend the rows run through ``lax.map`` instead: XLA:CPU's
    threaded runtime partitions batched loops non-uniformly across rows,
    which lets float rounding depend on a request's ROW POSITION — under
    map every row executes the same compiled body, so a request's tokens
    are bitwise independent of where it sits in the batch (the property
    the batched-vs-sequential parity tests pin down).
    """
    if jax.default_backend() != "tpu" and tokens.shape[0] > 1:
        kp, vp = state["k_pages"], state["v_pages"]

        def row_fn(row):
            st = {
                "k_pages": kp,
                "v_pages": vp,
                "block_tables": row["bt"][None],
                "prefix_len": row["plen"][None],
                "k_tail": row["tk"][:, None],
                "v_tail": row["tv"][:, None],
                "tail_pos": row["tpos"][None],
            }
            lg, st2 = paged_decode_step(
                params, cfg, st, row["tok"][None], row["pos"][None],
                mesh=mesh, moe_strategy=moe_strategy,
            )
            return {
                "lg": lg[0],
                "tk": st2["k_tail"][:, 0],
                "tv": st2["v_tail"][:, 0],
                "tpos": st2["tail_pos"][0],
            }

        rows = {
            "bt": state["block_tables"],
            "plen": state["prefix_len"],
            "tk": jnp.moveaxis(state["k_tail"], 1, 0),
            "tv": jnp.moveaxis(state["v_tail"], 1, 0),
            "tpos": state["tail_pos"],
            "tok": tokens,
            "pos": cur_pos,
        }
        out = jax.lax.map(row_fn, rows)
        return out["lg"], {
            "k_tail": jnp.moveaxis(out["tk"], 0, 1),
            "v_tail": jnp.moveaxis(out["tv"], 0, 1),
            "tail_pos": out["tpos"],
        }

    from repro.models.layers import attn_paged_decode_layer, slot_update as _slot_update

    with jax.named_scope("embed"):
        x = params["embed"][tokens][:, None, :]  # [B, 1, d]
    slot = cur_pos - state["prefix_len"]
    with jax.named_scope("tail_write"):
        tail_pos = _slot_update(
            state["tail_pos"][..., None], cur_pos[:, None, None], slot
        )[..., 0]

    def body(carry, xs):
        x, = carry
        lp, layer, tk, tv = xs
        x = constrain_activations(x, mesh, seq_dim=None)
        with jax.named_scope("attention"):
            h = apply_norm(cfg.norm, lp["ln1"], x)
            a, ntk, ntv = attn_paged_decode_layer(
                lp["attn"], cfg, h, state["k_pages"], state["v_pages"],
                state["block_tables"], state["prefix_len"],
                tk, tv, tail_pos, cur_pos, slot, layer=layer,
            )
            x = x + a
        with jax.named_scope("mlp"):
            h = apply_norm(cfg.norm, lp["ln2"], x)
            if cfg.moe.num_experts:
                m, _ = _moe_block(lp, cfg, h, mesh, moe_strategy)
            else:
                m = mlp_apply(lp["mlp"], h, cfg.activation)
            x = x + m
        return (x,), (constrain_activations(ntk, mesh), constrain_activations(ntv, mesh))

    # the pool stays stacked: each layer reads its pages in place by index
    (x,), (ntk, ntv) = jax.lax.scan(
        body,
        (x,),
        (params["layers"], jnp.arange(cfg.num_layers), state["k_tail"], state["v_tail"]),
    )
    with jax.named_scope("head"):
        x = apply_norm(cfg.norm, params["final_norm"], x)
        logits = (x[:, 0] @ unembed(cfg, params)).astype(jnp.float32)
    return logits, {"k_tail": ntk, "v_tail": ntv, "tail_pos": tail_pos}


def decode_step(params, cfg, cache, tokens, cur_pos, *, mesh=None, moe_strategy="auto"):
    """One decode step.  tokens, cur_pos: [B]. Returns (logits [B, V], cache)."""
    B = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]  # [B, 1, d]
    Sc = cache["k"].shape[2]
    slot = decode_slot(cfg, Sc, cur_pos)
    new_pos = slot_update(cache["pos"][..., None], cur_pos[:, None, None], slot)[..., 0]
    int8_kv = cfg.kv_cache_dtype == "int8"

    def body(carry, xs):
        x, = carry
        x = constrain_activations(x, mesh, seq_dim=None)
        if int8_kv:
            lp, qk, qv, sk, sv = xs
            # dequantize this layer's cache slice; requantize the new token.
            # On the TPU target the Pallas paged kernel dequantizes page-wise
            # in VMEM instead of materializing the bf16 view.
            ck = dequantize_kv(qk, sk)
            cv = dequantize_kv(qv, sv)
        else:
            lp, ck, cv = xs
        h = apply_norm(cfg.norm, lp["ln1"], x)
        a, nk, nv = attn_decode_layer(lp["attn"], cfg, h, ck, cv, new_pos, cur_pos, slot)
        x = x + a
        h = apply_norm(cfg.norm, lp["ln2"], x)
        if cfg.moe.num_experts:
            m, _ = _moe_block(lp, cfg, h, mesh, moe_strategy)
        else:
            m = mlp_apply(lp["mlp"], h, cfg.activation)
        x = x + m
        if int8_kv:
            nqk, nsk = quantize_kv(nk)
            nqv, nsv = quantize_kv(nv)
            ys = tuple(constrain_activations(t, mesh) for t in (nqk, nqv, nsk, nsv))
        else:
            ys = (constrain_activations(nk, mesh), constrain_activations(nv, mesh))
        return (x,), ys

    if int8_kv:
        xs = (params["layers"], cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
        (x,), (nk, nv, nks, nvs) = jax.lax.scan(body, (x,), xs)
        new_cache = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs, "pos": new_pos}
    else:
        (x,), (nk, nv) = jax.lax.scan(body, (x,), (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": nk, "v": nv, "pos": new_pos}
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = (x[:, 0] @ unembed(cfg, params)).astype(jnp.float32)
    return logits, new_cache
