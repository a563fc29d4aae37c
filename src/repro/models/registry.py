"""Model registry: one functional API over all assigned architectures.

``build_model(cfg, mesh=None)`` returns a ``ModelBundle`` exposing:
  - init_params(rng)
  - loss_fn(params, batch)            (train_step objective)
  - prefill_fn(params, batch)         -> (last logits, cache)
  - decode_fn(params, cache, tokens, cur_pos) -> (logits, cache)
  - make_cache(batch, cache_len) / batch_spec(shape) / cache_spec(shape)
The *_spec helpers return ShapeDtypeStruct pytrees for the multi-pod dry-run
(no allocation).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import hymba as hymba_lib
from repro.models import transformer as tf_lib
from repro.models import whisper as whisper_lib
from repro.models import xlstm as xlstm_lib


# ---------------------------------------------------------------------------
# analytic parameter counts (MODEL_FLOPS = 6 * N * D uses these)
# ---------------------------------------------------------------------------


def analytic_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    d, ff, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    embed = V * d * (1 if cfg.tie_embeddings else 2)

    if cfg.family == "ssm":  # xlstm
        per_m = 5 * d * d + 2 * d * cfg.num_heads  # q,k,v,g,o + i,f
        per_s = 5 * d * d + 4 * cfg.num_heads * (d // cfg.num_heads) ** 2
        G = L // (cfg.xlstm.mlstm_per_group + cfg.xlstm.slstm_per_group)
        return embed + G * (cfg.xlstm.mlstm_per_group * per_m + cfg.xlstm.slstm_per_group * per_s)

    attn = d * H * Dh + 2 * d * KV * Dh + H * Dh * d
    mlp_mats = 3 if cfg.activation == "silu" else 2
    dense_mlp = mlp_mats * d * ff

    if cfg.moe.num_experts:
        E, k = cfg.moe.num_experts, cfg.moe.experts_per_token
        experts = (k if active_only else E) * mlp_mats * d * ff
        per_layer = attn + experts + d * E
        if cfg.moe.dense_residual:
            per_layer += dense_mlp
    else:
        per_layer = attn + dense_mlp

    if cfg.family == "hybrid":
        di = cfg.ssm.expand * d
        dt_rank = cfg.ssm.dt_rank or max(1, math.ceil(d / 16))
        ssm = d * 2 * di + di * (dt_rank + 2 * cfg.ssm.state_dim) + dt_rank * di + di * d
        per_layer = attn + ssm + dense_mlp

    total = embed + L * per_layer
    if cfg.is_encoder_decoder:
        total += cfg.encoder_layers * (attn + dense_mlp)  # encoder stack
        total += L * (attn)  # decoder cross-attention
    return total


# ---------------------------------------------------------------------------
# bundle
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init_params: Callable[[jax.Array], Any]
    loss_fn: Callable[..., jnp.ndarray]
    prefill_fn: Callable[..., Any]
    decode_fn: Callable[..., Any]
    make_cache: Callable[[int, int], Any]
    batch_spec: Callable[[ShapeSpec], Dict[str, jax.ShapeDtypeStruct]]
    cache_spec: Callable[[ShapeSpec], Any]
    # paged serving entry points (transformer families only; None elsewhere):
    # paged_decode_fn(params, state, tokens, cur_pos) -> (logits, {k_tail, v_tail, tail_pos})
    # prefill_chunk_fn(params, state, tokens, positions) -> (ck, cv) [L,B,C,KV,Dh]
    paged_decode_fn: Optional[Callable[..., Any]] = None
    prefill_chunk_fn: Optional[Callable[..., Any]] = None


def _tokens_spec(b, s):
    return jax.ShapeDtypeStruct((b, s), jnp.int32)


def build_model(cfg: ModelConfig, mesh=None, moe_strategy: str = "auto") -> ModelBundle:
    fam = cfg.family

    if fam == "ssm":  # xlstm
        lib = xlstm_lib
        init_params = partial(lib.init_params, cfg)
        loss = lambda p, b: lib.loss_fn(p, cfg, b, mesh=mesh)
        pre = lambda p, b, cl: lib.prefill(p, cfg, b, mesh=mesh)
        dec = lambda p, c, t, pos: lib.decode_step(p, cfg, c, t, pos, mesh=mesh)
        mk_cache = lambda b, cl: lib.init_state(cfg, b)

        def batch_spec(shape):
            if shape.kind == "decode":
                return {"tokens": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)}
            return {"tokens": _tokens_spec(shape.global_batch, shape.seq_len)}

        def cache_spec(shape):
            return jax.eval_shape(lambda: lib.init_state(cfg, shape.global_batch))

    elif fam == "hybrid":
        lib = hymba_lib
        init_params = partial(lib.init_params, cfg)
        loss = lambda p, b: lib.loss_fn(p, cfg, b, mesh=mesh)
        pre = lambda p, b, cl: lib.prefill(p, cfg, b, cl, mesh=mesh)
        dec = lambda p, c, t, pos: lib.decode_step(p, cfg, c, t, pos, mesh=mesh)
        mk_cache = lambda b, cl: lib.make_cache(cfg, b, cl)

        def batch_spec(shape):
            if shape.kind == "decode":
                return {"tokens": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)}
            return {"tokens": _tokens_spec(shape.global_batch, shape.seq_len)}

        def cache_spec(shape):
            return jax.eval_shape(lambda: lib.make_cache(cfg, shape.global_batch, shape.seq_len))

    elif fam == "audio":  # whisper
        lib = whisper_lib
        init_params = partial(lib.init_params, cfg)
        loss = lambda p, b: lib.loss_fn(p, cfg, b, mesh=mesh)
        pre = lambda p, b, cl: lib.prefill(p, cfg, b, cl, mesh=mesh)
        dec = lambda p, c, t, pos: lib.decode_step(p, cfg, c, t, pos, mesh=mesh)
        mk_cache = lambda b, cl: lib.make_cache(cfg, b, cl)

        def batch_spec(shape):
            b = shape.global_batch
            if shape.kind == "decode":
                return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
            dec_len = min(whisper_lib.DEC_LEN, shape.seq_len)
            return {
                "frames": jax.ShapeDtypeStruct((b, shape.seq_len, cfg.d_model), jnp.bfloat16),
                "tokens": _tokens_spec(b, dec_len),
            }

        def cache_spec(shape):
            return jax.eval_shape(lambda: lib.make_cache(cfg, shape.global_batch, shape.seq_len))

    else:  # dense / moe / vlm -> transformer
        lib = tf_lib
        init_params = partial(lib.init_params, cfg)
        loss = lambda p, b: lib.loss_fn(p, cfg, b, mesh=mesh, moe_strategy=moe_strategy)
        pre = lambda p, b, cl: lib.prefill(p, cfg, b, cl, mesh=mesh, moe_strategy=moe_strategy)
        dec = lambda p, c, t, pos: lib.decode_step(p, cfg, c, t, pos, mesh=mesh, moe_strategy=moe_strategy)
        mk_cache = lambda b, cl: lib.make_cache(cfg, b, cl)
        paged_dec = lambda p, s, t, pos: lib.paged_decode_step(p, cfg, s, t, pos, mesh=mesh, moe_strategy=moe_strategy)
        prefill_chk = lambda p, s, t, pos: lib.prefill_chunk(p, cfg, s, t, pos, mesh=mesh, moe_strategy=moe_strategy)

        def batch_spec(shape):
            b = shape.global_batch
            out = {}
            if shape.kind == "decode":
                out["tokens"] = jax.ShapeDtypeStruct((b,), jnp.int32)
            else:
                out["tokens"] = _tokens_spec(b, shape.seq_len)
                if cfg.frontend == "image_patches":
                    out["patch_embeds"] = jax.ShapeDtypeStruct(
                        (b, cfg.frontend_len, cfg.d_model), jnp.bfloat16
                    )
            return out

        def cache_spec(shape):
            return jax.eval_shape(lambda: lib.make_cache(cfg, shape.global_batch, shape.seq_len))

    paged_kw = {}
    if fam not in ("ssm", "hybrid", "audio") and cfg.kv_cache_dtype != "int8":
        # int8 blocks carry no scale sidecar yet; the paged path requires it,
        # so int8 engines stay on the dense decode path
        paged_kw = {
            "paged_decode_fn": paged_dec,
            "prefill_chunk_fn": prefill_chk,
        }
    return ModelBundle(
        cfg=cfg,
        init_params=init_params,
        loss_fn=loss,
        prefill_fn=pre,
        decode_fn=dec,
        make_cache=mk_cache,
        batch_spec=batch_spec,
        cache_spec=cache_spec,
        **paged_kw,
    )
