"""Plain float32 reference of a dense decoder, and its lower-precision control.

Written from the published architecture (Llama / Qwen3 ``modeling`` code as
described in their configs and papers), with nothing taken from the
program: pre-norm blocks ``x + attn(rms(x))`` then ``x + mlp(rms(x))``;
RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; Qwen3 alone normalizes each
head's query and key the same way before the rotary embedding; rotary
embedding in the rotate-half form with inverse frequencies
``theta^(-2i/Dh)``; causal softmax attention with scale ``1/sqrt(Dh)``
and grouped key/value heads; SwiGLU MLP ``down(silu(gate x) * up x)``;
final RMSNorm and logits against the tied embedding or the output head.

Every matrix product runs at ``Precision.HIGHEST`` in float32 over the
bfloat16 weights the seed gives.  The whole forward pass runs over each
prompt with its served tokens, layer by layer (one layer's weights on the
device at a time), so it fits beside nothing else once the program's
state is freed.

The control computes the same pass with every matrix product's inputs
rounded to float8 (e4m3, one scale per row of activations, per output
channel of weights, per head vector of queries, keys and values): the
step below the bfloat16 the configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from servebench import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    """a [..., k] @ w [k, n] in float32, or with float8 inputs for the control."""
    if low:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, h, D]; rotate-half form."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2 :]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    theta: float
    eps: float
    qk_norm: bool
    tied: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            d=d,
            heads=H,
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or d // H),
            vocab=int(cfg["vocab_size"]),
            theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]),
            qk_norm=W.qk_norm(cfg),
            tied=bool(cfg["tie_word_embeddings"]),
        )


def _layer_one(x, w, arch: Arch, low: bool):
    """One block over one sequence x [S, d]."""
    S = x.shape[0]
    H, KV, D = arch.heads, arch.kv_heads, arch.head_dim
    pos = jnp.arange(S)
    h = _rms(x, w["ln1/w"], arch.eps)
    q = _mm(h, w["attn/wq"], low).reshape(S, H, D)
    k = _mm(h, w["attn/wk"], low).reshape(S, KV, D)
    v = _mm(h, w["attn/wv"], low).reshape(S, KV, D)
    if arch.qk_norm:
        q = _rms(q, w["attn/q_norm"], arch.eps)
        k = _rms(k, w["attn/k_norm"], arch.eps)
    q, k = _rope(q, pos, arch.theta), _rope(k, pos, arch.theta)
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    G = H // KV
    q = q.reshape(S, KV, G, D)
    s = jnp.einsum("qkgd,pkd->kgqp", q, k, precision=HI) / jnp.sqrt(jnp.float32(D))
    mask = pos[:, None] >= pos[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("kgqp,pkd->qkgd", p, v, precision=HI).reshape(S, H * D)
    x = x + _mm(a, w["attn/wo"], low)
    h = _rms(x, w["ln2/w"], arch.eps)
    m = jax.nn.silu(_mm(h, w["mlp/w_gate"], low)) * _mm(h, w["mlp/w_up"], low)
    return x + _mm(m, w["mlp/w_down"], low)


def _layer_batch(x, w, arch: Arch, low: bool):
    """x [n, S, d]: one sequence at a time, so attention scores of one
    sequence are all that is held."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return jax.lax.map(lambda xi: _layer_one(xi, w, arch, low), x)


_layer = jax.jit(_layer_batch, static_argnums=(2, 3))


def _logits_impl(h, norm_w, head, eps):
    """h [T, d] -> logits [T, V] against head [d, V]."""
    hn = _rms(h, norm_w.astype(jnp.float32), eps)
    return jnp.einsum("td,dv->tv", hn, head.astype(jnp.float32), precision=HI)


def _logits_low_impl(h, norm_w, head, eps):
    hn = _rms(h, norm_w.astype(jnp.float32), eps)
    return _mm(hn, head.astype(jnp.float32), True)


_logits = jax.jit(_logits_impl, static_argnums=(3,))
_logits_low = jax.jit(_logits_low_impl, static_argnums=(3,))


@dataclass
class Served:
    """One finished request: its prompt and the tokens the program served."""

    prompt: Tuple[int, ...]
    output: Tuple[int, ...]


@dataclass
class GapReading:
    max_gap: float  # widest gap of a served token below the reference's best
    tokens: int  # served tokens compared
    agree: float  # share of served tokens that are the reference's argmax
    control_max_gap: Optional[float] = None  # same, for the control's argmax
    control_agree: Optional[float] = None


def compare(cfg: dict, seed: int, served: Sequence[Served], seq_pad: int, *, control: bool = False) -> GapReading:
    """Run the reference over each prompt with its served tokens and read, at
    every served token, how far its reference logit lies below the
    reference's best.  With ``control`` the float8 pass runs beside it and
    the same is read for the token the control puts first."""
    arch = Arch.from_config(cfg)
    layer_spec, glob_spec, n_layers = W.expected_layout(cfg)
    n = len(served)
    toks = np.zeros((n, seq_pad), np.int32)
    targets: List[Tuple[int, int, int]] = []  # (row, position, served token)
    for i, r in enumerate(served):
        seq = r.prompt + r.output
        if len(seq) > seq_pad:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds the pad {seq_pad}")
        toks[i, : len(seq)] = seq
        for j, t in enumerate(r.output):
            targets.append((i, len(r.prompt) - 1 + j, int(t)))
    g = W.global_weights(seed, glob_spec, arch.d)
    x = jnp.take(g["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    xc = x if control else None
    for layer in range(n_layers):
        lw = W.layer_weights(seed, layer, layer_spec, arch.d)
        x = _layer(x, lw, arch, False)
        if control:
            xc = _layer(xc, lw, arch, True)
        del lw
    head = g["embed"].T if arch.tied else g["lm_head"]
    rows = jnp.asarray([t[0] for t in targets])
    cols = jnp.asarray([t[1] for t in targets])
    served_tok = np.asarray([t[2] for t in targets])
    ref = np.asarray(_logits(x[rows, cols], g["final_norm/w"], head, arch.eps))
    best = ref.max(axis=1)
    idx = np.arange(len(targets))
    gap = best - ref[idx, served_tok]
    out = GapReading(
        max_gap=float(gap.max()),
        tokens=len(targets),
        agree=float(np.mean(ref.argmax(axis=1) == served_tok)),
    )
    if control:
        low = np.asarray(_logits_low(xc[rows, cols], g["final_norm/w"], head, arch.eps))
        top = low.argmax(axis=1)
        out.control_max_gap = float((best - ref[idx, top]).max())
        out.control_agree = float(np.mean(ref.argmax(axis=1) == top))
    return out
