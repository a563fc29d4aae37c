"""Seeded weights, made on the device in the type they are served in.

Every leaf is drawn from a key folded from ``--seed``, the leaf's path and,
for stacked layer leaves, the layer index.  The whole model is one jitted
call; the reference regenerates one layer at a time through the same
function (a one-element layer index), so it reads the same numbers without
taking anything the program holds.

Scales: a weight matrix is N(0, 1/fan_in); the embedding and the output
head are N(0, (3/sqrt(d))^2), so logits have a standard deviation of about
3; a norm weight is 1 + N(0, 0.1^2), so that applying it is visible.
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
NORM_LEAVES = ("ln1/w", "ln2/w", "final_norm/w", "attn/q_norm", "attn/k_norm")
MATRIX_LEAVES = (
    "attn/wq", "attn/wk", "attn/wv", "attn/wo",
    "mlp/w_gate", "mlp/w_up", "mlp/w_down",
)
LayerSpec = Tuple[Tuple[str, Tuple[int, ...]], ...]  # (path, per-layer shape)


def root_key(seed: int) -> jax.Array:
    """A key for any whole number up to 2**62: PRNGKey takes 31 bits, the
    rest is folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key: jax.Array, path: str, shape: Tuple[int, ...], d_model: int) -> jax.Array:
    z = jax.random.normal(_leaf_key(key, path), shape, jnp.float32)
    if path.endswith(NORM_LEAVES):
        return (1.0 + 0.1 * z).astype(DTYPE)
    if path in ("embed", "lm_head"):
        return (z * (3.0 / d_model**0.5)).astype(DTYPE)
    if path.endswith(MATRIX_LEAVES):
        return (z * (1.0 / shape[-2] ** 0.5)).astype(DTYPE)
    raise KeyError(f"no weight rule for leaf {path!r}")


def _layers(key: jax.Array, idx: jax.Array, spec: LayerSpec, d_model: int) -> Dict[str, jax.Array]:
    lkey = jax.random.fold_in(key, 1)

    def one(i):
        k = jax.random.fold_in(lkey, i)
        return {p: _draw(k, "layers/" + p, shape, d_model) for p, shape in spec}

    return jax.vmap(one)(idx)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def program_layout(shapes) -> Tuple[LayerSpec, Tuple[Tuple[str, Tuple[int, ...]], ...], int]:
    """Split the program's parameter tree (from ``jax.eval_shape``) into the
    per-layer spec, the global leaves and the layer count."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    layer, glob, n_layers = [], [], None
    for kp, leaf in flat:
        p = _path(kp)
        if p.startswith("layers/"):
            layer.append((p[len("layers/"):], tuple(leaf.shape[1:])))
            n_layers = leaf.shape[0]
        else:
            glob.append((p, tuple(leaf.shape)))
        if leaf.dtype != DTYPE:
            raise TypeError(f"leaf {p} is {leaf.dtype}, the benchmark serves {DTYPE}")
    return tuple(sorted(layer)), tuple(sorted(glob)), int(n_layers)


def make_params(shapes, seed: int, d_model: int):
    """The program's whole parameter tree, in one jitted call on the device."""
    layer_spec, glob_spec, n_layers = program_layout(shapes)
    treedef = jax.tree_util.tree_structure(shapes)
    flat_paths = [_path(kp) for kp, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]

    @jax.jit
    def build(key):
        out = {p: _draw(key, p, shape, d_model) for p, shape in glob_spec}
        layers = _layers(key, jnp.arange(n_layers), layer_spec, d_model)
        out.update({"layers/" + p: a for p, a in layers.items()})
        return [out[p] for p in flat_paths]

    return jax.tree_util.tree_unflatten(treedef, build(root_key(seed)))


def layer_weights(seed: int, layer: int, layer_spec: LayerSpec, d_model: int) -> Dict[str, jax.Array]:
    """One layer's leaves, as ``make_params`` made them."""
    out = _one_layer(root_key(seed), jnp.asarray([layer]), layer_spec, d_model)
    return {p: a[0] for p, a in out.items()}


def global_weights(seed: int, glob_spec: Sequence[Tuple[str, Tuple[int, ...]]], d_model: int) -> Dict[str, jax.Array]:
    return _globals(root_key(seed), tuple(glob_spec), d_model)


_one_layer = jax.jit(_layers, static_argnums=(2, 3))


def _globals_impl(key, glob_spec, d_model):
    return {p: _draw(key, p, shape, d_model) for p, shape in glob_spec}


_globals = jax.jit(_globals_impl, static_argnums=(1, 2))


def expected_layout(cfg: dict) -> Tuple[LayerSpec, Tuple[Tuple[str, Tuple[int, ...]], ...], int]:
    """The leaves a dense decoder of this configuration has, by the
    benchmark's own naming, worked out from the configuration alone."""
    d, ff, V = int(cfg["hidden_size"]), int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    H, KV = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    Dh = int(cfg.get("head_dim") or d // H)
    layer = {
        "ln1/w": (d,), "ln2/w": (d,),
        "attn/wq": (d, H * Dh), "attn/wk": (d, KV * Dh), "attn/wv": (d, KV * Dh),
        "attn/wo": (H * Dh, d),
        "mlp/w_gate": (d, ff), "mlp/w_up": (d, ff), "mlp/w_down": (ff, d),
    }
    if qk_norm(cfg):
        layer.update({"attn/q_norm": (Dh,), "attn/k_norm": (Dh,)})
    glob = {"embed": (V, d), "final_norm/w": (d,)}
    if not cfg["tie_word_embeddings"]:
        glob["lm_head"] = (d, V)
    return tuple(sorted(layer.items())), tuple(sorted(glob.items())), int(cfg["num_hidden_layers"])


def qk_norm(cfg: dict) -> bool:
    """Qwen3 normalizes each head's query and key (RMSNorm over head_dim)
    before the rotary embedding; Llama-architecture models do not."""
    return cfg["model_type"] == "qwen3"
