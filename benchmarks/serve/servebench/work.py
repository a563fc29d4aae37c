"""Peak rates of the chip and the work each kernel and step needs.

The operations and bytes here count what the algorithm needs for the real
rows at their real context lengths and the published widths.  Padded
rows, masked pages, the page size and block-table rounding are choices of
an implementation and never enter, so a roofline share built from these
numbers reads the same work whatever computes it and cannot pass 100%.

Derived from ``repro.roofline.analytic`` (``forward_flops``,
``param_bytes``, ``cache_bytes``), restated per call and per row.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Sequence, Tuple

# Peaks per chip, keyed by ``jax.Device.device_kind``.  A kind that is not
# here is an error: no number is ever read against a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM at 819 GB/s per chip",
    },
}

BF16 = 2  # bytes per element of weights, activations and KV as served


def peaks_for(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} has no entry in the peaks table "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Dims:
    """Widths of a dense decoder that the work functions need."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        heads = int(cfg["num_attention_heads"])
        d = int(cfg["hidden_size"])
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            d_model=d,
            heads=heads,
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or d // heads),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
        )


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, object]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / float(peaks["bf16_flops"])
    t_m = nbytes / float(peaks["hbm_bytes_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ------------------------------------------------------------------ kernels
def decode_attention_work(contexts: Iterable[int], dims: Dims) -> Tuple[float, float]:
    """Paged decode attention over all layers for one launch.

    ``contexts``: for each real row, the keys its one query attends (its
    position + 1).  Per row and layer: QK^T and PV are 2 * 2 * H * Dh * n
    operations; K and V of the KV heads are read once (n * KV * Dh each),
    the query read and the output written once (H * Dh each).
    """
    L, H, KV, Dh = dims.layers, dims.heads, dims.kv_heads, dims.head_dim
    flops = nbytes = 0.0
    for n in contexts:
        flops += 4.0 * H * Dh * n
        nbytes += (2.0 * KV * n * Dh + 2.0 * H * Dh) * BF16
    return flops * L, nbytes * L


def causal_pairs(lo: int, hi: int) -> int:
    """Query-key pairs of causal attention for queries at positions lo..hi-1
    over keys 0..q: sum of (q + 1)."""
    if hi <= lo:
        return 0
    return (hi * (hi + 1) - lo * (lo + 1)) // 2


def prefill_attention_work(spans: Iterable[Tuple[int, int]], dims: Dims) -> Tuple[float, float]:
    """Chunked prefill attention over all layers for one launch.

    ``spans``: for each real row, the absolute positions ``[lo, hi)`` of its
    real queries in this chunk (prompt tokens only, no right padding).  Keys
    are the row's positions 0..hi-1: those before ``lo`` come from pages,
    the rest from the chunk itself.
    """
    L, H, KV, Dh = dims.layers, dims.heads, dims.kv_heads, dims.head_dim
    flops = nbytes = 0.0
    for lo, hi in spans:
        if hi <= lo:
            continue
        flops += 4.0 * H * Dh * causal_pairs(lo, hi)
        nbytes += (2.0 * KV * hi * Dh + 2.0 * H * (hi - lo) * Dh) * BF16
    return flops * L, nbytes * L


# --------------------------------------------------------------- whole step
def layer_matmul_flops(dims: Dims) -> float:
    """Projection and MLP operations of one layer for one token."""
    d, H, KV, Dh, ff = dims.d_model, dims.heads, dims.kv_heads, dims.head_dim, dims.d_ff
    return 2.0 * (d * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * ff)


def decode_token_flops(context: int, dims: Dims) -> float:
    """A token pushed through a decode launch (decoded or fed): every layer,
    attention over ``context`` keys, and the logits over the vocabulary."""
    attn = 4.0 * dims.heads * dims.head_dim * context
    return dims.layers * (layer_matmul_flops(dims) + attn) + 2.0 * dims.d_model * dims.vocab


def prefill_chunk_flops(spans: Sequence[Tuple[int, int]], dims: Dims) -> float:
    """Real prompt tokens of one chunk launch.  A chunk yields the KV of
    every layer and no logits, so the last layer needs only its K and V
    projections: L - 1 whole layers (with attention) plus those two."""
    d, KV, Dh = dims.d_model, dims.kv_heads, dims.head_dim
    full = dims.layers - 1
    total = 0.0
    for lo, hi in spans:
        n = max(0, hi - lo)
        total += n * (full * layer_matmul_flops(dims) + 2.0 * d * 2 * KV * Dh)
        total += full * 4.0 * dims.heads * Dh * causal_pairs(lo, hi)
    return total
