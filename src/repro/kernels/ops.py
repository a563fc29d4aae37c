"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; elsewhere (this CPU container) they run
in interpret mode, which executes the kernel body in Python for correctness
validation against ref.py.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kv_block_copy import kv_block_copy_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_decode_attention_pallas,
    paged_prefill_attention_pallas,
)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0, block_q=128, block_k=128, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *, softcap=0.0, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return paged_attention_pallas(
        q, k_pages, v_pages, block_tables, lengths, softcap=softcap, interpret=interpret
    )


@partial(jax.jit, static_argnames=("softcap", "window", "interpret"))
def paged_decode_attention(
    q, k_pages, v_pages, block_tables, prefix_len, k_tail, v_tail, tail_pos,
    cur_pos, *, softcap=0.0, window=0, layer=None, interpret=None,
):
    """Batched serving decode: block-table prefix + in-flight tail (``layer``
    picks one layer of a stacked [L, KV, N, page, D] pool)."""
    interpret = _interpret_default() if interpret is None else interpret
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, block_tables, prefix_len, k_tail, v_tail,
        tail_pos, cur_pos, softcap=softcap, window=window, layer=layer,
        interpret=interpret,
    )


@partial(jax.jit, static_argnames=("softcap", "window", "interpret"))
def paged_prefill_attention(
    q, k_pages, v_pages, block_tables, prefix_len, k_chunk, v_chunk,
    *, softcap=0.0, window=0, interpret=None,
):
    """Chunked prefill: one chunk of queries over block-table prefix pages
    plus the chunk's own keys (causal within chunk) — O(chunk) prefill KV."""
    interpret = _interpret_default() if interpret is None else interpret
    return paged_prefill_attention_pallas(
        q, k_pages, v_pages, block_tables, prefix_len, k_chunk, v_chunk,
        softcap=softcap, window=window, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("interpret",))
def kv_block_copy(src_pages, indices, *, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return kv_block_copy_pallas(src_pages, indices, interpret=interpret)
