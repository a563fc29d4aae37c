"""Pallas TPU KV block gather/copy — the claim-restoration hot path.

Restoring an offloaded ResidentClaim re-materializes its KV blocks into the
device pool: a gather of whole pages by an index table.  On TPU this is a
pure DMA problem — each grid step copies one page HBM->VMEM->HBM with the
source page selected by a scalar-prefetched index (Mosaic double-buffers
consecutive grid steps, so copies overlap).  The same kernel serves pool
defragmentation/compaction.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(idx_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def kv_block_copy_pallas(src_pages, indices, *, interpret: bool = False):
    """Gather pages: dst[m] = src[indices[m]].

    src_pages: [N, page_size, KV, D]; indices: [M] int32 -> [M, page, KV, D].
    """
    N, page, KV, D = src_pages.shape
    M = indices.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((1, page, KV, D), lambda m, idx: (idx[m], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, page, KV, D), lambda m, idx: (m, 0, 0, 0)),
    )
    with jax.named_scope("kv_block_copy"):
        return pl.pallas_call(
            _copy_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, page, KV, D), src_pages.dtype),
            interpret=interpret,
        )(indices, src_pages)


def gather_payloads(arrays: Sequence[np.ndarray], *, interpret: Optional[bool] = None) -> List[np.ndarray]:
    """Move N same-shape block payloads through ONE batched kernel gather.

    The transfer backend's multi-block jobs land here: instead of N separate
    per-block copies, the payloads are stacked into a [N, page, KV, D] slab
    and gathered in a single ``kv_block_copy`` launch (one grid, Mosaic
    double-buffers consecutive pages).

    Whether the kernel runs is decided from the shapes, before the launch:
    only uniform page payloads (one shape and dtype, non-empty, at least
    3-D, so the trailing [KV, D] dims tile as a page) go through it.
    Anything else (e.g. packed 1-D state snapshots, whose (size, 1, 1)
    page would pad every element to a full tile on the TPU) takes a plain
    per-array copy.  A kernel failure propagates to the caller — the
    transfer job — and is never papered over with a copy.

    Returns freshly materialized numpy arrays in input order.
    """
    from repro.kernels import ops

    arrays = [np.asarray(a) for a in arrays]
    if not arrays:
        return []
    first = arrays[0]
    uniform_pages = (
        len({(a.shape, a.dtype.str) for a in arrays}) == 1
        and first.size > 0
        and first.ndim >= 3
    )
    if not uniform_pages:
        return [np.array(a, copy=True) for a in arrays]
    # page layout: flatten leading dims so every payload is one page
    page_shape = (int(np.prod(first.shape[:-2])), first.shape[-2], first.shape[-1])
    src = jnp.asarray(np.stack([a.reshape(page_shape) for a in arrays]))
    idx = jnp.arange(len(arrays), dtype=jnp.int32)
    out = np.asarray(ops.kv_block_copy(src, idx, interpret=interpret))
    return [out[i].reshape(a.shape) for i, a in enumerate(arrays)]
