"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the TPU compiler checks whether Mosaic accepts them (block tiling,
VMEM budget).  These cases compile at qwen3-1.7b widths for one chip of a
described ``v5e:2x2`` topology — no chip is attached, nothing runs — and
assert that the compiled program carries the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry written without a chip cannot be read back).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.kv_block_copy import kv_block_copy_pallas
from repro.kernels.paged_attention import (
    paged_decode_attention_pallas,
    paged_prefill_attention_pallas,
)
from repro.models.registry import build_model

CFG = get_config("qwen3-1.7b")
KV, D = CFG.num_kv_heads, CFG.resolved_head_dim
G = CFG.num_heads // KV
PAGE, N_PAGES, P, T, C = 8, 512, 16, 24, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _decode_kernel(s, B, kv=KV, g=G, p=P):
    args = (
        s((B, kv, g, D)), s((kv, N_PAGES, PAGE, D)), s((kv, N_PAGES, PAGE, D)),
        s((B, p), jnp.int32), s((B,), jnp.int32),
        s((B, kv, T, D)), s((B, kv, T, D)),
        s((B, T), jnp.int32), s((B,), jnp.int32),
    )
    return paged_decode_attention_pallas, args


def _prefill_kernel(s, B):
    args = (
        s((B, KV, G, C, D)), s((KV, N_PAGES, PAGE, D)), s((KV, N_PAGES, PAGE, D)),
        s((B, 4 * P), jnp.int32), s((B,), jnp.int32),
        s((B, KV, C, D)), s((B, KV, C, D)),
    )
    return paged_prefill_attention_pallas, args


def _block_copy(s):
    # the [N, L*page, KV, D] slab gather_payloads stacks from KV blocks
    args = (s((6, CFG.num_layers * PAGE, KV, D)), s((6,), jnp.int32))
    return kv_block_copy_pallas, args


def _decode_step(s, n=64):
    bundle = build_model(CFG)
    params = jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0)),
    )
    L, W = CFG.num_layers, 4
    state = {
        "k_pages": s((L, KV, n, PAGE, D)),
        "v_pages": s((L, KV, n, PAGE, D)),
        "block_tables": s((W, P), jnp.int32),
        "prefix_len": s((W,), jnp.int32),
        "k_tail": s((L, W, T, KV, D)),
        "v_tail": s((L, W, T, KV, D)),
        "tail_pos": s((W, T), jnp.int32),
    }
    return bundle.paged_decode_fn, (params, state, s((W,), jnp.int32), s((W,), jnp.int32))


CASES = {
    "paged_decode_b1": lambda s: _decode_kernel(s, 1),
    "paged_decode_b4": lambda s: _decode_kernel(s, 4),  # (1, T) tail block regression
    # a run of 8 pages with all 8 KV heads a grid step (qwen3-1.7b)
    "paged_decode_b8_p256": lambda s: _decode_kernel(s, 8, p=256),
    # deepseek-7b's MHA: KV 32, G 1, 2 pages a step
    "paged_decode_mha_kv32_b8_p256": lambda s: _decode_kernel(s, 8, kv=32, g=1, p=256),
    "chunked_prefill_b4": lambda s: _prefill_kernel(s, 4),
    "kv_block_copy": _block_copy,
    "paged_decode_step": _decode_step,
}


@pytest.mark.parametrize("case", list(CASES))
def test_serving_path_compiles_for_v5e(one_chip, monkeypatch, case):
    # the model layers pick the kernel path by asking jax.default_backend(),
    # which still answers "cpu" here: steer it for this compile only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = CASES[case](_spec(one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the claim_docs cell's pool: at 64 pages the compiler stages the whole pool
# into another memory space (a pool-shaped ``ConcatBitcast``), at the cells'
# sizes it reads the pool where it lies
CELL_PAGES = 1414


def test_decode_step_passes_no_pool_through_for_v5e(one_chip, monkeypatch):
    # an input returned from the non-donating jit leaves it as a fresh
    # buffer: a pool returned would be copied, K and V, on every launch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = _decode_step(_spec(one_chip), n=CELL_PAGES)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    pool = re.escape("bf16[" + ",".join(map(str, args[1]["k_pages"].shape)) + "]")
    ops = re.findall(rf"= {pool}\{{[^}}]*\}} ([\w-]+)\(", hlo)
    # read in place: the pool enters as a parameter (the scan's loop
    # carries it), and no instruction writes a pool-shaped result
    assert ops and set(ops) <= {"parameter", "get-tuple-element"}, sorted(set(ops))
    entry = hlo[hlo.index("\nENTRY ") :]
    root = next(line for line in entry.splitlines() if line.lstrip().startswith("ROOT "))
    assert not re.search(pool, root), root[:300]
