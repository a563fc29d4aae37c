"""Kernel microbenchmarks: Pallas (interpret on CPU / native on TPU) vs
pure-jnp reference.  On this CPU container the numbers validate plumbing and
relative shapes only — wall-clock kernel performance is a TPU measurement."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(out_path: Path = Path("results/kernel-bench.json")):
    rng = np.random.default_rng(0)
    rows = []

    # flash attention
    B, H, KV, S, D = 1, 4, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    t_ref = _time(lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)
    t_pal = _time(lambda a, b, c: ops.flash_attention(a, b, c, block_q=64, block_k=64), q, k, v)
    err = float(jnp.max(jnp.abs(
        ops.flash_attention(q, k, v, block_q=64, block_k=64) - ref.flash_attention_ref(q, k, v)
    )))
    rows.append({"name": "flash_attention_256", "ref_us": t_ref, "pallas_interpret_us": t_pal, "max_err": err})

    # paged attention
    KV2, G, page, P, N = 2, 2, 16, 8, 32
    q2 = jnp.asarray(rng.normal(size=(2, KV2, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(KV2, N, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(KV2, N, page, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, N, (2, P)), jnp.int32)
    ln = jnp.asarray([P * page, P * page // 2], jnp.int32)
    t_ref = _time(lambda *a: ref.paged_attention_ref(*a), q2, kp, vp, bt, ln)
    t_pal = _time(lambda *a: ops.paged_attention(*a), q2, kp, vp, bt, ln)
    err = float(jnp.max(jnp.abs(
        ops.paged_attention(q2, kp, vp, bt, ln) - ref.paged_attention_ref(q2, kp, vp, bt, ln)
    )))
    rows.append({"name": "paged_attention_8pages", "ref_us": t_ref, "pallas_interpret_us": t_pal, "max_err": err})

    # kv block copy (claim restore gather)
    src = jnp.asarray(rng.normal(size=(64, 16, 4, 64)), jnp.bfloat16)
    idx = jnp.asarray(rng.permutation(64)[:16], jnp.int32)
    t_ref = _time(lambda *a: ref.kv_block_copy_ref(*a), src, idx)
    t_pal = _time(lambda *a: ops.kv_block_copy(*a), src, idx)
    rows.append({"name": "kv_block_copy_16x", "ref_us": t_ref, "pallas_interpret_us": t_pal, "max_err": 0.0})

    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(rows, indent=1))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(f"{r['name']},{r['pallas_interpret_us']:.1f},max_err={r['max_err']:.2e}")
