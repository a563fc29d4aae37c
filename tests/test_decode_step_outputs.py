"""The paged decode step returns the new tail only, never the page pool.

Under the plain (non-donating) ``jax.jit`` of the serving steps, an input
handed back as an output is a fresh output buffer: a step that returned the
pool would copy both whole pools on every launch.  The step returns
``(logits, {k_tail, v_tail, tail_pos})`` and ``StepLoop`` merges that into
the state it keeps, so the pool, block tables and ``prefix_len`` stay the
arrays the loop put there (the page mirror's own, for the pool).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.scheduler_loop as sl
from repro.configs import get_config, reduced
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine

TAIL = {"k_tail", "v_tail", "tail_pos"}
DOC = tuple(range(200, 224))  # 6 blocks of 4


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def _state_spec(cfg, W, n_pages=32, page=4, P=8, T=16):
    L, KV, D = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct
    return {
        "k_pages": s((L, KV, n_pages, page, D), jnp.bfloat16),
        "v_pages": s((L, KV, n_pages, page, D), jnp.bfloat16),
        "block_tables": s((W, P), jnp.int32),
        "prefix_len": s((W,), jnp.int32),
        "k_tail": s((L, W, T, KV, D), jnp.bfloat16),
        "v_tail": s((L, W, T, KV, D), jnp.bfloat16),
        "tail_pos": s((W, T), jnp.int32),
    }


# W 1 takes the kernel-path branch, W 4 the CPU backend's lax.map branch
@pytest.mark.parametrize("W", [1, 4])
def test_step_output_holds_the_tail_and_no_pool(bp, W):
    bundle, _ = bp
    cfg = bundle.cfg
    params = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    state = _state_spec(cfg, W)
    tok = jax.ShapeDtypeStruct((W,), jnp.int32)
    logits, out = jax.eval_shape(bundle.paged_decode_fn, params, state, tok, tok)
    assert logits.shape == (W, cfg.vocab_size)
    assert set(out) == TAIL
    for key in TAIL:
        assert (out[key].shape, out[key].dtype) == (state[key].shape, state[key].dtype)
    pool = state["k_pages"].shape
    assert all(leaf.shape != pool for leaf in jax.tree.leaves(out))


def test_served_round_keeps_the_mirror_and_the_launch_tails(bp, monkeypatch):
    bundle, params = bp
    eng = ServingEngine(bundle, params, block_size=4, device_blocks=64, cache_len=64)
    eng.run_batch([eng.submit(DOC, max_new_tokens=2)])
    launches = []
    decode = eng._jit_paged_decode

    def spy(params, state, toks, poss):
        lg, out = decode(params, state, toks, poss)
        launches.append((state, out))
        return lg, out

    eng._jit_paged_decode = spy
    checked = []
    real = sl.StepLoop._mixed_step

    def mixed_step(self, pages):
        n = len(launches)
        counts = real(self, pages)
        if len(launches) > n:
            sent, out = launches[-1]
            _, jk, jv = eng._pages_mirror
            assert self._state["k_pages"] is jk and self._state["v_pages"] is jv
            for key in ("block_tables", "prefix_len"):
                assert self._state[key] is sent[key]
            for key in TAIL:
                assert self._state[key] is out[key]
            checked.append(self.step_idx)
        return counts

    monkeypatch.setattr(sl.StepLoop, "_mixed_step", mixed_step)
    rng = np.random.default_rng(7)
    reqs = [
        eng.submit(DOC + tuple(int(t) for t in rng.integers(1, 64, n)), max_new_tokens=m)
        for n, m in [(3, 6), (5, 4), (9, 7)]
    ] + [eng.submit(tuple(int(t) for t in rng.integers(1, 64, 30)), max_new_tokens=5)]
    eng.run_batch(reqs)
    for r in reqs:
        assert r.status == "finished", (r.request_id, r.error)
        assert len(r.output_tokens) == r.max_new_tokens
    assert len(checked) == len(launches) >= 7
    assert all(set(out) == TAIL for _, out in launches)
