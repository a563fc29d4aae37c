"""The traffic generator and the seeded weights.

Every seed must get the same set of sizes (so the same compiled shapes),
in another order, with its own tokens; the reference's layer-by-layer
weights must be bit for bit the ones the program is given."""
from collections import Counter

import jax
import numpy as np
import pytest

import sbtiny
from servebench import weights as W
from servebench.harness import BENCH_DIR, REPO, load_json, program_config
from servebench.traffic import build_cycle

from sbtiny import CONFIGS, MIXES
SEEDS = [0, 1, 2**31 + 7, 2**33 + 1]


def _sizes(cycle):
    return Counter(
        tuple(
            (cycle.doc_lengths[s.doc] if s.doc is not None else 0, s.own_tokens, s.output_tokens)
            for s in rnd
        )
        for rnd in cycle.rounds
    )


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    m = load_json(BENCH_DIR / "traffic" / f"{mix}.json")
    cycles = [build_cycle(m, s, 151936) for s in SEEDS]
    assert all(_sizes(c) == _sizes(cycles[0]) for c in cycles)
    assert len({c.pool_pages() for c in cycles}) == 1
    if len(cycles[0].rounds) > 1:  # the order of the rounds moves with the seed
        assert len({tuple(tuple(r) for r in c.rounds) for c in cycles}) > 1


@pytest.mark.parametrize("mix", MIXES)
def test_tokens_come_from_the_seed(mix):
    m = load_json(BENCH_DIR / "traffic" / f"{mix}.json")
    a, b = build_cycle(m, 11, 151936), build_cycle(m, 11, 151936)
    c = build_cycle(m, 12, 151936)
    p = a.request_tokens(0, 0, 0)
    assert p == b.request_tokens(0, 0, 0)
    assert p != c.request_tokens(0, 0, 0)
    assert min(p) >= 1 and max(p) < 151936  # id 0 marks padding
    # a document keeps its content over replays; a request's own part does not
    again = a.request_tokens(1, 0, 0)
    spec = a.rounds[0][0]
    head = a.doc_lengths[spec.doc] if spec.doc is not None else 0
    assert again[:head] == p[:head]
    assert again[head:] != p[head:]


def test_claimed_documents_fit_the_pool_and_the_host_tier():
    m = load_json(BENCH_DIR / "traffic" / "claim_docs.json")
    c = build_cycle(m, 3, 151936)
    assert c.host_pages() >= c.doc_pages()
    assert c.pool_pages() >= c.doc_pages() + c.clients * 2
    assert build_cycle(load_json(BENCH_DIR / "traffic" / "chat_unshared.json"), 3, 10).host_pages() is None


def test_reference_layers_are_the_programs_weights():
    cell = sbtiny.tiny_cell()
    cfg = cell.config
    from repro.models.registry import build_model

    bundle = build_model(program_config(cfg))
    shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    assert W.program_layout(shapes) == W.expected_layout(cfg)
    seed = 2**32 + 3
    params = W.make_params(shapes, seed, cfg["hidden_size"])
    flat = {W._path(kp): leaf for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    layer_spec, glob_spec, n = W.expected_layout(cfg)
    for layer in range(n):
        for p, a in W.layer_weights(seed, layer, layer_spec, cfg["hidden_size"]).items():
            np.testing.assert_array_equal(np.asarray(flat["layers/" + p][layer]), np.asarray(a))
    for p, a in W.global_weights(seed, glob_spec, cfg["hidden_size"]).items():
        np.testing.assert_array_equal(np.asarray(flat[p]), np.asarray(a))
    other = W.make_params(shapes, seed + 1, cfg["hidden_size"])
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(params["embed"]))


@pytest.mark.parametrize("name", CONFIGS)
def test_the_program_holds_each_configuration_as_stated(name):
    cfg = load_json(REPO / CONFIGS[name])
    from repro.models.registry import build_model

    mc = program_config(cfg)
    assert (mc.num_layers, mc.d_model, mc.num_heads, mc.num_kv_heads, mc.d_ff, mc.vocab_size) == (
        cfg["num_hidden_layers"],
        cfg["hidden_size"],
        cfg["num_attention_heads"],
        cfg["num_key_value_heads"],
        cfg["intermediate_size"],
        cfg["vocab_size"],
    )
    shapes = jax.eval_shape(build_model(mc).init_params, jax.random.PRNGKey(0))
    assert W.program_layout(shapes) == W.expected_layout(cfg)
