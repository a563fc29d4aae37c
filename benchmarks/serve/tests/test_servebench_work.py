"""The peaks table and the work functions: they count the algorithm's work
only, so padding rows, page size and block-table rounding change nothing."""
import numpy as np
import pytest

from sbtiny import CONFIGS  # (also puts the benchmark on sys.path)
from servebench import spans, work
from servebench import weights as W
from servebench.harness import REPO, load_json

DIMS = work.Dims(layers=28, d_model=2048, heads=16, kv_heads=8, head_dim=128, d_ff=6144, vocab=151936)


def test_an_unknown_device_kind_is_an_error():
    assert work.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks_for("TPU v4")


def test_causal_pairs_match_a_count():
    for lo, hi in ((0, 1), (0, 32), (32, 64), (40, 41), (7, 3)):
        assert work.causal_pairs(lo, hi) == sum(q + 1 for q in range(lo, hi))


def _decode_launch(contexts, width, pad_pos):
    """Positions as the step loop passes them: real rows first, then the
    rows that pad the launch to ``width``."""
    pos = [c - 1 for c in contexts] + [pad_pos] * (width - len(contexts))
    return np.asarray(pos, np.int32)


@pytest.mark.parametrize("width,pad_pos", [(4, 0), (8, 999), (16, 5)])
def test_decode_work_ignores_padding_rows(width, pad_pos):
    contexts = [1100, 700, 33]
    log = spans.CallLog(decode_positions=[_decode_launch(contexts, width, pad_pos)])
    step = {"n_rows": 3, "prefill_tokens": 0, "prefill_rows": 0}
    decode, chunks = spans.pair_calls(log, [step])
    assert decode == [contexts] and chunks == []
    assert work.decode_attention_work(decode[0], DIMS) == work.decode_attention_work(contexts, DIMS)


@pytest.mark.parametrize("page", [4, 8, 16])
def test_decode_work_is_the_same_whatever_the_page_size(page):
    # the same rows laid out in pages of another size, block tables rounded
    # up to 4 pages as the engine does: the work reads only the contexts
    contexts = [1100, 700, 33]
    pages = [-(-c // page) for c in contexts]
    table_width = -(-max(pages) // 4) * 4
    assert table_width * page >= max(contexts)
    flops, nbytes = work.decode_attention_work(contexts, DIMS)
    n = sum(contexts)
    assert flops == 4.0 * DIMS.heads * DIMS.head_dim * n * DIMS.layers
    assert nbytes == pytest.approx(
        DIMS.layers * 2 * (2.0 * DIMS.kv_heads * DIMS.head_dim * n + 2.0 * DIMS.heads * DIMS.head_dim * 3)
    )


def test_chunk_work_counts_real_prompt_tokens_only():
    # a [4, 32] chunk at positions 32..63: rows of 50 and 40 tokens, a row
    # that ended before this chunk, and a padding row replicating row 0
    toks = np.zeros((4, 32), np.int32)
    toks[0, :18] = 7
    toks[1, :8] = 9
    toks[3, :18] = 7
    first = np.full(4, 32, np.int32)
    log = spans.CallLog(chunk_calls=[(toks, first)])
    step = {"n_rows": 0, "prefill_tokens": 96, "prefill_rows": 3}
    _, chunks = spans.pair_calls(log, [step])
    assert chunks == [[(32, 50), (32, 40), (32, 32)]]
    flops, _ = work.prefill_attention_work(chunks[0], DIMS)
    pairs = work.causal_pairs(32, 50) + work.causal_pairs(32, 40)
    assert flops == 4.0 * DIMS.heads * DIMS.head_dim * pairs * DIMS.layers


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_prefill_operations_do_not_depend_on_the_chunk(chunk):
    n = 200
    spans_ = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
    total = sum(work.prefill_attention_work([s], DIMS)[0] for s in spans_)
    assert total == work.prefill_attention_work([(0, n)], DIMS)[0]
    assert sum(work.prefill_chunk_flops([s], DIMS) for s in spans_) == pytest.approx(
        work.prefill_chunk_flops([(0, n)], DIMS)
    )


@pytest.mark.parametrize("name", CONFIGS)
def test_layer_operations_are_twice_the_layer_matrices(name):
    cfg = load_json(REPO / CONFIGS[name])
    dims = work.Dims.from_config(cfg)
    layer_spec, _, _ = W.expected_layout(cfg)
    matrices = sum(int(np.prod(s)) for p, s in layer_spec if len(s) == 2)
    assert work.layer_matmul_flops(dims) == 2.0 * matrices


def test_step_operations_reach_the_roofline_only_below_the_peak():
    # one decode row per step at 1000 keys, one step every 4.2 ms (the time
    # to read qwen3's weights once): far under the peak, as it must be
    flops = work.decode_token_flops(1000, DIMS)
    share = flops / (0.0042 * work.PEAKS["TPU v5 lite"]["bf16_flops"])
    assert 0 < share < 0.01
