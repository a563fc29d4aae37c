"""The benchmark's reductions: percentiles over all samples, rates over the
window, inter-token gaps from step records, and the trace reduction on a
small trace recorded on the CPU (``data/cpu_window.xplane.pb``: three
``sb.run_batch`` spans inside ``sb.window``, each a matmul followed by a
20 ms ``sb._device_pages`` span and 10 ms outside any inner span)."""
import math
from pathlib import Path

import pytest

import sbtiny  # noqa: F401  (puts the benchmark on sys.path)
from servebench import reduce as R

TRACE = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def test_percentile_is_nearest_rank_over_every_sample():
    xs = list(range(1, 101))
    assert R.percentile(xs, 90) == 90
    assert R.percentile(xs, 99) == 99
    assert R.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        R.percentile([], 50)


def test_a_failed_request_counts_as_an_infinite_time():
    ttft = [0.1] * 9 + [math.inf]
    assert R.percentile(ttft, 90) == 0.1
    assert R.percentile(ttft + [math.inf], 90) == math.inf


def test_weighted_percentile_equals_the_expanded_samples():
    pairs = [(0.02, 50), (0.5, 3), (0.03, 40), (1.0, 1)]
    flat = [v for v, c in pairs for _ in range(c)]
    for q in (50, 90, 95, 99, 100):
        assert R.weighted_percentile(pairs, q) == R.percentile(flat, q)


def _steps(gaps_s, rows):
    ts, out = 0.0, []
    for g in [0.0] + gaps_s:
        ts += g
        out.append({"ts": ts, "n_decode": rows})
    return out


def test_a_stall_shows_in_p99_over_all_gaps():
    # 200 steps of 20 ms with 8 decode rows, and three 400 ms steps (whole
    # pool uploads): 1.5% of the gaps, invisible in a median, the p99
    gaps = [0.02] * 200
    for k in (50, 100, 150):
        gaps[k] = 0.4
    pairs = R.step_token_gaps(_steps(gaps, 8), first_token_ts=[])
    assert sum(c for _, c in pairs) == 200 * 8
    assert R.weighted_percentile(pairs, 50) == pytest.approx(0.02)
    assert R.weighted_percentile(pairs, 99) == pytest.approx(0.4)


def test_a_first_token_is_not_an_inter_token_gap():
    steps = _steps([0.02, 0.02, 0.02], 4)  # ts 0, .02, .04, .06
    # two rows handed out their first token at the start of step 2
    pairs = R.step_token_gaps(steps, first_token_ts=[0.03, 0.035])
    assert pairs == [(pytest.approx(0.02), 4), (pytest.approx(0.02), 2), (pytest.approx(0.02), 4)]


def test_rate_is_over_the_whole_window():
    assert R.rate(600, 12.0) == 50.0
    with pytest.raises(ValueError):
        R.rate(1, 0.0)


@pytest.fixture(scope="module")
def trace():
    return R.load_trace(str(TRACE), device_plane_prefix="/host:CPU", op_line="tf_XLAPjRtCpuClient")


def test_trace_busy_and_idle_partition_the_window(trace):
    (ops,) = trace.device_ops.values()
    (win,) = [s for s in trace.host_spans if s.name == "sb.window"]
    busy = R.busy_ns(ops, win.start_ns, win.end_ns)
    idle = sum(e - s for s, e in R.idle_gaps(ops, win.start_ns, win.end_ns))
    assert 0 < busy < win.dur_ns
    assert busy + idle == pytest.approx(win.dur_ns)
    # the window sleeps 3 x 30 ms on the host: most of it is idle
    assert idle / 1e9 > 0.08


def test_idle_gaps_go_to_the_innermost_covering_span(trace):
    (ops,) = trace.device_ops.values()
    (win,) = [s for s in trace.host_spans if s.name == "sb.window"]
    index = R.SpanIndex([s for s in trace.host_spans if s.name != "sb.window"])
    gaps = R.idle_gaps(ops, win.start_ns, win.end_ns)
    by = R.idle_by_span(gaps, index)
    assert max(by, key=by.get) == "sb._device_pages"
    top = R.top_gaps(gaps, index, k=3)
    assert [name for name, _ in top] == ["sb._device_pages"] * 3
    assert all(s >= 0.02 for _, s in top)


def test_kernel_time_and_top_ops(trace):
    (ops,) = trace.device_ops.values()
    (win,) = [s for s in trace.host_spans if s.name == "sb.window"]
    t, n = R.kernel_ns(ops, "dot_general", win.start_ns, win.end_ns)
    assert n >= 3 and t > 0
    top = R.top_ops(ops, win.start_ns, win.end_ns, k=10)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_span_index_prefers_the_inner_span_over_its_parent():
    outer = R.Interval("sb.run_batch", 0, 1000)
    inner = R.Interval("sb._device_pages", 100, 400)
    index = R.SpanIndex([outer, inner])
    assert index.attribute((150, 350)) == "sb._device_pages"
    assert index.attribute((500, 900)) == "sb.run_batch"
    assert index.attribute((2000, 3000)) == "host:idle"
