"""Reductions from what a run recorded to the numbers it reports.

Percentiles are taken over every sample (nearest rank, no interpolation,
no medians of groups); rates over the whole window.  The trace reduction
reads a ``jax.profiler`` xplane file: device busy time is the union of the
intervals in which an operation ran, kernel time the sum of a kernel's
operation durations, and each idle gap on the device is attributed to the
host span that covers most of it.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


# ------------------------------------------------------------ percentiles
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over all samples: the smallest value with at
    least ``q``% of the samples at or below it.  ``inf`` samples count."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def weighted_percentile(pairs: Iterable[Tuple[float, int]], q: float) -> float:
    """Nearest-rank percentile of values that each stand for ``count``
    samples (a step gap shared by every row that decoded in that step)."""
    pts = sorted((v, c) for v, c in pairs if c > 0)
    total = sum(c for _, c in pts)
    if total == 0:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * total))
    seen = 0
    for v, c in pts:
        seen += c
        if seen >= rank:
            return v
    return pts[-1][0]


def rate(total: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return total / seconds


def step_token_gaps(
    steps: Sequence[dict], first_token_ts: Sequence[float]
) -> List[Tuple[float, int]]:
    """Inter-token gaps of one round's step loop.

    ``steps``: the round's ``step_scheduled`` records in order, each with
    ``ts`` (seconds) and ``n_decode``.  Every decode row in step k handed out
    one token at the start of k, so each of them waited ts[k] - ts[k-1]
    since its previous token, except a row whose FIRST token came in step
    k (that wait is its time to first token, not a gap).  A first token
    handed out at time t belongs to the first step whose ``ts`` is >= t.
    Returns (gap seconds, rows) pairs.
    """
    firsts: Dict[int, int] = defaultdict(int)
    ts = [s["ts"] for s in steps]
    for t in first_token_ts:
        k = _first_at_or_after(ts, t)
        if k is not None:
            firsts[k] += 1
    out = []
    for k in range(1, len(steps)):
        rows = steps[k]["n_decode"] - firsts.get(k, 0)
        if rows > 0:
            out.append((ts[k] - ts[k - 1], rows))
    return out


def _first_at_or_after(ts: Sequence[float], t: float) -> Optional[int]:
    lo, hi = 0, len(ts)
    while lo < hi:
        mid = (lo + hi) // 2
        if ts[mid] < t:
            lo = mid + 1
        else:
            hi = mid
    return lo if lo < len(ts) else None


# ---------------------------------------------------------------- traces
@dataclass
class Interval:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Trace:
    """Device operations per device, and the host's spans, on one clock."""

    device_ops: Dict[str, List[Interval]] = field(default_factory=dict)
    host_spans: List[Interval] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def op_name(text: str) -> str:
    """The HLO instruction name of a trace event (``%fusion.12``), without
    the instruction text that TPU traces append after `` = ``."""
    return text.split(" = ", 1)[0]


# control flow that encloses other operations: in the union of busy time,
# never among the operations that took the time
CONTAINERS = ("%while", "%conditional", "%call")


def load_trace(
    path: str,
    *,
    device_plane_prefix: str = "/device:TPU:",
    op_line: str = "XLA Ops",
    span_prefix: str = "sb.",
) -> Trace:
    """Read device operations and the harness's host spans from an xplane.

    A device is a plane whose name starts with ``device_plane_prefix``; its
    operations are the events of lines whose name starts with ``op_line``,
    named by their instruction name.  Host spans are the events named
    ``span_prefix...`` on the host planes.  Zero-length events are
    markers, not work, and are dropped.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(device_plane_prefix):
            ops = []
            for line in plane.lines:
                if not line.name.startswith(op_line):
                    continue
                for ev in line.events:
                    if ev.duration_ns > 0 and not ev.name.startswith(span_prefix):
                        ops.append(Interval(op_name(ev.name), ev.start_ns, ev.end_ns))
            if ops:
                tr.device_ops.setdefault(plane.name, []).extend(ops)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        tr.host_spans.append(Interval(ev.name, ev.start_ns, ev.end_ns))
    for ops in tr.device_ops.values():
        ops.sort(key=lambda iv: iv.start_ns)
    tr.host_spans.sort(key=lambda iv: iv.start_ns)
    return tr


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(ops: Sequence[Interval], lo: float, hi: float) -> float:
    """Union of operation intervals inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(((o.start_ns, o.end_ns) for o in ops), lo, hi)))


def idle_gaps(ops: Sequence[Interval], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] in which no operation ran."""
    gaps, cur = [], lo
    for s, e in merge(clip(((o.start_ns, o.end_ns) for o in ops), lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


class SpanIndex:
    """Host spans grouped by name, each group merged and sorted, so that a
    gap's overlap with every name is a pair of bisections."""

    def __init__(self, spans: Sequence[Interval]):
        groups: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for sp in spans:
            groups[sp.name].append((sp.start_ns, sp.end_ns))
        self.mean_ns = {
            n: sum(e - s for s, e in ivs) / len(ivs) for n, ivs in groups.items()
        }
        self.merged = {n: merge(ivs) for n, ivs in groups.items()}
        self.ends = {n: [e for _, e in ivs] for n, ivs in self.merged.items()}

    def overlap(self, name: str, s: float, e: float) -> float:
        ivs, ends = self.merged[name], self.ends[name]
        i = bisect.bisect_right(ends, s)
        tot = 0.0
        while i < len(ivs) and ivs[i][0] < e:
            tot += min(e, ivs[i][1]) - max(s, ivs[i][0])
            i += 1
        return tot

    def attribute(self, gap: Tuple[float, float]) -> str:
        """The innermost span name (shortest spans) that covers at least
        half of the gap; failing that, the one that overlaps it most.
        ``host:idle`` where no span overlaps it at all."""
        s, e = gap
        overlaps = {n: self.overlap(n, s, e) for n in self.merged}
        half = [n for n, ov in overlaps.items() if ov >= 0.5 * (e - s)]
        if half:
            return min(half, key=lambda n: self.mean_ns[n])
        best = max(overlaps.items(), key=lambda kv: kv[1], default=(None, 0.0))
        return best[0] if best[1] > 0 else "host:idle"


def kernel_ns(ops: Sequence[Interval], prefix: str, lo: float, hi: float) -> Tuple[float, int]:
    """Summed device time and count of operations whose name starts with
    ``prefix`` and that start inside [lo, hi]."""
    tot, n = 0.0, 0
    for o in ops:
        if o.name.startswith(prefix) and lo <= o.start_ns < hi:
            tot += o.dur_ns
            n += 1
    return tot, n


def top_ops(ops: Sequence[Interval], lo: float, hi: float, k: int = 10) -> List[List[object]]:
    by: Dict[str, float] = defaultdict(float)
    for o in ops:
        if lo <= o.start_ns < hi and not o.name.startswith(CONTAINERS):
            by[o.name] += o.dur_ns
    return [[n, t / 1e9] for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def top_gaps(gaps: Sequence[Tuple[float, float]], index: SpanIndex, k: int = 10) -> List[List[object]]:
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    return [[index.attribute(g), (g[1] - g[0]) / 1e9] for g in longest]


def idle_by_span(gaps: Sequence[Tuple[float, float]], index: SpanIndex) -> Dict[str, float]:
    by: Dict[str, float] = defaultdict(float)
    for g in gaps:
        by[index.attribute(g)] += (g[1] - g[0]) / 1e9
    return dict(by)
