"""One run of one cell: build, warm up, measure a window, check, report.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); its metrics are the entries of
``BENCHMARK.json`` that apply to it, each per-layer metric read by
``metrics/<name>.py``.  Everything is found by name, so a new model, mix or
metric is a new file and a new entry.

The run:

1. refuses to start without a TPU, with fewer chips than the cell asks
   for, or on a chip the peaks table does not know;
2. makes the weights on the device from ``--seed`` and builds the
   ``ServingEngine`` the cell's mix sizes;
3. generates the mix's cycle of rounds (each round is the cell's N
   clients' requests, served by one ``run_batch``: a closed loop) and
   serves whole cycles until one pass compiles nothing -- set-up;
4. serves whole cycles until ``--seconds`` have passed -- the window --
   counting every program JAX builds inside it;
5. with ``--trace 1``, traces one cycle instead, with the harness's host
   spans on the device's clock, and reads the per-layer metrics;
6. reads the chip's peak memory, frees the program, and runs the float32
   reference over a seeded sample of the window's requests (the longest
   among them) to decide ``correct``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from servebench import reduce as R
from servebench import reference, spans, work
from servebench import weights as W
from servebench.traffic import Cycle, build_cycle

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]
MAX_WARM_PASSES = 5
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoDevice(RuntimeError):
    """The machine cannot run this cell; nothing is measured."""


# ------------------------------------------------------------ what to run
@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_from_benchmark(bench: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (known: {sorted(cells)})")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per = [
        m
        for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return Cell(
        name=workload,
        config=load_json(REPO / conf["file"]),
        mix=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=e2e,
        per_layer=per,
    )


def metric_reader(name: str) -> Callable:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("servebench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------- the program, configured
HF_TO_PROGRAM = {
    "num_hidden_layers": ("num_layers", int),
    "hidden_size": ("d_model", int),
    "num_attention_heads": ("num_heads", int),
    "num_key_value_heads": ("num_kv_heads", int),
    "intermediate_size": ("d_ff", int),
    "vocab_size": ("vocab_size", int),
    "rope_theta": ("rope_theta", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
}
PROGRAM_RMS_EPS = 1e-6  # the program's RMSNorm epsilon (one value for every model)


def program_config(cfg: dict):
    """The program's ModelConfig holding exactly the file's numbers."""
    from repro.configs import get_config

    kw = {prog: conv(cfg[hf]) for hf, (prog, conv) in HF_TO_PROGRAM.items()}
    kw["head_dim"] = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    kw["qk_norm"] = W.qk_norm(cfg)
    kw["name"] = cfg["name"]
    mc = get_config(cfg["program_config"]).replace(**kw)
    problems = [
        what
        for what, ok in (
            ("hidden_act silu", cfg["hidden_act"] == "silu" and mc.activation == "silu"),
            ("RMSNorm", mc.norm == "rmsnorm"),
            (f"rms_norm_eps {PROGRAM_RMS_EPS}", float(cfg["rms_norm_eps"]) == PROGRAM_RMS_EPS),
            ("dense MLP", mc.family == "dense" and not mc.moe.num_experts),
            ("full attention", not mc.sliding_window and not mc.attn_logit_softcap),
            ("bf16 weights and KV", cfg["torch_dtype"] == "bfloat16" and mc.kv_cache_dtype == "bf16"),
        )
        if not ok
    ]
    if problems:
        raise ValueError(f"{cfg['name']}: the program cannot run this configuration as stated: {problems}")
    return mc


# ---------------------------------------------------------- compile clock
class CompileCounter:
    """Programs JAX builds in this process (backend compiles, whether the
    persistent cache served them or not) and persistent-cache misses."""

    def __init__(self) -> None:
        self.built = 0
        self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.built += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.misses += 1


# --------------------------------------------------------------- serving
@dataclass
class RoundRecord:
    reqs: list
    t_call: float
    ev_lo: int
    ev_hi: int


class Session:
    """The engine serving one cell's cycle, round after round."""

    def __init__(self, cell: Cell, seed: int, *, engine_hook=None):
        from repro.core.claims import ClaimMode
        from repro.models.registry import build_model
        from repro.serving.engine import ServingEngine

        self.cfg = cell.config
        self.mc = program_config(self.cfg)
        self.bundle = build_model(self.mc)
        shapes = jax.eval_shape(self.bundle.init_params, jax.random.PRNGKey(0))
        if W.program_layout(shapes) != W.expected_layout(self.cfg):
            raise ValueError(
                f"the program's parameters {W.program_layout(shapes)} are not the "
                f"configuration's {W.expected_layout(self.cfg)}"
            )
        self.params = jax.block_until_ready(W.make_params(shapes, seed, self.mc.d_model))
        self.cycle: Cycle = build_cycle(cell.mix, seed, self.mc.vocab_size)
        self.eng = ServingEngine(
            self.bundle,
            self.params,
            block_size=self.cycle.block,
            device_blocks=self.cycle.pool_pages(),
            host_blocks=self.cycle.host_pages(),
            cache_len=256,
        )
        if self.eng.decode_mode != "paged":
            raise RuntimeError(f"engine chose decode_mode={self.eng.decode_mode!r}, not paged")
        if engine_hook is not None:
            engine_hook(self.eng)
        self.claims = {}
        docs = cell.mix.get("documents") or {}
        if docs.get("claim") == "offloadable":
            for d, toks in enumerate(self.cycle.docs):
                self.claims[d] = self.eng.accept_claim(
                    toks, ClaimMode.OFFLOADABLE, priority=int(docs["claim_priority"])
                )
        self.offload_unread = bool(cell.mix.get("policy", {}).get("offload_unread_claims"))
        self.replay = 0

    def serve_round(self, r: int) -> RoundRecord:
        from repro.core.claims import ClaimState

        cyc, eng = self.cycle, self.eng
        reqs = [
            eng.submit(cyc.request_tokens(self.replay, r, c), spec.output_tokens)
            for c, spec in enumerate(cyc.rounds[r])
        ]
        ev_lo = len(eng.events)
        t_call = time.monotonic()
        eng.run_batch(reqs)
        if self.offload_unread:
            # operator policy: a claim this round did not read goes to the
            # host tier, so its next reader restores it before it is served
            read = {s.doc for s in cyc.rounds[r]}
            for d, claim in self.claims.items():
                if d not in read and claim.state in (ClaimState.MATERIALIZED, ClaimState.RESTORED):
                    eng.offload_claim(claim.claim_id)
        return RoundRecord(reqs, t_call, ev_lo, len(eng.events))

    def serve_cycle(self) -> List[RoundRecord]:
        out = [self.serve_round(r) for r in range(len(self.cycle.rounds))]
        self.replay += 1
        return out

    def close(self) -> None:
        self.eng.close()
        self.eng = self.params = self.bundle = None
        gc.collect()


# --------------------------------------------------------------- windows
@dataclass
class Window:
    """What a window recorded, as the metric readers see it."""

    seconds: float
    rounds: List[RoundRecord]
    steps: List[List[dict]]  # per round, its step_scheduled records
    stages: Dict[str, List[float]]  # stage -> seconds of each observation
    dims: work.Dims
    peaks: dict
    trace: Optional["TraceReading"] = None

    @property
    def requests(self) -> list:
        return [q for rd in self.rounds for q in rd.reqs]

    @property
    def all_steps(self) -> List[dict]:
        return [s for rs in self.steps for s in rs]


@dataclass
class TraceReading:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]  # kernel name fragment -> device seconds
    decode_contexts: List[List[int]]  # per decode launch, keys per real row
    chunk_spans: List[List[Tuple[int, int]]]  # per chunk launch, real query span per row
    upload_seconds: List[float]
    breakdown: dict
    idle_by_span: Dict[str, float] = field(default_factory=dict)


def window_record(eng, rounds: List[RoundRecord], seconds: float, dims, peaks) -> Window:
    events = eng.events.events
    steps = [
        [dict(e.payload, ts=e.ts) for e in events[rd.ev_lo : rd.ev_hi] if e.name == "step_scheduled"]
        for rd in rounds
    ]
    stages: Dict[str, List[float]] = {}
    for e in events[rounds[0].ev_lo : rounds[-1].ev_hi]:
        if e.name == "stage_latency":
            stages.setdefault(e.payload["stage"], []).append(float(e.payload["seconds"]))
    return Window(seconds, rounds, steps, stages, dims, peaks)


def end_to_end(w: Window, setup_s: float) -> Dict[str, float]:
    done = [q for q in w.requests if served_in_full(q)]
    ttft = []
    for rd in w.rounds:
        for q in rd.reqs:
            ok = served_in_full(q) and q.first_token_ts is not None
            ttft.append((q.first_token_ts - rd.t_call) if ok else math.inf)
    gaps: List[Tuple[float, int]] = []
    for rd, rs in zip(w.rounds, w.steps):
        firsts = [q.first_token_ts for q in rd.reqs if q.first_token_ts is not None]
        gaps.extend(R.step_token_gaps(rs, firsts))
    return {
        "output_tok_s": R.rate(sum(len(q.output_tokens) for q in done), w.seconds),
        "ttft_p90_ms": R.percentile(ttft, 90) * 1e3,
        "itl_p99_ms": R.weighted_percentile(gaps, 99) * 1e3,
        "setup_s": setup_s,
    }


def served_in_full(q) -> bool:
    return q.status == "finished" and len(q.output_tokens) == q.max_new_tokens


def judge(checks: Dict[str, dict]) -> bool:
    """``correct``: every number compared came (a reading that never came is
    ``None`` or infinite), and none is over its limit."""
    return all(
        c["value"] is not None and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )


def _number(x: float) -> Optional[float]:
    """A value for the result line: JSON has no infinity, so a reading that
    never came (a failed request's time, a comparison with no sample) is
    null there; such a run is not correct anyway."""
    return x if math.isfinite(x) else None


# ----------------------------------------------------------------- trace
def _read_trace(
    trace_dir: str, log: spans.CallLog, w: Window, matches: Dict[str, str], source: Tuple[str, str]
) -> TraceReading:
    tr = R.load_trace(R.find_xplane(trace_dir), device_plane_prefix=source[0], op_line=source[1])
    if not tr.device_ops:
        raise RuntimeError("the trace holds no device operations")
    plane = sorted(tr.device_ops)[0]
    ops = tr.device_ops[plane]
    win = [s for s in tr.host_spans if s.name == "sb.window"]
    if len(win) != 1:
        raise RuntimeError(f"expected one sb.window span in the trace, found {len(win)}")
    lo, hi = win[0].start_ns, win[0].end_ns
    gaps = R.idle_gaps(ops, lo, hi)
    index = R.SpanIndex([s for s in tr.host_spans if s.name != "sb.window"])
    decode, chunks = spans.pair_calls(log, w.all_steps)
    return TraceReading(
        window_s=(hi - lo) / 1e9,
        busy_s=R.busy_ns(ops, lo, hi) / 1e9,
        kernel_s={k: R.kernel_ns(ops, m, lo, hi)[0] / 1e9 for k, m in matches.items()},
        decode_contexts=decode,
        chunk_spans=chunks,
        upload_seconds=list(log.upload_seconds),
        breakdown={"device_ops": R.top_ops(ops, lo, hi), "idle_gaps": R.top_gaps(gaps, index)},
        idle_by_span=R.idle_by_span(gaps, index),
    )


# kernels by the instruction-name prefix they carry in the device trace
KERNELS = {"paged_decode": "%paged_decode_attention", "chunked_prefill": "%paged_prefill_attention"}
# where the chip's operations are in a trace: plane name prefix, line name
TPU_OPS = ("/device:TPU:", "XLA Ops")


# ----------------------------------------------------------- correctness
def sample_requests(w: Window, seed: int, k: int) -> list:
    """A seeded sample of the window's finished requests: the longest, a
    restored one and a radix-hit one where the window has them, then at
    random."""
    done = [q for q in w.requests if served_in_full(q)]
    rng = np.random.default_rng([seed, 7])
    picked = []
    if done:
        picked.append(max(done, key=lambda q: len(q.tokens) + len(q.output_tokens)))
    for want in (lambda q: q.restored_tokens > 0, lambda q: q.cached_tokens > 0 and not q.restored_tokens):
        pool = [q for q in done if want(q) and q not in picked]
        if pool:
            picked.append(pool[int(rng.integers(len(pool)))])
    rest = [q for q in done if q not in picked]
    for i in rng.permutation(len(rest))[: max(0, k - len(picked))]:
        picked.append(rest[int(i)])
    return picked


def _analyzers(eng, w: Window) -> List[str]:
    from repro.core.analyzer import (
        check_metrics_reconcile,
        check_observation_path,
        check_step_interleave_order,
    )

    failures = []
    for name, v in (
        ("step_interleave_order", check_step_interleave_order(eng.events)),
        ("metrics_reconcile", check_metrics_reconcile(eng.events, eng.metrics)),
    ):
        if not v.passed:
            failures.append(f"{name}: {v.reasons[:3]}")
    restored = {}
    for e in eng.events.named("resident_claim_restored"):
        restored[e.request_id] = e.claim_id
    for q in w.requests:
        if q.restored_tokens and q.request_id in restored:
            v = check_observation_path(eng.events, restored[q.request_id], q.request_id)
            if not v.passed:
                failures.append(f"observation_path {q.request_id}: {v.reasons[:3]}")
    return failures


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ------------------------------------------------------------------- run
def warm_up(sess: Session, compiles: CompileCounter, log_line: Callable[[str], None]) -> None:
    """Serve whole cycles until one builds no program (at most
    MAX_WARM_PASSES): every shape of the steady state is then compiled and
    the documents a deployment would hold are resident."""
    for p in range(MAX_WARM_PASSES):
        before, t0 = compiles.built, time.monotonic()
        sess.serve_cycle()
        log_line(
            f"warm-up pass {p + 1}: {compiles.built - before} programs built, "
            f"{time.monotonic() - t0:.1f} s"
        )
        if compiles.built == before:
            return


def serve_for(sess: Session, seconds: float) -> List[RoundRecord]:
    """Whole cycles until ``seconds`` have passed: every window holds the
    same mix of rounds."""
    t0 = time.monotonic()
    rounds: List[RoundRecord] = []
    while True:
        rounds.extend(sess.serve_cycle())
        if time.monotonic() - t0 >= seconds:
            return rounds


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    *,
    require_tpu: bool = True,
    engine_hook=None,
    log_line: Callable[[str], None] = print,
    trace_source: Tuple[str, str] = TPU_OPS,
) -> dict:
    """One run of ``cell``; returns the result object (the last line)."""
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoDevice(f"JAX finds no TPU (devices[0].platform={dev.platform!r})")
        if len(devices) < cell.chips:
            raise NoDevice(f"{cell.name} asks for {cell.chips} chips, JAX finds {len(devices)}")
        try:
            peaks = work.peaks_for(dev.device_kind)
        except KeyError as e:
            raise NoDevice(str(e)) from e
    else:
        peaks = work.PEAKS["TPU v5 lite"]  # test drives on the CPU print no device numbers
    compiles = CompileCounter()
    sess = Session(cell, seed, engine_hook=engine_hook)
    log_line(f"engine and weights ready {time.monotonic() - t_start:.1f} s after start")
    log = spans.CallLog()
    if trace:
        spans.install(sess.eng, log)
    log_line(
        f"{cell.name}: {sess.mc.num_layers} layers, pool {sess.cycle.pool_pages()} pages, "
        f"{len(sess.cycle.rounds)} rounds of {sess.cycle.clients} per cycle"
    )
    warm_up(sess, compiles, log_line)
    t_w0 = time.monotonic()
    setup_s = t_w0 - t_start
    log_line(f"set-up {setup_s:.1f} s, of which {compiles.seconds:.1f} s building programs")
    built0, miss0 = compiles.built, compiles.misses
    rounds: List[RoundRecord] = []
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="servebench-trace-")
        log.recording = True
        jax.profiler.start_trace(trace_dir)
        t_w0 = time.monotonic()
        with jax.profiler.TraceAnnotation("sb.window"):
            rounds.extend(sess.serve_cycle())
        t_w1 = time.monotonic()
        jax.profiler.stop_trace()
        log.recording = False
    else:
        rounds = serve_for(sess, seconds)
        t_w1 = time.monotonic()
    window_built = compiles.built - built0
    log_line(
        f"window compiles: {window_built} programs built "
        f"({compiles.misses - miss0} persistent-cache misses) over {len(rounds)} rounds"
    )
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    eng = sess.eng
    w = window_record(eng, rounds, t_w1 - t_w0, work.Dims.from_config(cell.config), peaks)
    result_device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem_peak,
    }
    breakdown = None
    if trace:
        try:
            w.trace = _read_trace(trace_dir, log, w, KERNELS, trace_source)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result_device["busy_s"] = w.trace.busy_s
        result_device["window_s"] = w.trace.window_s
        breakdown = w.trace.breakdown
        log_line(f"idle by host span (s): {json.dumps(w.trace.idle_by_span, sort_keys=True)}")
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(w, setup_s)
        metrics = {
            m["name"]: {"value": _number(values[m["name"]]), "unit": m["unit"]} for m in cell.end_to_end
        }
    attempted = len(w.requests)
    failed = sum(not served_in_full(q) for q in w.requests)
    analyzer_failures = _analyzers(eng, w)
    fail_closed = sum(eng.fail_closed_total().values())
    k = int(cell.mix["check"]["sample_requests"])
    sample = [reference.Served(tuple(q.tokens), tuple(q.output_tokens)) for q in sample_requests(w, seed, k)]
    sess.close()
    del eng
    gc.collect()
    seq_pad = round_up(sess.cycle.max_sequence(), 128)
    t_ref = time.monotonic()
    if sample:
        gap = reference.compare(cell.config, seed, sample, seq_pad)
        gap_value, tokens = gap.max_gap, gap.tokens
    else:
        gap_value, tokens = math.inf, 0
    log_line(
        f"reference over {len(sample)} requests, {tokens} served tokens: widest gap "
        f"{gap_value} in {time.monotonic() - t_ref:.1f} s"
    )
    for f in analyzer_failures:
        log_line(f"analyzer failure: {f}")
    limit = float(cell.config["check"]["max_logit_gap"])
    checks = {
        "logit_gap": {"value": _number(gap_value), "limit": limit},
        "unfinished": {"value": failed, "limit": 0},
        "fail_closed": {"value": fail_closed, "limit": 0},
        "analyzer_failures": {"value": len(analyzer_failures), "limit": 0},
    }
    out = {
        "correct": judge(checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": result_device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    return out
