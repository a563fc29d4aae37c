"""A cell of the serving benchmark shrunk to run on the CPU in seconds.

The widths are cut to a toy decoder and the mix to four clients over small
documents; the harness, the engine path, the reference and the checks are
the ones a chip run uses.  Tests drive ``harness.run`` with
``require_tpu=False``: nothing printed from here is a device number.
"""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

from servebench import harness  # noqa: E402

CPU_OPS = ("/host:CPU", "tf_XLAPjRtCpuClient")
BENCHMARK = harness.load_json(harness.REPO / "BENCHMARK.json")
# every configuration and traffic mix a cell names, found as the harness finds them
CONFIGS = {c["name"]: c["file"] for c in BENCHMARK["configs"]}
MIXES = sorted({w["traffic"] for w in BENCHMARK["workloads"]})
# Widest gap the sound small program may read.  At these widths (d 128,
# vocab 16384) its bf16 readings on the CPU were 0 to 0.018 over three
# seeds and the float8 control's 0.18 to 0.65.
TINY_LIMIT = 0.1


def tiny_cell(workload: str = "qwen3-1.7b.claim_docs") -> harness.Cell:
    cell = harness.cell_from_benchmark(BENCHMARK, workload)
    cfg = dict(
        cell.config,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=32,
        vocab_size=16384,
    )
    cfg["check"] = dict(cfg["check"], max_logit_gap=TINY_LIMIT)
    mix = copy.deepcopy(cell.mix)
    mix.update(clients=4, rounds_per_cycle=2)
    if "documents" in mix:
        mix["documents"].update(count=min(3, mix["documents"]["count"]), tokens_min=16, tokens_max=48)
    mix["own_tokens"] = dict(dist="uniform", min=4, max=12)
    mix["output_tokens"] = dict(dist="uniform", min=8, max=16)
    mix["check"] = dict(mix["check"], sample_requests=4)
    cell.config, cell.mix = cfg, mix
    return cell


def run_tiny(cell=None, *, seed: int = 2**31 + 5, trace: bool = False, engine_hook=None) -> dict:
    cell = cell or tiny_cell()
    return harness.run(
        cell,
        seed,
        0.0,
        trace,
        time.monotonic(),
        require_tpu=False,
        engine_hook=engine_hook,
        log_line=lambda _msg: None,
        trace_source=CPU_OPS,
    )
