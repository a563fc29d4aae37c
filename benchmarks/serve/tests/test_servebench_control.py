"""The control, and how run_cell.py refuses to run.

The control is the reference in the program's place at float8, the step
below the bf16 the configurations state: at the small cell's size it must
read above the limit that the sound program reads under.  Without a TPU,
and in a checkout that holds only the benchmark, run_cell.py exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import sbtiny
import calibrate
from servebench import harness

ROOT = sbtiny.BENCH.parents[1]


def test_the_float8_control_fails_where_the_program_passes():
    rows = calibrate.calibrate(sbtiny.tiny_cell(), [2**31 + 5, 77], 0.0, require_tpu=False, log=lambda _m: None)
    for r in rows:
        assert r["weights_match"]
        assert r["unfinished"] == 0
        assert r["program_gap"] <= sbtiny.TINY_LIMIT < r["control_gap"], r
        # judged by the benchmark's own predicate at the cell's limit
        assert r["program_correct"] and not r["control_correct"], r


@pytest.mark.parametrize(
    "checks, correct",
    [
        ({"logit_gap": (0.2, 0.7), "unfinished": (0, 0)}, True),
        ({"logit_gap": (0.7, 0.7), "unfinished": (0, 0)}, True),
        ({"logit_gap": (0.71, 0.7), "unfinished": (0, 0)}, False),
        ({"logit_gap": (None, 0.7), "unfinished": (0, 0)}, False),
        ({"logit_gap": (float("inf"), 0.7), "unfinished": (0, 0)}, False),
        ({"logit_gap": (0.2, 0.7), "unfinished": (1, 0)}, False),
    ],
    ids=["under", "at", "over", "never_came", "infinite", "one_unfinished"],
)
def test_correct_means_every_number_came_and_is_within_its_limit(checks, correct):
    assert harness.judge({k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}) is correct


@pytest.mark.parametrize("name", sorted(sbtiny.CONFIGS))
def test_each_limit_lies_between_its_chip_readings(name):
    """The committed limit stays above the sound program's largest reading
    and below the float8 control's smallest, with room on both sides: raise
    it to where the control would pass, or drop it under the program, and
    this fails."""
    check = harness.load_json(ROOT / sbtiny.CONFIGS[name])["check"]
    limit, seen = check["max_logit_gap"], check["readings"]
    assert 1.5 * seen["program_max"] <= limit <= seen["control_min"] / 1.5
    control = {"logit_gap": {"value": seen["control_min"], "limit": limit}}
    program = {"logit_gap": {"value": seen["program_max"], "limit": limit}}
    assert harness.judge(program) and not harness.judge(control)


def _run_cell(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/serve/run_cell.py", "--workload", "qwen3-1.7b.claim_docs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run_cell(ROOT)
    assert p.returncode != 0
    assert _no_result(p)
    assert "no TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path: Path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(
        ROOT / "benchmarks" / "serve",
        tmp_path / "benchmarks" / "serve",
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    p = _run_cell(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)
