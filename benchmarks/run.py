"""Benchmark driver — one function per paper table plus the roofline table.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only matrix,gates

Prints ``name,value,derived`` CSV lines per table and writes artifacts under
results/.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache


def _section(name):
    print(f"\n== {name} " + "=" * max(0, 60 - len(name)))


def bench_matrix():
    """Paper §6/§8.1: generated lowering matrix + provenance + central table."""
    from repro.core import checker
    from repro.core.native_descriptor import NATIVE_DESCRIPTOR_PATH, generate_native_descriptor

    t0 = time.perf_counter()
    if not NATIVE_DESCRIPTOR_PATH.exists():
        generate_native_descriptor()
    stats = checker.write_outputs()
    dt = (time.perf_counter() - t0) * 1e6
    _section("lowering matrix (Tables 3/6; §8.1)")
    print(f"lowering_matrix_rows,{stats['rows']},{dt:.0f}us")
    print(f"native_sound_rows,{stats['native_sound']},repro-jax-native only")
    print(f"sound_with_adapter_rows,{stats['sound_with_adapter']},adapter/patch positives")
    from repro.core.independent_audit import run_audit

    audit = run_audit()
    print(f"independent_rc14_audit,{audit['agreement']},second-implementation agreement")
    assert audit["agreement"] == "14/14"
    return stats


def bench_bad_lowering():
    """Paper §9 Table 9: feature-table counterexamples fail closed."""
    from repro.core import bad_lowering

    t0 = time.perf_counter()
    stats = bad_lowering.write_outputs()
    dt = (time.perf_counter() - t0) * 1e6
    _section("bad-lowering counterexamples (Table 9)")
    print(f"bad_lowering_fail_closed,{stats['fail_closed']}/{stats['total']},{dt:.0f}us")
    assert stats["fail_closed"] == stats["total"]
    return stats


def bench_mutations():
    """Paper §8.2: 16/16 descriptor/evidence mutation controls fail closed."""
    from repro.core import mutations

    t0 = time.perf_counter()
    stats = mutations.write_outputs()
    dt = (time.perf_counter() - t0) * 1e6
    _section("descriptor/evidence mutation controls (§8.2)")
    print(f"mutation_controls_fail_closed,{stats['fail_closed']}/{stats['total']},{dt:.0f}us")
    assert stats["fail_closed"] == stats["total"] == 16
    return stats


def bench_gates():
    """Paper §8.3 Table 8: 131-run connector repetition gates."""
    from benchmarks.bench_connector_gates import run_gates

    t0 = time.perf_counter()
    summary = run_gates()
    dt = time.perf_counter() - t0
    _section("connector repetition gates (Table 8)")
    for k, v in summary.items():
        print(f"{k},{v},{dt:.1f}s total")
    assert summary["failure_outcome_passes"] == "30/30"
    assert summary["false_positive_control_passes"] == "0/41"
    return summary


def bench_multi_claim():
    """Paper §7 path C: 3/3 target-only attribution."""
    from benchmarks.bench_multi_claim import run

    summary = run()
    _section("multi-claim attribution control (path C)")
    for k, v in summary.items():
        print(f"{k},{v},")
    assert summary["target_only_attribution"] == "3/3"
    return summary


def bench_roofline():
    """Deliverable g: roofline table from the dry-run artifacts."""
    from benchmarks.bench_roofline import run

    out = run()
    _section("roofline table (from results/dryrun)")
    if not out:
        print("roofline,SKIPPED,run `python -m repro.launch.dryrun --all --mesh both` first")
    for mesh, desc in out.items():
        print(f"roofline_{mesh},{desc},")
    return out


def bench_kernels():
    """Pallas kernels vs jnp oracles (interpret mode on CPU)."""
    from benchmarks.bench_kernels import run

    rows = run()
    _section("kernel microbench (interpret mode)")
    for r in rows:
        print(f"{r['name']},{r['pallas_interpret_us']:.1f}us,max_err={r['max_err']:.2e}")
    return rows


ALL = {
    "matrix": bench_matrix,
    "bad_lowering": bench_bad_lowering,
    "mutations": bench_mutations,
    "gates": bench_gates,
    "multi_claim": bench_multi_claim,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset of: " + ",".join(ALL))
    args = ap.parse_args()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(ALL)
    results = {}
    for n in names:
        results[n] = ALL[n]()
    Path("results").mkdir(exist_ok=True)
    Path("results/bench-summary.json").write_text(json.dumps(results, indent=1, default=str))
    print("\nall benchmarks complete; artifacts in results/")


if __name__ == "__main__":
    enable_compile_cache()
    main()
