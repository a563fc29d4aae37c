"""Run one cell of the serving benchmark once, on the chip this process holds.

  python3 benchmarks/serve/run_cell.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number the correctness comparison read, beside its limit.  The same
checks are the last lines of standard error.  Without a TPU, with fewer
chips than the cell asks for, or on a chip the peaks table does not know,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))


def process_settings() -> None:
    """Before JAX is imported: its persistent compilation cache at one fixed
    path inside the checkout (the path is part of the cache key; the
    program takes it from this variable), and no libtpu log files."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


EXIT_NO_DEVICE = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    process_settings()

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    from servebench import harness

    enable_compile_cache()
    cell = harness.cell_from_benchmark(harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoDevice as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    print(json.dumps(out), flush=True)
    # the checks come last on standard error too
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
