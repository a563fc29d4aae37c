"""Readings that set a cell's correctness limit: the program and its control.

  python3 benchmarks/serve/calibrate.py --workload <cell> --seeds 11,12,13 \
      [--seconds 0] [--out chiprun_out/calibrate-<cell>.json]

For each seed, in one process (the chip belongs to one process): the cell's
engine is built from the seed, warmed up, and serves the cell's load for at
least one whole cycle; then, with the program freed, the float32 reference
runs over the same seeded sample of finished requests a benchmark run
compares, and beside it the float8 control (``reference.compare`` with
``control=True``).  Each seed gives two readings of the widest logit gap:
the program's served tokens, and the tokens the control puts first.  The
limit in the configuration file lies between the largest program reading
and the smallest control reading (see PERF.md).  Each reading is judged at
that limit by the benchmark's own ``correct`` (``harness.judge``): the
program has to come out correct and the control not, on every seed, or
this exits 1.

It also checks that the reference's weights, regenerated layer by layer
from the seed, are bit for bit the ones the program was given.  The
benchmark's own runs never run the control.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))


def weights_match(sess, seed: int) -> bool:
    """Layer 0 and the global leaves, regenerated as the reference does,
    against what the program holds."""
    import jax
    import numpy as np

    from servebench import weights as W

    layer_spec, glob_spec, _ = W.expected_layout(sess.cfg)
    d = int(sess.cfg["hidden_size"])
    flat = {
        W._path(kp): leaf for kp, leaf in jax.tree_util.tree_flatten_with_path(sess.params)[0]
    }
    ok = True
    for p, a in W.layer_weights(seed, 0, layer_spec, d).items():
        ok &= bool(np.array_equal(np.asarray(flat["layers/" + p][0]), np.asarray(a)))
    for p, a in W.global_weights(seed, glob_spec, d).items():
        ok &= bool(np.array_equal(np.asarray(flat[p]), np.asarray(a)))
    return ok


def calibrate(cell, seeds, seconds: float, *, require_tpu: bool = True, engine_hook=None, log=print):
    import jax

    from servebench import harness, reference, work

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise harness.NoDevice(f"JAX finds no TPU (devices[0].platform={dev.platform!r})")
    compiles = harness.CompileCounter()
    limit = float(cell.config["check"]["max_logit_gap"])
    rows = []
    for seed in seeds:
        t0 = time.monotonic()
        sess = harness.Session(cell, seed, engine_hook=engine_hook)
        same = weights_match(sess, seed)
        harness.warm_up(sess, compiles, log)
        rounds = harness.serve_for(sess, seconds)
        w = harness.window_record(sess.eng, rounds, 1.0, work.Dims.from_config(cell.config), {})
        k = int(cell.mix["check"]["sample_requests"])
        picked = harness.sample_requests(w, seed, k)
        unfinished = sum(not harness.served_in_full(q) for q in w.requests)
        sample = [reference.Served(tuple(q.tokens), tuple(q.output_tokens)) for q in picked]
        seq_pad = harness.round_up(sess.cycle.max_sequence(), 128)
        sess.close()
        g = reference.compare(cell.config, seed, sample, seq_pad, control=True)

        def correct(gap: float) -> bool:
            return harness.judge(
                {
                    "logit_gap": {"value": gap, "limit": limit},
                    "unfinished": {"value": unfinished, "limit": 0},
                }
            )

        row = {
            "seed": seed,
            "weights_match": same,
            "requests": len(w.requests),
            "unfinished": unfinished,
            "compared_tokens": g.tokens,
            "program_gap": g.max_gap,
            "program_agree": g.agree,
            "control_gap": g.control_max_gap,
            "control_agree": g.control_agree,
            "program_correct": correct(g.max_gap),
            "control_correct": correct(g.control_max_gap),
            "seconds": time.monotonic() - t0,
        }
        log(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from run_cell import process_settings

    process_settings()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    from servebench import harness

    enable_compile_cache()
    cell = harness.cell_from_benchmark(harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        rows = calibrate(cell, seeds, args.seconds)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    summary = {
        "workload": args.workload,
        "device": jax.devices()[0].device_kind,
        "program_gap_max": max(r["program_gap"] for r in rows),
        "control_gap_min": min(r["control_gap"] for r in rows),
        "limit_now": cell.config["check"]["max_logit_gap"],
        "program_correct_on_every_seed": all(r["program_correct"] for r in rows),
        "control_incorrect_on_every_seed": not any(r["control_correct"] for r in rows),
        "rows": rows,
    }
    out = Path(args.out or ROOT / "chiprun_out" / f"calibrate-{args.workload}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    separates = summary["program_correct_on_every_seed"] and summary["control_incorrect_on_every_seed"]
    return 0 if separates else 1


if __name__ == "__main__":
    sys.exit(main())
