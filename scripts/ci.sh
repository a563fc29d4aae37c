#!/usr/bin/env bash
# CI entry point: tier-1 tests + the fast serving perf gates.
#
#   bash scripts/ci.sh
#
# 1. Runs the repo's tier-1 verify command (ROADMAP.md) over the FULL test
#    suite — including tests/test_checker.py, whose data assets
#    (src/repro/core/data/modes.yaml + descriptor YAMLs) are committed.
#    pytest -x fails the gate on the first regression.
# 2. Runs the fast subset of benchmarks/bench_multi_claim.py: the 3/3
#    multi-claim attribution control, the batched-vs-sequential decode
#    throughput gate (>= 2x), the paged-decode batch×context ceiling gate
#    (>= 2x the dense-assembly ceiling under one device-KV budget, at
#    equal logits parity), and the chunked-prefill prompt ceiling gate
#    (>= 2x the dense prefill ceiling under the same budget, at logits
#    parity with the dense engine's monolithic prefill), emitting
#    results/BENCH_serving.json.  The bench exits non-zero if any gate
#    fails.
# 3. Runs the seeded chaos campaign (benchmarks/bench_chaos.py): >= 200
#    injected faults (transient/permanent/corruption/worker-death/
#    capacity) plus the tier-quarantine phase, gating on zero crashes,
#    zero analyzer order violations, zero cross-claim contamination,
#    fail_closed_total{trigger} matching the injected plan EXACTLY, and
#    metric<->event reconciliation (analyzer.check_metrics_reconcile) on
#    EVERY engine's trace — counter or histogram drift from the ordered
#    event-log witnesses fails the campaign.  The quarantine-phase engine
#    exports the observability artifacts: results/chaos_trace.json
#    (Perfetto trace-event JSON, validated, covering refused AND
#    successful claims), results/chaos_metrics.prom (Prometheus text
#    exposition) and results/chaos_metrics.json (registry snapshot).  The
#    summary (counters, refusal rates, retry histogram, p50/p95/p99 stage
#    latencies for prefill/decode/restore/transfer) merges into
#    results/BENCH_serving.json under "chaos_campaign".
# 4. Runs the mixed-step scheduler bench (benchmarks/bench_scheduler.py
#    --fast): ten decode streams measured with and without a concurrent
#    prefill-admission burst, gating on decode ITL p99 under admission
#    <= 1.5x isolated (best-of-reps both sides), zero decode-stall steps,
#    analyzer-clean traces (step interleave order + metric reconciliation)
#    and every request finishing its full token budget.  Summary merges
#    into results/BENCH_serving.json under "mixed_scheduler".
# 5. Runs the radix prefix-reuse bench (benchmarks/bench_radix.py --fast):
#    replays a prefix-heavy multi-turn chat trace (shared system prompt,
#    per-session turns that extend the previous turn's full sequence) on
#    the sharing engine and on a prefix_sharing=False baseline, gating on
#    effective capacity (requests served before the first pressure
#    eviction) >= 1.5x the baseline, warm-vs-cold logits byte-identity
#    over reused pages, zero analyzer violations on both traces
#    (sequence, step interleave, metric reconciliation, shared-page
#    immutability), and every trace request finishing.  Summary merges
#    into results/BENCH_serving.json under "radix_reuse".
# 6. Static analysis, two layers.  First the claim-lifecycle invariant
#    linter (python -m repro.analysis.lint src/repro --strict): AST rules
#    for emit-site discipline vs PAYLOAD_SCHEMA, pin/unpin balance on
#    exception exits, fail-closed except handlers in serving/, metric
#    registration vs analyzer-reconcile drift, wall-clock/unseeded-random
#    bans, and jit purity — any unsuppressed finding fails the gate, and
#    every "# lint: allow[rule]" suppression must carry a reason (see
#    docs/static-analysis.md).  Report lands in results/lint_report.json.
#    Then mypy with the tolerant scoped config (mypy.ini: src/repro/core,
#    src/repro/serving, src/repro/analysis) — skipped with a notice when
#    mypy is not installed (requirements.txt lists it; the container image
#    may not bake it in).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# every gate below is a CPU number (Pallas in interpret mode); the chip run
# is chip_smoke.py, which refuses to run without a TPU
export JAX_PLATFORMS=cpu

echo "== tier-1: pytest (full suite, checker included) =="
python -m pytest -x -q

echo "== serving gates: attribution + batched decode + paged & prefill ceilings (fast) =="
python benchmarks/bench_multi_claim.py --fast

echo "== chaos campaign: seeded fault plans, exact fail-closed attribution =="
python benchmarks/bench_chaos.py

echo "== mixed-step scheduler: decode ITL under prefill admission (fast) =="
python benchmarks/bench_scheduler.py --fast

echo "== radix prefix reuse: effective capacity + byte-identity (fast) =="
python benchmarks/bench_radix.py --fast

echo "== static analysis: invariant linter (strict) =="
python -m repro.analysis.lint src/repro --strict

echo "== static analysis: mypy (scoped, tolerant) =="
if python -c "import mypy" >/dev/null 2>&1; then
  python -m mypy --config-file mypy.ini
else
  echo "mypy not installed — skipping (pip install -r requirements.txt for full coverage)"
fi

echo "== BENCH_serving.json =="
cat results/BENCH_serving.json
echo
echo "CI OK"
