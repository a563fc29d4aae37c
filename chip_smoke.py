"""Chip smoke: serve qwen3-1.7b at its published widths on one TPU through the
claim-native serving path, and check what comes out.

  python chip_smoke.py [--seed N]      # on a TPU host; exits non-zero elsewhere
  JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --rehearse

Phases, in one process (the chip belongs to one process at a time):

  (a) build qwen3-1.7b (28 layers, d 2048, GQA 16/8, head 128, vocab
      151936) with random weights made on the device from ``--seed``, and a
      ServingEngine over a few hundred pool pages;
  (b) serve 10 requests (64-512 prompt tokens, 16 new tokens each) through
      ONE ``run_batch`` -> ``StepLoop``: four share a resident system prompt
      (radix hits), four form a B=4 prefill bucket, decode runs at W >= 4.
      Every request must finish with its full token budget, nothing may
      fail closed, and the interleave/metrics analyzers must pass;
  (c) an OFFLOADABLE claim is served, offloaded to the host tier and
      restored by a continuation (``kv_block_copy`` both ways); the restored
      pages must equal the offloaded bytes (witness path A);
  (d) witness path B: a controlled restoration failure on a second claim
      must take the ordered, claim-scoped fail-closed path — the only
      failure the smoke expects;
  (e) on the device: first-token logits of the Pallas kernel path against
      the jnp attention path (``decode_mode="dense"``), and the paged
      kernels against their ``kernels/ref.py`` oracles at serving widths.

The last line of stdout is one JSON object naming the device, printed only
after every phase passed on a TPU.  Times printed before it are smoke
timings (compile included), not benchmark numbers.  ``--rehearse`` runs
every phase at ``reduced()`` size on whatever backend JAX has (the CPU,
with Pallas in interpret mode) and never prints that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.core.analyzer import (  # noqa: E402
    check_failure_outcome_path,
    check_metrics_reconcile,
    check_observation_path,
    check_step_interleave_order,
)
from repro.core.claims import ClaimMode  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

MODEL = "qwen3-1.7b"
BLOCK = 8  # the engine's default page size
POOL_PAGES = 384  # ~0.9 MiB per page at full width; the pool is host-resident
NEW_TOKENS = 16

# First-token logits, kernel path vs jnp path.  Both hold weights,
# activations and KV in bf16 and accumulate attention in f32; they differ
# in how attention is split (pool pages + in-flight tail vs one dense pass)
# and so in where values are rounded to bf16 (relative step 2**-8).  Over
# 28 residual layers such roundings add up to about a percent of the logit
# scale, so the bound is 5% of the reference's largest |logit|.  A wrong
# page, mask or position moves logits by their own order of magnitude.
LOGITS_RTOL = 0.05
# Kernel vs oracle on random bf16 inputs: the tests' bf16 tolerance.
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)


class SmokeFailure(RuntimeError):
    pass


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own events)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def log(msg: str) -> None:
    print(msg, flush=True)


def has_custom_call(jitted, *args) -> bool:
    """Whether the compiled program carries a Pallas TPU kernel."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def no_fail_closed(eng, phase: str) -> None:
    counts = eng.fail_closed_total()
    expect(sum(counts.values()) == 0, f"{phase}: fail_closed_total={counts}")


def check_analyzers(eng, phase: str) -> None:
    for name, verdict in (
        ("check_step_interleave_order", check_step_interleave_order(eng.events)),
        ("check_metrics_reconcile", check_metrics_reconcile(eng.events, eng.metrics)),
    ):
        expect(verdict.passed, f"{phase}: {name} failed: {verdict.reasons}")


def finished_in_full(reqs, phase: str) -> None:
    for r in reqs:
        expect(
            r.status == "finished" and len(r.output_tokens) == r.max_new_tokens,
            f"{phase}: {r.request_id} status={r.status} "
            f"tokens={len(r.output_tokens)}/{r.max_new_tokens} error={r.error}",
        )


# ---------------------------------------------------------------- phases
def phase_serve(eng, rng, vocab: int, on_tpu: bool):
    """(b) one run_batch of 10 requests through the step loop."""
    toks = lambda n: tuple(int(t) for t in rng.integers(0, vocab, n))
    system = toks(384)
    warm = eng.submit(system + toks(8), max_new_tokens=NEW_TOKENS)
    eng.run(warm)  # makes the system prompt resident for the radix hits
    finished_in_full([warm], "(b) warm-up")
    batch = [eng.submit(system + toks(n), NEW_TOKENS) for n in (8, 12, 16, 20)]
    batch += [eng.submit(toks(n), NEW_TOKENS) for n in (121, 124, 126, 128)]
    batch += [eng.submit(toks(n), NEW_TOKENS) for n in (64, 512)]
    hits_before = len(eng.events.named("prefix_reuse"))
    first_step = len(eng.events.named("step_scheduled"))
    eng.run_batch(batch)
    finished_in_full(batch, "(b)")
    no_fail_closed(eng, "(b)")
    check_analyzers(eng, "(b)")
    steps = [e.payload for e in eng.events.named("step_scheduled")[first_step:]]
    hits = len(eng.events.named("prefix_reuse")) - hits_before
    max_decode = max(s["n_decode"] for s in steps)
    max_bucket = max(s["prefill_rows"] for s in steps)
    expect(hits >= 4, f"(b) radix hits {hits} < 4")
    expect(max_decode >= 4, f"(b) widest decode launch had {max_decode} live rows")
    expect(max_bucket == 4, f"(b) widest prefill bucket had {max_bucket} rows")
    expect(eng.decode_stalls.value() == 0, "(b) decode stalled")
    # the compiled serving steps must carry the Pallas kernels, not jnp
    state = eng._make_paged_state([[]] * 4, [0] * 4, 8, pages=eng._device_pages())
    pos = jnp.zeros((4,), jnp.int32)
    decode_kernel = has_custom_call(eng._jit_paged_decode, eng.params, state, pos, pos)
    chunk = {k: state[k] for k in ("k_pages", "v_pages", "block_tables", "prefix_len")}
    cpos = jnp.zeros((4, eng.prefill_chunk), jnp.int32)
    chunk_kernel = has_custom_call(eng._jit_prefill_chunk, eng.params, chunk, cpos, cpos)
    if on_tpu:
        expect(decode_kernel and chunk_kernel, "(b) serving steps compiled without kernels")
    log(
        f"(b) served {len(batch)} requests + 1 warm-up in {len(steps)} steps; "
        f"radix hits={hits}, max decode rows={max_decode}, max prefill bucket="
        f"{max_bucket}, tpu_custom_call in decode step={decode_kernel}, "
        f"in prefill chunk step={chunk_kernel}"
    )


def phase_offload_restore(eng, rng, vocab: int):
    """(c) accept -> serve -> offload to host -> restoring continuation."""
    prefix = tuple(int(t) for t in rng.integers(0, vocab, 128))
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    first = eng.run(eng.submit(prefix + (1, 2, 3, 4, 5, 6, 7, 8), NEW_TOKENS))
    finished_in_full([first], "(c) first serve")
    blocks = eng._claim_device_blocks(claim)
    expect(blocks is not None, "(c) claim blocks not device-resident")
    chains = [b.chain for b in blocks]
    saved = [(np.array(b.k, copy=True), np.array(b.v, copy=True)) for b in blocks]
    expect(eng.offload_claim(claim.claim_id), "(c) offload failed")
    cont = eng.run(eng.submit(prefix + (9, 10, 11, 12), NEW_TOKENS))
    finished_in_full([cont], "(c) continuation")
    verdict = check_observation_path(eng.events, claim.claim_id, cont.request_id)
    expect(verdict.passed, f"(c) witness path A: {verdict.reasons}")
    restored = eng.events.named("resident_claim_restored")
    done = [
        e for e in eng.events.named("request_finished") if e.request_id == cont.request_id
    ]
    expect(
        any(e.claim_id == claim.claim_id for e in restored)
        and min(e.seq for e in restored if e.claim_id == claim.claim_id) < done[0].seq,
        "(c) no resident_claim_restored before the continuation's output",
    )
    # the restore went through kv_block_copy: the pages are the offloaded bytes
    for chain, (k, v) in zip(chains, saved):
        blk = eng.pool.blocks[eng.pool.prefix_index[chain]]
        expect(
            np.array_equal(np.asarray(blk.k), k) and np.array_equal(np.asarray(blk.v), v),
            "(c) restored page bytes differ from the offloaded bytes",
        )
    no_fail_closed(eng, "(c)")
    check_analyzers(eng, "(c)")
    log(f"(c) claim {claim.claim_id}: {len(blocks)} pages offloaded to host and "
        f"restored bit-exact; continuation served {len(cont.output_tokens)} tokens")


def phase_witness_b(eng, rng, vocab: int):
    """(d) controlled same-claim restoration failure -> fail closed."""
    prefix = tuple(int(t) for t in rng.integers(0, vocab, 128))
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    finished_in_full([eng.run(eng.submit(prefix + (21, 22), NEW_TOKENS))], "(d) serve")
    expect(eng.offload_claim(claim.claim_id), "(d) offload failed")
    inj = eng.connector.injection
    inj.resident_claim_load_failure, inj.fail_claim_id = True, claim.claim_id
    try:
        r = eng.run(eng.submit(prefix + (23, 24), NEW_TOKENS))
    finally:
        inj.resident_claim_load_failure, inj.fail_claim_id = False, None
    verdict = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id)
    expect(verdict.passed, f"(d) witness path B: {verdict.reasons}")
    expect(r.output_tokens == [], "(d) refused request served output")
    counts = eng.fail_closed_total()
    expect(counts == {"injected_load_failure": 1}, f"(d) fail_closed_total={counts}")
    log(f"(d) witness path B: {r.request_id} {r.status}, claim {claim.state.value}, "
        f"fail_closed_total={counts}")


def phase_correctness(eng, bundle, params, rng, cfg):
    """(e) kernel path vs jnp path on the device, and kernels vs oracles."""
    prompt = tuple(int(t) for t in rng.integers(0, cfg.vocab_size, 203))
    got = eng.prefill_logits(prompt)
    with ServingEngine(
        bundle, params, block_size=BLOCK, device_blocks=64,
        cache_len=256, decode_mode="dense",
    ) as dense:
        want = dense.prefill_logits(prompt)
    expect(got.shape == want.shape == (cfg.vocab_size,), f"(e) logits shape {got.shape}")
    expect(bool(np.isfinite(got).all() and np.isfinite(want).all()), "(e) non-finite logits")
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    log(f"(e) first-token logits, kernel vs jnp: max abs diff={diff} "
        f"(bound {LOGITS_RTOL} x max|logit| {scale} = {LOGITS_RTOL * scale}), "
        f"argmax {int(got.argmax())} vs {int(want.argmax())}")
    expect(diff <= LOGITS_RTOL * scale, "(e) kernel-path logits outside the bf16 bound")

    # paged kernels vs kernels/ref.py at this model's serving widths
    kv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    g = cfg.num_heads // kv
    B, page, N, P, T, C = 4, BLOCK, 64, 16, 24, 32
    r = np.random.default_rng(int(rng.integers(1 << 31)))
    bf = lambda *s: jnp.asarray(r.normal(size=s), jnp.bfloat16)
    kp, vp = bf(kv, N, page, d), bf(kv, N, page, d)
    bt = jnp.asarray(r.integers(0, N, (B, P)), jnp.int32)
    plen = jnp.asarray(r.integers(1, P * page + 1, B), jnp.int32)
    used = r.integers(1, T + 1, B)
    tpos = np.full((B, T), -1, np.int32)
    for b in range(B):
        tpos[b, : used[b]] = int(plen[b]) + np.arange(used[b])
    dec_args = (bf(B, kv, g, d), kp, vp, bt, plen, bf(B, kv, T, d), bf(B, kv, T, d),
                jnp.asarray(tpos), plen + jnp.asarray(used - 1, jnp.int32))
    pre_args = (bf(B, kv, g, C, d), kp, vp, bt,
                jnp.asarray(r.integers(0, P + 1, B) * page, jnp.int32),
                bf(B, kv, C, d), bf(B, kv, C, d))
    for name, fn, oracle, args in (
        ("paged decode", ops.paged_decode_attention, ref.paged_decode_attention_ref, dec_args),
        ("chunked prefill", ops.paged_prefill_attention, ref.paged_prefill_attention_ref, pre_args),
    ):
        out = np.asarray(fn(*args), np.float32)
        exp = np.asarray(oracle(*args), np.float32)
        err = float(np.max(np.abs(out - exp)))
        log(f"(e) {name} kernel vs ref at B={B}, KV={kv}, G={g}, D={d}: max abs diff={err}")
        try:
            np.testing.assert_allclose(out, exp, **KERNEL_TOL)
        except AssertionError as e:
            raise SmokeFailure(f"(e) {name} kernel disagrees with its oracle: {e}")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="run every phase at reduced() size on any backend; never prints the ok line",
    )
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(
            f"chip_smoke: no TPU (jax.devices()[0].platform={dev.platform!r}); "
            "use --rehearse for a CPU dry run",
            file=sys.stderr,
        )
        return 2
    enable_compile_cache()
    clock = CompileClock()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"mode: {'rehearsal at reduced() size' if args.rehearse else 'full width'}")

    t0 = time.monotonic()
    cfg = get_config(MODEL)
    if args.rehearse:
        cfg = reduced(cfg)
    bundle = build_model(cfg)
    params = jax.block_until_ready(jax.jit(bundle.init_params)(jax.random.PRNGKey(args.seed)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    eng = ServingEngine(
        bundle, params, block_size=BLOCK, device_blocks=POOL_PAGES, cache_len=256
    )
    expect(eng.decode_mode == "paged", f"(a) engine fell back to {eng.decode_mode!r}")
    log(f"(a) {cfg.name}: {cfg.num_layers} layers, d {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}x{cfg.resolved_head_dim}, vocab "
        f"{cfg.vocab_size}, {n_params} params; pool {POOL_PAGES} pages of {BLOCK}")

    rng = np.random.default_rng(args.seed)
    phases = (
        ("(b) serve", lambda: phase_serve(eng, rng, cfg.vocab_size, on_tpu)),
        ("(c) offload/restore", lambda: phase_offload_restore(eng, rng, cfg.vocab_size)),
        ("(d) witness B", lambda: phase_witness_b(eng, rng, cfg.vocab_size)),
        ("(e) correctness", lambda: phase_correctness(eng, bundle, params, rng, cfg)),
    )
    try:
        for name, run in phases:
            c0, w0 = clock.seconds, time.monotonic()
            run()
            wall = time.monotonic() - w0
            comp = clock.seconds - c0
            log(f"smoke timing {name}: {wall:.1f} s wall, of which {comp:.1f} s compiling")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        eng.close()
    total = time.monotonic() - t0
    log(f"smoke timing total: {total:.1f} s wall, {clock.seconds:.1f} s compiling, "
        f"{total - clock.seconds:.1f} s serving and checking")
    if args.rehearse:
        log("rehearsal passed (no device claim is made)")
        return 0
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
