"""Tail read-backs copy only the written slots of the rows they store.

When a row finishes feeding, retires, or survives a host rebuild of the
batched state, the step loop copies that row's tail slots ``[0, T)`` to the
host, ``T`` its written slot count rounded up a ladder of powers of two
from the block size, capped at the tail's capacity — not the whole
``[L, W, tail_cap, KV, Dh]`` tail.  The folds read no slot past the written
ones, so the pool's pages and the served tokens are bitwise what a
whole-tail read-back gives.
"""
import math
import sys

import jax
import numpy as np
import pytest

import repro.serving.scheduler_loop as sl
from repro.configs import get_config, reduced
from repro.core.claims import ClaimMode
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine

BS = 4
DOC = tuple(range(300, 340))  # 10 blocks of 4: two prefill chunks
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# continuations of DOC as (own tokens, max new tokens), and fresh prompts as
# (prompt tokens, max new tokens); a fresh prompt of 3 chunks joins at the
# third step with a larger need, so the tail's capacity grows while the
# continuations hold written slots (a host rebuild)
CASES = {
    "w4_three_rows": dict(conts=[(3, 6), (3, 9), (11, 3)], fresh=[(70, 24)]),
    "w8_five_rows": dict(conts=[(5, 4), (5, 7), (17, 3), (2, 12), (9, 5)], fresh=[(66, 30)]),
    "exact_hit_and_two_fresh": dict(
        conts=[(0, 5), (6, 8), (6, 3), (13, 6)], fresh=[(70, 20), (71, 26)]
    ),
}


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp):
    bundle, params = bp
    return ServingEngine(
        bundle, params, block_size=BS, device_blocks=96, cache_len=64, host_blocks=64
    )


def ladder(t, cap):
    """Slots a read-back of ``t`` written slots copies."""
    return min(cap, BS * 2 ** math.ceil(math.log2(math.ceil(t / BS))))


def rungs(cap):
    return len({ladder(t, cap) for t in range(1, cap + 1)})


def requests(eng, case, replay=0):
    rng = np.random.default_rng([replay, len(case["conts"])])
    reqs = []
    for n_own, new in case["conts"]:
        own = tuple(int(t) for t in rng.integers(1, 64, n_own))
        reqs.append(eng.submit(DOC + own, max_new_tokens=new))
    for n, new in case["fresh"]:
        toks = tuple(int(t) for t in rng.integers(1, 64, n))
        reqs.append(eng.submit(toks, max_new_tokens=new))
    return reqs


def serve(bp, case):
    """DOC stored, then one batch of the case; returns (engine, requests)."""
    eng = make_engine(bp)
    eng.run_batch([eng.submit(DOC, max_new_tokens=2)])
    reqs = requests(eng, case)
    eng.run_batch(reqs)
    for r in reqs:
        assert r.status == "finished", (r.request_id, r.error)
    return eng, reqs


def record_reads(monkeypatch, reads):
    """Record every tail read-back: (caller, step, picks, W, live rows,
    capacity, events before it)."""
    real = sl.StepLoop._read_tails

    def recording(self, picks):
        k = self._state["k_tail"]
        caller = sys._getframe(1).f_code.co_name
        reads.append(
            (caller, self.step_idx, list(picks), k.shape[1], len(self._members),
             k.shape[2], len(self.eng.events))
        )
        return real(self, picks)

    monkeypatch.setattr(sl.StepLoop, "_read_tails", recording)


def whole_tail(self, picks):
    """The reference: the whole batched tail read back, sliced on the host."""
    k, v = self.eng._readback_kv(self._state["k_tail"], self._state["v_tail"])
    return [(k[:, i], v[:, i]) for i, _ in picks]


def pool_state(eng):
    pool = eng.pool
    used = sorted(set(range(pool.k_pages.shape[2])) - set(pool._free_pages))
    blocks = sorted((b.page_index, tuple(b.tokens)) for b in pool.blocks.values())
    return used, blocks, pool.k_pages[:, :, used].tobytes(), pool.v_pages[:, :, used].tobytes()


@pytest.mark.parametrize("name", sorted(CASES))
def test_written_slot_reads_match_the_whole_tail_bitwise(bp, monkeypatch, name):
    case = CASES[name]
    reads = []
    with monkeypatch.context() as m:
        record_reads(m, reads)
        eng, reqs = serve(bp, case)
    with monkeypatch.context() as m:
        m.setattr(sl.StepLoop, "_read_tails", whole_tail)
        ref, ref_reqs = serve(bp, case)
    assert [r.output_tokens for r in reqs] == [r.output_tokens for r in ref_reqs]
    got, want = pool_state(eng), pool_state(ref)
    assert got[:2] == want[:2]
    assert got[2] == want[2] and got[3] == want[3]
    eng.pool.assert_consistent()
    # the batch ran every read-back site, past one rung, with a padded width
    callers = {c for c, *_ in reads}
    assert callers == {"_finish_feed", "_retire", "_sync_state"}
    feeds = [(step, t, cap) for c, step, picks, _, _, cap, _ in reads
             if c == "_finish_feed" for _, t in picks]
    assert len({ladder(t, cap) for _, t, cap in feeds}) >= 2
    steps = [step for step, _, _ in feeds]
    assert any(steps.count(s) >= 2 for s in steps)
    assert any(w > live for _, _, _, w, live, _, _ in reads)


def test_step_records_carry_the_taken_slots_only(bp, monkeypatch):
    reads, chunks = [], []
    record_reads(monkeypatch, reads)
    advance = sl.PrefillJob.advance

    def recording(self):
        chunks.append((len(self.eng.events), self.B, self.C))
        return advance(self)

    monkeypatch.setattr(sl.PrefillJob, "advance", recording)
    eng, _ = serve(bp, CASES["w8_five_rows"])
    cfg = eng.cfg
    slot = cfg.num_layers * cfg.num_kv_heads * cfg.resolved_head_dim * 2 * eng.pool.k_pages.itemsize
    copies = []  # (events before the copy, bytes taken, at most this many)
    for _, _, picks, _, _, cap, n in reads:
        taken = sum(ladder(t, cap) for _, t in picks) * slot
        copies.append((n, taken, sum(2 * max(t, BS) for _, t in picks) * slot))
    for n, B, C in chunks:
        copies.append((n, B * C * slot, B * C * slot))
    events = eng.events.events
    step_at = [i for i, e in enumerate(events) if e.name == "step_scheduled"]
    assert all(n <= step_at[-1] for n, _, _ in copies)
    lo = -1
    for j in step_at:
        mine = [c for c in copies if lo < c[0] <= j]
        got = events[j].payload["readback_bytes"]
        assert got == sum(c[1] for c in mine)
        assert got <= sum(c[2] for c in mine)
        lo = j


def test_a_repeated_claim_cycle_builds_no_program(bp, monkeypatch):
    """The claim cycle (offload, restore, feed folds, a rebuild, retires)
    served three times: the takes build programs in the first pass only,
    at most the ladder's rungs per tail shape, and the third pass builds no
    program at all (the second may still build a mirror scatter for a
    dirty-page count the first pass's pool did not give)."""
    eng = make_engine(bp)
    claim = eng.accept_claim(DOC, ClaimMode.OFFLOADABLE)
    eng.run_batch([eng.submit(DOC, max_new_tokens=2)])
    seen = set()
    take = sl._take_tail

    def recording(k, v, i, n):
        seen.add((k.shape, n))
        return take(k, v, i, n)

    monkeypatch.setattr(sl, "_take_tail", recording)
    built = [0]

    def on_compile(event, duration, **_kw):
        if event == COMPILE_EVENT:
            built[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        passes, takes = [], [take._cache_size()]
        for replay in (1, 2, 3):
            before = built[0]
            assert eng.offload_claim(claim.claim_id)
            reqs = requests(eng, CASES["exact_hit_and_two_fresh"], replay)
            eng.run_batch(reqs)
            assert all(r.status == "finished" for r in reqs)
            assert any(r.restored_tokens for r in reqs)
            passes.append(built[0] - before)
            takes.append(take._cache_size())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert passes[2] == 0, passes
    assert takes[1] == takes[2] == takes[3], takes
    shapes = {shape for shape, _ in seen}
    for shape in shapes:
        assert len({n for s, n in seen if s == shape}) <= rungs(shape[2])
    assert takes[1] - takes[0] <= len(seen) <= sum(rungs(shape[2]) for shape in shapes)
