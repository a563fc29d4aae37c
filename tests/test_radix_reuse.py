"""Pool-wide radix prefix reuse at the engine level.

Multi-turn conversations reuse turn-1 prompt AND decode-tail pages (the
radix fold at request end), diverging continuations copy-on-write at the
divergence block, a chaos fault on a shared restored page fails EVERY
sharing claim closed with its own attribution while bystanders serve
byte-identically, and claim expiry releases only the expired claim's
scope on shared blocks — a sharer's claim is never invalidated by
another claim's end-of-life.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.analyzer import (
    _counter_series,
    check_fail_closed_attribution,
    check_metrics_reconcile,
    check_shared_page_immutability,
    check_step_interleave_order,
    validate_event_sequence,
)
from repro.core.claims import ClaimMode, ClaimState
from repro.models.registry import build_model
from repro.serving.chaos import (
    FaultPlan,
    FaultSpec,
    TRIGGER_CORRUPTION,
    TRIGGER_PERMANENT,
)
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp, **kw):
    bundle, params = bp
    kw.setdefault("block_size", 4)
    kw.setdefault("device_blocks", 64)
    kw.setdefault("cache_len", 64)
    return ServingEngine(bundle, params, decode_mode="paged", **kw)


# ------------------------------------------------------------ multi-turn reuse


def test_multi_turn_reuse_shares_pages_and_logits(bp):
    """Turn 2 of a conversation reuses turn 1's prompt blocks AND its
    readmitted decode tail: the reused payloads are the SAME pool pages
    (np.shares_memory), the admission emits ``prefix_reuse``, and the
    prefill logits are byte-identical to a cold engine serving the
    concatenated prompt from scratch."""
    eng = make_engine(bp)
    t1 = tuple(range(10, 26))  # 16 tokens = 4 blocks
    r1 = eng.submit(t1, max_new_tokens=6)
    eng.run(r1)
    assert r1.status == "finished" and len(r1.output_tokens) == 6
    # turn-1 sequence = 22 tokens: 5 full blocks + a 2-token decode tail
    t2 = t1 + tuple(r1.output_tokens) + (901, 902)

    blocks = eng.pool.lookup_prefix(t2, eng.block_size)
    assert len(blocks) == 5, "prompt + folded decode tokens must be resident"
    pb = eng.pool.lookup_partial(blocks[-1].chain, t2[20:])
    assert pb is not None and pb.tokens == tuple(r1.output_tokens[4:])
    for b in blocks + [pb]:
        assert b.page_index is not None
        assert np.shares_memory(b.k, eng.pool.k_pages), "reuse must be zero-copy"

    cold = make_engine(bp)
    lg_warm = eng.prefill_logits(t2, max_new_tokens=2)
    lg_cold = cold.prefill_logits(t2, max_new_tokens=2)
    assert np.array_equal(lg_warm, lg_cold), "shared-prefix serve must be byte-identical"

    ev = eng.events.named("prefix_reuse")
    assert ev, "warm admission must witness the reuse"
    assert ev[-1].payload["n_tokens"] == 22
    assert ev[-1].payload["n_blocks"] == 6
    assert ev[-1].payload["partial_tokens"] == 2

    # full decode over reused pages matches a cold serve token-for-token
    r2 = eng.submit(t2, max_new_tokens=4)
    eng.run(r2)
    cold2 = make_engine(bp)
    rc = cold2.submit(t2, max_new_tokens=4)
    cold2.run(rc)
    assert r2.status == "finished"
    assert r2.output_tokens == rc.output_tokens

    eng.pool.assert_consistent()
    assert validate_event_sequence(eng.events).passed
    assert check_shared_page_immutability(eng.events).passed
    assert check_metrics_reconcile(eng.events, eng.metrics).passed
    # the prefill_logits probe's request finishes like any served request
    assert check_step_interleave_order(eng.events).passed


def test_no_sharing_baseline_isolates_requests(bp):
    """With prefix_sharing=False chains are request-salted: a repeat serve
    of the same prompt reuses nothing and emits no reuse events, but the
    outputs still agree (sharing is a pure capacity optimisation)."""
    eng = make_engine(bp, prefix_sharing=False)
    prompt = tuple(range(120, 136)) + (30, 31)
    r1 = eng.submit(prompt, max_new_tokens=3)
    eng.run(r1)
    r2 = eng.submit(prompt, max_new_tokens=3)
    eng.run(r2)
    assert r2.cached_tokens == 0
    assert not eng.events.named("prefix_reuse")
    assert not eng.events.named("page_cow")
    assert r1.output_tokens == r2.output_tokens


# --------------------------------------------------------------- COW divergence


def test_divergent_continuations_cow_shared_tail(bp):
    """Two continuations of the SAME turn-1 conversation diverge inside the
    shared decode-tail block: the extension must copy-on-write (fresh page,
    refcount witnessed), the shared bytes never move, and both serves are
    byte-identical to cold serves of their concatenated prompts."""
    eng = make_engine(bp)
    t1 = tuple(range(40, 56))
    r1 = eng.submit(t1, max_new_tokens=6)
    eng.run(r1)
    seq1 = t1 + tuple(r1.output_tokens)  # 22 tokens
    blocks = eng.pool.lookup_prefix(seq1, eng.block_size)
    pb = eng.pool.lookup_partial(blocks[-1].chain, seq1[20:])
    assert pb is not None
    n_shared = len(pb.tokens)
    shared_before = np.array(pb.k[:, :n_shared])

    p2, p3 = seq1 + (901, 902), seq1 + (911, 912)
    r2 = eng.submit(p2, max_new_tokens=2)
    r3 = eng.submit(p3, max_new_tokens=2)
    eng.run_batch([r2, r3])
    assert r2.status == "finished" and r3.status == "finished"

    cows = eng.events.named("page_cow")
    assert cows, "diverging continuations over a shared partial must COW"
    for e in cows:
        assert e.payload["refcount"] > 1
        assert e.payload["new_page_index"] != e.payload["page_index"]
    cow_count = sum(
        _counter_series(eng.metrics.snapshot(), "cow_copies_total").values()
    )
    assert cow_count == len(cows)
    # the shared content bytes were never mutated in place
    assert np.array_equal(np.asarray(pb.k[:, :n_shared]), shared_before)

    for req, prompt in ((r2, p2), (r3, p3)):
        cold = make_engine(bp)
        rc = cold.submit(prompt, max_new_tokens=2)
        cold.run(rc)
        assert req.output_tokens == rc.output_tokens

    eng.pool.assert_consistent()
    assert check_shared_page_immutability(eng.events).passed
    assert check_metrics_reconcile(eng.events, eng.metrics).passed


# ------------------------------------------------------------- chaos interplay


@pytest.mark.parametrize("trigger", [TRIGGER_CORRUPTION, TRIGGER_PERMANENT])
def test_fault_on_shared_restore_fails_every_sharer_closed(bp, trigger):
    """A {trigger} fault on a restore whose leading blocks are covered by
    TWO nested claims fails BOTH closed — each gets its own E12 in its own
    ordered stream, the refusal names both — while a bystander claim on a
    disjoint prefix restores and serves byte-identically to a cold engine."""
    plan = FaultPlan(seed=11)
    eng = make_engine(bp, fault_plan=plan, quarantine_after=None)
    p8 = tuple(range(200, 208))
    p16 = p8 + tuple(range(210, 218))  # extends p8: leading blocks shared
    pc = tuple(range(300, 316))  # disjoint bystander prefix
    a = eng.accept_claim(p8, ClaimMode.OFFLOADABLE)
    b = eng.accept_claim(p16, ClaimMode.OFFLOADABLE)
    c = eng.accept_claim(pc, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(p16 + (30, 31), max_new_tokens=1))
    eng.run(eng.submit(pc + (30, 31), max_new_tokens=1))
    # the shared leading blocks carry BOTH claims
    for blk in eng.pool.lookup_prefix(p8, eng.block_size):
        assert {a.claim_id, b.claim_id} <= blk.claim_ids

    # offload B (all 4 blocks leave the device), bring A's 2 leading blocks
    # back via an unclaimed restore, then offload A — now BOTH claims are
    # OFFLOADED and the next p16 restore covers both objects
    assert eng.offload_claim(b.claim_id)
    r_mid = eng.submit(p8 + (40, 41), max_new_tokens=1)
    eng.run(r_mid)
    assert r_mid.status == "finished" and b.state == ClaimState.OFFLOADED
    if trigger == TRIGGER_CORRUPTION:
        # corrupt the shared block as it lands at rest in A's store
        plan.schedule(FaultSpec(TRIGGER_CORRUPTION, boundary="host", claim_id=a.claim_id))
    assert eng.offload_claim(a.claim_id)
    assert eng.offload_claim(c.claim_id)
    if trigger == TRIGGER_PERMANENT:
        # fail the shared block's transfer on the way back up
        plan.schedule(
            FaultSpec(TRIGGER_PERMANENT, boundary="host_to_device", claim_id=a.claim_id)
        )

    r = eng.submit(p16 + (50, 51), max_new_tokens=2)
    eng.run(r)
    assert r.status == "refused" and r.output_tokens == []
    assert a.state == ClaimState.RESTORATION_FAILED
    assert b.state == ClaimState.RESTORATION_FAILED
    # per-sharer attribution: each claim's own E12, one refusal naming both
    e12 = eng.events.named("scheduler_resident_claim_restoration_failed")
    assert {e.claim_id for e in e12} >= {a.claim_id, b.claim_id}
    e13 = [
        e
        for e in eng.events.named("scheduler_active_request_refused")
        if e.request_id == r.request_id
    ]
    assert e13 and set(e13[0].payload["blocking_claim_ids"]) == {a.claim_id, b.claim_id}
    assert eng.fail_closed_total() == {trigger: 1}

    # bystander: untouched, restores, serves byte-identically to cold
    r4 = eng.submit(pc + (60, 61), max_new_tokens=2)
    eng.run(r4)
    assert r4.status == "finished" and c.state == ClaimState.RESTORED
    cold = make_engine(bp)
    rc = cold.submit(pc + (60, 61), max_new_tokens=2)
    cold.run(rc)
    assert r4.output_tokens == rc.output_tokens

    assert validate_event_sequence(eng.events).passed
    assert check_fail_closed_attribution(eng.events).passed
    assert check_metrics_reconcile(eng.events, eng.metrics).passed
    assert check_shared_page_immutability(eng.events).passed
    eng.close()


# ------------------------------------------------------------ claim-scoped end


def test_claim_expiry_releases_only_its_scope(bp):
    """Expiry of one sharer decrements — never invalidates: the shared
    blocks lose the expired claim's membership and keep the survivor's,
    stay resident, and keep serving the surviving claim's requests."""
    eng = make_engine(bp)
    p8 = tuple(range(600, 608))
    p16 = p8 + tuple(range(610, 618))
    a = eng.accept_claim(p8, ClaimMode.EXPIRING, duration_s=3600.0)
    b = eng.accept_claim(p16, ClaimMode.SOFT_PRIORITY, priority=5)
    r1 = eng.submit(p16 + (30, 31), max_new_tokens=1)
    eng.run(r1)
    blocks = eng.pool.lookup_prefix(p16, eng.block_size)
    assert len(blocks) == 4
    shared = blocks[:2]
    for blk in shared:
        assert {a.claim_id, b.claim_id} <= blk.claim_ids
        assert blk.priority == 5

    expired = eng.scheduler.sweep_expiry(now=float("inf"))
    assert [cl.claim_id for cl in expired] == [a.claim_id]
    eng._release_claim_blocks(expired)
    assert a.state == ClaimState.EXPIRED
    for blk in shared:
        assert a.claim_id not in blk.claim_ids
        assert b.claim_id in blk.claim_ids, "live sharer must keep its claim"
        assert blk.priority == 5, "priority recomputed from the survivor"
        assert blk.block_id in eng.pool.blocks, "shared block never invalidated"
    assert eng.pool.lookup_prefix(p16, eng.block_size) == blocks

    r2 = eng.submit(p16 + (40, 41), max_new_tokens=1)
    eng.run(r2)
    assert r2.status == "finished" and r2.cached_tokens >= len(p16)
    eng.pool.assert_consistent()
