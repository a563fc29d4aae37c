"""Chaos subsystem: deterministic fault plans, retry/backoff, corruption
checksums, worker death, tier quarantine, and per-trigger attribution.

Unit layer (no model): FaultPlan determinism and statelessness, the
TransferQueue worker-death regression (a poisoned job must unblock its
waiter AND leave the queue serviceable), DiskTier explicit close().

Engine layer (reduced qwen3): each fault class drives the ordered
fail-closed lifecycle — transients recover via bounded retry with no
counter movement; permanent/corruption/worker-death faults become
claim-scoped refusals whose reason, blocking claim and
``fail_closed_total`` trigger all match the injected plan; repeated tier
failures quarantine the tier while host-resident chains keep serving.
"""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.analyzer import (
    check_fail_closed_attribution,
    check_failure_outcome_path,
    check_retry_bounded,
    validate_event_sequence,
)
from repro.core.claims import ClaimMode, ClaimState
from repro.models.registry import build_model
from repro.serving.chaos import (
    FaultPlan,
    FaultSpec,
    TransferWorkerDied,
    WorkerKilled,
    corrupted_copy,
    payload_checksum,
    TRIGGER_CAPACITY,
    TRIGGER_CORRUPTION,
    TRIGGER_PERMANENT,
    TRIGGER_QUARANTINE,
    TRIGGER_TRANSIENT,
    TRIGGER_TRANSFER_ERROR,
    TRIGGER_TRANSIENT_EXHAUSTED,
    TRIGGER_WORKER_DEATH,
)
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import KVBlock
from repro.serving.tiers import DiskTier
from repro.serving.transfer_queue import RetryPolicy, TransferJob, TransferQueue


# ---------------------------------------------------------------------------
# unit layer: FaultPlan
# ---------------------------------------------------------------------------


def _draw_stream(plan, sites):
    return [
        (d.trigger if d else None)
        for d in (
            plan.draw_transfer(direction, {cid}, bid) for direction, cid, bid in sites
        )
    ]


def test_fault_plan_rates_deterministic_and_stateless():
    sites = [("host_to_device", f"c{i}", i) for i in range(64)]
    rates = {TRIGGER_TRANSIENT: 0.2, TRIGGER_PERMANENT: 0.1}
    a = _draw_stream(FaultPlan(seed=7, rates=rates), sites)
    b = _draw_stream(FaultPlan(seed=7, rates=rates), sites)
    assert a == b
    assert any(t is not None for t in a)  # the rates actually fire
    # statelessness: drawing OTHER sites in between must not shift a site's
    # decision — one claim's faults cannot perturb a bucket-mate's draws
    plan = FaultPlan(seed=7, rates=rates)
    for direction, cid, bid in sites[:32]:  # interleaved extra draws
        plan.draw_transfer(direction, {cid}, bid + 1000)
        plan.draw_transfer(direction, {cid}, bid)
    interleaved = _draw_stream(FaultPlan(seed=7, rates=rates), sites)
    assert interleaved == a
    # a different seed yields a different stream
    assert _draw_stream(FaultPlan(seed=8, rates=rates), sites) != a


def test_fault_plan_scheduled_specs_exact():
    plan = FaultPlan(seed=0).schedule(
        FaultSpec(TRIGGER_PERMANENT, boundary="disk_to_device", claim_id="c1"),
        FaultSpec(TRIGGER_TRANSIENT, boundary="host_to_device", claim_id="c2", repeats=2),
    )
    assert plan.armed_remaining == 2
    # non-matching boundary / claim: no fault
    assert plan.draw_transfer("host_to_device", {"c1"}, 1) is None
    assert plan.draw_transfer("disk_to_device", {"c9"}, 1) is None
    d = plan.draw_transfer("disk_to_device", {"c1"}, 1)
    assert d.trigger == TRIGGER_PERMANENT and not d.transient
    # transient spec: repeats consecutive failures on the SAME site, then clear
    d1 = plan.draw_transfer("host_to_device", {"c2"}, 5)
    d2 = plan.draw_transfer("host_to_device", {"c2"}, 5)
    assert d1.transient and d2.transient
    assert plan.draw_transfer("host_to_device", {"c2"}, 5) is None
    assert plan.armed_remaining == 0
    assert plan.stats.injected == {TRIGGER_PERMANENT: 1, TRIGGER_TRANSIENT: 2}


def test_checksum_detects_corrupted_copy():
    k = np.arange(64, dtype=np.float32).reshape(2, 8, 2, 2)
    v = np.ones_like(k)
    c = payload_checksum(k, v)
    assert c == payload_checksum(k.copy(), v.copy())
    bad = corrupted_copy(k)
    assert bad.shape == k.shape and bad.dtype == k.dtype
    assert payload_checksum(bad, v) != c
    assert not np.array_equal(bad, k) and k[0, 0, 0, 0] == 0  # input untouched


# ---------------------------------------------------------------------------
# unit layer: transfer queue worker death (satellite: no stranded wait())
# ---------------------------------------------------------------------------


def test_worker_death_unblocks_waiter_and_queue_stays_serviceable():
    q = TransferQueue()
    gate = threading.Event()
    j_hold = TransferJob(0, "store", gate.wait)
    j_die = TransferJob(1, "load", lambda: (_ for _ in ()).throw(
        WorkerKilled("chaos:worker_death", 7, "host_to_device")))
    j_queued = TransferJob(2, "load", lambda: None)
    q.submit(j_hold)
    q.submit(j_die)  # queued behind the holder
    q.submit(j_queued)  # queued behind the dying job
    gate.set()
    # the poisoned job's waiter unblocks with the death error (no deadlock)
    with pytest.raises(TransferWorkerDied):
        j_die.wait(timeout=5)
    # jobs queued behind the death are drained with the same error
    with pytest.raises(TransferWorkerDied):
        j_queued.wait(timeout=5)
    assert q.worker_deaths == 1
    # the NEXT submit restarts a fresh worker: the queue is serviceable
    done = []
    j_next = TransferJob(3, "store", lambda: done.append(True))
    q.submit(j_next)
    j_next.wait(timeout=5)
    assert done == [True]
    q.shutdown()
    q.shutdown()  # idempotent


def test_transient_retry_in_queue_reruns_fn():
    q = TransferQueue()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            from repro.serving.chaos import TransientTransferFault

            raise TransientTransferFault("chaos:transient_io@x", 1, "host_to_device")

    j = TransferJob(0, "load", flaky, policy=RetryPolicy(max_attempts=4, backoff_base_s=0.0))
    q.submit(j)
    j.wait(timeout=5)
    assert calls["n"] == 3
    assert q.retries_performed == 2
    q.shutdown()


# ---------------------------------------------------------------------------
# unit layer: DiskTier explicit close (satellite: no __del__)
# ---------------------------------------------------------------------------


def _mk_block(bid=1):
    k = np.arange(32, dtype=np.float32).reshape(2, 2, 2, 4)
    return KVBlock(bid, (1, 2), f"ch{bid}", k, k.copy(), np.arange(2))


def test_disk_tier_close_removes_spill_files():
    import os

    tier = DiskTier()
    tier.put(_mk_block())
    d = tier._tmp
    assert d is not None and os.path.isdir(d) and os.listdir(d)
    tier.close()
    assert not os.path.isdir(d)
    assert tier.used == 0
    tier.close()  # idempotent
    assert not hasattr(DiskTier, "__del__")  # lifecycle is explicit now


def test_disk_tier_context_manager():
    import os

    with DiskTier() as tier:
        tier.put(_mk_block())
        d = tier._tmp
    assert not os.path.isdir(d)


# ---------------------------------------------------------------------------
# engine layer
# ---------------------------------------------------------------------------

PREFIX = tuple(range(10, 26))  # 16 tokens = 4 blocks of 4


@pytest.fixture(scope="module")
def kv():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    def make(**kw):
        kw.setdefault("block_size", 4)
        kw.setdefault("device_blocks", 64)
        kw.setdefault("cache_len", 64)
        return ServingEngine(bundle, params, **kw)

    return make


def _offloaded_claim(eng, prefix=PREFIX, tier="host"):
    claim = eng.accept_claim(prefix, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(prefix + (30, 31), max_new_tokens=1))
    assert eng.offload_claim(claim.claim_id, tier=tier)
    return claim


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_transient_fault_recovers_via_retry(kv, tier):
    plan = FaultPlan(seed=1)
    eng = kv(fault_plan=plan, quarantine_after=None)
    claim = _offloaded_claim(eng, tier=tier)
    plan.schedule(
        FaultSpec(
            TRIGGER_TRANSIENT,
            boundary=f"{tier}_to_device",
            claim_id=claim.claim_id,
            repeats=2,
        )
    )
    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "finished" and r.cached_tokens == len(PREFIX)
    assert claim.state == ClaimState.RESTORED
    # two failing attempts, two retries, zero fail-closed outcomes
    assert plan.stats.injected == {TRIGGER_TRANSIENT: 2}
    assert eng.fail_closed_total() == {}
    retries = eng.events.named("transfer_retry_scheduled")
    assert [e.payload["attempt"] for e in retries] == [1, 2]
    assert eng.connector.retry_histogram == {1: 1, 2: 1}
    assert check_retry_bounded(eng.events, eng.connector.retry_policy.max_attempts).passed
    assert validate_event_sequence(eng.events).passed
    eng.close()


def test_transient_exhaustion_escalates_fail_closed(kv):
    plan = FaultPlan(seed=2)
    eng = kv(fault_plan=plan, quarantine_after=None)
    claim = _offloaded_claim(eng)
    # more consecutive failures than the retry budget: must NOT loop forever
    plan.schedule(
        FaultSpec(
            TRIGGER_TRANSIENT,
            boundary="host_to_device",
            claim_id=claim.claim_id,
            repeats=10,
        )
    )
    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused"
    assert "exhausted" in r.error
    assert eng.fail_closed_total() == {TRIGGER_TRANSIENT_EXHAUSTED: 1}
    v = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id)
    assert v.passed, v.reasons
    assert check_retry_bounded(eng.events, eng.connector.retry_policy.max_attempts).passed
    eng.close()


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_permanent_fault_is_attributed_claim_refusal(kv, tier):
    plan = FaultPlan(seed=3)
    eng = kv(fault_plan=plan, quarantine_after=None)
    claim = _offloaded_claim(eng, tier=tier)
    plan.schedule(
        FaultSpec(TRIGGER_PERMANENT, boundary=f"{tier}_to_device", claim_id=claim.claim_id)
    )
    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused" and f"chaos:{TRIGGER_PERMANENT}" in r.error
    assert claim.state == ClaimState.RESTORATION_FAILED
    assert eng.fail_closed_total() == {TRIGGER_PERMANENT: 1}
    v = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id, source_tier=tier)
    assert v.passed, v.reasons
    assert check_fail_closed_attribution(eng.events).passed
    eng.close()


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_corruption_detected_at_restore_never_reaches_device(kv, tier):
    plan = FaultPlan(seed=4)
    eng = kv(fault_plan=plan, quarantine_after=None)
    claim = eng.accept_claim(PREFIX, ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(PREFIX + (30, 31), max_new_tokens=1))
    # corrupt the first claim block as it lands at rest (post-checksum)
    plan.schedule(FaultSpec(TRIGGER_CORRUPTION, boundary=tier, claim_id=claim.claim_id))
    assert eng.offload_claim(claim.claim_id, tier=tier)
    assert plan.stats.injected == {TRIGGER_CORRUPTION: 1}

    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused" and "checksum_mismatch" in r.error
    assert eng.fail_closed_total() == {TRIGGER_CORRUPTION: 1}
    # the corrupted payload never reached the device pool
    bad = [e.payload["block_id"] for e in eng.events.named("offload_worker_load_failed")]
    for bid in bad:
        assert bid not in eng.pool.blocks
    v = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id, source_tier=tier)
    assert v.passed, v.reasons
    eng.close()


def test_worker_death_is_claim_refusal_and_engine_survives(kv):
    plan = FaultPlan(seed=5)
    eng = kv(fault_plan=plan, quarantine_after=None)
    claim = _offloaded_claim(eng)
    plan.schedule(
        FaultSpec(TRIGGER_WORKER_DEATH, boundary="host_to_device", claim_id=claim.claim_id)
    )
    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused" and TRIGGER_WORKER_DEATH in r.error
    assert eng.fail_closed_total() == {TRIGGER_WORKER_DEATH: 1}
    assert eng.connector.queue.worker_deaths == 1
    v = check_failure_outcome_path(eng.events, claim.claim_id, r.request_id)
    assert v.passed, v.reasons
    # the engine's transfer path is still serviceable after the death
    other = tuple(range(300, 316))
    c2 = _offloaded_claim(eng, prefix=other, tier="disk")
    r2 = eng.submit(other + (40, 41), max_new_tokens=1)
    eng.run(r2)
    assert r2.status == "finished" and c2.state == ClaimState.RESTORED
    assert validate_event_sequence(eng.events).passed
    eng.close()


def test_copy_kernel_failure_is_attributed_claim_refusal(kv, monkeypatch):
    """A restore whose kv_block_copy launch raises is no silent copy: the
    transfer job fails with trigger ``transfer_error`` and the claim takes
    the ordered fail-closed path; the engine keeps serving afterwards."""
    from repro.kernels import ops

    eng = kv(quarantine_after=None)
    claim = _offloaded_claim(eng)

    def broken_copy(*_a, **_kw):
        raise RuntimeError("copy kernel failed")

    monkeypatch.setattr(ops, "kv_block_copy", broken_copy)
    r = eng.submit(PREFIX + (40, 41), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused" and "copy kernel failed" in r.error
    assert r.output_tokens == []
    assert claim.state == ClaimState.RESTORATION_FAILED
    assert eng.fail_closed_total() == {TRIGGER_TRANSFER_ERROR: 1}
    assert check_fail_closed_attribution(eng.events).passed
    monkeypatch.undo()
    other = tuple(range(300, 316))
    c2 = _offloaded_claim(eng, prefix=other)
    r2 = eng.submit(other + (40, 41), max_new_tokens=1)
    eng.run(r2)
    assert r2.status == "finished" and c2.state == ClaimState.RESTORED
    assert validate_event_sequence(eng.events).passed
    eng.close()


def test_capacity_pressure_refused_at_admission(kv):
    plan = FaultPlan(seed=6).schedule(FaultSpec(TRIGGER_CAPACITY))
    eng = kv(fault_plan=plan, quarantine_after=None)
    r = eng.submit(tuple(range(100, 108)), max_new_tokens=1)
    eng.run(r)
    assert r.status == "refused" and TRIGGER_CAPACITY in r.error
    assert eng.fail_closed_total() == {TRIGGER_CAPACITY: 1}
    fin = [e for e in eng.events.named("request_finished") if e.request_id == r.request_id]
    assert fin and fin[0].payload["status"] == "REFUSED_ADMISSION"
    # the next admission is clean
    r2 = eng.submit(tuple(range(200, 208)), max_new_tokens=1)
    eng.run(r2)
    assert r2.status == "finished"
    eng.close()


def test_tier_quarantine_refuses_attributed_and_host_keeps_serving(kv):
    plan = FaultPlan(seed=7)
    eng = kv(fault_plan=plan, quarantine_after=2, device_blocks=128)
    # two disk claims that will fail permanently, one that rides out the
    # quarantine, one host claim that must keep serving
    victims, prefixes = [], []
    for i in range(3):
        p = tuple(range(1000 + 100 * i, 1016 + 100 * i))
        victims.append(_offloaded_claim(eng, prefix=p, tier="disk"))
        prefixes.append(p)
    host_p = tuple(range(5000, 5016))
    host_c = _offloaded_claim(eng, prefix=host_p, tier="host")

    for c, p in zip(victims[:2], prefixes[:2]):
        plan.schedule(
            FaultSpec(TRIGGER_PERMANENT, boundary="disk_to_device", claim_id=c.claim_id)
        )
        r = eng.submit(p + (1, 2), max_new_tokens=1)
        eng.run(r)
        assert r.status == "refused"
    q = eng.events.named("tier_quarantined")
    assert len(q) == 1 and q[0].payload["tier"] == "disk"
    assert eng.connector.health.is_quarantined("disk")

    # third disk claim: refused with quarantine attribution, disk untouched
    reads = eng.connector.disk.bytes_read
    r3 = eng.submit(prefixes[2] + (1, 2), max_new_tokens=1)
    eng.run(r3)
    assert r3.status == "refused" and f"tier_quarantined:disk" in r3.error
    assert eng.connector.disk.bytes_read == reads
    # new offloads to the quarantined tier are refused (claim NOT offloaded)
    c_new = eng.accept_claim(tuple(range(7000, 7016)), ClaimMode.OFFLOADABLE)
    eng.run(eng.submit(tuple(range(7000, 7016)) + (1,), max_new_tokens=1))
    assert not eng.offload_claim(c_new.claim_id, tier="disk")
    assert c_new.state == ClaimState.MATERIALIZED

    # host-resident chain still serves through the quarantine
    rh = eng.submit(host_p + (1, 2), max_new_tokens=1)
    eng.run(rh)
    assert rh.status == "finished" and host_c.state == ClaimState.RESTORED

    assert eng.fail_closed_total() == {
        TRIGGER_PERMANENT: 2,
        TRIGGER_QUARANTINE: 2,  # refused restore + refused offload
    }
    assert check_fail_closed_attribution(eng.events).passed
    assert validate_event_sequence(eng.events).passed
    eng.close()


def test_engine_close_is_idempotent_and_cleans_disk(kv):
    import os

    eng = kv()
    _offloaded_claim(eng, tier="disk")
    d = eng.connector.disk._tmp
    assert d is not None and os.path.isdir(d)
    eng.close()
    assert not os.path.isdir(d)
    eng.close()  # idempotent
    # context-manager form
    with kv() as eng2:
        _offloaded_claim(eng2, tier="disk")
        d2 = eng2.connector.disk._tmp
    assert not os.path.isdir(d2)
