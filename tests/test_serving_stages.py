"""The step loop's stages and KV byte counters.

``EngineCore.stage`` is the one timing mechanism: a ``serve.<name>``
profiler span plus, on normal exit, one ``stage_seconds`` observation and
one ``stage_latency`` event whose ``ts - seconds .. ts`` is the span.  In
the step loop every iteration is a ``step``; inside it ``kv_upload``,
``kv_readback``, ``page_store``, ``restore``, ``prefill_chunk`` and
``decode_step`` never nest in one another.  Each ``step_scheduled`` record
carries the KV bytes its iteration copied each way, and every request is
stamped ``scheduled_ts`` at the first launch that carried its tokens.
"""
import time

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core.analyzer import check_metrics_reconcile
from repro.core.claims import ClaimMode
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine

INNER = ("kv_upload", "kv_readback", "page_store", "restore", "prefill_chunk", "decode_step")
DOC = tuple(range(300, 340))  # 10 blocks of 4: two prefill chunks


@pytest.fixture(scope="module")
def bp():
    cfg = reduced(get_config("qwen3-1.7b"))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, params


def make_engine(bp):
    bundle, params = bp
    return ServingEngine(
        bundle, params, block_size=4, device_blocks=96, cache_len=64, host_blocks=64
    )


def _spans(eng, name=None):
    """(stage, start, end) of every stage_latency event, in order."""
    return [
        (e.payload["stage"], e.ts - e.payload["seconds"], e.ts)
        for e in eng.events.named("stage_latency")
        if name is None or e.payload["stage"] == name
    ]


def _spy(eng, attr, calls):
    """Record (start, end, len(events) before, args, result) of every call
    to one engine attribute (instance attributes shadow the methods, so the
    engine's own calls go through the spy)."""
    fn = getattr(eng, attr)

    def wrapper(*args, **kwargs):
        n_ev, t0 = len(eng.events), time.monotonic()
        out = fn(*args, **kwargs)
        calls.append((t0, time.monotonic(), n_ev, args, out))
        return out

    setattr(eng, attr, wrapper)


def _serve_claim_cycle(eng, spies=()):
    """A claimed document prefilled in chunks and decoded; the claim
    offloaded; then a restored continuation of it beside a fresh prompt
    (restore, feed, chunk, mixed steps, retirement).  Returns the
    (run_batch call time, requests) of both batches."""
    claim = eng.accept_claim(DOC, ClaimMode.OFFLOADABLE)
    for attr, calls in spies:
        _spy(eng, attr, calls)
    batches = []
    first = [eng.submit(DOC, max_new_tokens=3)]
    batches.append((time.monotonic(), first))
    eng.run_batch(first)
    assert eng.offload_claim(claim.claim_id)
    second = [
        eng.submit(DOC + (7, 8, 9, 10, 11), max_new_tokens=4),
        eng.submit(tuple(range(500, 530)), max_new_tokens=5),
    ]
    batches.append((time.monotonic(), second))
    eng.run_batch(second)
    for _, reqs in batches:
        for r in reqs:
            assert r.status == "finished", (r.request_id, r.error)
    assert second[0].restored_tokens > 0
    return batches


def test_stages_are_disjoint_inside_every_step_and_sum_to_no_more(bp):
    eng = make_engine(bp)
    _serve_claim_cycle(eng)
    spans = _spans(eng)
    steps = [s for s in spans if s[0] == "step"]
    inner = [s for s in spans if s[0] in INNER]
    assert set(INNER) <= {s[0] for s in inner}
    # every inner stage lies inside exactly one step
    for name, lo, hi in inner:
        hosts = [st for st in steps if st[1] <= lo and hi <= st[2]]
        assert len(hosts) == 1, (name, lo, hi)
    for _, s_lo, s_hi in steps:
        mine = sorted((lo, hi, name) for name, lo, hi in inner if s_lo <= lo and hi <= s_hi)
        for (lo_a, hi_a, a), (lo_b, _, b) in zip(mine, mine[1:]):
            assert hi_a <= lo_b, f"{a} overlaps {b}"
        assert sum(hi - lo for lo, hi, _ in mine) <= s_hi - s_lo
    assert check_metrics_reconcile(eng.events, eng.metrics).passed


def test_each_stage_covers_its_own_work(bp):
    """Stores of computed KV run inside page_store; a mirror upload runs
    inside kv_upload, and the re-key path uploads nothing."""
    eng = make_engine(bp)
    stores, pages = [], []
    spies = [(a, stores) for a in ("_store_prefix_blocks", "_fold_sequence_blocks")]
    spies.append(("_device_pages", pages))
    _serve_claim_cycle(eng, spies)
    store_spans = _spans(eng, "page_store")
    assert stores and store_spans
    for t0, t1, *_ in stores:
        assert any(lo <= t0 and t1 <= hi for _, lo, hi in store_spans)
    uploads = _spans(eng, "kv_upload")
    prev = None
    n_uploading = 0
    for t0, t1, _, _, out in pages:
        inside = [u for u in uploads if t0 <= u[1] and u[2] <= t1]
        # a new mirror of the pool's pages, not the one-page placeholder
        # of an empty pool
        uploaded = (prev is None or out[0] is not prev[0]) and out[0].shape[2] > 1
        n_uploading += uploaded
        assert len(inside) == int(uploaded)
        prev = out
    assert n_uploading >= 2 and n_uploading < len(pages)


def test_step_records_carry_the_bytes_copied(bp):
    eng = make_engine(bp)
    ups, backs = [], []
    _serve_claim_cycle(eng, [("_upload_kv", ups), ("_readback_kv", backs)])
    assert ups and backs
    events = eng.events.events
    step_at = [i for i, e in enumerate(events) if e.name == "step_scheduled"]
    copied = []  # (events before the copy, upload bytes, readback bytes)
    copied += [(n, sum(a.nbytes for a in args), 0) for _, _, n, args, _ in ups]
    copied += [(n, 0, sum(a.nbytes for a in out)) for _, _, n, _, out in backs]
    assert all(n <= step_at[-1] for n, _, _ in copied)
    lo = -1
    for j in step_at:
        mine = [c for c in copied if lo < c[0] <= j]
        payload = events[j].payload
        assert payload["upload_bytes"] == sum(c[1] for c in mine)
        assert payload["readback_bytes"] == sum(c[2] for c in mine)
        lo = j
    # one kv stage event per copy
    assert len(_spans(eng, "kv_upload")) == len(ups)
    assert len(_spans(eng, "kv_readback")) == len(backs)


def test_queue_wait_ends_between_the_call_and_the_first_token(bp):
    eng = make_engine(bp)
    for t_call, reqs in _serve_claim_cycle(eng):
        for r in reqs:
            assert r.scheduled_ts is not None and r.first_token_ts is not None
            assert t_call <= r.scheduled_ts <= r.first_token_ts


def test_a_failed_launch_records_no_stage(bp):
    eng = make_engine(bp)
    r = eng.submit(tuple(range(100, 112)), max_new_tokens=2)

    def boom(params, state, toks, pos):
        raise RuntimeError("injected decode launch failure")

    eng._jit_paged_decode = boom
    eng.run_batch([r])
    assert r.status == "error"
    names = {s[0] for s in _spans(eng)}
    assert "decode_step" not in names
    assert {"step", "prefill_chunk"} <= names
    n = len(eng.events)
    with pytest.raises(RuntimeError):
        with eng.stage("decode_step"):
            raise RuntimeError("a body that raises")
    assert len(eng.events) == n
    assert check_metrics_reconcile(eng.events, eng.metrics).passed
