"""Claim-native KV serving engine: paged zero-copy decode + continuous
batching over the shared core.

This is the runtime the paper's patched-vLLM witness *demonstrates the
implementability of* — here built natively (DESIGN.md §2).  The decisive
property is the ordered, claim-scoped path:

  accept(C, P, leading_prefix_at_least(k)) -> materialized(C) ->
  offloaded(C) -> restore_required(C) -> same-claim load failure ->
  scheduler_resident_claim_restoration_failed(C) ->
  scheduler_active_request_refused(blocking_claim_ids=[C]) ->
  ... before terminal request-finished handling.

The claim lifecycle itself lives in ``core_engine.EngineCore`` — ONE
implementation shared with the snapshot engine; this module adds what is
specific to KV block chains and the execution strategy:

**Paged decode (default).**  Block payloads live in the pool's page store
(kv_cache.BlockPool) and decode attends over them IN PLACE through
per-request block tables (models/transformer.paged_decode_step; on TPU the
Pallas kernel kernels/paged_attention.py).  No dense per-request cache is
ever assembled: a reused or restored block is consumed at its page slot,
shared prefixes occupy their pages ONCE across the whole batch, and context
length is bounded by pool pages — not by a per-request cache shape.  Only
the in-flight tail (trailing partial block + decoded tokens) is per-request
state.  ``decode_mode="dense"`` keeps the gather-to-dense path as the jnp
reference, and serves int8-KV and non-transformer bundles, which have no
paged functions.

**One paged execution path.**  ``run_batch`` and ``prefill_logits`` both
drive the token-budget step loop in ``scheduler_loop.StepLoop``: every
scheduler step carries ALL live decode/feed rows in one mixed launch plus
at most one in-flight prefill chunk under ``max_tokens_per_step``, waiting
requests are admitted/restored between steps, and a request that completes
mid-stream frees its pages immediately.  Fresh prompts group into
same-bucket ``PrefillJob``s that run chunk by chunk
(models/transformer.prefill_chunk): each launch attends the pages already
written for its rows plus the in-flight chunk, and completed blocks land
in page slots before the next chunk, so peak prefill KV is
O(``prefill_chunk``) and admissible prompt length is bounded by pool pages.
The chunk graph stores bitwise-identical page bytes for every chunk size,
so chunk-size and restored-vs-cold parity are structural.  ``run(req)`` is
``run_batch([req])``.

The engine runs a REAL JAX model: cached/restored page payloads are the
bytes decode attends over, so a failed restore genuinely leaves the request
without its claimed KV (no fallback recompute is attempted for claim-scoped
restoration failure — that is the fail-closed semantics).
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.claims import ClaimState, ResidentClaim
from repro.serving.cache_object import KVChainKind
from repro.serving.chaos import TRIGGER_CAPACITY
from repro.serving.core_engine import (
    EngineCore,
    Request,
    Scheduler,
    SchedulerOutcome,
    _jitted_steps,
)
from repro.serving.kv_cache import (
    BlockPool,
    KVBlock,
    PoolExhausted,
    chain_hash,
    pin_chain,
    prefix_object_id,
    unpin_chain,
)
from repro.serving.offload import FailureInjectionConfig, OffloadingConnector
from repro.serving.scheduler_loop import (
    BATCH_PAD,
    DEFAULT_MAX_TOKENS_PER_STEP,
    StepLoop,
    _round_up,
)

__all__ = [
    "BATCH_PAD",
    "DEFAULT_MAX_TOKENS_PER_STEP",
    "DEFAULT_PREFILL_CHUNK",
    "Request",
    "Scheduler",
    "SchedulerOutcome",
    "ServingEngine",
    "_jitted_steps",
    "_round_up",
]

# Chunked prefill default (tokens per chunk): O(chunk) peak prefill KV and
# decode-interleavable prefill launches.  The chunk graph stores
# bitwise-identical page bytes for EVERY chunk size (including one chunk
# covering the whole prompt), so the size moves no logits surface.
DEFAULT_PREFILL_CHUNK = 32

# step_scheduled payload fields: KV bytes copied host->device (kv_upload)
# and device->host (kv_readback) during the step's loop iteration
KV_BYTE_FIELDS = ("upload_bytes", "readback_bytes")


@lru_cache(maxsize=16)
def _jitted_paged_steps(bundle):
    """Shared jitted paged decode and prefill chunk per bundle
    (cross-engine cache, like core_engine._jitted_steps)."""
    if bundle.paged_decode_fn is None:
        return None
    return jax.jit(bundle.paged_decode_fn), jax.jit(bundle.prefill_chunk_fn)


@jax.jit
def _scatter_pages(km, vm, idx, kd, vd):
    """Page slots ``idx`` of the K/V mirror ([L, KV, N, page, Dh]) set to
    ``kd``/``vd`` ([L, KV, len(idx), page, Dh]), out of place."""
    return km.at[:, :, idx].set(kd), vm.at[:, :, idx].set(vd)


class ServingEngine(EngineCore):
    """Claim-native engine over KV block chains: paged decode + batching."""

    kind = KVChainKind()

    def __init__(
        self,
        bundle,
        params,
        *,
        block_size: int = 8,
        device_blocks: int = 64,
        cache_len: int = 128,
        event_log=None,
        injection: Optional[FailureInjectionConfig] = None,
        namespace: str = "default",
        host_blocks: Optional[int] = None,
        disk_dir=None,
        decode_mode: str = "paged",
        prefill_chunk: Optional[int] = None,
        max_tokens_per_step: int = DEFAULT_MAX_TOKENS_PER_STEP,
        fault_plan=None,
        retry_policy=None,
        quarantine_after: Optional[int] = 3,
        prefix_sharing: bool = True,
    ):
        super().__init__(
            bundle,
            params,
            block_size=block_size,
            device_blocks=device_blocks,
            cache_len=cache_len,
            event_log=event_log,
            injection=injection,
            namespace=namespace,
            host_blocks=host_blocks,
            disk_dir=disk_dir,
            fault_plan=fault_plan,
            retry_policy=retry_policy,
            quarantine_after=quarantine_after,
        )
        paged = _jitted_paged_steps(bundle)
        if decode_mode == "paged" and paged is None:
            decode_mode = "dense"  # int8 / non-transformer bundles
        self.decode_mode = decode_mode
        if paged is not None:
            self._jit_paged_decode, self._jit_prefill_chunk = paged
        # prefill_chunk bounds peak prefill KV at O(chunk): every fresh
        # bucket runs chunk-by-chunk, each completed chunk's blocks landing
        # in pool pages before the next chunk launches
        if prefill_chunk is None:
            prefill_chunk = DEFAULT_PREFILL_CHUNK
        if prefill_chunk <= 0:
            raise ValueError(f"prefill_chunk must be positive, got {prefill_chunk}")
        self.prefill_chunk = _round_up(prefill_chunk, block_size)
        # unified step-scheduler budget: live rows (1 token each) + at most
        # one prefill chunk (chunk_len x bucket rows) per step
        self.max_tokens_per_step = max_tokens_per_step
        self._pages_mirror: Optional[Tuple[int, Any, Any]] = None
        # host buffers of the dirty pages a mirror sync uploads, per count
        self._page_bufs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.mirror_full_uploads = self.metrics.counter(
            "page_mirror_full_uploads_total",
            "uploads of the whole page pool to its device mirror",
        )
        self.mirror_scatter_syncs = self.metrics.counter(
            "page_mirror_scatter_syncs_total",
            "device mirror syncs that scattered only the dirty page slots",
        )
        self.mirror_pages_scattered = self.metrics.counter(
            "page_mirror_pages_scattered_total",
            "dirty pages copied by scatter syncs (padding not counted)",
        )
        # KV bytes the kv_upload / kv_readback stages copied since the step
        # loop last took them onto a step_scheduled record
        self._kv_bytes = dict.fromkeys(KV_BYTE_FIELDS, 0)
        # step-scheduler observability (registered unconditionally so the
        # reconcile rule step_tokens.count == |step_scheduled| holds 0==0
        # for dense/idle engines too)
        self.step_tokens = self.metrics.histogram(
            "scheduler_step_tokens",
            "tokens carried per unified scheduler step (decode+feed rows + prefill chunk)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
        )
        self.decode_stalls = self.metrics.counter(
            "decode_stall_steps_total",
            "scheduler steps where live decode rows did NOT launch (must stay 0)",
        )
        # pool-wide radix prefix sharing.  Gated on the cache-object kind:
        # a KV chain is position-sliceable, so any block-aligned prefix of
        # it is reusable by any request; a recurrent state snapshot
        # summarizes its exact prefix and is not (shareable = False).
        # prefix_sharing=False salts every chain with the request id —
        # request-private chains, the measured no-sharing baseline of
        # benchmarks/bench_radix.py.
        self.prefix_sharing = bool(
            prefix_sharing and getattr(self.kind, "shareable", False)
        )
        self.prefix_reuse_hits = self.metrics.counter(
            "prefix_reuse_hits_total",
            "admissions that found resident prefix pages (radix hit)",
        )
        self.cow_copies = self.metrics.counter(
            "cow_copies_total",
            "copy-on-write page copies at shared-page divergence points",
        )
        # the pool invokes this once per page_cow emit (metric witness 1:1)
        self.pool.on_cow = self.cow_copies.inc

    # ------------------------------------------------------------------ claims
    def _chain_root(self, req: Request) -> str:
        """Root hash for a request's block chains.  Sharing ON -> "" (the
        pool-wide radix root: content-equal prefixes collide on the same
        chain hashes and reuse each other's pages).  Sharing OFF -> a
        per-request salt, making every chain request-private.  Claims bind
        to root-"" chains (``_claims_covering_block`` walks from ""), so
        claim offload/restore requires sharing on; the salted mode exists
        as the measured no-sharing baseline."""
        return "" if self.prefix_sharing else "!" + req.request_id

    def _claims_covering_block(self, chain: str, block_index: int) -> Set[str]:
        """Claim ids whose prefix includes the block at this chain position."""
        out = set()
        for cid, prefix in self._claim_prefixes.items():
            nblocks = len(prefix) // self.block_size
            if block_index < nblocks:
                h = ""
                for i in range(block_index + 1):
                    h = chain_hash(h, prefix[i * self.block_size : (i + 1) * self.block_size])
                if h == chain:
                    out.add(cid)
        return out

    def _claim_device_blocks(self, claim: ResidentClaim) -> Optional[List[KVBlock]]:
        prefix = self._claim_prefixes[claim.claim_id]
        blocks = self.pool.lookup_prefix(prefix, self.block_size)
        nblocks = len(prefix) // self.block_size
        if len(blocks) < nblocks:
            return None
        return blocks[:nblocks]

    # ---------------------------------------------------------------- requests
    def submit(self, tokens: Sequence[int], max_new_tokens: int = 4) -> Request:
        return self._new_request(tokens, max_new_tokens)

    # ------------------------------------------------------------ cache plumbing
    def _dense_cache(self, blocks: List[KVBlock], batch: int = 1):
        """Gather-to-dense assembly (decode_mode="dense" only): copies every
        block payload into a per-request contiguous cache."""
        cache = self.bundle.make_cache(batch, self.cache_len)
        if not blocks:
            return cache, 0
        k = np.concatenate([b.k for b in blocks], axis=1)  # [L, n_tok, KV, Dh]
        v = np.concatenate([b.v for b in blocks], axis=1)
        pos = np.concatenate([b.positions for b in blocks])
        n = k.shape[1]
        cache["k"] = cache["k"].at[:, 0, :n].set(jnp.asarray(k))
        cache["v"] = cache["v"].at[:, 0, :n].set(jnp.asarray(v))
        cache["pos"] = cache["pos"].at[0, :n].set(jnp.asarray(pos))
        return cache, n

    def _device_pages(self) -> Tuple[Any, Any]:
        """Device mirror of the pool page store, keyed by the pool's page
        version and brought up to date by the cheapest of three paths:

        * frees only (no dirty page): the mirror is re-keyed.  No block
          table references a freed slot, so its stale bytes are unreachable.
        * a mirror of the pool's shape exists: the dirty page slots alone
          are copied to the device and scattered into the mirror, out of
          place, inside one ``kv_upload`` stage.  Their count is padded to
          a power of two by repeating the last slot with its own bytes
          (the duplicate writes agree), so there is one scatter program
          per power of two below the capacity.  On the chip the scatter
          costs one HBM read and write of the mirror (1.65 ms for each of
          K and V of a 1.05-GiB pool on a TPU v5e) against the ~150 ms of
          the whole pool's host-to-device copy it replaces.
        * the first build, a shape change, or a padded dirty count that
          would reach the capacity: the whole pool is uploaded.

        The scatter does not donate the old mirror: live decode states and
        the step loop's page snapshot still hold it, and keep valid arrays.
        The pool itself is not yet device-resident: this mirror is its
        only device copy."""
        pool = self.pool
        ver = pool._pages_version if pool.k_pages is not None else -1
        if self._pages_mirror is not None and self._pages_mirror[0] == ver:
            return self._pages_mirror[1], self._pages_mirror[2]
        if pool.k_pages is None:
            cfg = self.cfg
            z = jnp.zeros(
                (cfg.num_layers, cfg.num_kv_heads, 1, self.block_size, cfg.resolved_head_dim),
                jnp.bfloat16,
            )
            self._pages_mirror = (ver, z, z)
            return z, z
        dirty = pool._dirty_pages
        km = vm = None
        if self._pages_mirror is not None:
            _, km, vm = self._pages_mirror
        n = len(dirty)
        pad = 1 << (n - 1).bit_length() if n else 0
        if km is None or km.shape != pool.k_pages.shape or pad >= pool.k_pages.shape[2]:
            km, vm = self._upload_kv(pool.k_pages, pool.v_pages)
            self.mirror_full_uploads.inc()
            self.events.emit("page_mirror_synced", path="full", pages=pool.k_pages.shape[2])
        elif n:
            idx = np.fromiter(sorted(dirty), np.int32, n)
            idx = np.pad(idx, (0, pad - n), mode="edge")
            km, vm = self._upload_kv(
                *self._gather_pages(idx), then=partial(_scatter_pages, km, vm, idx)
            )
            self.mirror_scatter_syncs.inc()
            self.mirror_pages_scattered.inc(n)
            self.events.emit("page_mirror_synced", path="scatter", pages=n)
        dirty.clear()
        self._pages_mirror = (ver, km, vm)
        return km, vm

    def _gather_pages(self, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The pool's K and V page slots ``idx`` ([L, KV, len(idx), page,
        Dh]) copied into host buffers kept per count: fresh host memory
        costs a page fault per 4 KiB on first touch, which made a 256-page
        gather into new arrays take 266 ms on the TPU v5e host, against
        33 ms for the upload of the result.  Each buffer is free again
        once ``_upload_kv`` has synced on the scatter that reads it."""
        pool = self.pool
        bufs = self._page_bufs.get(len(idx))
        if bufs is None:
            shape = pool.k_pages.shape[:2] + (len(idx),) + pool.k_pages.shape[3:]
            bufs = (np.empty(shape, pool.k_pages.dtype), np.empty(shape, pool.k_pages.dtype))
            self._page_bufs[len(idx)] = bufs
        for pages, buf in zip((pool.k_pages, pool.v_pages), bufs):
            np.take(pages, idx, axis=2, out=buf, mode="clip")  # "raise" buffers its copy
        return bufs

    def _upload_kv(self, *arrays: np.ndarray, then=None) -> Tuple[Any, ...]:
        """Host->device copy of KV as the ``kv_upload`` stage, synced inside
        it: the next launch consumes the arrays at once, so the wait moves
        here from that launch.  ``then``, if given, runs on the copies
        inside the same stage and its result is synced and returned in
        their place.  The bytes copied go on the next step record."""
        with self.stage("kv_upload"):
            out = tuple(jnp.asarray(a) for a in arrays)
            if then is not None:
                out = then(*out)
            out = jax.block_until_ready(out)
        self._kv_bytes["upload_bytes"] += sum(a.nbytes for a in arrays)
        return out

    def _readback_kv(self, *arrays: Any, take=None) -> Tuple[np.ndarray, ...]:
        """Device->host copy of KV as the ``kv_readback`` stage (the copy is
        synchronous).  ``take``, if given, runs on the arrays inside the same
        stage and only the arrays it returns are copied.  The bytes copied go
        on the next step record."""
        with self.stage("kv_readback"):
            if take is not None:
                arrays = tuple(take(*arrays))
            out = tuple(jax.device_get(arrays))
        self._kv_bytes["readback_bytes"] += sum(a.nbytes for a in out)
        return out

    def _take_kv_bytes(self) -> Dict[str, int]:
        """KV bytes copied since the last call (a step record's payload)."""
        out, self._kv_bytes = self._kv_bytes, dict.fromkeys(KV_BYTE_FIELDS, 0)
        return out

    def _store_prefix_blocks(
        self, req: Request, ck, cv, upto: int, *, start: int = 0, pin: bool = True
    ) -> List[KVBlock]:
        """Slice a request's KV into reusable pool pages.

        ck/cv: [L, S, KV, Dh] (numpy or jnp) — the request's KV for token
        positions ``start..upto`` (``start`` must be block-aligned; blocks
        before it are assumed resident and are skipped, their chain hashes
        still folded in).

        With ``pin=True`` returns the stored/reused blocks from ``start``
        onward, every block PINNED (ref+1): a later allocation in the same
        batch must not evict a page this request's block table will attend.
        The caller unpins after decode.  Chunked prefill calls this once
        per chunk (``start`` = the chunk's first token) and accumulates the
        returned segments into one pinned chain; claim metadata is bound
        identically on every chunk — ``_claims_covering_block`` walks the
        same chain hashes and the protected set whichever chunk stores the
        block, so a claim accepted before prefill covers its blocks from
        the FIRST chunk onward.  On PoolExhausted the partial pins of THIS
        call are unwound before re-raising (a chunked caller unwinds its
        accumulated chain).
        """
        chain: List[KVBlock] = []
        h = self._chain_root(req)
        protected = self.scheduler.protected_claim_ids()
        ck = np.asarray(ck)
        cv = np.asarray(cv)
        try:
            for bi in range(upto // self.block_size):
                lo, hi = bi * self.block_size, (bi + 1) * self.block_size
                btoks = req.tokens[lo:hi]
                parent, h = h, chain_hash(h, btoks)
                if lo < start:
                    continue
                bid = self.pool.prefix_index.get(h)
                blk = self.pool.blocks.get(bid) if bid is not None else None
                if blk is not None and blk.chain == h and not blk.partial:
                    pass  # already resident (shared prefix)
                else:
                    claim_ids = self._claims_covering_block(h, bi)
                    prio = max(
                        [self.registry.get(c).priority for c in claim_ids],
                        default=0,
                    )
                    blk = self.pool.add_block(
                        btoks,
                        h,
                        ck[:, lo - start : hi - start],
                        cv[:, lo - start : hi - start],
                        np.arange(lo, hi),
                        priority=prio,
                        claim_ids=claim_ids,
                        protected_claims=protected,
                        parent=parent,
                    )
                if pin:
                    pin_chain((blk,))
                    chain.append(blk)
        except PoolExhausted:
            unpin_chain(chain)
            raise
        return chain

    def _fold_sequence_blocks(
        self,
        req: Request,
        seq: Sequence[int],
        tail_k: np.ndarray,
        tail_v: np.ndarray,
        plen: int,
        *,
        held_blocks: Sequence[KVBlock] = (),
        trailing_partial: bool = False,
        best_effort: bool = False,
    ) -> None:
        """Fold a request's computed KV back into pool pages along its
        radix path.

        ``seq`` is the request's token sequence (prompt, optionally plus
        generated output); ``tail_k``/``tail_v`` ([L, T, KV, Dh] numpy)
        hold the KV computed through the in-flight tail for positions
        ``plen..plen+T``.  Resident full blocks are skipped (radix
        descent); a matching partial block is EXTENDED — in place while
        this caller is its only holder (``held_blocks``), copy-on-write
        once shared; missing blocks are cut from the tail.  With
        ``trailing_partial`` the sub-block remainder is folded too, so
        decode tails become reusable prefix.  ``best_effort`` (retirement
        readmission) never evicts and never raises: it stops at the first
        allocation that would need a page the pool doesn't have free —
        readmitted blocks are an opportunistic cache fill, not an
        obligation anyone accepted.
        """
        bs = self.block_size
        tail_len = int(tail_k.shape[1]) if tail_k is not None else 0
        protected = self.scheduler.protected_claim_ids()
        held_ids = {id(b) for b in held_blocks}
        h = self._chain_root(req)
        seq = tuple(int(t) for t in seq)
        upto = len(seq) if trailing_partial else len(seq) - len(seq) % bs
        bi = 0
        lo = 0
        while lo < upto:
            hi = min(lo + bs, upto)
            btoks = seq[lo:hi]
            parent, h = h, chain_hash(h, btoks)
            is_full = hi - lo == bs
            bid = self.pool.prefix_index.get(h) if is_full else None
            blk = self.pool.blocks.get(bid) if bid is not None else None
            if blk is not None and blk.chain == h and not blk.partial:
                bi += 1
                lo = hi
                continue
            claim_ids = self._claims_covering_block(h, bi) if is_full else set()
            prio = max(
                [self.registry.get(c).priority for c in claim_ids], default=0
            )
            pb = self.pool.lookup_partial(parent, btoks)
            if pb is not None and len(pb.tokens) == len(btoks):
                return  # identical partial already resident (remainder)
            if pb is not None:
                ext_lo = lo + len(pb.tokens)
                if ext_lo < plen or hi - plen > tail_len:
                    return  # extension KV not covered by this tail
                held = 1 if id(pb) in held_ids else 0
                if best_effort and pb.ref > held and self.pool.free_slots <= 0:
                    return  # COW would need a page; never evict here
                self.pool.extend_block(
                    pb,
                    seq[ext_lo:hi],
                    tail_k[:, ext_lo - plen : hi - plen],
                    tail_v[:, ext_lo - plen : hi - plen],
                    block_size=bs,
                    held=held,
                    priority=prio,
                    claim_ids=claim_ids,
                    protected_claims=protected,
                )
            else:
                if lo < plen or hi - plen > tail_len:
                    return  # KV for these positions not covered by this tail
                if best_effort and self.pool.free_slots <= 0:
                    return
                ks = tail_k[:, lo - plen : hi - plen]
                vs = tail_v[:, lo - plen : hi - plen]
                pos = np.arange(lo, hi)
                if is_full:
                    self.pool.add_block(
                        btoks, h, ks, vs, pos,
                        priority=prio, claim_ids=claim_ids,
                        protected_claims=protected, parent=parent,
                    )
                else:
                    self.pool.add_partial_block(
                        btoks, parent, ks, vs, pos,
                        block_size=bs, priority=prio,
                        protected_claims=protected,
                    )
            bi += 1
            lo = hi

    def _readmit_decode_tail(
        self,
        req: Request,
        blocks: Sequence[KVBlock],
        plen: int,
        tail_k: np.ndarray,
        tail_v: np.ndarray,
    ) -> None:
        """Fold a finished request's decode tail back into the page store:
        generated tokens become reusable prefix for ANY later request (the
        next turn of the same conversation descends onto them like any
        other radix path).  Best-effort by design — readmitted blocks
        arrive unpinned and claimless (claims bind at prefill observation
        points, never retroactively), so they are ordinary eviction
        victims and a full pool skips readmission rather than evict."""
        if not (self.prefix_sharing and self.decode_mode == "paged"):
            return
        seq = tuple(req.tokens) + tuple(int(t) for t in req.output_tokens)
        self._fold_sequence_blocks(
            req, seq, tail_k, tail_v, plen,
            held_blocks=blocks, trailing_partial=True, best_effort=True,
        )

    def _materialize_claims(self, req: Request, materialized_tokens: int) -> None:
        """Named observation point: prefill_complete."""
        for claim in self._matching_claims(req.tokens):
            if claim.state != ClaimState.ACCEPTED:
                continue
            if claim.predicate.evaluate(materialized_tokens):
                prefix = self._claim_prefixes[claim.claim_id]
                nblocks = len(prefix) // self.block_size
                bytes_per_block = next(
                    (b.nbytes for b in self.pool.blocks.values()), 0
                )
                self._materialize_claim(
                    claim,
                    materialized_tokens=materialized_tokens,
                    n_blocks=nblocks,
                    footprint_bytes=nblocks * bytes_per_block,
                    request_id=req.request_id,
                )

    # ---------------------------------------------------------------- admission
    def _admit_and_restore(self, req: Request) -> Optional[List[KVBlock]]:
        """Admission + restore-before-reuse for one request.

        Returns the device-resident prefix blocks (possibly empty) when the
        request may proceed to prefill/decode, or None when it already
        terminated (admission refusal or fail-closed restoration outcome).
        The claim lifecycle here is entirely the shared EngineCore
        implementation.
        """
        req.status = "running"

        # --- injected pool/capacity pressure (chaos): refuse at admission,
        # attributed, before any allocation touches the pool ---
        if self.fault_plan is not None and self.fault_plan.draw_capacity(req.request_id):
            req.status = "refused"
            req.error = f"chaos:{TRIGGER_CAPACITY}"
            self.events.emit(
                "scheduler_admission_refused",
                request_id=req.request_id,
                blocking_claim_ids=[],
                conflict_action="refuse",
                stage="capacity_pressure",
                trigger=TRIGGER_CAPACITY,
            )
            self.fail_closed.increment(TRIGGER_CAPACITY)
            self.events.emit(
                "request_finished", request_id=req.request_id, status="REFUSED_ADMISSION"
            )
            return None

        # --- dense cache-shape ceiling (fail closed, not silent truncation) ---
        # The dense path writes prefill KV into a fixed [cache_len] cache;
        # a longer prompt would silently drop leading KV (make_cache keeps
        # the trailing slice) and decode would overwrite the last slot.
        # Refuse instead — the paged path has no such shape: context is
        # bounded by pool pages (SWA rings are exempt: the window is the
        # contract there).
        if (
            self.decode_mode != "paged"
            and not self.cfg.sliding_window
            and len(req.tokens) + req.max_new_tokens > self.cache_len
        ):
            req.status = "refused"
            req.error = (
                f"dense_cache_overflow: {len(req.tokens)} prompt + "
                f"{req.max_new_tokens} new tokens > cache_len={self.cache_len}"
            )
            self.events.emit(
                "scheduler_admission_refused",
                request_id=req.request_id,
                blocking_claim_ids=[],
                conflict_action="refuse",
                stage="cache_shape",
                trigger="dense_cache_overflow",
            )
            self.fail_closed.increment("dense_cache_overflow")
            self.events.emit(
                "request_finished", request_id=req.request_id, status="REFUSED_ADMISSION"
            )
            return None

        # --- device-resident prefix reuse (radix descent from this
        # request's chain root) ---
        root = self._chain_root(req)
        dev_blocks = self.pool.lookup_prefix(req.tokens, self.block_size, root=root)

        # --- explicit active/resident conflict action (admission) ---
        if self.decode_mode == "paged":
            # paged: decode tokens live in the tail, not in pool pages, and
            # already-resident blocks are shared — only missing full prompt
            # blocks need pages
            needed = len(req.tokens) // self.block_size - len(dev_blocks)
        else:
            needed = math.ceil(
                (len(req.tokens) + req.max_new_tokens) / self.block_size
            )
        refusal = self.scheduler.admission_check(req, needed)
        if refusal is not None:
            req.status = "refused"
            req.error = refusal.reason
            self.fail_closed.increment("admission_conflict")
            self.events.emit(
                "request_finished", request_id=req.request_id, status="REFUSED_ADMISSION"
            )
            return None

        # --- off-device (offloaded) continuation: restore-before-reuse ---
        hit_blocks = self.connector.lookup(
            req.tokens,
            self.block_size,
            req.request_id,
            skip_blocks=len(dev_blocks),
            start_chain=dev_blocks[-1].chain if dev_blocks else root,
        )
        if hit_blocks:
            if not self._restore_for_request(req, hit_blocks):
                return None
            dev_blocks = self.pool.lookup_prefix(
                req.tokens, self.block_size, root=root
            )

        # --- sub-block (decode-tail) reuse: the longest partial child under
        # the full-block hit.  Paged only — the partial page relies on
        # prefix_len masking past its valid length; dense assembly needs
        # contiguous full payloads. ---
        partial_tokens = 0
        if self.decode_mode == "paged":
            covered = len(dev_blocks) * self.block_size
            pb = self.pool.lookup_partial(
                dev_blocks[-1].chain if dev_blocks else root,
                req.tokens[covered:],
            )
            if pb is not None:
                partial_tokens = len(pb.tokens)
                dev_blocks = dev_blocks + [pb]

        req.cached_tokens = sum(len(b.tokens) for b in dev_blocks)
        if self.prefix_sharing and req.cached_tokens:
            self.events.emit(
                "prefix_reuse",
                request_id=req.request_id,
                n_blocks=len(dev_blocks),
                n_tokens=req.cached_tokens,
                partial_tokens=partial_tokens,
            )
            self.prefix_reuse_hits.inc()
        return dev_blocks

    # ------------------------------------------------------------- paged phase
    def _make_paged_state(
        self,
        blocks_per_req: List[List[KVBlock]],
        plens: List[int],
        tail_cap: int,
        tails: Optional[List[Optional[Dict[str, Any]]]] = None,
        *,
        pages: Tuple[Any, Any],
    ) -> Dict[str, Any]:
        """Assemble the jitted paged-decode state: pool pages + per-request
        block tables + in-flight tails.  ``pages`` is the step's device
        mirror snapshot (``StepLoop._sync_state``)."""
        B = len(blocks_per_req)
        jk, jv = pages
        L, KV, _, page, Dh = jk.shape
        P = _round_up(max((len(bl) for bl in blocks_per_req), default=0), 4)
        bt = np.zeros((B, P), np.int32)
        for i, bl in enumerate(blocks_per_req):
            pt = self.pool.page_table(bl)
            bt[i, : len(pt)] = pt
        tk = np.zeros((L, B, tail_cap, KV, Dh), jk.dtype)
        tv = np.zeros_like(tk)
        tpos = np.full((B, tail_cap), -1, np.int32)
        if tails is not None:
            for i, t in enumerate(tails):
                if t is None:
                    continue
                n = t["k"].shape[1]
                tk[:, i, :n] = t["k"]
                tv[:, i, :n] = t["v"]
                tpos[i, :n] = t["pos"]
        k_tail, v_tail = self._upload_kv(tk, tv)
        return {
            "k_pages": jk,
            "v_pages": jv,
            "block_tables": jnp.asarray(bt),
            "prefix_len": jnp.asarray(np.asarray(plens, np.int32)),
            "k_tail": k_tail,
            "v_tail": v_tail,
            "tail_pos": jnp.asarray(tpos),
        }

    # ------------------------------------------------------------- dense phase
    def _prepare_dense(self, req: Request, dev_blocks: List[KVBlock]) -> Optional[Dict[str, Any]]:
        """Dense-assembly prefill (decode_mode="dense"): gathers the block
        chain into a contiguous per-request cache."""
        cached = req.cached_tokens
        pin_chain(dev_blocks)
        try:
            if cached == 0:
                with self.stage("prefill"):
                    logits, cache = self._jit_prefill(
                        self.params, {"tokens": jnp.asarray([req.tokens], jnp.int32)}
                    )
                    jax.block_until_ready(logits)
                logits = logits[0]
            else:
                cache, _n = self._dense_cache(dev_blocks)
                logits = None
                for i, tok in enumerate(req.tokens[cached:]):
                    lg, cache = self._jit_decode(
                        self.params,
                        cache,
                        jnp.asarray([tok], jnp.int32),
                        jnp.asarray([cached + i], jnp.int32),
                    )
                    logits = lg[0]
                if logits is None:  # full prefix cached: replay last token
                    lg, cache = self._jit_decode(
                        self.params,
                        cache,
                        jnp.asarray([req.tokens[-1]], jnp.int32),
                        jnp.asarray([len(req.tokens) - 1], jnp.int32),
                    )
                    logits = lg[0]
            ck = np.asarray(cache["k"][:, 0])  # [L, S, KV, Dh]
            cv = np.asarray(cache["v"][:, 0])
            # dense decode owns a private cache copy, so the pins taken by
            # the store (to protect the chain mid-store) release right away
            unpin_chain(self._store_prefix_blocks(req, ck, cv, len(req.tokens)))
            self._materialize_claims(
                req, len(req.tokens) - len(req.tokens) % self.block_size
            )
        finally:
            unpin_chain(dev_blocks)
        return {"req": req, "cache": cache, "logits": logits, "pos": len(req.tokens)}

    @staticmethod
    def _stack_caches(caches: List[Any]):
        """Stack B single-request dense caches into one [B]-batched cache.

        ServingEngine caches are transformer-style dicts: ``pos`` is
        [B, Sc] (batch axis 0); ``k``/``v`` (and int8 scales) carry the
        batch on axis 1.
        """
        out = {}
        for key in caches[0]:
            axis = 0 if key == "pos" else 1
            out[key] = jnp.concatenate([c[key] for c in caches], axis=axis)
        return out

    def _decode_dense(self, entries: List[Dict[str, Any]]) -> None:
        reqs = [e["req"] for e in entries]
        cache = self._stack_caches([e["cache"] for e in entries])
        logits = jnp.stack([e["logits"] for e in entries])  # [B, V]
        step = lambda c, t, p: self._jit_decode(self.params, c, t, p)
        self._greedy_decode_loop(reqs, cache, logits, [e["pos"] for e in entries], step)

    # ---------------------------------------------------------------- execution
    def _refuse_allocation(self, req: Request, e: PoolExhausted) -> None:
        """Mid-prefill allocation hit protected-claim blocks: refuse THIS
        request with blocking-claim attribution (per-request isolation)."""
        req.status = "refused"
        req.error = str(e)
        self.fail_closed.increment("allocation_conflict")
        self.events.emit(
            "scheduler_admission_refused",
            request_id=req.request_id,
            blocking_claim_ids=e.blocking_claim_ids,
            conflict_action="refuse",
            stage="allocation",
            trigger="allocation_conflict",
        )
        self.events.emit(
            "request_finished",
            request_id=req.request_id,
            status="REFUSED_ADMISSION",
        )

    def run(self, req: Request) -> Request:
        """Execute a request to completion (prefill + greedy decode)."""
        return self.run_batch([req])[0]

    def prefill_logits(self, tokens: Sequence[int], max_new_tokens: int = 1) -> np.ndarray:
        """Serve one request and return its entry logits [V] as float32
        numpy — the logits its first output token is picked from, the
        comparison surface for parity tests and benches.

        Paged mode runs the request through the same ``StepLoop`` as
        ``run_batch`` and takes its row of the launch in which its prompt
        feed emptied.  The request then finishes like any served request:
        ``max_new_tokens`` decoded, its decode tail readmitted to the pool,
        a terminal ``request_finished``.  Dense mode prefills only and
        leaves the request un-decoded.  Raises ``RuntimeError`` if the
        request is refused or fails."""
        req = self.submit(tokens, max_new_tokens=max_new_tokens)
        entry: List[Any] = []
        if self.decode_mode == "paged":
            self._schedule_batch([req])
            StepLoop(self, [req], on_entry=lambda _req, lg: entry.append(lg)).run()
        else:
            dev = self._admit_and_restore(req)
            if dev is not None:
                entry.append(self._prepare_dense(req, dev)["logits"])
        if req.status in ("refused", "error"):
            raise RuntimeError(f"request terminated: {req.status} ({req.error})")
        return np.asarray(entry[0], np.float32)

    def _schedule_batch(self, reqs: List[Request]) -> None:
        """Batch preamble: the claim-expiry sweep, then ``batch_scheduled``.
        An expired claim's blocks lose that claim's membership (and its
        priority boost) but stay resident for their remaining sharers."""
        self._release_claim_blocks(self.scheduler.sweep_expiry())
        # uniform for EVERY batch size (including 1): span tracing and
        # metrics reconciliation never special-case singletons
        self.events.emit(
            "batch_scheduled",
            batch_size=len(reqs),
            request_ids=[r.request_id for r in reqs],
        )

    def run_batch(self, reqs: Sequence[Request]) -> List[Request]:
        """Continuous batching through the unified token-budget step loop
        (scheduler_loop.StepLoop): requests enter the waiting queue in
        submission order and are admitted FIFO; every scheduler step
        carries all live decode/feed rows plus at most one prefill chunk
        under ``max_tokens_per_step``; completion mid-stream frees pages
        immediately.

        Per-request event ordering (E0 .. terminal) is exactly the
        single-request stream (check_step_interleave_order enforces the
        grammar over any interleaving); claim-scoped admission refusals and
        fail-closed restoration outcomes drop a request from the batch
        without affecting the others (PoolExhausted attribution and
        blocking_claim_ids are per-request, as in witness path C), and a
        launch failure terminates its rows through the fail-closed boundary
        (``_fail_closed_error``) instead of escaping with requests stranded
        non-terminal.
        """
        reqs = list(reqs)
        self._schedule_batch(reqs)
        if self.decode_mode == "paged":
            StepLoop(self, reqs).run()
            return reqs
        # --- dense mode: prefill-then-decode (the jnp reference) ---
        entries: List[Dict[str, Any]] = []
        for req in reqs:
            try:
                dev_blocks = self._admit_and_restore(req)
                if dev_blocks is None:
                    continue
                entry = self._prepare_dense(req, dev_blocks)
                if entry is not None:
                    entries.append(entry)
            except PoolExhausted as e:
                self._refuse_allocation(req, e)
                continue
        if entries:
            try:
                self._decode_dense(entries)
            except Exception as e:  # noqa: BLE001 — launch boundary fails closed
                reason = f"{type(e).__name__}: {e}"
                for entry in entries:
                    self._fail_closed_error(
                        entry["req"], scope="decode_step",
                        trigger="decode_launch_failure", reason=reason,
                    )
                return reqs
        for entry in entries:
            self._finish_ok(entry["req"])
        return reqs
