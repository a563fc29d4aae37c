"""Host spans the harness puts around the engine's calls, for a traced run.

Each wrapped attribute of one ``ServingEngine`` instance runs inside a
``jax.profiler.TraceAnnotation`` named ``sb.<attribute>``, so the spans
land in the profiler's trace on the device's clock, and the reduction can
say what the host was doing in each idle gap of the device.  Nothing is
wrapped in an untraced run.

Besides the spans, the wrappers record what the work functions need:
the positions of every row of each decode launch and the real (non-zero)
prompt tokens of each chunk launch, and the host time of every upload of
the page mirror that changed it (synchronized inside its span).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Tuple

import jax
import numpy as np


@dataclass
class CallLog:
    recording: bool = False
    decode_positions: List[np.ndarray] = field(default_factory=list)  # [W] per launch
    chunk_calls: List[Tuple[np.ndarray, np.ndarray]] = field(default_factory=list)  # (tokens [B, C], first positions [B])
    upload_seconds: List[float] = field(default_factory=list)


def _annotated(name: str, fn):
    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return wrapper


def install(eng, log: CallLog) -> None:
    """Wrap the engine instance's attributes (instance attributes shadow the
    class's methods, so the engine's own calls go through them)."""
    for attr in ("run_batch", "_admit_and_restore"):
        setattr(eng, attr, _annotated("sb." + attr, getattr(eng, attr)))

    pages = eng._device_pages

    def device_pages():
        with jax.profiler.TraceAnnotation("sb._device_pages"):
            before = eng._pages_mirror
            t0 = time.monotonic()
            out = pages()
            if eng._pages_mirror is not before and (before is None or out[0] is not before[1]):
                jax.block_until_ready(out)
                if log.recording:
                    log.upload_seconds.append(time.monotonic() - t0)
            return out

    eng._device_pages = device_pages

    decode = eng._jit_paged_decode

    def paged_decode(params, state, toks, poss):
        with jax.profiler.TraceAnnotation("sb._jit_paged_decode"):
            if log.recording:
                log.decode_positions.append(np.asarray(poss))
            return decode(params, state, toks, poss)

    eng._jit_paged_decode = paged_decode

    chunk = eng._jit_prefill_chunk

    def prefill_chunk(params, state, toks, pos):
        with jax.profiler.TraceAnnotation("sb._jit_prefill_chunk"):
            if log.recording:
                log.chunk_calls.append((np.asarray(toks), np.asarray(pos)[:, 0]))
            return chunk(params, state, toks, pos)

    eng._jit_prefill_chunk = prefill_chunk


def pair_calls(log: CallLog, steps: List[dict]) -> Tuple[List[List[int]], List[List[Tuple[int, int]]]]:
    """Real work of each launch in the window.

    The k-th decode launch is the step of the k-th ``step_scheduled`` record
    that carried rows; its first ``n_rows`` rows are real (the rest pad the
    width), and a row at position p attends p + 1 keys.  The k-th chunk
    launch is the k-th record with prefill tokens; its first
    ``prefill_rows`` rows are real, and a real row's queries are its
    non-zero tokens from the chunk's first position on.
    """
    mixed = [s for s in steps if s["n_rows"] > 0]
    chunked = [s for s in steps if s["prefill_tokens"] > 0]
    if len(mixed) != len(log.decode_positions) or len(chunked) != len(log.chunk_calls):
        raise RuntimeError(
            f"launches and step records disagree: {len(log.decode_positions)} decode "
            f"launches for {len(mixed)} steps, {len(log.chunk_calls)} chunk launches "
            f"for {len(chunked)} steps"
        )
    decode = [
        [int(p) + 1 for p in pos[: s["n_rows"]]] for s, pos in zip(mixed, log.decode_positions)
    ]
    chunks = []
    for s, (toks, first) in zip(chunked, log.chunk_calls):
        rows = []
        for i in range(s["prefill_rows"]):
            n = int(np.count_nonzero(toks[i]))
            rows.append((int(first[i]), int(first[i]) + n))
        chunks.append(rows)
    return decode, chunks

