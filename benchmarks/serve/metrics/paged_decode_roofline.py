"""Kernels (paged_decode_attention): the least time the chip needs for the
window's decode attention -- real rows at their real context, by the
peaks table -- over the kernel's device time in the trace; moves
``itl_p99_ms``."""

from servebench import work


def read(w):
    if w.trace is None or not w.trace.kernel_s.get("paged_decode"):
        return None
    least = 0.0
    for contexts in w.trace.decode_contexts:
        flops, nbytes = work.decode_attention_work(contexts, w.dims)
        least += work.least_seconds(flops, nbytes, w.peaks)[0]
    return 100.0 * least / w.trace.kernel_s["paged_decode"]
