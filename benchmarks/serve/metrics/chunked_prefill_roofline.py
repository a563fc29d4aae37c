"""Kernels (paged_prefill_attention): the least time the chip needs for the
window's chunked-prefill attention -- real prompt tokens only, by the
peaks table -- over the kernel's device time in the trace; moves
``ttft_p90_ms``.  Nothing to read where the window ran no chunk."""

from servebench import work


def read(w):
    if w.trace is None or not w.trace.kernel_s.get("chunked_prefill"):
        return None
    least = 0.0
    for spans in w.trace.chunk_spans:
        flops, nbytes = work.prefill_attention_work(spans, w.dims)
        least += work.least_seconds(flops, nbytes, w.peaks)[0]
    return 100.0 * least / w.trace.kernel_s["chunked_prefill"]
