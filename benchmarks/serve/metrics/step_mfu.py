"""Device (whole step): operations the window's tokens needed -- every
token decoded, fed or prefilled, with attention at its own context -- over
the traced window times the chip's bf16 peak; moves ``output_tok_s``."""

from servebench import work


def read(w):
    if w.trace is None:
        return None
    flops = 0.0
    for contexts in w.trace.decode_contexts:
        flops += sum(work.decode_token_flops(n, w.dims) for n in contexts)
    for spans in w.trace.chunk_spans:
        flops += work.prefill_chunk_flops(spans, w.dims)
    return 100.0 * flops / (w.trace.window_s * float(w.peaks["bf16_flops"]))
