"""Model step (prefill_chunk): mean time of one chunked-prefill launch,
from the program's ``prefill_chunk`` stage (host clock around
``block_until_ready``); moves ``ttft_p90_ms``."""


def read(w):
    xs = w.stages.get("prefill_chunk")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
