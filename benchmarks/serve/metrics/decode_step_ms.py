"""Model step (paged_decode_step): mean time of one mixed decode launch,
from the program's ``decode_step`` stage (host clock around
``block_until_ready``); moves ``itl_p99_ms``."""


def read(w):
    xs = w.stages.get("decode_step")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
