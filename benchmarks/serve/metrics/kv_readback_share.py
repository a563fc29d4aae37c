"""Page pool (BlockPool): share of the window spent copying KV from the
device to the host (tail read-backs, chunk results), from the program's
``kv_readback`` stage; moves ``itl_p99_ms``.  Nothing to read where the
program times no such copy."""


def read(w):
    xs = w.stages.get("kv_readback")
    if not xs:
        return None
    return 100.0 * sum(xs) / w.seconds
