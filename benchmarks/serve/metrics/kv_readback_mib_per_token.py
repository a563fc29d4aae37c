"""Page pool (BlockPool): MiB of KV copied from the device to the host per
token the window's steps carried, from the ``readback_bytes`` of each
``step_scheduled`` record; moves ``itl_p99_ms``.  Nothing to read where
the records carry no such count."""


def read(w):
    steps = w.all_steps
    tokens = sum(s["step_tokens"] for s in steps)
    if tokens == 0 or any("readback_bytes" not in s for s in steps):
        return None
    return sum(s["readback_bytes"] for s in steps) / 2**20 / tokens
