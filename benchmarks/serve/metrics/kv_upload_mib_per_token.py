"""Page pool (BlockPool): MiB of KV copied from the host to the device
(page-mirror and tail-state uploads) per token the window's steps
carried, from the ``upload_bytes`` of each ``step_scheduled`` record;
moves ``itl_p99_ms``.  Nothing to read where the records carry no such
count."""


def read(w):
    steps = w.all_steps
    tokens = sum(s["step_tokens"] for s in steps)
    if tokens == 0 or any("upload_bytes" not in s for s in steps):
        return None
    return sum(s["upload_bytes"] for s in steps) / 2**20 / tokens
