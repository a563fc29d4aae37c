"""Scheduler (StepLoop): host time per loop iteration outside every finer
program stage -- the program's ``step`` stage less its launches, restores,
KV copies and page stores; moves ``itl_p99_ms``.  Nothing to read where
the program times no ``step``."""

INNER = ("decode_step", "prefill_chunk", "restore", "kv_upload", "kv_readback", "page_store")


def read(w):
    steps = w.stages.get("step")
    if not steps:
        return None
    inner = sum(sum(w.stages.get(name, ())) for name in INNER)
    return 1e3 * (sum(steps) - inner) / len(steps)
