"""Claims and tiers (EngineCore restore, OffloadingConnector): share of the
window spent restoring offloaded claims into the pool, from the program's
``restore`` stage; moves ``ttft_p90_ms``.  Nothing to read where the
window restored nothing."""


def read(w):
    xs = w.stages.get("restore")
    if not xs:
        return None
    return 100.0 * sum(xs) / w.seconds
