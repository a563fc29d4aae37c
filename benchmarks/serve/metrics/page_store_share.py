"""Page pool (BlockPool): share of the window spent writing computed KV
into pool pages on the host, from the program's ``page_store`` stage;
moves ``ttft_p90_ms``.  Nothing to read where the program times no
store."""


def read(w):
    xs = w.stages.get("page_store")
    if not xs:
        return None
    return 100.0 * sum(xs) / w.seconds
