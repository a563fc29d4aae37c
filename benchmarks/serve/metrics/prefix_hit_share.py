"""Page pool (BlockPool): share of the window's prompt tokens that were
served from pool pages (radix hits and restored claims) instead of being
computed; moves ``ttft_p90_ms``."""


def read(w):
    reqs = w.requests
    total = sum(len(q.tokens) for q in reqs)
    if total == 0:
        return None
    return 100.0 * sum(q.cached_tokens for q in reqs) / total
