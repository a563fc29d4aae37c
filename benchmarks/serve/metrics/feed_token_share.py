"""Scheduler (StepLoop): share of the tokens the window's steps carried
that were prompt tokens pushed one per decode launch (the feed path of a
continuation) rather than decoded or prefilled in chunks; moves
``ttft_p90_ms``."""


def read(w):
    steps = w.all_steps
    total = sum(s["n_decode"] + s["n_feed"] + s["prefill_tokens"] for s in steps)
    if total == 0:
        return None
    return 100.0 * sum(s["n_feed"] for s in steps) / total
