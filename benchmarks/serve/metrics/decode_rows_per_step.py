"""Scheduler (StepLoop): mean decode rows per step over the window's
``step_scheduled`` records.  Every decode row yields one output token, so
this is output tokens per step; moves ``output_tok_s``."""


def read(w):
    steps = w.all_steps
    if not steps:
        return None
    return sum(s["n_decode"] for s in steps) / len(steps)
