"""Scheduler (StepLoop): p90 over every request of the window of its wait
from its round's ``run_batch`` call to the first launch that carried its
tokens (the program's ``Request.scheduled_ts``); a request never scheduled
waits forever.  Moves ``ttft_p90_ms``.  Nothing to read where the program
stamps no ``scheduled_ts`` or the p90 is such a request."""

import math

from servebench import reduce as R


def read(w):
    waits = []
    for rd in w.rounds:
        for q in rd.reqs:
            t = getattr(q, "scheduled_ts", None)
            waits.append(math.inf if t is None else t - rd.t_call)
    if not waits:
        return None
    p90 = R.percentile(waits, 90)
    return 1e3 * p90 if math.isfinite(p90) else None
