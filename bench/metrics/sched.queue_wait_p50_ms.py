"""Scheduler queue wait: arrival to admission (the host-clock stamps
``ServeEngine.serve`` keeps per request), median over the window's
requests, in ms."""
import numpy as np


def read(run):
    w = [r.admitted_s - r.arrival_s for r in run.stats.results]
    return 1e3 * float(np.percentile(w, 50)) if w else None
