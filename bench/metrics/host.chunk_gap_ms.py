"""Host time between decode chunks, from the program's own spans
(``ServeStats.timeline``, ``serve/timeline.py``): for every chunk but
the last, the time from the end of its ``decode.sync`` span to the start
of the next chunk's ``decode.dispatch``, less the ``arrival_wait``,
``admit``, ``prefill`` and ``claim`` spans inside that interval; the
median over the window's chunks, in ms.  None where the program keeps no
timeline."""
import numpy as np

OUTSIDE = ("arrival_wait", "admit", "prefill", "claim")


def gaps_s(timeline):
    """The host gap after each chunk but the last, in seconds."""
    syncs = timeline.named("decode.sync")
    starts = [s.start_s for s in timeline.named("decode.dispatch")][1:]
    away = timeline.named(*OUTSIDE)
    out = []
    for sync, nxt in zip(syncs, starts):
        lo = sync.end_s
        inside = sum(min(s.end_s, nxt) - max(s.start_s, lo) for s in away
                     if s.start_s < nxt and s.end_s > lo)
        out.append(nxt - lo - inside)
    return out


def read(run):
    tl = getattr(run.stats, "timeline", None)
    if tl is None:
        return None
    g = gaps_s(tl)
    return 1e3 * float(np.median(g)) if g else None
