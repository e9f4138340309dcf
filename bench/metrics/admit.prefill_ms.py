"""Device time of one admission: the device seconds of the programs
named ``prefill`` or ``claim`` (the trace's XLA Modules line) run inside
the window, over the admissions the program counted
(``ServeStats.timeline`` counter ``admissions``), in ms.  None where the
program keeps no timeline or admitted nothing."""

PROGRAMS = ("prefill", "claim")


def read(run):
    t = run.trace
    tl = getattr(run.stats, "timeline", None)
    if t is None or tl is None:
        return None
    n = tl.counters.get("admissions", 0)
    if n == 0:
        return None
    return 1e3 * sum(t.module_runs(p)[1] for p in PROGRAMS) / n
