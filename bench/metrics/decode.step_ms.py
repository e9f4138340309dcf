"""Device time of one decode step: the device seconds of the decode
program's runs (``jit(decode_loop)`` in the trace's XLA Modules line)
over the steps they ran, in ms.  Re-runs of a chunk under streaming
count as the steps they ran."""


def read(run):
    if run.trace is None:
        return None
    runs, secs = run.trace.module_runs("decode_loop")
    if runs == 0:
        return None
    return 1e3 * secs / (runs * run.stats.chunk)
