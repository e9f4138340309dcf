"""Share of its roofline that the fused expert kernel reaches at decode:
the least time the routed tokens' work needs (``work.kernel_roofline_s``
over the window's router trace: experts hit, true ranks, live rows)
over the device time of the ``fused_expert_b*`` calls inside the decode
program's runs, in %."""
import work


def read(run):
    t = run.trace
    if t is None or run.stats.router_trace is None:
        return None
    spans = t.module_spans("decode_loop")
    busy = t.kernel_s("fused_expert_b", inside=spans)
    if busy <= 0:
        return None
    need = work.kernel_roofline_s(run.geometry, run.stats.router_trace,
                                  run.ranks, run.peaks)["roofline_s"]
    return 100.0 * need / busy
