"""The whole serving window's share of the chip's bf16 peak: the model
FLOPs that the window's finished requests required (every prompt token
and every generated token, compensation at the mean true rank; the
model's ``request_flops``) over the traced window's length (the
profiler's clock, from the first to the last event of the window
span), in %."""
import numpy as np


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    ranks = [r for layer in run.ranks for p in layer.values() for r in p]
    mean_rank = float(np.mean(ranks))
    flops = sum(run.model.request_flops(run.geometry, r.prompt_len,
                                        len(r.tokens), mean_rank)
                for r in run.stats.results)
    return 100.0 * flops / t.window_s / run.peaks["bf16_flops_per_s"]
