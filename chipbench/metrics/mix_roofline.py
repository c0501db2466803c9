"""The mix's roofline time over its measured time, in %.

Least time = max(bytes / HBM peak, FLOPs / peak) with bytes = 2·n·d·4
(every node's float32 parameters read and written once) and FLOPs =
2·(directed edges + n)·d, whatever renders the mix (``chipbench.counts``)."""
import numpy as np

from chipbench import counts, traffic
from chipbench.peaks import peaks_for


def read(ctx):
    s = ctx.trace.scope_s("dfl_mix")
    if not s:
        return None
    adj = traffic.make_graph(ctx.traffic["graph"])
    n, d = adj.shape[0], counts.params_per_node(ctx.cfg)
    least = counts.mix_least_seconds(n, d, int(np.count_nonzero(adj)), peaks_for(ctx.device_kind))
    return 100.0 * least / (s / ctx.window.rounds)
