"""Training FLOPs of the untraced window over its wall seconds × chips ×
peak, in %.

Training FLOPs are 3 × forward FLOPs per sample × the local samples of
every node-round in the window (``chipbench.counts``); evaluation, the mix
and recomputation are not counted.  The wall time is the untraced
window's, so the profiler's cost does not enter it."""
from chipbench import counts
from chipbench.peaks import peaks_for


def read(ctx):
    w = ctx.timed
    flops = counts.train_flops_per_node_round(ctx.cfg, ctx.traffic) * w.node_rounds
    peak = peaks_for(ctx.device_kind)["flops"] * ctx.chips
    return 100.0 * flops / (w.wall_s * peak)
