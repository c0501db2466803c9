"""The local steps' convolutions: their least time over their measured
time per round (``conv_ms_per_round``), in %.

Least time = FLOPs ÷ peak, with FLOPs = the configuration reference's
``conv_train_flops`` (forward, input and weight gradient of every
convolution of one sample) × the samples of a node-round × nodes.  The
measured time holds every op that computes part of those FLOPs
(``chipbench.scopes.conv_op``), so the share cannot pass 100 %; what XLA
fused into them (bias, ReLU, a max-pool, the momentum update of the
kernels) lowers it."""
from chipbench import counts, scopes, traffic
from chipbench.peaks import peaks_for


def read(ctx):
    s = scopes.conv_s(ctx.trace)
    if not s:
        return None
    tr = ctx.traffic
    n = traffic.make_graph(tr["graph"]).shape[0]
    per_sample = counts.reference_module(ctx.cfg).conv_train_flops(ctx.cfg)
    flops = per_sample * tr["local_batches"] * tr["batch_size"] * n
    least = flops / peaks_for(ctx.device_kind)["flops"]
    return 100.0 * least / (s / ctx.window.rounds)
