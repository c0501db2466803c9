"""Device time of the local steps' 2×2 max-pooling and its
``select-and-scatter`` backward (``chipbench.scopes.pool_op``: under
``dfl_local`` and ``model_pool``, less the convolutions fused into it),
per round of the window, mean over chips, in ms."""
from chipbench import scopes, spans


def read(ctx):
    s = spans.ops_s(ctx.trace, scopes.pool_op)
    return None if s is None else 1e3 * s / ctx.window.rounds
