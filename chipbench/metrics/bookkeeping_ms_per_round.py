"""Device time of the ops under the round body's bookkeeping scopes
(``chipbench.spans.BOOKKEEPING``: PRNG split and loss means, minibatch
gather, delivered-message replay, optimizer re-initialisation, σ moments),
each op once, per round, mean over chips, in ms."""
from chipbench import spans


def read(ctx):
    s = spans.ops_s(ctx.trace, spans.under(*spans.BOOKKEEPING))
    return None if s is None else 1e3 * s / ctx.window.rounds
