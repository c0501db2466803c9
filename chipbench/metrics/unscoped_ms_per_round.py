"""Device time of the window's leaf ops under none of the program's scopes
(no ``dfl_*`` or ``halo_exchange`` in their ``tf_op`` path), per round,
mean over chips, in ms: work no other device metric reads.  None where no
op carries a scope."""
from chipbench import spans


def read(ctx):
    if spans.ops_s(ctx.trace, spans.scoped) is None:
        return None
    s = spans.ops_s(ctx.trace, lambda op: not spans.scoped(op))
    return 1e3 * (s or 0.0) / ctx.window.rounds
