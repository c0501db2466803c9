"""Device time of the ops under the ``dfl_mix`` scope (DecAvg with its
per-round link masks and renormalisation), per round, mean over chips, ms."""


def read(ctx):
    s = ctx.trace.scope_s("dfl_mix")
    return None if s is None else 1e3 * s / ctx.window.rounds
