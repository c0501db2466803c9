"""Device time of the ops under the ``dfl_local`` scope (the local SGD
steps of every node), per round of the window, mean over chips, in ms."""


def read(ctx):
    s = ctx.trace.scope_s("dfl_local")
    return None if s is None else 1e3 * s / ctx.window.rounds
