"""Device time of the ops under the ``dfl_eval`` scope, per evaluated round
of the window, mean over chips, in ms."""


def read(ctx):
    s = ctx.trace.scope_s("dfl_eval")
    if s is None or not ctx.eval_rounds():
        return None
    return 1e3 * s / ctx.eval_rounds()
