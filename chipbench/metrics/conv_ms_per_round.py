"""Device time of the local steps' convolution work (``chipbench.scopes
.conv_op``: the ops under ``dfl_local`` and ``model_conv``, and every MXU
fusion of the local steps but the classifier's, which holds the forward
convolutions XLA fused into a max-pool and the weight gradients it fused
into the optimizer's update), per round of the window, mean over chips,
in ms."""
from chipbench import scopes


def read(ctx):
    s = scopes.conv_s(ctx.trace)
    return None if s is None else 1e3 * s / ctx.window.rounds
