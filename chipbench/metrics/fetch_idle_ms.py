"""Device idle time inside the program's ``dfl.chunk.fetch`` spans (the
copy of a chunk's metric buffers to the host for the caller's callback),
per chunk boundary of the window, mean over chips, in ms."""
from chipbench import spans


def read(ctx):
    s, k = spans.span_idle_s(ctx.trace, {"dfl.chunk.fetch"}), spans.boundaries(ctx.trace)
    return None if s is None or not k else 1e3 * s / k
