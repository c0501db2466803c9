"""Device idle time inside the program's ``dfl.chunk.slice`` and
``dfl.chunk.dispatch`` spans (the chunk's schedule and mask slices, and the
enqueue of its program), per chunk boundary of the window, mean over chips,
in ms."""
from chipbench import spans


def read(ctx):
    names = {"dfl.chunk.slice", "dfl.chunk.dispatch"}
    s, k = spans.span_idle_s(ctx.trace, names), spans.boundaries(ctx.trace)
    return None if s is None or not k else 1e3 * s / k
