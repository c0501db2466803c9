"""Device idle time at each chunk (one chip) or call (four chips) boundary
of the window, mean over boundaries and chips, in ms: the length of the
idle gap in which the harness's boundary annotation fell."""


def read(ctx):
    gaps = ctx.trace.boundary_gaps_s()
    return None if not gaps else 1e3 * sum(gaps) / len(gaps)
