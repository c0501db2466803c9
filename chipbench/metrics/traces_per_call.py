"""Traces of a chunk program per executor call, over the whole run's
process (set-up, warm-up and both windows), from the program's counters
``dfl.chunk_traces`` and ``dfl.calls``.  None where the program keeps no
such counters or made no call."""


def read(ctx):
    try:
        from repro.obs.trace import counts
    except ImportError:
        return None
    c = counts()
    calls = c.get("dfl.calls", 0)
    return c.get("dfl.chunk_traces", 0) / calls if calls else None
