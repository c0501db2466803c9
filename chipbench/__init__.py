"""On-chip benchmark of the DFL trainer: harness, traffic, reference, trace
reduction and per-layer metric readers (see ``PERF.md``)."""
