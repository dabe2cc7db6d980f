"""Chip benchmark of the checkpointing service (see BENCHMARK.json).

Everything the benchmark owns lives here: configurations, traffic mixes,
drivers, per-layer metric readers, the peak table, FLOP and byte counts,
the trace reduction and the plain references that decide ``correct``.
"""
