"""Shared pieces of the benchmark: the cell's files, peaks, spans and
counters, the trace reduction, FLOP/byte counts and the correctness
comparison.  Nothing here knows a particular configuration, traffic mix
or per-layer metric: those are files found by name (see ``spec``)."""
