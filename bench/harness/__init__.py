"""Shared machinery of the benchmark: finding cells by name, the device
and its peaks, host spans, the profiler trace and its reduction, and the
operation and byte counts.  Nothing here names a cell; a cell is data."""
