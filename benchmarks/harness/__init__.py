"""The yardstick: what a PR that claims a gain may not move.

Traffic generation, the reduction from spans and the profiler's trace to
metrics, the table of peaks, the functions that compute a kernel's operations
and bytes, the plain references and the comparison that decides ``correct``.
From the program (``cfk_tpu``) the benchmark takes only the system under test
and its spans, counters and kernel names.
"""
