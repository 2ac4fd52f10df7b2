"""chipbench: the benchmark of sparkflow-tpu (BENCHMARK.json's ``paths``).

Everything a number is judged by lives here, where a PR that claims a gain
cannot change it: traffic generation, the plain reference, the operation
counts and the table of peaks, the reduction from a profiler trace to
metrics, and the comparison that decides ``correct``. From the program it
takes the system under test and its counters and spans.
"""
