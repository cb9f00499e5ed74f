"""Support code of the chip benchmark (``benchmarks/chip/run.py``).

``core`` finds cells, configurations, entries, traffic generators and
metric readers by name and runs one cell once; ``reference`` holds the
plain numpy references, the comparisons that decide ``correct`` and the
lower-precision control; ``profile_trace`` reads the JAX profiler's trace
and reduces it to busy time, idle gaps and the longest device ops.
"""
