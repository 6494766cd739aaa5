"""Median over all lookup requests of the window, due time -> reply (ms)."""
from benchmarks.harness import readers

read = readers.lookup_ms(0.50)
