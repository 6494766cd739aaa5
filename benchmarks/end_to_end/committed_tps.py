"""Transfers acknowledged inside the window over the window's seconds (transfers/s)."""
from benchmarks.harness import readers

read = readers.committed_tps
