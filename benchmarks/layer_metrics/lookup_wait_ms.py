"""consensus + WAL: a lookup's dispatch exit -> finalize entry, window mean of latency.lookup.commit_wait_us (ms)."""
from benchmarks.harness import readers

read = readers.hist_ms("latency.lookup.commit_wait_us")
