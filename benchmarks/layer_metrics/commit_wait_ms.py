"""consensus + WAL: the latency.commit_wait_us leg, mean over the window's samples (ms)."""
from benchmarks.harness import readers

read = readers.hist_ms("latency.commit_wait_us")
