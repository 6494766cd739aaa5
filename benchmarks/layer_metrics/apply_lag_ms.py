"""commit backend (follower): latency.device_apply_lag_us, mean over the window's samples (ms)."""
from benchmarks.harness import readers

read = readers.hist_ms("latency.device_apply_lag_us")
