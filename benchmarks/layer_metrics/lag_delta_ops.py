"""commit backend (follower): shadow.device_lag_ops at the window's end less at its start (ops)."""
from benchmarks.harness import readers

read = readers.lag_delta_ops
