"""device: 1 - union of device-op intervals over the traced window (%)."""
from benchmarks.harness import readers

read = readers.device_idle_share
