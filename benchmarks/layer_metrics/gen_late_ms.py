"""load generator: send time - due time, p90 over the window's requests (ms)."""
from benchmarks.harness import readers

read = readers.gen_late_ms
