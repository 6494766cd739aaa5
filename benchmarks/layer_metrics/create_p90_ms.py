"""load generator: closed-loop send -> reply of create requests, p90 (ms)."""
from benchmarks.harness import readers

read = readers.create_p90_ms
