"""wire: client send -> reply less the server's latency.e2e_us, window means (ms)."""
from benchmarks.harness import readers

read = readers.wire_ms
