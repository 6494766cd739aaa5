"""90th percentile over all create_transfers requests of the window, due time -> reply (ms)."""
from benchmarks.harness import readers

read = readers.batch_ms(0.90)
