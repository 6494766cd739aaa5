"""commit backend: device program launches in the traced span (reads left out) over the span's batches (count)."""
from benchmarks.harness import readers

read = readers.launches_per_batch
