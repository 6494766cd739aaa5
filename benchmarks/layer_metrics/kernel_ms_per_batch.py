"""kernels: device time of the commit programs in the traced span over the span's batches = its commit launches (trace) x batches a launch ([stats] counters over the window) (ms)."""
from benchmarks.harness import readers

read = readers.kernel_ms_per_batch
