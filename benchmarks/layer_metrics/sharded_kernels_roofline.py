"""kernels: the algorithm's bytes of the span's batches over the HBM peak of all the chips the state lives on, as a share of the sharded commit program's device time a chip (%)."""
from benchmarks.harness import roofline_sharded

read = roofline_sharded.sharded_kernels_roofline
