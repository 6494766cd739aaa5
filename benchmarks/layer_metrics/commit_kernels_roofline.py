"""kernels: the algorithm's bytes over the HBM peak, as a share of the commit programs' device time (%)."""
from benchmarks.harness import readers

read = readers.commit_kernels_roofline
