"""kernels: the algorithm's bytes of the span's pending and post batches over the HBM peak, as a share of the commit programs' device time (%)."""
from benchmarks.harness import roofline_twophase

read = roofline_twophase.twophase_kernels_roofline
