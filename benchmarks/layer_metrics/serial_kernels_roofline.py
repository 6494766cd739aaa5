"""kernels: the algorithm's bytes of the span's linked batches (a fraction of one: the window's serial batches a second times the span the profiler collected) over the HBM peak, as a share of the span's commit-program device time (%)."""
from benchmarks.harness import roofline_linked

read = roofline_linked.serial_kernels_roofline
