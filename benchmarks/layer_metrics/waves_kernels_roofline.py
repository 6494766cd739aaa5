"""kernels: the algorithm's bytes of the span's batches (committed transfers as a plain commit, declined ones as a read; a fraction of a batch: the window's create batches a second times the span the profiler collected) over the HBM peak, as a share of the span's commit-program device time (%)."""
from benchmarks.harness import roofline_limits

read = roofline_limits.waves_kernels_roofline
