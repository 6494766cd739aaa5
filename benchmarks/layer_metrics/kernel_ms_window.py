"""kernels: device seconds booked to commit launches over the batches they carried, whole window ([stats] device.commit_busy_s / device.commit_batches_done deltas) (ms)."""
from benchmarks.harness import window

read = window.kernel_ms_window
