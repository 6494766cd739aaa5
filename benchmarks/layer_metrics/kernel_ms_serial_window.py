"""kernels: device seconds booked to serial-tier launches over the batches they carried, whole window ([stats] device.tier_busy_s.serial / device.tier_batches_done.serial deltas) (ms)."""
from benchmarks.harness import roofline_linked

read = roofline_linked.serial_batch_ms
