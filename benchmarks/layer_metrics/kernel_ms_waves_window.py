"""kernels: device seconds booked to waves-tier launches over the batches they carried, whole window ([stats] device.tier_busy_s.waves / device.tier_batches_done.waves deltas) (ms)."""
from benchmarks.harness import window


def read(ctx):
    return window._per(ctx, "device.tier_busy_s.waves",
                       "device.tier_batches_done.waves", 1e3)
