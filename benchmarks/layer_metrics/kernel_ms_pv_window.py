"""kernels: device seconds booked to fast_pv (post/void) launches over the batches they carried, whole window ([stats] device.tier_busy_s.fast_pv / device.tier_batches_done.fast_pv deltas) (ms)."""
from benchmarks.harness import window


def read(ctx):
    return window._per(ctx, "device.tier_busy_s.fast_pv",
                       "device.tier_batches_done.fast_pv", 1e3)
