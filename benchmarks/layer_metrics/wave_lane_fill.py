"""kernels: live lanes over the lanes the wave passes ran, whole window ([stats] waves.live_lanes / waves.scan_lanes deltas): each wave's live lanes packed into passes of a fixed width, the last pass of a wave padded (%)."""
from benchmarks.harness import readers


def read(ctx):
    scan = readers.counter_delta(ctx, "waves.scan_lanes")
    live = readers.counter_delta(ctx, "waves.live_lanes")
    if scan is None or live is None or not scan:
        return None
    return 100.0 * live / scan
