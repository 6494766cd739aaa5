"""kernels: wave stepper steps that held no lane over the steps run, whole window ([stats] (waves.scan_steps - waves.live_steps) / waves.scan_steps deltas): each batch's wave count padded up to its compiled bucket (%)."""
from benchmarks.harness import readers


def read(ctx):
    scan = readers.counter_delta(ctx, "waves.scan_steps")
    live = readers.counter_delta(ctx, "waves.live_steps")
    if scan is None or live is None or not scan:
        return None
    return 100.0 * (scan - live) / scan
