"""commit backend: host time inside the planner and its pending registry over the batches launched, whole window (the window's total of [stats] histogram ledger.plan_us / device.commit_batches delta) (ms)."""
from benchmarks.harness import readers


def _total_us(snap):
    h = (snap or {}).get("metrics", {}).get("histograms", {}).get("ledger.plan_us")
    return None if not h else h["count"] * h["mean"]


def read(ctx):
    t1 = _total_us(ctx.get("stats1"))
    batches = readers.counter_delta(ctx, "device.commit_batches")
    if t1 is None or not batches:
        return None
    return (t1 - (_total_us(ctx.get("stats0")) or 0.0)) / 1e3 / batches
