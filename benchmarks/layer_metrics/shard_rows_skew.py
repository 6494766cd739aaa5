"""commit backend: transfer rows charged to the fullest shard over the mean over shards, at the window's end ([stats] gauges sharded.xfer_rows_max / sharded.xfer_rows_mean) (ratio)."""
from benchmarks.harness import readers


def read(ctx):
    fullest = readers.gauge(ctx, "sharded.xfer_rows_max", "stats1")
    mean = readers.gauge(ctx, "sharded.xfer_rows_mean", "stats1")
    return fullest / mean if fullest is not None and mean else None
