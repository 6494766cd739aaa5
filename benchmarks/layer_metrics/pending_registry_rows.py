"""commit backend: unresolved pending transfers in the planner's registry at the window's end ([stats] gauge ledger.pending_registry_rows) (rows)."""
from benchmarks.harness import readers


def read(ctx):
    rows = readers.gauge(ctx, "ledger.pending_registry_rows", "stats1")
    return None if rows is None else float(rows)
