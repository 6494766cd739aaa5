"""commit backend: wall time inside a solo launch's jit call (span ledger.solo_dispatch) over the solo launches timed, whole window ([stats] ledger.solo_dispatch_us / ledger.solo_dispatches deltas): the event loop's wait for the launch before, apart from its own work (ms)."""
from benchmarks.harness import window


def read(ctx):
    return window._per(ctx, "ledger.solo_dispatch_us",
                       "ledger.solo_dispatches", 1e-3)
