"""commit backend: calls of the planner over the batches launched, whole window ([stats] ledger.plan_calls / device.commit_batches deltas); 1.0 = every batch planned once (count)."""
from benchmarks.harness import window


def read(ctx):
    return window._per(ctx, "ledger.plan_calls", "device.commit_batches", 1.0)
