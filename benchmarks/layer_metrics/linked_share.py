"""commit backend: lanes the planner saw inside a linked chain over the events it planned, whole window ([stats] ledger.linked_events / (ledger.plan_calls x the configuration's batch) deltas); 100 = the cell still sends nothing but chains and the planner still sees them (%)."""
from benchmarks.harness import readers


def read(ctx):
    linked = readers.counter_delta(ctx, "ledger.linked_events")
    plans = readers.counter_delta(ctx, "ledger.plan_calls")
    if linked is None or not plans:
        return None
    return 100.0 * linked / (plans * ctx["config"]["batch_events"])
