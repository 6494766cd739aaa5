"""commit backend: create batches launched in the waves tier over all create batches launched, whole window ([stats] ledger.tier.waves / the sum of ledger.tier.* deltas); 100 = no batch took the whole-batch serial scan (%)."""
from benchmarks.harness import readers

TIERS = ("fast", "fast_pv", "waves", "serial")


def read(ctx):
    waves = readers.counter_delta(ctx, "ledger.tier.waves")
    launched = [readers.counter_delta(ctx, f"ledger.tier.{t}") for t in TIERS]
    if waves is None or None in launched or not sum(launched):
        return None
    return 100.0 * waves / sum(launched)
