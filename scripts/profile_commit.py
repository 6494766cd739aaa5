"""Per-stage profiling of the fast-tier commit kernel on the device JAX
gives this process (printed first; an unasked CPU is refused).

Explains the bench's bimodal batch latency (p25 ~1.7ms vs p50 ~7ms) by timing
(a) back-to-back commits, (b) isolated sub-kernels: account-table lookup,
transfer-table lookup, claim rounds, digit fold + scatters.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from bench import build_accounts, build_transfers  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tigerbeetle_tpu.constants import BATCH_PAD, ConfigProcess  # noqa: E402
from tigerbeetle_tpu.models.ledger import DeviceLedger, transfers_to_batch  # noqa: E402
from tigerbeetle_tpu.ops import hashtable as ht  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

N_ACCOUNTS = 10_000
BATCH = 8190


def timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e3)
    ts = np.array(ts)
    return ts


def main():
    from tigerbeetle_tpu.cli import announce_device

    announce_device()  # names the device; refuses an unasked CPU
    process = ConfigProcess(account_slots_log2=16, transfer_slots_log2=25)
    ledger = DeviceLedger(process=process, mode="auto")
    ledger.pad_to = BATCH_PAD
    rng = np.random.default_rng(7)
    ts_base = 1 << 40

    next_id = 1
    t = ts_base
    while next_id <= N_ACCOUNTS:
        n = min(BATCH, N_ACCOUNTS - next_id + 1)
        t += n
        ledger.execute_async(Operation.create_accounts, t, build_accounts(next_id, n))
        next_id += n

    # sequential commits, individually timed
    state_holder = {"t": t, "next": 1}

    def commit_once():
        b = build_transfers(rng, state_holder["next"], BATCH)
        state_holder["next"] += BATCH
        state_holder["t"] += BATCH
        p = ledger.execute_async(Operation.create_transfers, state_holder["t"], b)
        return p.results

    lat = timeit(commit_once, n=40)
    print(f"commit e2e ms: min={lat.min():.2f} p25={np.percentile(lat,25):.2f} "
          f"p50={np.percentile(lat,50):.2f} p75={np.percentile(lat,75):.2f} "
          f"max={lat.max():.2f}")
    print("  first 20:", " ".join(f"{x:.1f}" for x in lat[:20]))

    # isolated sub-kernels over the live state
    state = ledger.state
    b = build_transfers(rng, 10_000_000, BATCH)
    rows_b = transfers_to_batch(b, BATCH_PAD)["rows"]
    a_log2, t_log2 = process.account_slots_log2, process.transfer_slots_log2

    both_k4 = jnp.concatenate([rows_b[:, 4:8], rows_b[:, 8:12]], axis=0)

    acct_lookup = jax.jit(lambda rows, k4: ht.lookup(k4, rows, a_log2)[0])
    xfer_lookup = jax.jit(lambda rows, k4: ht.lookup(k4, rows, t_log2)[0])
    lat = timeit(lambda: acct_lookup(state["acct_rows"], both_k4))
    print(f"acct lookup (16384 lanes, W=32): p50={np.percentile(lat,50):.2f}ms")
    lat = timeit(lambda: xfer_lookup(state["xfer_rows"], rows_b[:, :4]))
    print(f"xfer lookup (8192 lanes, W=32):  p50={np.percentile(lat,50):.2f}ms")

    ok = jnp.ones(BATCH_PAD, dtype=bool)
    claim_fn = jax.jit(
        lambda rows, claim, k4: ht.claim_slots(k4, ok, rows, claim, t_log2)[0]
    )
    lat = timeit(lambda: claim_fn(state["xfer_rows"], state["xfer_claim"], rows_b[:, :4]))
    print(f"claim_slots (8192 lanes, 4 rounds): p50={np.percentile(lat,50):.2f}ms")

    # gather+scatter of full rows on the transfer table (the insert write)
    slots = jnp.arange(BATCH_PAD, dtype=jnp.int32) * 97 % (1 << t_log2)
    scatter_fn = jax.jit(lambda rows, s, v: rows.at[s].set(v))
    lat = timeit(lambda: scatter_fn(state["xfer_rows"], slots, rows_b))
    print(f"xfer row scatter (8192x128B): p50={np.percentile(lat,50):.2f}ms")
    gather_fn = jax.jit(lambda rows, s: rows[s])
    lat = timeit(lambda: gather_fn(state["xfer_rows"], slots))
    print(f"xfer row gather  (8192x128B): p50={np.percentile(lat,50):.2f}ms")

    lat = timeit(lambda: scatter_fn(
        state["acct_rows"], slots & jnp.int32((1 << a_log2) - 1), rows_b))
    print(f"acct row scatter (8192x128B): p50={np.percentile(lat,50):.2f}ms")


if __name__ == "__main__":
    main()
