"""Commit-kernel timing on the live device (no d2h transfers — see
ops/hashtable.py's note: the first device->host copy permanently switches
this process to the slow dispatch path, so this script only uses
block_until_ready and prints timings, never values).

Run from the repo root: python scripts/profile_kernel.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from tigerbeetle_tpu.constants import BATCH_PAD, ConfigProcess
from tigerbeetle_tpu.models import ledger as L
from tigerbeetle_tpu.types import ACCOUNT_DTYPE, TRANSFER_DTYPE

N_ACCOUNTS = 10_000
BATCH = 8190


def main():
    from tigerbeetle_tpu.cli import announce_device

    announce_device()  # names the device; refuses an unasked CPU
    probe = jax.jit(lambda x: x + 1)
    xp = jnp.ones(16384, jnp.uint32)

    def dispatch_ms(n=20):
        jax.block_until_ready(probe(xp))
        t0 = time.perf_counter()
        outs = [probe(xp) for _ in range(n)]
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / n * 1e3

    print(f"dispatch baseline:      {dispatch_ms():8.3f} ms")

    process = ConfigProcess(account_slots_log2=16, transfer_slots_log2=25)
    kern = L.LedgerKernels(process)
    state = L.init_state(process)

    arr = np.zeros(N_ACCOUNTS, dtype=ACCOUNT_DTYPE)
    arr["id_lo"] = np.arange(1, N_ACCOUNTS + 1, dtype=np.uint64)
    arr["ledger"] = 1
    arr["code"] = 1
    ts = 1 << 40
    state, r = kern.commit_accounts(
        state, L.accounts_to_batch(arr, 1 << 14), jnp.int32(N_ACCOUNTS),
        jnp.uint64(ts), mode="fast",
    )
    jax.block_until_ready(r)

    rng = np.random.default_rng(0)
    t = np.zeros(BATCH, dtype=TRANSFER_DTYPE)
    t["id_lo"] = np.arange(1, BATCH + 1, dtype=np.uint64)
    dr = rng.integers(1, N_ACCOUNTS + 1, size=BATCH, dtype=np.uint64)
    off = rng.integers(1, N_ACCOUNTS, size=BATCH, dtype=np.uint64)
    t["debit_account_id_lo"] = dr
    t["credit_account_id_lo"] = (dr - 1 + off) % N_ACCOUNTS + 1
    t["amount_lo"] = 1
    t["ledger"] = 1
    t["code"] = 1
    ev = L.transfers_to_batch(t, BATCH_PAD)
    n = jnp.int32(BATCH)

    # warmup/compile
    state, r = kern.commit_transfers(state, ev, n, jnp.uint64(ts + 10**6), mode="fast")
    jax.block_until_ready(r)

    # synced single-batch latency
    lat = []
    for i in range(10):
        t0 = time.perf_counter()
        state, r = kern.commit_transfers(
            state, ev, n, jnp.uint64(ts + 2 * 10**6 + i * 10**4), mode="fast"
        )
        jax.block_until_ready(r)
        lat.append((time.perf_counter() - t0) * 1e3)
    print(f"commit fast synced:     {np.median(lat):8.3f} ms (median of 10)")

    # async chain throughput
    t0 = time.perf_counter()
    rs = []
    for i in range(50):
        state, r = kern.commit_transfers(
            state, ev, n, jnp.uint64(ts + 3 * 10**6 + i * 10**4), mode="fast"
        )
        rs.append(r)
    jax.block_until_ready(rs)
    dt = (time.perf_counter() - t0) * 1e3
    print(f"commit fast x50 async:  {dt/50:8.3f} ms/batch -> {50*BATCH/dt*1000:,.0f} tps")
    print(f"dispatch after commits: {dispatch_ms():8.3f} ms (poison check)")


if __name__ == "__main__":
    main()
