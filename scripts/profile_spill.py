#!/usr/bin/env python
"""Stage-level profile of the spill-active steady state (bench cfg_spill).

Instruments SpillManager.cycle / _reload_rows / admit and the commit drain
so the ~4k TPS bill (VERDICT r4 weak #3) gets an itemized receipt:
  - cycle.d2h      gather of cold rows device->host
  - cycle.lsm      forest bulk insert (host CPU)
  - cycle.rebuild  device-side table rebuild
  - reload         LSM fetch + h2d reinsert of referenced spilled rows
  - commit         everything else (kernel dispatch + drain)

Usage: python scripts/profile_spill.py [--batches N]
"""

import argparse
import sys
import time
from collections import defaultdict

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

TIMES = defaultdict(float)
COUNTS = defaultdict(int)


def timed(name, fn):
    def wrap(*a, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            TIMES[name] += time.perf_counter() - t0
            COUNTS[name] += 1
    return wrap


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=12)
    args = ap.parse_args()

    from bench import BATCH, N_ACCOUNTS, build_accounts, build_transfers
    from tigerbeetle_tpu.cli import announce_device

    announce_device()  # names the device; refuses an unasked CPU
    from tigerbeetle_tpu.constants import BATCH_PAD, TEST_CLUSTER, ConfigProcess
    from tigerbeetle_tpu.io.storage import MemoryStorage, ZoneLayout
    from tigerbeetle_tpu.lsm.grid import Grid
    from tigerbeetle_tpu.lsm.groove import Forest
    from tigerbeetle_tpu.models import spill as spill_mod
    from tigerbeetle_tpu.models.ledger import DeviceLedger
    from tigerbeetle_tpu.types import Operation

    # -- instrument the spill internals ---------------------------------
    orig_cycle = spill_mod.SpillManager.cycle
    orig_reload = spill_mod.SpillManager._reload_rows
    orig_fetch = spill_mod.SpillManager._fetch
    spill_mod.SpillManager.cycle = timed("cycle", orig_cycle)
    spill_mod.SpillManager._reload_rows = timed("reload", orig_reload)
    spill_mod.SpillManager._fetch = timed("fetch", orig_fetch)

    rng = np.random.default_rng(7)
    layout = ZoneLayout(TEST_CLUSTER, grid_size=768 * 1024 * 1024)
    forest = Forest(Grid(
        MemoryStorage(layout), offset=0, block_count=5760, cache_blocks=128,
    ), memtable_max=8192)
    process = ConfigProcess(account_slots_log2=16, transfer_slots_log2=16)
    ledger = DeviceLedger(process=process, mode="auto", forest=forest)
    ledger.pad_to = BATCH_PAD

    g = forest.transfers
    orig_bulk = type(g).insert_bulk
    type(g).insert_bulk = timed("lsm_insert_bulk", orig_bulk)
    orig_enc = type(forest.grid).encode_free_set
    type(forest.grid).encode_free_set = timed("free_set", orig_enc)

    ts2 = 1 << 41
    next_id = 1
    while next_id <= N_ACCOUNTS:
        k = min(BATCH, N_ACCOUNTS - next_id + 1)
        ts2 += k
        ledger.execute_async(Operation.create_accounts, ts2,
                             build_accounts(next_id, k))
        next_id += k

    # warm (compiles outside the timed loop)
    warm_pend = build_transfers(rng, 4_000_000, BATCH)
    warm_pend["flags"] = 2
    ts2 += BATCH
    ledger.drain(ledger.execute_async(Operation.create_transfers, ts2, warm_pend))
    wg = 0
    while ledger.spill.stats["cycles"] < 1 and wg < 8:
        warm = build_transfers(rng, 4_500_000 + wg * BATCH, BATCH)
        ts2 += BATCH
        ledger.drain(ledger.execute_async(Operation.create_transfers, ts2, warm))
        wg += 1
    warm_post = np.zeros(BATCH, dtype=warm_pend.dtype)
    warm_post["id_lo"] = np.arange(4_900_000, 4_900_000 + BATCH, dtype=np.uint64)
    warm_post["pending_id_lo"] = warm_pend["id_lo"]
    warm_post["flags"] = 4
    ts2 += BATCH
    ledger.drain(ledger.execute_async(Operation.create_transfers, ts2, warm_post))

    TIMES.clear()
    COUNTS.clear()

    nbatches = args.batches
    n_pend = max(2, nbatches // 6)
    n_post = n_pend // 2
    pend_bodies = []
    n_sp = 0
    t0 = time.perf_counter()
    for gi in range(nbatches):
        if gi < n_pend:
            b = build_transfers(rng, 6_000_000 + gi * BATCH, BATCH)
            b["flags"] = 2
            pend_bodies.append(b.copy())
        elif gi >= nbatches - n_post and pend_bodies:
            p = pend_bodies.pop(0)
            b = np.zeros(BATCH, dtype=p.dtype)
            b["id_lo"] = np.arange(8_000_000 + gi * BATCH,
                                   8_000_000 + (gi + 1) * BATCH, dtype=np.uint64)
            b["pending_id_lo"] = p["id_lo"]
            b["flags"] = 4
        else:
            b = build_transfers(rng, 6_000_000 + gi * BATCH, BATCH)
        ts2 += BATCH
        ledger.drain(ledger.execute_async(Operation.create_transfers, ts2, b))
        n_sp += BATCH
        if gi % 4 == 3:  # checkpoint cadence; drain first — the spill-IO
            ledger.spill.io_drain()  # worker mutates the same free-set
            forest.grid.encode_free_set()
    total = time.perf_counter() - t0

    print(f"\n== spill profile: {nbatches} batches, {n_sp} transfers, "
          f"{total:.2f}s total, {n_sp/total:,.0f} TPS ==")
    print(f"spill stats: {ledger.spill.stats}")
    acc = 0.0
    for name in sorted(TIMES, key=lambda k: -TIMES[k]):
        t = TIMES[name]
        if name in ("lsm_insert_bulk", "fetch"):
            continue  # nested inside cycle/reload
        acc += t
        print(f"  {name:16s} {t:8.2f}s  ({100*t/total:5.1f}%)  x{COUNTS[name]}")
    print(f"  {'(nested) lsm':16s} {TIMES['lsm_insert_bulk']:8.2f}s  x{COUNTS['lsm_insert_bulk']}")
    print(f"  {'(nested) fetch':16s} {TIMES['fetch']:8.2f}s  x{COUNTS['fetch']}")
    print(f"  {'commit+drain':16s} {total-acc:8.2f}s  ({100*(total-acc)/total:5.1f}%)")


if __name__ == "__main__":
    main()
