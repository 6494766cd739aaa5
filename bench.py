"""Benchmark driver: the reference's scripts/benchmark.sh protocol on TPU.

Reference protocol (reference: src/benchmark.zig:23-73, scripts/benchmark.sh):
10_000 accounts, 10_000_000 transfers submitted in batches of 8190
(id_order=reversed, two uniform-random distinct accounts per transfer,
amount=1), measure transfers/s and batch-latency percentiles
p00/p25/p50/p75/p100 (reference: src/benchmark.zig main loop printout).

Measured paths:

- **Durable (the BASELINE protocol)**: a REAL replica process (WAL +
  consensus + TCP session clients at batch=8190), conservation verified
  over the wire. The commit engine is the native C++ host ledger
  (native/ledger.cc) — measured on an earlier rig, a process's first
  device->host fetch permanently slowed its dispatch and uploads (see
  models/native_ledger.py; chip_smoke.py's `probe` re-measures it), so a
  reply-serving server did not run its hot loop through the device. A
  short device-backend durable run is reported
  separately (durable_device_tps) as the honest through-stack TPU number,
  plus a two-phase-heavy durable run (durable_two_phase_tps).
- **Flagship (device-generated ingest)**: the protocol workload is generated
  ON DEVICE from a seeded PRNG (same distribution: reversed sequential ids,
  uniform random distinct account pairs, amount=1) and committed batch by
  batch, K batches fused per dispatch — the TPU commit kernel's throughput,
  the way the reference's loopback benchmark measures its state machine.
  Median of 5 timed segments with the per-run values reported.
- **Ingest-limited (host-upload)**: batches built on host and uploaded
  per-batch (1 MiB each), pipelined, no d2h until the clock stops. Reported
  as `ingest_tps`.
- **Tracked configs**: lookups, two-phase, linked chains, balancing, mixed
  split, and the spill-active steady state (which INCLUDES posts of
  spilled pendings so the pre-commit reload path is measured; its ceiling
  is set by the degraded-transport artifact above — the first cold row
  shipped to the host LSM degrades every later 1 MiB batch upload).

No device->host transfer happens in the flagship/ingest phases until their
clocks stop. Verification (result-code maxes, fault word, conservation
sums) runs after, reduced on device to scalars.

Prints exactly ONE JSON line to stdout:
  {"metric": ..., "value": N, "unit": "transfers/s", "vs_baseline": N, ...}
vs_baseline is value / 10_000_000 — BASELINE.json's target (>= 10M
transfers/s on one v5e chip). The stage-time table goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from tigerbeetle_tpu.artifact import jax_cache_bytes
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.tracer import NULL_TRACER, JsonTracer

# The bench's shared observability pair (tigerbeetle_tpu/metrics.py):
# every phase reports into METRICS (stage spans, batch-latency histogram,
# the instrumented spill pipeline), and `--trace <path>` swaps TRACER for
# a JsonTracer whose dump — merged with the e2e server's span dump — is
# one Perfetto-loadable file covering driver AND server.
METRICS = Metrics()
TRACER = NULL_TRACER

# Size of the persistent XLA compilation cache at driver start (and again
# at the end): the summary carries compile-cache provenance — growth here
# IS the recompiles the sentinel counted.
_JAX_CACHE_BYTES_START = jax_cache_bytes()


# Segments that raised. Each segment is wrapped so that one failure does
# not lose the others' numbers (the JSON line still prints), but a run in
# which any segment failed EXITS NON-ZERO: a caller that reads only the
# exit code must not take a half-failed benchmark for a good one.
SEGMENT_ERRORS: list[str] = []


def _segment_failed(name: str, e: Exception) -> str:
    err = f"{type(e).__name__}: {e}"
    SEGMENT_ERRORS.append(f"{name}: {err}")
    print(f"[{name}] FAILED: {err}", file=sys.stderr)
    return err


def _sentinel_summary() -> dict | None:
    """Compile-sentinel totals for THIS driver process (the in-process
    device phases; subprocess servers report theirs via SIGQUIT/stats).
    None when the device stack never got imported (host-only runs)."""
    mod = sys.modules.get("tigerbeetle_tpu.models.ledger")
    if mod is None:
        return None
    snap = mod.COMPILE_SENTINEL.snapshot()
    return {
        "total": snap["total"],
        "post_warmup": snap["post_warmup"],
        "per_fn": snap["per_fn"],
    }


BASELINE_TPS = 10_000_000.0  # BASELINE.json north-star target
N_ACCOUNTS = 10_000
BATCH = 8190  # (1 MiB - 128 B) / 128 B, reference: src/constants.zig:167-168
N_TRANSFERS = int(os.environ.get("BENCH_TRANSFERS", 10_000_000))
N_INGEST = int(os.environ.get("BENCH_INGEST_TRANSFERS", 1_000_000))
N_LATENCY = 30  # synced batches for the latency percentiles
K_FUSE = 8  # batches committed per device dispatch in the flagship phase


def build_accounts(start_id: int, count: int, ledger: int = 1) -> np.ndarray:
    from tigerbeetle_tpu.types import ACCOUNT_DTYPE

    arr = np.zeros(count, dtype=ACCOUNT_DTYPE)
    arr["id_lo"] = np.arange(start_id, start_id + count, dtype=np.uint64)
    arr["ledger"] = ledger
    arr["code"] = 1
    return arr


def build_transfers(rng, start_id: int, count: int, ledger: int = 1) -> np.ndarray:
    from tigerbeetle_tpu.types import TRANSFER_DTYPE

    arr = np.zeros(count, dtype=TRANSFER_DTYPE)
    # id_order=reversed (reference: src/benchmark.zig:66-73 default).
    arr["id_lo"] = np.arange(start_id + count - 1, start_id - 1, -1, dtype=np.uint64)
    dr = rng.integers(1, N_ACCOUNTS + 1, size=count, dtype=np.uint64)
    off = rng.integers(1, N_ACCOUNTS, size=count, dtype=np.uint64)
    arr["debit_account_id_lo"] = dr
    arr["credit_account_id_lo"] = (dr - 1 + off) % N_ACCOUNTS + 1  # distinct
    arr["amount_lo"] = 1
    arr["ledger"] = ledger
    arr["code"] = 1
    return arr


def make_device_stepper(kernels, n_pad: int, k_fuse: int):
    """Jitted: generate k_fuse protocol batches on device (seeded PRNG, same
    distribution as build_transfers) and run the fast-tier commit kernel on
    each, sequentially, in ONE dispatch. Returns (state', code_max')."""
    import jax
    import jax.numpy as jnp

    B = n_pad
    n_acc = np.uint64(N_ACCOUNTS)  # np constants embed as XLA literals
    mask32 = np.uint64(0xFFFFFFFF)

    def gen_rows(key, start_id):
        lane = jnp.arange(B, dtype=jnp.uint64)
        id_lo = start_id + jnp.uint64(BATCH - 1) - lane  # reversed ids
        k1, k2 = jax.random.split(key)
        dr = jax.random.randint(
            k1, (B,), 1, N_ACCOUNTS + 1, dtype=jnp.uint32
        ).astype(jnp.uint64)
        off = jax.random.randint(
            k2, (B,), 1, N_ACCOUNTS, dtype=jnp.uint32
        ).astype(jnp.uint64)
        cr = (dr - jnp.uint64(1) + off) % n_acc + jnp.uint64(1)
        u32 = jnp.uint32
        z = jnp.zeros(B, dtype=u32)
        one = jnp.ones(B, dtype=u32)
        words = [z] * 32
        words[0] = (id_lo & mask32).astype(u32)
        words[1] = (id_lo >> jnp.uint64(32)).astype(u32)
        words[4] = dr.astype(u32)  # account ids < 2^32
        words[8] = cr.astype(u32)
        words[12] = one  # amount = 1
        words[28] = one  # ledger = 1
        words[29] = one  # code = 1, flags = 0
        return jnp.stack(words, axis=1)

    def step(state, code_max, key, start_id, ts_end):
        # Batch j of this dispatch: ids [start_id + j*BATCH, ...), final
        # timestamp ts_end - (k_fuse-1-j)*BATCH (per-event ts assigned by the
        # kernel as timestamp - n + i + 1).
        for j in range(k_fuse):
            kj = jax.random.fold_in(key, j)
            rows = gen_rows(kj, start_id + jnp.uint64(j * BATCH))
            ts_j = ts_end - jnp.uint64((k_fuse - 1 - j) * BATCH)
            state, r = kernels._commit_transfers(
                state, {"rows": rows}, jnp.int32(BATCH), ts_j, mode="fast"
            )
            code_max = jnp.maximum(code_max, jnp.max(r))
        return state, code_max

    return jax.jit(step, donate_argnums=(0,))


def bench_tracked_configs(stage) -> dict:
    """BASELINE.json's five tracked configs beyond the flagship: the read
    path, pure two-phase, linked chains, balancing (exact serial tier), and
    a realistic mixed batch exercising the conflict-partitioned middle
    tier. Synced per batch (these are serial/residue-dominated, so dispatch
    overlap is irrelevant); a warmup batch per config absorbs compiles."""
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.constants import BATCH_PAD, ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger, ids_to_batch
    from tigerbeetle_tpu.types import TRANSFER_DTYPE, Operation

    out = {}
    n_runs = int(os.environ.get("BENCH_CFG_RUNS", 3))
    # Events per tracked-config batch. Default = the protocol BATCH (the
    # rig artifact); smaller values exist for CPU-sandbox artifacts — the
    # serial tier is a lax.scan of one step per EVENT, so a full 8190-
    # event chains/balancing config costs hours on one CPU core. The
    # chosen value rides out in the artifact (`cfg_batch` field below)
    # and all config-vs-config ratios stay batch-size-consistent.
    cbatch = int(os.environ.get("BENCH_CFG_BATCH", BATCH))
    cpad = BATCH_PAD if cbatch >= BATCH else max(
        8, 1 << (cbatch - 1).bit_length()
    )
    # Transfer-table size scales with cbatch at the protocol's load factor
    # (2^22 slots for 5 full batches): the serial tier's lax.scan carries
    # the whole table as loop state, and XLA-CPU materializes it per step
    # — table SIZE, not event count, drives serial cost off the rig
    # (measured: 256-event chains batch, 2^22 table 50 s vs 2^18 1.8 s;
    # on the rig donation aliases the update in place and this is free).
    xfer_log2 = 22
    while xfer_log2 > 16 and (1 << (xfer_log2 - 1)) * BATCH >= (1 << 22) * cbatch:
        xfer_log2 -= 1
    out["cfg_batch"] = cbatch

    def fresh(n_accounts=N_ACCOUNTS):
        process = ConfigProcess(
            account_slots_log2=16, transfer_slots_log2=xfer_log2
        )
        ledger = DeviceLedger(process=process, mode="auto")
        ledger.pad_to = cpad
        ts = 1 << 40
        next_id = 1
        while next_id <= n_accounts:
            k = min(cbatch, n_accounts - next_id + 1)
            ts += k
            ledger.execute_async(
                Operation.create_accounts, ts, build_accounts(next_id, k)
            )
            next_id += k
        return ledger, ts

    def run_batches(ledger, ts, batches, events_per_batch=None,
                    warmup=1) -> float:
        """`warmup` batches absorb jit compiles and must exercise every tier
        the timed batches hit (two-phase passes 2: pending=fast,
        post=fast_pv). Returns the timed TPS."""
        if events_per_batch is None:
            events_per_batch = cbatch
        pends = []
        for b in batches[:warmup]:
            ts += events_per_batch
            pends.append(ledger.execute_async(Operation.create_transfers, ts, b))
        jax.block_until_ready(pends[-1].results)
        t0 = time.perf_counter()
        n = 0
        for b in batches[warmup:]:
            ts += events_per_batch
            p = ledger.execute_async(Operation.create_transfers, ts, b)
            jax.block_until_ready(p.results)
            n += events_per_batch
        return n / (time.perf_counter() - t0)

    def median_config(name, one_run) -> None:
        """Each tracked config runs N times over FRESH ledgers (kernels
        are process-cached, so only run 1 pays compiles — its warmup
        batches absorb them) and reports median + per-run values + spread
        (round-4 verdict: single samples swung 2x between bench runs)."""
        t0 = time.perf_counter()
        vals = [one_run(np.random.default_rng(77 + 13 * i))
                for i in range(n_runs)]
        med = float(np.median(vals))
        out[name] = round(med, 1)
        out[name + "_runs"] = [round(v, 1) for v in vals]
        out[name + "_spread"] = (
            round((max(vals) - min(vals)) / med, 4) if med else None
        )
        # progress attribution: the configs are the bench's longest silent
        # stretch — without this line a stall cannot be pinned to a config
        print(
            f"[cfg] {name}: {out[name]:.1f} spread="
            f"{out[name + '_spread']} ({time.perf_counter() - t0:.1f}s)",
            file=sys.stderr,
        )

    # 1. read path: lookup_accounts over full id batches
    def cfg_lookup(rng):
        ledger, ts = fresh()
        ids = ids_to_batch(
            [int(x) for x in rng.integers(1, N_ACCOUNTS + 1, size=cbatch)],
            cpad,
        )
        k = ledger.kernels.lookup_accounts
        jax.block_until_ready(k(ledger.state, ids)[0])  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            found, rows, res = k(ledger.state, ids)
        jax.block_until_ready(found)
        return 20 * cbatch / (time.perf_counter() - t0)

    with stage("cfg_lookup"):
        median_config("lookup_accounts_per_s", cfg_lookup)

    # 2. two-phase: full pending batches (fast tier) then full post batches
    # (the VECTORIZED fast_pv tier — distinct prior-batch pendings)
    def cfg_two_phase(rng):
        ledger, ts = fresh()
        batches = []
        for g in range(4):
            base = 1 + g * 2 * cbatch
            pend = build_transfers(rng, base, cbatch)
            pend["flags"] = 2  # pending
            post = np.zeros(cbatch, dtype=TRANSFER_DTYPE)
            post["id_lo"] = np.arange(base + cbatch, base + 2 * cbatch, dtype=np.uint64)
            post["pending_id_lo"] = pend["id_lo"]
            post["flags"] = 4  # post_pending_transfer
            batches += [pend, post]
        return run_batches(ledger, ts, batches, warmup=2)

    with stage("cfg_two_phase"):
        median_config("two_phase_tps", cfg_two_phase)

    # 3. linked chains: every batch is chains of 4 (exact serial tier)
    def cfg_chains(rng):
        ledger, ts = fresh()
        batches = []
        for g in range(3):
            b = build_transfers(rng, 1 + g * cbatch, cbatch)
            b["flags"] = 1  # linked
            b["flags"][3::4] = 0  # chain terminators every 4th event
            b["flags"][-1] = 0
            batches.append(b)
        return run_batches(ledger, ts, batches)

    with stage("cfg_chains"):
        median_config("linked_chains_tps", cfg_chains)

    # 4. balancing: balancing_debit over funded accounts (exact serial tier)
    def cfg_balancing(rng):
        ledger, ts = fresh()
        seed_batch = build_transfers(rng, 1, cbatch)  # fund accounts first
        ts += cbatch
        ledger.execute_async(Operation.create_transfers, ts, seed_batch)
        batches = []
        for g in range(3):
            b = build_transfers(rng, 1 + (g + 1) * cbatch, cbatch)
            b["flags"] = 16  # balancing_debit
            batches.append(b)
        return run_batches(ledger, ts, batches)

    with stage("cfg_balancing"):
        median_config("balancing_tps", cfg_balancing)

    # 5. mixed: ~88% simple transfers + ~6% posts (fast_pv lanes) + ~6%
    # linked-chain pairs on their own accounts -> the conflict-WAVE
    # scheduler with a serial residue (the chains; everything else rides
    # one fast_pv wave)
    def cfg_mixed(rng):
        ledger, ts = fresh()
        pend0 = build_transfers(rng, 1, cbatch)
        pend0["flags"] = 2
        # keep pending accounts in a reserved low range, disjoint from the
        # fast majority below
        # pending accounts 1..599: disjoint from the chain range (600..900)
        # AND the fast majority (>1000), so the fixpoint cannot cascade
        pend0["debit_account_id_lo"] = 1 + (np.arange(cbatch) % 300)
        pend0["credit_account_id_lo"] = 301 + (np.arange(cbatch) % 299)
        ts += cbatch
        ledger.execute_async(Operation.create_transfers, ts, pend0)
        batches = []
        n_res = cbatch // 16  # residue events (~512 at the protocol BATCH)
        for g in range(4):
            b = build_transfers(rng, 1 + (g + 1) * cbatch, cbatch)
            # fast majority over accounts > 1000
            dr = rng.integers(1001, N_ACCOUNTS + 1, size=cbatch, dtype=np.uint64)
            off = rng.integers(1, N_ACCOUNTS - 1001, size=cbatch, dtype=np.uint64)
            b["debit_account_id_lo"] = dr
            b["credit_account_id_lo"] = (dr - 1001 + off) % (N_ACCOUNTS - 1000) + 1001
            # residue: posts of the pending batch, scattered through the lanes
            # chains: the first 2*k lanes form linked pairs CLOSED over a
            # reserved account range (so the disjointness fixpoint cannot
            # cascade into the fast majority) — the serial residue that
            # forces the SPLIT executor
            k = n_res // 2
            heads = np.arange(0, 2 * k, 2)
            pair = np.arange(0, 2 * k)
            b["flags"][heads] = 1  # linked; the adjacent lane terminates
            b["debit_account_id_lo"][pair] = 600 + (pair % 150)
            b["credit_account_id_lo"][pair] = 751 + (pair % 150)
            # posts of prior-batch pendings (fast_pv lanes) in the remainder
            post_lanes = rng.choice(
                np.arange(2 * k, cbatch), size=n_res, replace=False
            )
            b["pending_id_lo"][post_lanes] = pend0["id_lo"][g * n_res:(g + 1) * n_res]
            b["debit_account_id_lo"][post_lanes] = 0
            b["credit_account_id_lo"][post_lanes] = 0
            b["amount_lo"][post_lanes] = 0
            b["flags"][post_lanes] = 4
            batches.append(b)
        tps = run_batches(ledger, ts, batches)
        # plan_stats carries the wave-planner keys AND the deprecated
        # split/split_pv compat keys (same dict) — dashboards reading
        # split_stats keep working, new readers take the wave keys
        ps = ledger.hazards.plan_stats
        out["split_stats"] = dict(ledger.hazards.split_stats)
        out["wave_plan_stats"] = dict(ps)
        assert ps.get("waves", 0) >= 3, (
            "mixed config must exercise the conflict-wave scheduler"
        )
        assert ps.get("residue_events", 0) > 0, (
            "mixed config's linked chains must fall to the serial residue"
        )
        return tps

    with stage("cfg_mixed"):
        median_config("mixed_split_tps", cfg_mixed)

    # 5b. hot-account waves (ROADMAP item 2's workload): a few viral hot
    # accounts absorb most traffic AND every batch carries same-batch
    # pend->post dependency pairs. The retired all-or-nothing analysis
    # serialized such batches whole; the wave planner runs them as ~2
    # dependency-ordered waves (each post one wave after its creator),
    # with NO serial residue.
    def cfg_mixed_hot(rng):
        ledger, ts = fresh()
        batches = []
        n_dep = cbatch // 8  # same-batch pend->post pairs per batch
        for g in range(4):
            b = build_transfers(rng, 1 + g * cbatch, cbatch)
            # zipf-flavored mix: ~25% of debits hit ONE hot account, the
            # rest spread power-law across the id space
            u = rng.random(cbatch)
            dr = (1 + (N_ACCOUNTS - 1) * u**3).astype(np.uint64)
            dr[rng.random(cbatch) < 0.25] = 1
            off = rng.integers(1, N_ACCOUNTS, size=cbatch, dtype=np.uint64)
            b["debit_account_id_lo"] = dr
            b["credit_account_id_lo"] = (dr - 1 + off) % N_ACCOUNTS + 1
            b["flags"][:n_dep] = 2  # pendings...
            post_lanes = rng.choice(  # ...posted later IN THE SAME BATCH
                np.arange(n_dep, cbatch), size=n_dep, replace=False
            )
            b["pending_id_lo"][post_lanes] = b["id_lo"][:n_dep]
            b["debit_account_id_lo"][post_lanes] = 0
            b["credit_account_id_lo"][post_lanes] = 0
            b["amount_lo"][post_lanes] = 0
            b["flags"][post_lanes] = 4
            batches.append(b)
        tps = run_batches(ledger, ts, batches)
        ps = ledger.hazards.plan_stats
        out["mixed_hot_plan_stats"] = dict(ps)
        assert ps.get("waves", 0) >= 3, (
            "hot config must run the conflict-wave scheduler"
        )
        assert ps.get("residue_events", 0) == 0, (
            "hot config has no chains/balancing: nothing may fall serial"
        )
        return tps

    with stage("cfg_mixed_hot"):
        median_config("mixed_hot_tps", cfg_mixed_hot)

    # dependent-transfer segments vs the fast path, measured under the
    # SAME synced per-batch protocol (two_phase_tps is the pure
    # fast/fast_pv configuration) — ROADMAP item 2 targets >= 0.5x
    if out.get("two_phase_tps"):
        out["mixed_vs_fast_ratio"] = round(
            out["mixed_split_tps"] / out["two_phase_tps"], 4
        )
        out["mixed_hot_vs_fast_ratio"] = round(
            out["mixed_hot_tps"] / out["two_phase_tps"], 4
        )

    # 6. spill-active steady state: the transfer table's HBM budget is a
    # fraction of the workload, so the cold tail spills to the LSM forest
    # every few batches and the pre-commit reload path stays hot — the
    # bounded-memory cliff, measured rather than assumed.
    try:
        _bench_spill_config(stage, out, np.random.default_rng(77))
    except Exception as e:  # recorded; the run exits non-zero at the end
        out["spill_active_tps"] = 0.0
        out["spill_error"] = _segment_failed("spill config", e)

    return out


def _bench_spill_config(stage, out, rng) -> None:
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.constants import BATCH_PAD, TEST_CLUSTER, ConfigProcess
    from tigerbeetle_tpu.io.storage import MemoryStorage, ZoneLayout
    from tigerbeetle_tpu.lsm.grid import Grid
    from tigerbeetle_tpu.lsm.groove import Forest
    from tigerbeetle_tpu.models.ledger import DeviceLedger
    from tigerbeetle_tpu.types import Operation

    # A/B transport probe (round-4 verdict: the "degraded transport" claim
    # needs its isolating artifact, like the flagship's dispatch probe).
    # This config is the bench's only phase that DRAINS every batch — and
    # the first drain is this process-section's first device->host fetch,
    # the cliff measured on an earlier rig (see `probe`). Probing launch
    # latency before ANY drain, after the first drain, and after the first
    # spill cycle separates "any reply-serving d2h degrades the transport"
    # from "the spill machinery is slow".
    _pz = jnp.zeros(1, dtype=jnp.uint32)
    _pf = jax.jit(lambda a, b: jnp.maximum(a, jnp.max(b)))
    jax.block_until_ready(_pf(jnp.uint32(0), _pz))  # absorb the compile

    def probe_dispatch(n=40):
        x = jnp.uint32(0)
        t0 = time.perf_counter()
        for _ in range(n):
            x = _pf(x, _pz)
        jax.block_until_ready(x)
        return round((time.perf_counter() - t0) / n * 1e6, 1)  # us/launch

    probe = {"dispatch_us_fresh": probe_dispatch()}  # pre-ANY-d2h

    with stage("cfg_spill"):
        layout = ZoneLayout(TEST_CLUSTER, grid_size=768 * 1024 * 1024)
        forest = Forest(Grid(
            MemoryStorage(layout), offset=0, block_count=5760,
            cache_blocks=128,
        ), memtable_max=8192)  # spill-heavy: bigger tables, less churn
        process = ConfigProcess(account_slots_log2=16,
                                transfer_slots_log2=16)  # 32k-row budget
        ledger = DeviceLedger(process=process, mode="auto", forest=forest)
        # shared registry: spill_overlap / spill_lookup_batch below are
        # read back out of METRICS (overlap_report reads the registry-
        # backed StatGroup), and --trace records the prefetch/admit spans
        ledger.instrument(METRICS, TRACER)
        ledger.pad_to = BATCH_PAD
        ts2 = 1 << 41
        next_id = 1
        while next_id <= N_ACCOUNTS:
            k = min(BATCH, N_ACCOUNTS - next_id + 1)
            ts2 += k
            ledger.execute_async(
                Operation.create_accounts, ts2, build_accounts(next_id, k)
            )
            next_id += k
        n_sp = 0
        nbatches = int(os.environ.get("BENCH_SPILL_BATCHES", 24))
        n_pend = max(2, nbatches // 6)  # oldest batches: spilled first
        n_post = n_pend // 2  # posts of (by then) SPILLED pendings
        # Warm until a spill CYCLE and a RELOAD have both run: the cycle's
        # kernels (ts/occ scan, gather, reload, post tier) otherwise
        # compile inside the timed loop — tens of seconds of remote
        # compiles booked against the steady-state number.
        warm_pend = build_transfers(rng, 4_000_000, BATCH)
        warm_pend["flags"] = 2
        ts2 += BATCH
        ledger.drain(ledger.execute_async(
            Operation.create_transfers, ts2, warm_pend
        ))
        # the drain above was the first d2h: THE transport cliff
        probe["dispatch_us_post_first_drain"] = probe_dispatch()
        wg = 0
        pre_spill_batch_s = []
        while ledger.spill.stats["cycles"] < 1 and wg < 8:
            warm = build_transfers(rng, 4_500_000 + wg * BATCH, BATCH)
            ts2 += BATCH
            tb = time.perf_counter()
            ledger.drain(ledger.execute_async(
                Operation.create_transfers, ts2, warm
            ))
            if ledger.spill.stats["cycles"] == 0:  # pure commit, no cycle
                pre_spill_batch_s.append(time.perf_counter() - tb)
            wg += 1
        # after the first spill cycle's own gathers: unchanged from the
        # post-drain value when the cycle adds no further transport damage
        probe["dispatch_us_post_first_cycle"] = probe_dispatch()
        if pre_spill_batch_s:
            probe["commit_ms_best_pre_spill"] = round(
                min(pre_spill_batch_s) * 1e3, 1
            )
        warm_post = np.zeros(BATCH, dtype=warm_pend.dtype)
        warm_post["id_lo"] = np.arange(
            4_900_000, 4_900_000 + BATCH, dtype=np.uint64
        )
        warm_post["pending_id_lo"] = warm_pend["id_lo"]
        warm_post["flags"] = 4  # posts of spilled pendings: reload + tier
        ts2 += BATCH
        ledger.drain(ledger.execute_async(
            Operation.create_transfers, ts2, warm_post
        ))
        # Build the whole workload BEFORE the clock (the flagship generates
        # on device for the same reason: batch construction is workload
        # generation, not the system under test).
        pend_bodies = []
        batches = []
        for g in range(nbatches):
            if g < n_pend:
                # two-phase pendings on a reserved account range; their
                # rows age out to the LSM store before the posts arrive
                b = build_transfers(rng, 6_000_000 + g * BATCH, BATCH)
                b["flags"] = 2  # pending
                pend_bodies.append(b.copy())
            elif g >= nbatches - n_post and pend_bodies:
                # posts referencing SPILLED pendings: the pre-commit
                # reload path (the prefetch contract) under measurement
                p = pend_bodies.pop(0)
                b = np.zeros(BATCH, dtype=p.dtype)
                b["id_lo"] = np.arange(
                    8_000_000 + g * BATCH, 8_000_000 + (g + 1) * BATCH,
                    dtype=np.uint64,
                )
                b["pending_id_lo"] = p["id_lo"]
                b["flags"] = 4  # post_pending_transfer
            else:
                b = build_transfers(rng, 6_000_000 + g * BATCH, BATCH)
            batches.append(b)

        # The OVERLAPPED spill pipeline under measurement (models/spill.py
        # module docstring): a window of W batches stays in flight (drain
        # lags dispatch, so the per-batch d2h never serializes the degraded
        # transport), and batch g+1's referenced-spilled rows prefetch on
        # the spill IO worker while batch g's commit kernel runs — admit()
        # then finds them staged. spill_overlap (reported below) accounts
        # the hidden fraction of the gather, the analog of PR 1's
        # shadow_upload_overlap.
        W = int(os.environ.get("BENCH_SPILL_WINDOW", 4))
        window = []
        dispatch_s = []
        t0 = time.perf_counter()
        for g, b in enumerate(batches):
            ts2 += BATCH
            tb = time.perf_counter()
            window.append(ledger.execute_async(
                Operation.create_transfers, ts2, b
            ))
            if g + 1 < len(batches):
                ledger.spill.prefetch_async(batches[g + 1])
            while len(window) > W:
                ledger.drain(window.pop(0))
            dispatch_s.append(time.perf_counter() - tb)
            n_sp += BATCH
            # the checkpoint-cadence free-set apply: staged releases from
            # compaction churn become reusable, as the durable system's
            # checkpoint chain would do (grid.py contract). io_drain first:
            # the spill-IO worker mutates the same lock-free grid/free-set
            # (the SpillManager.checkpoint_meta pattern); every 4th batch,
            # a real checkpoint cadence, so the drain barrier doesn't
            # serialize every batch against the worker
            if g % 4 == 3:
                ledger.spill.io_drain()
                forest.grid.encode_free_set()
        for p in window:
            ledger.drain(p)
        out["spill_active_tps"] = round(n_sp / (time.perf_counter() - t0), 1)
        # best dispatch+lagged-drain turn = a cycle-free post-d2h commit:
        # against commit_ms_best_pre_spill it splits the bill between "the
        # first d2h slowed every dispatch" and "cycles/reloads cost time"
        probe["commit_ms_best_spill_active"] = round(
            min(dispatch_s) * 1e3, 1
        )
        out["spill_transport_probe"] = probe
        out["spill_window"] = W
        out["spill_stats"] = {
            k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in ledger.spill.stats.items()
        }
        # overlap accounting: spill_overlap = fraction of prefetch-gather
        # seconds hidden behind commits; spill_lookup_batch = mean ids per
        # batched LSM multi-point-read
        out.update(ledger.spill.overlap_report())
        assert ledger.spill.stats["cycles"] >= 2, "spill never engaged"
        assert ledger.spill.stats["reloaded"] > 0, (
            "spill bench never exercised the reload path"
        )
        assert ledger.spill.stats["prefetches"] >= 1, (
            "spill bench never exercised the prefetch overlap path"
        )


def _median_e2e(stage, name: str, n_runs: int, log, trace: bool = False,
                **kw) -> dict:
    """run_e2e N times (fresh server each), report the median with per-run
    values + spread (round-4 verdict: single samples hid a 30%+ swing).
    Dual-mode runs must ALL verify their device shadow. With trace=True
    the FIRST run's server dumps its commit-pipeline spans; they ride out
    as `trace_events` for the driver to merge into the --trace file."""
    from tigerbeetle_tpu.benchmark import run_e2e

    backend = kw.get("backend", "native")
    dual = "+" in backend or backend == "dual"
    runs, shadows, hash_logs, hits, last = [], [], [], [], None
    trace_events = None
    for i in range(n_runs):
        kw_i = dict(kw, trace="server") if (trace and i == 0) else kw
        with stage(f"{name}_{i}"):
            last = run_e2e(log=log, **kw_i)
        if trace and i == 0:
            trace_events = last.pop("trace_events", None)
        runs.append(last["durable_tps"])
        hits.append(last.get("group_commit_hit_rate"))
        if dual:
            # a run whose server died before printing [stats] has no
            # device_shadow at all — that is an UNVERIFIED run, not a
            # skippable one
            shadows.append(
                last.get("device_shadow", {}).get("verified")
            )
            hash_logs.append(last.get("device_hash_log_ok"))
    med = float(np.median(runs))
    out = dict(last)
    out["durable_tps"] = round(med, 1)
    out["durable_runs"] = [round(x, 1) for x in runs]
    out["durable_spread"] = (
        round((max(runs) - min(runs)) / med, 4) if med else None
    )
    # per-run fuse hit rates (the fuse-window regression's artifact:
    # a single aggregated rate hid which segment/run had the bad window)
    out["group_commit_hit_rate_runs"] = hits
    if dual:
        out["shadow_verified_all"] = all(v is True for v in shadows)
        if backend == "dual":
            # follower runs MUST carry the per-op ring check: a missing
            # report (server died before [stats], finalize timed out) is
            # an UNVERIFIED run, not a skippable one — same rule as
            # shadow_verified_all. Shadow-mode segments have no ring and
            # no flag at all.
            out["hash_log_ok_all"] = all(v is True for v in hash_logs)
    if trace_events is not None:
        out["trace_events"] = trace_events
    return out


def bench_e2e(stage, trace: bool = False) -> dict:
    """The durable, through-consensus numbers: format a data file, start a
    REAL replica process (WAL on), drive create_transfers through TCP
    session clients at batch=8190 and verify conservation over the wire —
    the reference's actual measurement protocol (reference:
    scripts/benchmark.sh:34-78, src/benchmark.zig:23-73). Three workloads,
    each median-of-N over fresh server processes:

    - DUAL backend (native+device), simple transfers: the headline
      durable_tps. The C++ engine serves replies while the TPU applies the
      same prepares asynchronously (h2d only, models/dual_ledger.py);
      shutdown verifies device state bit-exact (reply-code digests +
      state fingerprints) — the TPU holds real durable state without a
      d2h in the timed path.
    - DUAL backend, two-phase-heavy (pend->post pairs);
    - dual-commit durable mode (`--backend dual`, the e2e_device
      segment): the device applier FOLLOWS the committed op stream off
      the reply path (h2d only) — durable_device_tps is the
      through-stack TPU number with the device holding real, verified
      state (per-op hash-log ring + fingerprints), replacing the old
      reply-through-the-device configuration that paid a device round
      trip per commit (15x under native in r05).

    MUST run before this process touches JAX: the server subprocesses own
    the TPU chip."""
    log = lambda *a: print("[e2e]", *a, file=sys.stderr)  # noqa: E731
    n = int(os.environ.get("BENCH_E2E_TRANSFERS", 2_000_000))
    n_runs = int(os.environ.get("BENCH_E2E_RUNS", 3))
    clients = int(os.environ.get("BENCH_E2E_CLIENTS", 10))
    # ONE client process drives the whole protocol through the async packet
    # ABI (native/tb_client.cc session pool) — BENCH_E2E_DRIVER=python
    # falls back to the per-session Python driver
    driver = os.environ.get("BENCH_E2E_DRIVER", "async")
    try:
        out = _median_e2e(
            stage, "e2e_durable", n_runs, log, trace=trace,
            n_accounts=N_ACCOUNTS, n_transfers=n, clients=clients,
            backend="native+device", driver=driver,
        )
    except Exception as e:  # recorded; the run exits non-zero at the end
        return {"durable_tps": 0.0, "error": _segment_failed("e2e", e)}
    try:
        tp = _median_e2e(
            stage, "e2e_two_phase", n_runs, log,
            n_accounts=N_ACCOUNTS,
            n_transfers=int(os.environ.get("BENCH_E2E_TP", 1_000_000)),
            clients=clients, workload="two_phase", backend="native+device",
            driver=driver,
        )
        out["two_phase"] = tp
        out["durable_two_phase_tps"] = tp["durable_tps"]
        out["durable_two_phase_runs"] = tp["durable_runs"]
        out["durable_two_phase_spread"] = tp["durable_spread"]
        # the headline verified flag covers EVERY dual run, both workloads
        out["shadow_verified_all"] = bool(
            out.get("shadow_verified_all")
        ) and bool(tp.get("shadow_verified_all"))
    except Exception as e:
        out["two_phase"] = {"error": _segment_failed("e2e two-phase", e)}
    try:
        # The e2e_device segment now MEASURES dual-commit durable mode
        # (`--backend dual`): the native engine serves replies on the
        # critical path while the device applier follows the committed op
        # stream asynchronously (h2d only) — so durable_device_tps is the
        # honest through-stack number for a server whose device state is
        # real, verified state, instead of the reply-through-the-device
        # configuration that paid a device round trip per commit (47.2k
        # in r05, 15x under the native path). Parity is proven per run:
        # state fingerprints + code-stream digests + the per-op hash-log
        # ring check, all after the clock stops.
        dv = _median_e2e(
            stage, "e2e_device", n_runs, log,
            n_accounts=N_ACCOUNTS,
            n_transfers=int(os.environ.get("BENCH_E2E_DEV", 1_000_000)),
            clients=clients, backend="dual", driver=driver,
        )
        out["device_backend"] = dv
        out["durable_device_tps"] = dv["durable_tps"]
        out["durable_device_runs"] = dv["durable_runs"]
        out["durable_device_spread"] = dv["durable_spread"]
        out["device_shadow_verified_all"] = dv.get("shadow_verified_all")
        out["device_hash_log_ok"] = dv.get("hash_log_ok_all")
        out["device_lag_ops"] = dv.get("device_lag_ops")
        out["device_apply_overlap"] = dv.get("device_apply_overlap")
    except Exception as e:
        out["device_backend"] = {"error": _segment_failed("e2e device", e)}
    try:
        # CDC A/B: same backend/driver/batch protocol as the headline
        # durable run, with a live change-stream pump attached to a
        # DELIBERATELY slow (refusing, never blocking) sink. The contract
        # under measurement: durable_cdc_tps within noise of durable_tps
        # — backpressure pauses the pump (cdc_backpressure_pauses), the
        # stream lags (cdc_lag_ops), the commit path never waits.
        with stage("e2e_cdc"):
            from tigerbeetle_tpu.benchmark import run_e2e

            cdc = run_e2e(
                n_accounts=N_ACCOUNTS,
                n_transfers=int(os.environ.get("BENCH_E2E_CDC", 1_000_000)),
                clients=clients, backend="native+device", driver=driver,
                # ~50 ops/s sink ceiling — well below the durable commit
                # rate, so the sink genuinely saturates and the lag/pause
                # counters prove the pump (not the replica) absorbed it
                cdc_slow_us=20_000, log=log,
            )
        out["cdc"] = cdc
        out["durable_cdc_tps"] = cdc["durable_tps"]
        out["cdc_lag_ops"] = cdc.get("cdc_lag_ops")
        out["cdc_backpressure_pauses"] = cdc.get("cdc_backpressure_pauses")
        out["cdc_ops_streamed"] = cdc.get("cdc_ops_streamed")
    except Exception as e:
        out["cdc"] = {"error": _segment_failed("e2e cdc", e)}
    # Fuse-window regression artifact: the hit rate (and the window the
    # autotune ended at) PER SEGMENT — r05's single 0.4562 aggregate could
    # not say which workload/window pairing produced it.
    segs = {
        "e2e_durable": out,
        "e2e_two_phase": out.get("two_phase", {}),
        "e2e_device": out.get("device_backend", {}),
        "e2e_cdc": out.get("cdc", {}),
    }
    out["group_hit_rate_by_segment"] = {
        k: {
            "hit_rate": v.get("group_commit_hit_rate"),
            "hit_rate_runs": v.get("group_commit_hit_rate_runs"),
            "fuse_window_us": v.get("fuse_window_us"),
            "fuse_holds": v.get("group_fuse_holds"),
            "fuse_expired": v.get("group_fuse_expired"),
        }
        for k, v in segs.items()
    }
    return out


def bench_ingress(stage) -> dict:
    """The ingress_sessions segment: 10k live multiplexed sessions
    through the gateway (tigerbeetle_tpu/ingress) against one replica —
    p99 vs the 10-session baseline, plus a deliberately saturating phase
    whose sheds must not collapse throughput. Host-only (numpy +
    sockets): runs in the pre-JAX section like the e2e phases."""
    log = lambda *a: print("[ingress]", *a, file=sys.stderr)  # noqa: E731
    n = int(os.environ.get("BENCH_INGRESS_SESSIONS", 10_000))
    try:
        with stage("ingress_sessions"):
            from tigerbeetle_tpu.benchmark import run_ingress_sessions

            return run_ingress_sessions(
                n_sessions=n,
                conns=int(os.environ.get("BENCH_INGRESS_CONNS", 16)),
                log=log,
            )
    except Exception as e:  # recorded; the run exits non-zero at the end
        return {"error": _segment_failed("ingress", e)}


def bench_failover(stage) -> dict:
    """The failover segment (live chaos harness, testing/chaos.py): a
    real 3-replica cluster under a multiplexed fleet, the primary
    SIGKILLed mid-run — reports failover_recovery_ms (kill to first
    client reply) and the post-failover throughput ratio, with zero
    lost/duplicated transfers verified (conservation + CDC). Host-only
    like the other live segments: the servers own the accelerator.

    RETRY-ONCE: the segment drives real processes under real signals, so
    a single scheduler flake (r06's 1-core chaos timeout) used to null
    the artifact's failover fields for the whole round — one retry with
    a fresh cluster keeps one flake from erasing the measurement. Both
    attempts failing is reported as the error it is."""
    log = lambda *a: print("[failover]", *a, file=sys.stderr)  # noqa: E731
    last: dict = {}
    for attempt in (1, 2):
        try:
            with stage("failover" if attempt == 1 else "failover_retry"):
                from tigerbeetle_tpu.testing.chaos import run_failover

                out = run_failover(
                    n_sessions=int(
                        os.environ.get("BENCH_FAILOVER_SESSIONS", 128)
                    ),
                    conns=8,
                    events_per_batch=int(
                        os.environ.get("BENCH_FAILOVER_EVENTS", 64)
                    ),
                    batches_per_session=int(
                        os.environ.get("BENCH_FAILOVER_BATCHES", 10)
                    ),
                    backend=os.environ.get(
                        "BENCH_FAILOVER_BACKEND", "native"
                    ),
                    jax_platform=None,  # servers inherit the rig platform
                    # measurement mode: a CDC stream-audit failure is
                    # REPORTED (cdc_ok/verification_error) instead of
                    # nulling the recovery numbers — wire conservation
                    # (zero lost/dup ledger effects) is still asserted
                    strict_stream=False,
                    log=log,
                )
            out["failover_attempts"] = attempt
            if out.get("failover_recovery_ms") is not None:
                return out
            last = out  # completed but measured nothing: retry once
            print("[failover] recovery_ms null — retrying once",
                  file=sys.stderr)
        except Exception as e:  # the one retry follows
            print(
                f"[failover] attempt {attempt} FAILED: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )
            last = {"error": f"{type(e).__name__}: {e}",
                    "failover_attempts": attempt}
    if "error" in last:  # both attempts raised: the segment failed
        SEGMENT_ERRORS.append(f"failover: {last['error']}")
    return last


def bench_frontier(stage) -> dict:
    """The load/latency frontier segment (benchmark.run_frontier): an
    offered-load ladder against one live gateway-fronted durable server
    (default `--backend dual`) — per step, offered vs achieved tps,
    client p50/p95/p99, the typed-shed rate, and the dominant critical-
    path leg from the server's per-request latency anatomy. The
    ROADMAP-item-4 artifact: it names the leg to attack first and the
    load where the knee is. Host-only (numpy + sockets) like the other
    live segments."""
    log = lambda *a: print("[frontier]", *a, file=sys.stderr)  # noqa: E731
    steps = tuple(
        int(x) for x in os.environ.get(
            "BENCH_FRONTIER_STEPS", "25000,50000,100000,200000,400000"
        ).split(",") if x
    )
    try:
        with stage("frontier"):
            from tigerbeetle_tpu.benchmark import run_frontier

            return run_frontier(
                steps=steps,
                step_s=float(os.environ.get("BENCH_FRONTIER_STEP_S", 6.0)),
                batch=int(os.environ.get("BENCH_FRONTIER_BATCH", 2048)),
                sessions=int(
                    os.environ.get("BENCH_FRONTIER_SESSIONS", 32)
                ),
                backend=os.environ.get("BENCH_FRONTIER_BACKEND", "dual"),
                jax_platform=None,  # the server inherits the platform
                log=log,
            )
    except Exception as e:  # recorded; the run exits non-zero at the end
        return {"error": _segment_failed("frontier", e)}


def bench_cross_ledger(stage) -> dict:
    """The cross_ledger_tps segment (federation/live.py): two real
    regions — each a live replica cluster with commitment chains and an
    AOF-backed CDC tail — with the settlement agent posting mirror/
    resolve legs between them through the client runtime. Measurement
    mode runs WITHOUT the region kill (that path is the chaos harness
    and its tier-1 test); the number is settled origin pendings per
    wall second of the drive (each one costs a pending + a remote
    mirror + a resolve, all consensus ops), with the settlement lag
    bound (ops) and the counterparty commitment-stream audit attached.
    Host-only (numpy + sockets) like the other live segments."""
    log = lambda *a: print("[cross_ledger]", *a, file=sys.stderr)  # noqa: E731
    try:
        with stage("cross_ledger"):
            from tigerbeetle_tpu.federation.live import run_federation_chaos

            out = run_federation_chaos(
                payments=int(os.environ.get("BENCH_CROSS_PAYMENTS", 96)),
                batch=8,
                kill_cluster=False,
                backend=os.environ.get("BENCH_CROSS_BACKEND", "native"),
                jax_platform=None,  # servers inherit the rig platform
                log=log,
            )
        out["cross_ledger_tps"] = round(
            out["issued"] / out["drive_wall_s"], 1
        )
        out["commitment_verify_ok"] = all(
            v["checked"] > 0 for v in out["stream_verify"].values()
        )
        return out
    except Exception as e:  # recorded; the run exits non-zero at the end
        return {"error": _segment_failed("cross_ledger", e)}


def _parse_trace_arg(argv) -> str | None:
    """`--trace <path>` / `--trace=<path>`: dump a merged Chrome
    trace-event JSON (driver spans + the first e2e server's spans) there."""
    it = iter(argv)
    trace = None
    for a in it:
        if a == "--trace":
            trace = next(it, None)
        elif a.startswith("--trace="):
            trace = a.split("=", 1)[1]
    return trace


def main() -> int:
    global TRACER
    trace_path = _parse_trace_arg(sys.argv[1:])
    if trace_path:
        TRACER = JsonTracer(metrics=METRICS)
    stages: dict[str, float] = {}

    def stage(name):
        class _T:
            def __enter__(self):
                self.t0 = time.perf_counter()
                self.tok = TRACER.start(f"bench.{name}")

            def __exit__(self, *a):
                TRACER.stop(self.tok)
                stages[name] = time.perf_counter() - self.t0

        return _T()

    # ONE PROCESS PER CHIP, so the order here is load-bearing: every
    # segment that spawns device-backed servers runs FIRST, while this
    # process is still host-only (numpy + sockets; importing the package
    # initialises no backend) and one server at a time owns the chip. Only
    # after the last server has exited does this process touch JAX — from
    # then on IT holds the chip and a spawned device server would fail or
    # hang. Keep it that way.
    e2e = bench_e2e(stage, trace=bool(trace_path))
    ingress = bench_ingress(stage)
    failover = bench_failover(stage)
    frontier = bench_frontier(stage)
    cross_ledger = bench_cross_ledger(stage)

    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.cli import announce_device
    from tigerbeetle_tpu.constants import BATCH_PAD, ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger, ids_to_batch
    from tigerbeetle_tpu.types import Operation

    # name the device the in-process phases measure, and refuse a CPU that
    # JAX fell back to unasked (asked-for CPU — JAX_PLATFORMS=cpu — is a
    # sandbox run and says so in the JSON line)
    device = announce_device()

    # Transfers at load factor <= 1/2: flagship (10M) + ingest (1M) need 2^25
    # transfer slots (4 GiB of HBM rows); 10k accounts sit in 2^16.
    slots_log2 = 25
    while (N_TRANSFERS + N_INGEST) > (1 << slots_log2) // 2:
        slots_log2 += 1
    process = ConfigProcess(account_slots_log2=16, transfer_slots_log2=slots_log2)
    ledger = DeviceLedger(process=process, mode="auto")
    ledger.pad_to = BATCH_PAD

    rng = np.random.default_rng(42)
    ts = 1 << 40

    fold_max = jax.jit(lambda acc, r: jnp.maximum(acc, jnp.max(r)))
    code_max = jnp.uint32(0)

    # --- phase 0: load accounts (async; verified after the timed runs) ---
    with stage("accounts"):
        next_id = 1
        while next_id <= N_ACCOUNTS:
            n = min(BATCH, N_ACCOUNTS - next_id + 1)
            ts += n
            pending = ledger.execute_async(
                Operation.create_accounts, ts, build_accounts(next_id, n)
            )
            code_max = fold_max(code_max, pending.results)
            next_id += n
        jax.block_until_ready(code_max)

    # =========== FLAGSHIP: device-generated protocol workload ===========
    n_flag_batches = N_TRANSFERS // BATCH  # whole batches only
    n_flag = n_flag_batches * BATCH
    stepper = make_device_stepper(ledger.kernels, BATCH_PAD, K_FUSE)
    stepper1 = make_device_stepper(ledger.kernels, BATCH_PAD, 1)
    key = jax.random.PRNGKey(42)
    next_id = 1_000_000_000  # flagship id namespace (disjoint from ingest)
    state = ledger.state

    # warmup/compile both steppers
    with stage("compile"):
        for s, k in ((stepper, K_FUSE), (stepper1, 1)):
            ts += k * BATCH
            state, code_max = s(
                state, code_max, jax.random.fold_in(key, 0),
                jnp.uint64(next_id), jnp.uint64(ts),
            )
            next_id += k * BATCH
            jax.block_until_ready(code_max)
        done = K_FUSE + 1

    # latency: synced single-batch dispatches (shrunk if the transfer budget
    # is smaller than the compile+latency overheads)
    n_latency = min(N_LATENCY, max(0, n_flag_batches - done))
    lat_ms = []
    with stage("latency"):
        for i in range(n_latency):
            ts += BATCH
            t0 = time.perf_counter()
            state, code_max = stepper1(
                state, code_max, jax.random.fold_in(key, done + i),
                jnp.uint64(next_id), jnp.uint64(ts),
            )
            jax.block_until_ready(code_max)
            lat_ms.append((time.perf_counter() - t0) * 1e3)
            next_id += BATCH
        done += n_latency

    # Dispatch-health probe: the flagship's inter-segment spread tracks
    # the dispatch path's launch latency, not the kernels — measure it
    # directly so the spread has its artifact.
    _probe_z = jnp.zeros(1, dtype=jnp.uint32)  # outside the timed loop
    jax.block_until_ready(fold_max(code_max, _probe_z))  # absorb the compile

    def probe_dispatch(n=40):
        t0 = time.perf_counter()
        x = code_max
        for _ in range(n):
            x = fold_max(x, _probe_z)
        jax.block_until_ready(x)
        return (time.perf_counter() - t0) / n * 1e6  # us/launch

    dispatch_us_before = round(probe_dispatch(), 1)

    # throughput: K-fused dispatches in equal segments, each blocked at
    # its end. The headline is the median over segments SELECTED by a
    # printed dispatch-health rule: the inter-segment spread tracks the
    # REMOTE launch path's latency, not the kernels (round-5 verdict: a
    # 0.49 spread whose outlier segment coincided with a degraded probe).
    # Round-8 tightening (r05 still printed 0.49 vs the <= 0.15 target):
    # (1) WARMUP DISCIPLINE — a few untimed steady-state groups run
    # before segment 0 (the compile/latency phases exercised stepper1,
    # so the first timed segment used to pay sustained-run establishment
    # inside its clock); (2) each segment is probed BEFORE AND AFTER
    # (a mid-segment transport degradation lands in the post-probe that
    # the pre-probe missed); (3) the health factor drops 2.0 -> 1.5.
    # Every decision input (both probe arrays, the floor, the factor)
    # rides out in the bench JSON so the artifact shows whether the rule
    # held, not just its verdict.
    SEG_PLAN, SEG_SPARE, SEG_PROBE_FACTOR = 5, 2, 1.5
    n_groups = max(0, (n_flag_batches - done) // K_FUSE)
    n_total = SEG_PLAN + SEG_SPARE
    # small-budget runs (BENCH_TRANSFERS shrunk) still get the SEG_PLAN
    # multi-segment median — only the spares are dropped; a single
    # segment would hide exactly the variance segmentation measures
    if n_groups >= 4 * n_total:
        n_segs = n_total
    elif n_groups >= SEG_PLAN:
        n_segs = SEG_PLAN
    else:
        n_segs = 1 if n_groups else 0
    warm_groups = 2 if n_groups >= 4 * n_total else 0
    n_seg_groups = n_groups - warm_groups
    seg_size = n_seg_groups // n_segs if n_segs else 0
    seg_runs_all: list[float] = []
    seg_probes: list[float] = []
    seg_probes_after: list[float] = []
    g = 0
    t_all = time.perf_counter()
    for _ in range(warm_groups):
        # untimed steady-state establishment (counts toward conservation)
        ts += K_FUSE * BATCH
        state, code_max = stepper(
            state, code_max, jax.random.fold_in(key, 10_000 + g),
            jnp.uint64(next_id), jnp.uint64(ts),
        )
        next_id += K_FUSE * BATCH
        g += 1
    if warm_groups:
        jax.block_until_ready(code_max)
    for seg in range(n_segs):
        seg_probes.append(round(probe_dispatch(20), 1))
        take = (
            seg_size if seg < n_segs - 1
            else n_seg_groups - seg_size * (n_segs - 1)
        )
        t0 = time.perf_counter()
        for _ in range(take):
            ts += K_FUSE * BATCH
            state, code_max = stepper(
                state, code_max, jax.random.fold_in(key, 10_000 + g),
                jnp.uint64(next_id), jnp.uint64(ts),
            )
            next_id += K_FUSE * BATCH
            g += 1
        jax.block_until_ready(code_max)
        dt = time.perf_counter() - t0
        seg_probes_after.append(round(probe_dispatch(20), 1))
        if take:
            seg_runs_all.append(take * K_FUSE * BATCH / dt)
    stages["flagship"] = time.perf_counter() - t_all
    dispatch_us_after = round(probe_dispatch(), 1)
    n_timed = n_groups * K_FUSE * BATCH
    # -- segment selection (the printed rule) --
    seg_rule = (
        f"keep segments whose pre- AND post-segment dispatch probes <= "
        f"{SEG_PROBE_FACTOR}x min(all probes); first {SEG_PLAN} healthy "
        f"count ({warm_groups} untimed warm groups precede segment 0)"
    )
    if seg_runs_all:
        floor = min(min(seg_probes), min(seg_probes_after))
        healthy = [
            i for i in range(len(seg_runs_all))
            if seg_probes[i] <= SEG_PROBE_FACTOR * floor
            and seg_probes_after[i] <= SEG_PROBE_FACTOR * floor
        ]
        if not healthy:
            # a uniformly degraded run still needs a headline: fall back
            # to the least-degraded segment rather than reporting nothing
            # (the JSON carries the probes, so the fallback is visible)
            healthy = [
                int(np.argmin(np.maximum(seg_probes, seg_probes_after)))
            ]
        selected = healthy[:SEG_PLAN]
    else:
        floor = None
        selected = []
    seg_runs = [seg_runs_all[i] for i in selected]
    print(
        f"flagship segment rule: {seg_rule}; probes_us={seg_probes} "
        f"probes_after_us={seg_probes_after} floor={floor} "
        f"selected={selected} "
        f"discarded={[i for i in range(len(seg_runs_all)) if i not in selected]}",
        file=sys.stderr,
    )
    flagship_tps = float(np.median(seg_runs)) if seg_runs else 0.0
    flagship_spread = (
        round((max(seg_runs) - min(seg_runs)) / flagship_tps, 4)
        if seg_runs and flagship_tps
        else None
    )
    ledger.state = state
    ledger._xfer_used += done * BATCH + n_timed

    # =========== SECONDARY: host-upload (ingest-limited) path ===========
    with stage("ingest_build"):
        batches = []
        next_id = 1
        remaining = N_INGEST
        while remaining > 0:
            n = min(BATCH, remaining)
            batches.append(build_transfers(rng, next_id, n))
            next_id += n
            remaining -= n

    # warmup: the host-path commit kernel compiles on first dispatch
    with stage("ingest_warmup"):
        n_warm = min(2, len(batches))
        for b in batches[:n_warm]:
            ts += len(b)
            pending = ledger.execute_async(Operation.create_transfers, ts, b)
            code_max = fold_max(code_max, pending.results)
        jax.block_until_ready(code_max)

    t0 = time.perf_counter()
    n_ingest = 0
    for b in batches[n_warm:]:
        ts += len(b)
        pending = ledger.execute_async(Operation.create_transfers, ts, b)
        n_ingest += len(b)
        code_max = fold_max(code_max, pending.results)
    jax.block_until_ready(code_max)
    ingest_dt = time.perf_counter() - t0
    stages["ingest"] = ingest_dt
    ingest_tps = n_ingest / ingest_dt if n_ingest else 0.0
    n_ingest += sum(len(b) for b in batches[:n_warm])  # total for conservation

    # =========== tracked configs (BASELINE.json's five workloads) =======
    # BEFORE verification: the first d2h permanently degrades this
    # runtime's dispatch path (see module docstring), and the configs do no
    # device->host reads themselves.
    configs = bench_tracked_configs(stage)

    # --- verification: the process's FIRST d2h transfers happen here ---
    with stage("verify"):
        # Conservation, reduced on device: every committed transfer moves
        # amount=1, so sum(debits_posted) == sum(credits_posted) == total.
        from tigerbeetle_tpu.models.ledger import unpack_account
        from tigerbeetle_tpu.ops import hashtable as ht

        ids = ids_to_batch(list(range(1, N_ACCOUNTS + 1)), 1 << 14)

        def conservation(state, ids):
            slot, found, res = ht.lookup(
                ids["key4"], state["acct_rows"], process.account_slots_log2
            )
            rows = state["acct_rows"][slot]
            a = unpack_account(rows)
            real = jnp.arange(rows.shape[0]) < N_ACCOUNTS
            w = found & real
            dpo = jnp.sum(jnp.where(w, a["dpo_lo"], jnp.uint64(0)))
            cpo = jnp.sum(jnp.where(w, a["cpo_lo"], jnp.uint64(0)))
            # resolve gated on REQUESTED lanes only (padding probes key 0)
            return dpo, cpo, jnp.sum(w.astype(jnp.int32)), jnp.all(res | ~real)

        dpo, cpo, nfound, resolved = jax.jit(conservation)(ledger.state, ids)
        assert bool(np.asarray(resolved)), "verify lookup probe-window overflow"
        # All committed transfers (compile + latency + timed + ingest), amount=1.
        total = (done + n_groups * K_FUSE) * BATCH + n_ingest
        tmax = int(np.asarray(code_max))
        assert tmax == 0, f"nonzero result code: max {tmax}"
        assert int(np.asarray(nfound)) == N_ACCOUNTS
        assert int(np.asarray(dpo)) == int(np.asarray(cpo)) == total, (
            int(np.asarray(dpo)), int(np.asarray(cpo)), total,
        )
        ledger.check_fault()

    # batch-latency histogram: the registry's snapshot is the quoted
    # artifact (same store the server/spill stats live in)
    h_lat = METRICS.histogram("bench.batch_latency_us")
    for ms in lat_ms:
        h_lat.observe(ms * 1000.0)
    lat_hist = h_lat.snapshot()
    print(f"batch latency histogram (us): {lat_hist}", file=sys.stderr)

    lat = np.percentile(lat_ms if lat_ms else [float("nan")], [0, 25, 50, 75, 100])
    print(
        "stage times (s): "
        + ", ".join(f"{k}={v:.2f}" for k, v in stages.items()),
        file=sys.stderr,
    )
    print(
        f"batch latency ms: p00={lat[0]:.2f} p25={lat[1]:.2f} "
        f"p50={lat[2]:.2f} p75={lat[3]:.2f} p100={lat[4]:.2f}",
        file=sys.stderr,
    )
    # The COMPACT headline (the driver's tail capture parses the LAST stdout
    # line; round 4's nested sub-objects grew it past the capture window and
    # the artifact recorded "parsed": null). Full detail — per-run durable
    # metrics, server stats, tracked configs — goes to BENCH_DETAIL.json
    # next to this script plus stderr.
    server_trace_events = e2e.pop("trace_events", None)
    detail = {"durable": e2e, "ingress": ingress, "failover": failover,
              "frontier": frontier, "cross_ledger": cross_ledger,
              "configs": configs,
              "stages_s": {
                  k: round(v, 2) for k, v in stages.items()
              }}
    detail_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_DETAIL.json")
    with open(detail_path, "w") as f:
        json.dump(detail, f, indent=1)
    print("detail: " + json.dumps(detail), file=sys.stderr)
    if trace_path:
        # ONE Perfetto-loadable file, stitched (tracer.stitch): driver
        # spans (pid 0) + the traced e2e server's commit-pipeline spans
        # (pid 1 — fuse holds, journal writes, commit dispatch/finalize,
        # CDC emits, shadow uploads), with the per-op trace tags turned
        # into cross-pid FLOW events — clicking an op follows it from
        # the bus ingress through reply and device apply.
        from tigerbeetle_tpu.tracer import dump_stitched

        n_events = dump_stitched(
            trace_path,
            [TRACER.events_ordered(), server_trace_events or []],
            labels=["bench driver", "e2e server"],
        )
        print(f"trace: {n_events} events (stitched) -> {trace_path}",
              file=sys.stderr)
    print(
        json.dumps(
            {
                "metric": "create_transfers transfers/s, batch=8190, 10k "
                f"accounts ({device['platform']} commit kernel, "
                "device-generated protocol "
                "workload, conservation+codes verified; median of "
                f"{len(seg_runs)} probe-selected segments of "
                f"{len(seg_runs_all)} run; detail in BENCH_DETAIL.json)",
                "value": round(flagship_tps, 1),
                "unit": "transfers/s",
                "vs_baseline": round(flagship_tps / BASELINE_TPS, 4),
                "flagship_runs": [round(x, 1) for x in seg_runs],
                "flagship_spread": flagship_spread,
                # the selection rule is part of the artifact: the headline
                # is reproducible only with the rule that produced it —
                # and EVERY decision input rides along (both probe
                # arrays, the floor, the factor), so the next driver
                # artifact shows whether the rule held
                "flagship_rule": seg_rule,
                "flagship_runs_all": [round(x, 1) for x in seg_runs_all],
                "flagship_probe_us": seg_probes,
                "flagship_probe_after_us": seg_probes_after,
                "flagship_probe_floor_us": floor,
                "flagship_probe_factor": SEG_PROBE_FACTOR,
                "flagship_selected": selected,
                "dispatch_us_per_launch": [
                    dispatch_us_before, dispatch_us_after
                ],
                "latency_ms_p00_p25_p50_p75_p100": [round(x, 2) for x in lat],
                # registry-sourced histogram snapshot (metrics.py buckets)
                "latency_hist_us": lat_hist,
                "ingest_tps": round(ingest_tps, 1),
                "durable_tps": e2e.get("durable_tps", 0.0),
                "durable_spread": e2e.get("durable_spread"),
                "durable_two_phase_tps": e2e.get("durable_two_phase_tps", 0.0),
                "durable_shadow_verified_all": e2e.get("shadow_verified_all"),
                # dual-commit durable mode (`--backend dual`): the device
                # follows the committed stream asynchronously, so the
                # through-stack device number rides the native reply path
                # — parity (fingerprints + digests + per-op hash-log
                # ring) verified per run, after the clock stops
                "durable_device_tps": e2e.get("durable_device_tps", 0.0),
                "durable_device_spread": e2e.get("durable_device_spread"),
                "device_shadow_verified_all": e2e.get(
                    "device_shadow_verified_all"
                ),
                "device_hash_log_ok": e2e.get("device_hash_log_ok"),
                "device_lag_ops": e2e.get("device_lag_ops"),
                "device_apply_overlap": e2e.get("device_apply_overlap"),
                # CDC A/B: live change stream into a deliberately slow
                # sink — throughput must hold vs durable_tps while the
                # pump (not the replica) absorbs the backpressure
                "durable_cdc_tps": e2e.get("durable_cdc_tps", 0.0),
                "cdc_lag_ops": e2e.get("cdc_lag_ops"),
                "cdc_backpressure_pauses": e2e.get("cdc_backpressure_pauses"),
                "group_commit_hit_rate": e2e.get("group_commit_hit_rate", 0.0),
                "group_fuse_width": e2e.get("group_fuse_width"),
                # per-segment fuse diagnostics (hit rate, holds/expired,
                # the window autotune ended at) — the 0.4562-vs-0.85
                # regression's attribution artifact
                "group_hit_rate_by_segment": e2e.get(
                    "group_hit_rate_by_segment"
                ),
                "fuse_window_us": e2e.get("fuse_window_us"),
                "shadow_upload_overlap": e2e.get("shadow_upload_overlap"),
                "loop_us_per_batch": e2e.get("loop_us_per_batch"),
                # conflict-wave scheduler segments (dependent transfers):
                # mixed = chains+posts+fast majority (wave + serial
                # residue), hot = zipfian hot accounts + same-batch
                # pend->post pairs (pure waves); ratios are vs
                # two_phase_tps, the fast-path segment under the same
                # synced per-batch protocol (ROADMAP item 2: >= 0.5x)
                "mixed_split_tps": configs.get("mixed_split_tps", 0.0),
                "mixed_split_spread": configs.get("mixed_split_tps_spread"),
                "mixed_hot_tps": configs.get("mixed_hot_tps", 0.0),
                "mixed_hot_spread": configs.get("mixed_hot_tps_spread"),
                "mixed_vs_fast_ratio": configs.get("mixed_vs_fast_ratio"),
                "mixed_hot_vs_fast_ratio": configs.get(
                    "mixed_hot_vs_fast_ratio"
                ),
                "two_phase_tps": configs.get("two_phase_tps", 0.0),
                "spill_active_tps": configs.get("spill_active_tps", 0.0),
                # overlap accounting: reload gather time hidden behind
                # commits (1.0 = admit never waited on the IO worker) and
                # mean ids per batched LSM multi-point-read
                "spill_overlap": configs.get("spill_overlap"),
                "spill_lookup_batch": configs.get("spill_lookup_batch"),
                # [fresh, post-first-d2h] us/launch: the transport cliff
                # that caps every reply-serving device path on this rig
                "spill_dispatch_cliff_us": [
                    configs.get("spill_transport_probe", {}).get(
                        "dispatch_us_fresh"
                    ),
                    configs.get("spill_transport_probe", {}).get(
                        "dispatch_us_post_first_drain"
                    ),
                ],
                # ingress gateway: 10k live multiplexed sessions — p99
                # vs the 10-session baseline (target <= 2x), and the
                # saturation phase's shed/throughput contract (sheds in
                # ingress.shed, event tps holds vs unshedded)
                "ingress_sessions": ingress.get("sessions", 0),
                "ingress_p99_ms": [
                    ingress.get("p99_baseline_ms"),
                    ingress.get("p99_live_ms"),
                ],
                "ingress_p99_ratio": ingress.get("p99_ratio"),
                "ingress_tps_saturated_ratio": ingress.get(
                    "tps_saturated_ratio"
                ),
                "ingress_shed": ingress.get("ingress_shed"),
                "ingress_busy_replies": ingress.get("busy_replies"),
                # failover: the primary SIGKILLed under live multiplexed
                # load — kill-to-first-reply ms and the throughput ratio
                # after recovery, with zero lost/duplicated transfers
                # proven (conservation + CDC); full report in detail
                "failover_recovery_ms": failover.get(
                    "failover_recovery_ms"
                ),
                "failover_tps_ratio": failover.get(
                    "post_failover_tps_ratio"
                ),
                "failover_lost_events": failover.get("lost_events"),
                # load/latency frontier (run_frontier): per-step offered/
                # achieved/p50/p99/shed/dominant-leg ladder — the compact
                # headline keeps the knee + peak; full steps in detail
                "frontier_peak_tps": frontier.get("peak_achieved_tps"),
                "frontier_knee_tps": frontier.get(
                    "saturation_offered_tps"
                ),
                "frontier_steps": [
                    [s.get("offered_tps"), s.get("achieved_tps"),
                     s.get("p50_ms"), s.get("p99_ms"), s.get("shed_rate"),
                     s.get("dominant_leg"),
                     s.get("dominant_device_subleg")]
                    for s in frontier.get("steps", [])
                ],
                "frontier_accounted_ratio": (
                    frontier.get("breakdown") or {}
                ).get("accounted_ratio"),
                # cross-ledger federation: settled origin pendings per
                # wall second across two live regions (pending + remote
                # mirror + resolve per payment), the settlement lag
                # bound in ops, and the external counterparty audit of
                # each region's commitment stream; full report in detail
                "cross_ledger_tps": cross_ledger.get("cross_ledger_tps"),
                "settlement_lag_ops": cross_ledger.get(
                    "settlement_lag_max_ops"
                ),
                "commitment_verify_ok": cross_ledger.get(
                    "commitment_verify_ok"
                ),
                # device anatomy: commit_wait decomposed on the applier
                # thread — the slowest sampled apply item's sub-legs must
                # account for its span exactly (ratio 1.0 at device
                # granularity), and the knee names the sub-leg to attack
                "frontier_device_accounted_ratio": (
                    frontier.get("device_breakdown") or {}
                ).get("accounted_ratio"),
                "frontier_device_dominant": (
                    frontier.get("device_breakdown") or {}
                ).get("dominant"),
                # compile-sentinel + .jax_cache provenance: recompiles
                # observed in THIS driver process and the cache growth it
                # caused — post-warmup compiles are the pathology signal
                "compile_sentinel": _sentinel_summary(),
                "jax_cache_bytes_start": _JAX_CACHE_BYTES_START,
                "jax_cache_bytes_end": jax_cache_bytes(),
                # where the in-process phases ran, as JAX reports it, and
                # the segments that raised (any => exit code 1)
                "device": device,
                "segment_errors": SEGMENT_ERRORS,
            }
        )
    )
    return 1 if SEGMENT_ERRORS else 0


if __name__ == "__main__":
    sys.exit(main())
