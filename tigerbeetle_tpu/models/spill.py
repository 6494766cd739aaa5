"""HBM↔LSM spill scheduler: the bounded-memory story.

The device ledger's transfer table is a capacity-bounded HBM hash table
(models/ledger.py); the reference's store is an unbounded LSM forest with a
residency-guaranteed in-memory cache (reference: src/lsm/groove.zig:602-760
prefetch contract; src/lsm/cache_map.zig:10-25 CacheMap residency). This
module closes that gap the TPU-native way:

- HBM is the CacheMap: every row a batch can touch is resident BEFORE the
  kernel runs, so the kernels stay pure, synchronous, and data-parallel.
- The LSM forest (lsm/groove.py over the grid) is the backing store: when
  HBM occupancy reaches the spill trigger, the OLDEST transfers spill to
  the forest (timestamp order — the reference's object trees are
  timestamp-keyed for exactly this access pattern) and the HBM table is
  rebuilt with only the hot tail. Rebuilding also sheds rollback
  tombstones, so a cycle resets probe-chain density to the live load.
- Before every commit, the host checks the batch's id and pending_id
  references against the spilled-id set (sorted-limb prefilter + exact
  set — the host analog of the reference's per-table bloom filters,
  src/lsm/bloom_filter.zig) and RELOADS referenced spilled rows into HBM.
  This is the prefetch contract: after admit(), the kernels' HBM lookups
  are equivalent to lookups against the full store.

The OVERLAPPED SPILL PIPELINE (the reference saturates IO depth while the
previous op commits, src/lsm/groove.zig:710-760; all storage IO rides one
async loop, src/io/linux.zig:17-42):

- prefetch/commit overlap: a driver that knows batch N+1 while batch N's
  commit kernel runs calls ``prefetch_async(arr)`` — the referenced-
  spilled id scan happens inline (cheap numpy), and the LSM point reads +
  row staging run on the IO executor into a double-buffered host slot.
  The admit() that later commits the batch finds the rows staged and pays
  only the device reload launch; ``stats`` accounts how much of the gather
  time was hidden (``t_prefetch_worker`` vs ``t_prefetch_wait``).
- vectorized multi-lookup: cold-row fetches resolve through ONE batched
  LSM multi-point-read per tree (lsm/tree.py Tree.get_many) — memtable and
  each level walked once per id set, bloom probes vectorized, index blocks
  parsed once per table per call — instead of a full per-id cascade.
- the reload staging buffers double-buffer against device execution the
  same way the group-commit upload slots do (models/ledger.py
  _group_staging_slot): two alternating preallocated host buffers, each
  fenced on the last reload dispatched from it.

Accounts do not spill: account rows are the working set of every batch
(dr/cr balance updates), and the reference's workload shape is a bounded
account population with unbounded transfer history — the transfer table is
the wall that matters (BASELINE.md: 10k accounts, 10M+ transfers). The
account-table guard stays hard.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from tigerbeetle_tpu import types
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.models.ledger import (
    FAULT_CAPACITY,
    FAULT_CLAIM,
    FAULT_PROBE,
    raise_on_fault,
)
from tigerbeetle_tpu.models.validate import F_POST, F_VOID
from tigerbeetle_tpu.ops import hashtable as ht
from tigerbeetle_tpu.tracer import NULL_TRACER

U64 = jnp.uint64
U32 = jnp.uint32
ROW_WORDS = 32

CHUNK = 8192  # static shape of gather/reload kernels (= BATCH_PAD)


# ----------------------------------------------------------------------
# the IO executor seam (reference: ALL storage IO rides one event loop off
# the replica's hot path, src/io/linux.zig:17-42). Two implementations:
#
# - ThreadedSpillIO (production): ONE worker thread, FIFO — the insert
#   order is deterministic, and LSM insertion/compaction truly overlaps
#   the caller's commits in wall time.
# - DeferredSpillIO (deterministic harnesses — the VSR replica, cluster
#   tests, the simulator): jobs queue and run inline at pump()/drain() on
#   the caller's thread, so seeded runs never depend on thread timing,
#   while the commit dispatch path still never executes LSM insertion —
#   jobs run at the event loop's tick boundary (Replica.tick pumps).
#   Grid-block ALLOCATION order stays identical to the threaded executor's
#   (same FIFO job order), which is what cross-replica repair-by-address
#   depends on.
# ----------------------------------------------------------------------


class ThreadedSpillIO:
    """Single-worker FIFO executor: real async IO for wall-clock overlap."""

    settle_in_worker = True  # jobs may settle trees (raises surface at drain)

    def __init__(self):
        self._ex = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="spill-io"
        )
        self._jobs: list[Future] = []

    def submit(self, fn, *args) -> Future:
        f = self._ex.submit(fn, *args)
        self._jobs.append(f)
        return f

    def drain(self) -> None:
        """Barrier: wait for EVERY queued job even when an earlier one
        raised — dropping the tail would let a healed-and-retried caller
        read trees the worker is still mutating. The first exception
        surfaces after the whole queue has settled."""
        jobs, self._jobs = self._jobs, []
        err = None
        for f in jobs:
            try:
                f.result()
            except BaseException as e:
                if err is None:
                    err = e
        if err is not None:
            raise err

    def pump(self) -> None:
        """Reap finished jobs (surfacing their exceptions) without
        blocking on the ones still running. Finished jobs are evicted
        BEFORE any exception propagates — a failed job must raise once,
        not on every subsequent pump."""
        keep, finished = [], []
        for f in self._jobs:
            (keep if not f.done() else finished).append(f)
        self._jobs = keep
        err = None
        for f in finished:
            try:
                f.result()
            except BaseException as e:
                if err is None:
                    err = e
        if err is not None:
            raise err

    def wait(self, fut: Future):
        return fut.result()

    def pending(self) -> int:
        return len(self._jobs)


class DeferredSpillIO:
    """Deterministic executor: jobs queue and run inline at pump()/drain()
    — off the commit dispatch path, with zero thread timing. Jobs here
    must be pure pending-appends (settle_in_worker=False): a
    GridBlockCorrupt raised from a tick-boundary pump would have no
    heal-and-retry context, so settles stay in admit's _settle_forest,
    where the replica's repair path catches them."""

    settle_in_worker = False

    def __init__(self):
        self._q: deque = deque()

    def submit(self, fn, *args) -> Future:
        f: Future = Future()
        self._q.append((f, fn, args))
        return f

    def _run_one(self) -> None:
        f, fn, args = self._q.popleft()
        try:
            r = fn(*args)
        except BaseException as e:
            f.set_exception(e)
            raise
        f.set_result(r)

    def pump(self) -> None:
        while self._q:
            self._run_one()

    drain = pump

    def wait(self, fut: Future):
        while self._q and not fut.done():
            self._run_one()
        return fut.result()

    def pending(self) -> int:
        return len(self._q)


def _make_io(async_io: bool, io):
    if os.environ.get("TB_SPILL_SYNC") == "1":
        return None  # forced inline IO (debugging)
    if io == "threaded":
        return ThreadedSpillIO()
    if io == "deferred":
        return DeferredSpillIO()
    if io is not None:
        return io  # caller-provided executor instance
    return ThreadedSpillIO() if async_io else None


_SPILL_KERNELS_CACHE: dict = {}


def get_spill_kernels(process) -> "SpillKernels":
    """One SpillKernels per table geometry (stateless; same contract as
    models.ledger.get_kernels — fresh managers reuse the jit cache)."""
    k = _SPILL_KERNELS_CACHE.get(process)
    if k is None:
        k = _SPILL_KERNELS_CACHE[process] = SpillKernels(process)
    return k


class SpillKernels:
    """Jitted device ops for the spill cycle, closed over table geometry."""

    def __init__(self, process):
        self.t_log2 = process.transfer_slots_log2
        self.t_dump = 1 << self.t_log2
        self.ts_occ = jax.jit(self._ts_occ)
        self.cycle_head = jax.jit(self._cycle_head)
        self.split_idx = jax.jit(self._split_idx)
        self.gather = jax.jit(self._gather)
        self.reload = jax.jit(self._reload, donate_argnums=(0, 1, 2))

    def _ts_occ(self, xfer_rows):
        """Per-slot (timestamp u64, occupied bool) — the cycle's scan."""
        occ = ht.occupied_mask(xfer_rows).at[self.t_dump].set(False)
        ts = xfer_rows[:, 30].astype(U64) | (
            xfer_rows[:, 31].astype(U64) << jnp.uint64(32)
        )
        return ts, occ

    def _cycle_head(self, xfer_rows, fault):
        """[live count, fault]: the ONLY words the cycle fetches before
        deciding the split — the old path shipped the full per-slot
        (ts, occ) arrays device->host and sorted on host, a whole-table
        d2h + sync per cycle on the degraded-transport rig."""
        _, occ = self._ts_occ(xfer_rows)
        live = jnp.sum(occ.astype(U32))
        return jnp.stack([live, fault.astype(U32)])

    def _split_idx(self, xfer_rows, n_cold):
        """Device-side cold/hot partition: sort the live timestamps, take
        the watermark at n_cold (timestamps are unique by construction, so
        the split is exact), and emit padded index arrays the gather
        kernels consume DIRECTLY — no host round trip. Padding lanes hold
        t_dump (the gather sentinel row); the arrays are oversized by one
        CHUNK so every CHUNK-window slice is full-width (one gather
        compile)."""
        ts, occ = self._ts_occ(xfer_rows)
        inf = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        ts_m = jnp.where(occ, ts, inf)
        watermark = jnp.sort(ts_m)[n_cold]
        cold = occ & (ts_m < watermark)
        hot = occ & ~(ts_m < watermark)
        size = self.t_dump + CHUNK
        cold_idx = jnp.nonzero(cold, size=size, fill_value=self.t_dump)[0]
        hot_idx = jnp.nonzero(hot, size=size, fill_value=self.t_dump)[0]
        return cold_idx.astype(jnp.int32), hot_idx.astype(jnp.int32)

    def _gather(self, xfer_rows, fulfill, idx):
        return xfer_rows[idx], fulfill[idx]

    def _reload(self, xfer_rows, fulfill, claim, used_slots, fault,
                rows_b, ful_b, active):
        """Insert absent rows (verbatim stored content, fulfill included)
        into the transfer table. Lanes whose key is already resident are
        skipped — reload is idempotent. Every write gates on the sticky
        fault word (models/ledger.py fault protocol)."""
        key4 = rows_b[:, :4]
        _, found, res = ht.lookup(key4, xfer_rows, self.t_log2)
        need = active & ~found
        slots, claim, ins_res = ht.claim_slots(
            key4, need, xfer_rows, claim, self.t_log2
        )
        n_new = jnp.sum(need).astype(U64)
        cap_bad = used_slots + n_new > np.uint64(self.t_dump // 2)
        fault = (
            fault
            | jnp.where(jnp.any(active & ~res), jnp.uint32(FAULT_PROBE), jnp.uint32(0))
            | jnp.where(jnp.any(~ins_res), jnp.uint32(FAULT_CLAIM), jnp.uint32(0))
            | jnp.where(cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0))
        )
        proceed = fault == 0
        w = jnp.where(proceed & need, slots, self.t_dump)
        xfer_rows = xfer_rows.at[w].set(rows_b)
        fulfill = fulfill.at[w].set(ful_b)
        used_slots = used_slots + jnp.where(proceed, n_new, jnp.uint64(0))
        # probe: a dedicated output NOTHING else consumes — the staging
        # double-buffer fences on it (state outputs get donated by later
        # kernels, so their buffers may be deleted before the fence fires;
        # the xor keeps it a distinct graph node so XLA cannot alias it
        # onto a state output's buffer)
        probe = used_slots.astype(U32) ^ fault.astype(U32)
        return xfer_rows, fulfill, claim, used_slots, fault, probe


class SpillManager:
    """Owns the spilled-id set, the LSM backing store, and the cycle.

    Attached to a DeviceLedger via ``DeviceLedger(forest=...)``; the ledger
    calls ``admit(arr, n)`` before every create_transfers commit and merges
    spilled rows into lookups/extract.
    """

    STAT_KEYS = (
        "cycles", "spilled", "reloaded",
        "t_scan", "t_gather_d2h", "t_stage",
        "t_rebuild", "t_reload", "t_lsm_worker",
        "prefetches", "prefetched",
        "t_prefetch_worker", "t_prefetch_wait",
        "lookup_batches", "lookup_ids",
    )

    def instrument(self, metrics, tracer) -> None:
        """Re-bind onto a shared registry/tracer (the replica's, or the
        bench driver's). Accumulated values carry over; the forest's trees
        and grid report into the same registry. A worker-side stat update
        racing the carry-over/rebind window lands in the discarded old
        group and is dropped from the new registry — at most one update,
        and instrument() runs at setup before IO jobs flow."""
        for key in self.STAT_KEYS:
            metrics.counter(f"spill.{key}").add(self.stats[key])
        self.metrics = metrics
        # rebound on the event loop while IO-worker jobs read per use —
        # a GIL-atomic reference swap (worst case one span lands in the
        # old tracer); registry counters serialize internally
        self.tracer = tracer  # vet: handoff
        self.stats = metrics.group("spill", self.STAT_KEYS)  # vet: handoff
        for tree in self.forest._trees():
            tree.metrics = metrics
            tree.tracer = tracer
        self.forest.grid.metrics = metrics

    def __init__(self, ledger, forest, keep_frac: float = 0.25,
                 async_io: bool = True, io=None):
        assert 0.0 < keep_frac < 1.0
        self.ledger = ledger
        self.forest = forest
        self.keep_frac = keep_frac
        self.kernels = get_spill_kernels(ledger.process)
        # ids present ONLY in the LSM store (reloading removes the id; the
        # stale LSM row is overwritten on the next spill of that id).
        self.spilled: set[int] = set()
        # Sorted lo-limb prefilter over `spilled` (may carry stale entries
        # between cycles; exactness comes from the set).
        self._lo = np.empty(0, dtype=np.uint64)
        # Grid block chain holding the checkpointed spilled-id set (the
        # set can exceed the superblock's copy size; only the addresses
        # ride the superblock meta — the trailer pattern, reference:
        # src/vsr/superblock.zig:31-34).
        self._id_chain: list[int] = []
        # t_* keys: cumulative seconds per cycle stage (the spill bench's
        # isolating artifact — which part of the cycle carries the bill).
        # Overlap accounting: t_prefetch_worker = executor seconds spent
        # gathering prefetched rows; t_prefetch_wait = seconds admit
        # BLOCKED on an unfinished prefetch (0 wait = the gather fully hid
        # behind the previous batch's commit). lookup_ids/lookup_batches =
        # multi-lookup amortization (mean ids per batched LSM read).
        # `stats` is a registry-backed Mapping (tigerbeetle_tpu/metrics.py
        # StatGroup under the `spill.` prefix): dict reads everywhere stay
        # valid, and instrument() re-binds the storage onto the replica's /
        # bench's shared registry so overlap_report and the [stats] line
        # read the same numbers.
        self.metrics = Metrics()
        self.tracer = NULL_TRACER
        self.stats = self.metrics.group("spill", self.STAT_KEYS)
        # the IO executor seam (see module docstring / ThreadedSpillIO vs
        # DeferredSpillIO); None = fully inline synchronous IO
        self._io = _make_io(async_io, io)
        # rows in flight to the LSM sit in _staged (id -> (row, ful));
        # fetches check _staged first and barrier on the executor before
        # any direct forest read
        self._staged: dict[int, tuple[np.ndarray, int]] = {}  # vet: guarded-by=_staged_lock
        self._staged_lock = threading.Lock()
        # one outstanding prefetch (consumed by the next reload) + its two
        # alternating host staging slots
        self._prefetch: dict | None = None
        self._pf_slots = {"i": 0, "slots": [None, None]}
        # double-buffered reload staging (pad -> two fenced slots)
        self._reload_slots: dict[int, dict] = {}

    # ------------------------------------------------------------------
    # the IO executor seam
    # ------------------------------------------------------------------

    def _io_submit(self, fn, *args) -> None:
        if self._io is None:
            fn(*args)
            return
        self._io.submit(fn, *args)

    def io_drain(self) -> None:
        """Barrier: every queued LSM job has run (and surfaced its
        exception, if any). After this the forest is safe to read inline —
        only the commit thread submits jobs, so none can appear while the
        caller holds the drained state."""
        if self._io is not None:
            self._io.drain()

    def io_pump(self) -> None:
        """Non-blocking housekeeping: run deferred jobs (DeferredSpillIO)
        or reap finished worker jobs (ThreadedSpillIO). The replica calls
        this at its tick boundary — LSM insertion then never runs inside
        the commit dispatch path."""
        if self._io is not None:
            self._io.pump()

    def io_pending(self) -> int:
        """Queued-but-undrained job count (the replica's scrub pass skips
        a turn while inserts are in flight rather than reading blocks the
        worker may be mid-writing)."""
        return 0 if self._io is None else self._io.pending()

    # ------------------------------------------------------------------
    # membership
    # ------------------------------------------------------------------

    def _prefilter(self, lo: np.ndarray) -> np.ndarray:
        """Lanes whose id lo-limb appears in the sorted prefilter."""
        if len(self._lo) == 0:
            return np.zeros(len(lo), dtype=bool)
        pos = np.searchsorted(self._lo, lo)
        pos_c = np.minimum(pos, len(self._lo) - 1)
        return self._lo[pos_c] == lo

    def referenced_spilled(self, arr: np.ndarray) -> list[int]:
        """Distinct spilled ids this batch references: its own ids (the
        exists/idempotency checks, reference: src/state_machine.zig:767-777,
        886-905) and post/void pending_id references (reference: :907-1014).
        """
        out: set[int] = set()
        if not self.spilled:
            return []
        cand = self._prefilter(arr["id_lo"])
        for i in np.nonzero(cand)[0]:
            key = int(arr["id_lo"][i]) | (int(arr["id_hi"][i]) << 64)
            if key in self.spilled:
                out.add(key)
        pv = (arr["flags"] & np.uint16(F_POST | F_VOID)) != 0
        if pv.any():
            cand = self._prefilter(arr["pending_id_lo"]) & pv
            for i in np.nonzero(cand)[0]:
                key = int(arr["pending_id_lo"][i]) | (
                    int(arr["pending_id_hi"][i]) << 64
                )
                if key in self.spilled:
                    out.add(key)
        return sorted(out)

    # ------------------------------------------------------------------
    # prefetch/commit overlap
    # ------------------------------------------------------------------

    @property
    def prefetch_enabled(self) -> bool:
        """True when prefetch_async can actually overlap (threaded
        executor) — callers gate side work (e.g. the backup's WAL peek)
        on this."""
        return self._io is not None and getattr(
            self._io, "settle_in_worker", False
        )

    def _pf_slot(self, k: int) -> dict:
        """One of two alternating prefetch staging slots, grown to cover
        k rows. Only one prefetch is ever outstanding and its rows are
        copied out synchronously at consume time, so alternation alone
        keeps a lingering job from racing a fresh submission."""
        pool = self._pf_slots
        i = pool["i"]
        pool["i"] = 1 - i
        slot = pool["slots"][i]
        cap = _next_pow2(k)
        if slot is None or slot["cap"] < cap:
            slot = pool["slots"][i] = {
                "rows": np.zeros((cap, ROW_WORDS), dtype=np.uint32),
                "ful": np.zeros(cap, dtype=np.uint32),
                "cap": cap,
            }
        return slot

    def prefetch_async(self, arr: np.ndarray) -> None:
        """Start gathering the referenced-spilled rows of an UPCOMING
        batch on the IO executor: the id scan runs inline (cheap numpy —
        and `spilled` mutates only on the commit thread, so the scan must
        not move to the worker), the LSM point reads + row staging run as
        one FIFO job behind every queued insert (so no drain barrier is
        needed). The admit() that commits the batch consumes the staged
        rows; content is stable meanwhile because an id's LSM row can only
        change after a reload removes it from `spilled`, and reloads
        happen only in admit on this same thread.

        Threaded executors only: on DeferredSpillIO the job would run
        inline on this same thread (no overlap to win), and its
        read-triggered settle could raise GridBlockCorrupt at the tick
        pump — outside the admit context where the replica's
        heal-and-retry contract lives."""
        if not self.prefetch_enabled or not self.spilled:
            return
        pf = self._prefetch
        if pf is not None and not pf["fut"].done():
            return  # one outstanding prefetch; don't pile up slot reuse
        ids = self.referenced_spilled(arr)
        if not ids:
            return
        slot = self._pf_slot(len(ids))
        fut = self._io.submit(self._prefetch_job, ids, slot)
        self._prefetch = {
            "fut": fut,
            "rows": slot["rows"],
            "ful": slot["ful"],
            "by_id": {id_: j for j, id_ in enumerate(ids)},
        }
        self.stats.add("prefetches")

    def _prefetch_job(self, ids: list[int], slot: dict) -> None:
        import time as _time

        t0 = _time.perf_counter()
        tok = self.tracer.start("spill.prefetch_worker", ids=len(ids))
        try:
            rows, ful = slot["rows"], slot["ful"]
            missing: list[tuple[int, int]] = []
            with self._staged_lock:
                for j, id_ in enumerate(ids):
                    hit = self._staged.get(id_)
                    if hit is not None:
                        rows[j] = hit[0]
                        ful[j] = hit[1]
                    else:
                        missing.append((j, id_))
            if missing:
                # FIFO position: every earlier insert already landed
                self._fetch_forest(missing, rows, ful)
            self.stats.add("t_prefetch_worker", _time.perf_counter() - t0)
        finally:
            self.tracer.stop(tok)

    def _consume_prefetch(self, ids, rows: np.ndarray,
                          ful: np.ndarray) -> list[tuple[int, int]]:
        """Fill rows/ful lanes served by the outstanding prefetch; returns
        the (lane, id) pairs it did not cover. Consumed once on any hit;
        a COMPLETE miss keeps it armed for a later batch (a driver may
        prefetch op N+1 before op N's own reload runs) — sound because a
        kept entry's id is still in `spilled` (only a reload that served
        it would have removed it), and an id's backing content is stable
        while spilled (see prefetch_async)."""
        import time as _time

        pf = self._prefetch
        if pf is None:
            return list(enumerate(ids))
        by_id = pf["by_id"]
        if not any(id_ in by_id for id_ in ids):
            return list(enumerate(ids))  # foreign batch: keep it armed
        self._prefetch = None
        t0 = _time.perf_counter()
        with self.tracer.span("spill.prefetch_wait"):
            # pump-aware (DeferredSpillIO runs inline)
            self._io.wait(pf["fut"])
        self.stats.add("t_prefetch_wait", _time.perf_counter() - t0)
        prows, pful = pf["rows"], pf["ful"]
        remaining: list[tuple[int, int]] = []
        for i, id_ in enumerate(ids):
            j = by_id.get(id_)
            if j is None:
                remaining.append((i, id_))
            else:
                rows[i] = prows[j]
                ful[i] = pful[j]
                self.stats.add("prefetched")
        return remaining

    # ------------------------------------------------------------------
    # admission: called before every create_transfers commit
    # ------------------------------------------------------------------

    def admit(self, arr: np.ndarray, n: int) -> None:
        with self.tracer.span("spill.admit", n=n), \
                self.metrics.histogram("spill.admit_us").time():
            self._admit(arr, n)

    def _admit(self, arr: np.ndarray, n: int) -> None:
        led = self.ledger
        # Capacity to free: the CONSERVATIVE occupancy transient, not the
        # true row growth. True growth is <= n + n_pv (an event's own id
        # yields a fresh insert OR a reload-then-exists, never both), but
        # the ledger charges +n at dispatch and only reconciles at drain —
        # so between reload and drain the counter can read
        # reloads (<= n + n_pv) + n. `need` must cover that transient or
        # the hard load guard would raise on a batch that actually fits.
        n_pv = int(((arr["flags"] & np.uint16(F_POST | F_VOID)) != 0).sum())
        reload_ids = self.referenced_spilled(arr)
        if led._xfer_used + n + len(reload_ids) > led._xfer_limit:
            self.cycle(need=2 * n + n_pv)
            # the cycle may have spilled rows this batch references
            reload_ids = self.referenced_spilled(arr)
        if reload_ids:
            self._reload_rows(reload_ids)
        if self._io is None or not self._io.settle_in_worker:
            # sync/deferred mode: discharge the deferred settles /
            # compaction debt HERE, after the cycle has committed (HBM
            # rebuilt, counters updated) — a GridBlockCorrupt raise from a
            # settle leaves the cycle done, so the replica's heal-and-retry
            # re-enters this admit with nothing to re-cycle and the settle
            # RESUMES
            self._settle_forest()

    def _settle_forest(self) -> None:
        """Discharge compaction debt and settle trees whose pending
        buffers crossed the size threshold, in the forest's fixed tree
        order (deterministic across replicas). Thresholded, not eager:
        settling every admit would write many tiny tables and churn the
        grid; below-threshold pendings settle lazily at reads/flush."""
        for tree in self.forest._trees():
            if (
                tree._compact_debt
                or tree._pending_rows >= tree.settle_max
            ):
                tree._settle()

    def _fetch(self, id_: int) -> tuple[bytes, int]:
        """One spilled row + fulfill byte: the in-flight staging area
        first (no barrier), then the LSM store (barrier: the queued
        inserts must land before a direct forest read)."""
        with self._staged_lock:
            hit = self._staged.get(id_)
        if hit is not None:
            return hit[0].tobytes(), hit[1]
        self.io_drain()
        g = self.forest.transfers
        ts_key = g.ids.get(g._id_key(id_))
        assert ts_key is not None, f"spilled id {id_} missing from LSM"
        row = g.objects.get(ts_key)
        assert row is not None
        ful = self.forest.posted.get(ts_key)
        return row, (ful[0] if ful else 0)

    def _fetch_forest(self, missing: list[tuple[int, int]],
                      rows: np.ndarray, ful: np.ndarray) -> None:
        """Resolve (lane, id) pairs against the forest with ONE vectorized
        multi-point-read per tree (IdTree -> ObjectTree -> posted) — the
        bloom/index amortization lives in Tree.get_many. Caller guarantees
        the forest is current (drained, or running ON the FIFO worker)."""
        g = self.forest.transfers
        ids_list = [id_ for _, id_ in missing]
        row_list, ts_keys = g.get_many_rows(ids_list)
        fuls = self.forest.posted.get_many(
            [t if t is not None else b"\x00" * 8 for t in ts_keys]
        )
        for (i, id_), row, tsk, f in zip(missing, row_list, ts_keys, fuls):
            assert tsk is not None and row is not None, (
                f"spilled id {id_} missing from LSM"
            )
            rows[i] = np.frombuffer(row, dtype=np.uint32)
            ful[i] = f[0] if f else 0
        self.stats.add("lookup_batches")
        self.stats.add("lookup_ids", len(missing))

    def _fetch_many(self, ids: list[int], rows: np.ndarray,
                    ful: np.ndarray) -> None:
        """Fill rows[:k]/ful[:k] for `ids`: prefetched rows first (no IO),
        then staged hits (no barrier), then ONE batched forest read after
        ONE io_drain."""
        remaining = self._consume_prefetch(ids, rows, ful)
        if not remaining:
            return
        missing: list[tuple[int, int]] = []
        with self._staged_lock:
            for i, id_ in remaining:
                hit = self._staged.get(id_)
                if hit is not None:
                    rows[i] = hit[0]
                    ful[i] = hit[1]
                else:
                    missing.append((i, id_))
        if not missing:
            return
        self.io_drain()
        self._fetch_forest(missing, rows, ful)

    def _reload_slot(self, pad: int) -> dict:
        """One of TWO alternating preallocated reload staging buffers per
        pad (the PR-1 _group_staging_slot pattern): batch N+1's rows stage
        into buffer B while buffer A's reload kernel (batch N) may still
        run. `fence` is the device result of the last reload dispatched
        from the buffer — on backends where jnp.asarray aliases host
        memory, mutating the buffer before that kernel retires would
        corrupt the in-flight rows. `used` bounds the stale-tail zeroing."""
        pool = self._reload_slots
        entry = pool.get(pad)
        if entry is None:
            entry = pool[pad] = {"i": 0, "slots": [None, None]}
        i = entry["i"]
        entry["i"] = 1 - i
        slot = entry["slots"][i]
        if slot is None:
            slot = entry["slots"][i] = {
                "rows": np.zeros((pad, ROW_WORDS), dtype=np.uint32),
                "ful": np.zeros(pad, dtype=np.uint32),
                "used": 0,
                "fence": None,
            }
        if slot["fence"] is not None:
            with self.tracer.span("spill.staging_wait"), \
                    self.metrics.histogram("spill.staging_wait_us").time():
                jax.block_until_ready(slot["fence"])
            slot["fence"] = None
        return slot

    def _reload_rows(self, ids: list[int]) -> None:
        import time as _time

        t0 = _time.perf_counter()
        led = self.ledger
        st = led.state
        for start in range(0, len(ids), CHUNK):
            chunk = ids[start : start + CHUNK]
            k = len(chunk)
            pad = CHUNK if len(ids) > CHUNK else _next_pow2(k)
            slot = self._reload_slot(pad)
            rows, ful = slot["rows"], slot["ful"]
            if slot["used"] > k:  # zero only the stale tail
                rows[k : slot["used"]] = 0
                ful[k : slot["used"]] = 0
            slot["used"] = k
            self._fetch_many(chunk, rows, ful)
            active = np.zeros(pad, dtype=bool)
            active[:k] = True
            (
                st["xfer_rows"], st["fulfill"], st["xfer_claim"],
                st["xfer_used_slots"], st["fault"], probe,
            ) = self.kernels.reload(
                st["xfer_rows"], st["fulfill"], st["xfer_claim"],
                st["xfer_used_slots"], st["fault"],
                jnp.asarray(rows), jnp.asarray(ful), jnp.asarray(active),
            )
            slot["fence"] = probe
            for id_ in chunk:
                self.spilled.discard(id_)
            led._xfer_used += k
            self.stats.add("reloaded", k)
        self.stats.add("t_reload", _time.perf_counter() - t0)

    def _stage_and_submit(self, rows: np.ndarray, ful: np.ndarray,
                          ids_lo: np.ndarray, ids_hi: np.ndarray,
                          ts_np: np.ndarray) -> None:
        """Stage one gathered cold chunk (rows visible to _fetch at once)
        and queue its LSM insertion on the IO worker. The job unstages
        only entries it staged itself (identity check): a later cycle may
        re-spill an id and overwrite the staged tuple before this job
        lands — its newer insert is FIFO-behind ours, so the LSM ends
        newest-wins either way."""
        k = len(rows)
        entries: dict[int, tuple] = {}
        with self._staged_lock:
            for i in range(k):
                key = int(ids_lo[i]) | (int(ids_hi[i]) << 64)
                tup = (rows[i], int(ful[i]))
                self._staged[key] = tup
                entries[key] = tup

        def job():
            import time as _time

            t0 = _time.perf_counter()
            # APPEND-THEN-SETTLE, always: the appends (settle=False) are
            # pure pending-appends that CANNOT raise, so every row and
            # fulfillment lands — and unstages — exactly once even when
            # the settle below trips GridBlockCorrupt. A raise then only
            # interrupts settling/compaction, which is resume-safe by the
            # _pending/_compact_debt contract (the next settle — a later
            # job, admit's _settle_forest, or the checkpoint flush —
            # resumes it); the old settle-inside-append ordering lost the
            # chunk's posted flags + unstage when a threaded worker raised
            # mid-insert and the tick pump routed the error to repair.
            g = self.forest.transfers
            g.insert_bulk(rows.view(np.uint8).reshape(k, 128), ts_np,
                          settle=False)
            nz = np.nonzero(ful)[0]
            if len(nz):
                self.forest.posted.put_array(
                    np.ascontiguousarray(
                        ts_np[nz].astype(">u8")
                    ).view(np.uint8).reshape(len(nz), 8),
                    ful[nz].astype(np.uint8).reshape(len(nz), 1),
                    settle=False,
                )
            with self._staged_lock:
                for key, tup in entries.items():
                    if self._staged.get(key) is tup:
                        del self._staged[key]
            # worker-thread seconds (accumulated under the stats lock's
            # coarse protection — a float add race would only smear stats)
            self.stats.add("t_lsm_worker", _time.perf_counter() - t0)
            if self._io is not None and self._io.settle_in_worker:
                # threaded mode settles on the worker; sync/deferred mode
                # leaves it to admit's _settle_forest (heal-retry context)
                self._settle_forest()

        self._io_submit(job)

    # ------------------------------------------------------------------
    # the spill cycle
    # ------------------------------------------------------------------

    def cycle(self, need: int) -> None:
        """Spill the cold majority to the LSM forest and rebuild the HBM
        table with the hot tail, guaranteeing room for `need` new rows.
        A host-paced maintenance op (the analog of the reference's paced
        compaction beats trading throughput for bounded memory). The scan
        and cold/hot split run ON DEVICE (SpillKernels.cycle_head /
        split_idx): the host fetches two words, not the whole table."""
        with self.tracer.span("spill.cycle", need=need):
            self._cycle(need)

    def _cycle(self, need: int) -> None:
        import time as _time

        led = self.ledger
        st = led.state
        t0 = _time.perf_counter()
        head = np.asarray(self.kernels.cycle_head(st["xfer_rows"], st["fault"]))
        live, fault = int(head[0]), int(head[1])
        if fault:
            raise_on_fault(fault, "spill cycle")
        if led._xfer_limit - need < 0:
            raise RuntimeError(
                f"batch needs {need} transfer slots but the table limit is "
                f"{led._xfer_limit}: grow ConfigProcess.transfer_slots_log2"
            )
        keep = min(int(live * self.keep_frac), led._xfer_limit - need)
        n_cold = live - keep
        if n_cold <= 0:
            return  # nothing live to spill
        cold_idx, hot_idx = self.kernels.split_idx(
            st["xfer_rows"], jnp.int32(n_cold)
        )
        n_hot = live - n_cold
        self.stats.add("t_scan", _time.perf_counter() - t0)
        t0 = _time.perf_counter()

        # 1. Cold rows -> host. The d2h gather is synchronous (the spilled
        # set must be exact before the next admit()), pipelined across
        # chunks; LSM insertion is NOT — rows stage in _staged and the IO
        # worker drains them into the forest while commits continue
        # (reference keeps all storage IO off the replica's hot path,
        # src/io/linux.zig:17-42).
        gathered = []
        for start in range(0, n_cold, CHUNK):
            k = min(CHUNK, n_cold - start)
            rows_d, ful_d = self.kernels.gather(
                st["xfer_rows"], st["fulfill"],
                cold_idx[start : start + CHUNK],
            )
            for buf in (rows_d, ful_d):
                try:
                    buf.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    pass
            gathered.append((k, rows_d, ful_d))
        for k, rows_d, ful_d in gathered:
            # ascontiguousarray: a backend may hand back arrays the later
            # .view(uint8) reinterpretation rejects
            rows = np.ascontiguousarray(np.asarray(rows_d)[:k])
            ful = np.ascontiguousarray(np.asarray(ful_d)[:k])
            self.stats.add("t_gather_d2h", _time.perf_counter() - t0)
            t0 = _time.perf_counter()
            ids_lo = rows[:, 0].astype(np.uint64) | (
                rows[:, 1].astype(np.uint64) << np.uint64(32)
            )
            ids_hi = rows[:, 2].astype(np.uint64) | (
                rows[:, 3].astype(np.uint64) << np.uint64(32)
            )
            ts_np = rows[:, 30].astype(np.uint64) | (
                rows[:, 31].astype(np.uint64) << np.uint64(32)
            )
            self._stage_and_submit(rows, ful, ids_lo, ids_hi, ts_np)
            self.spilled.update(
                (int(lo) | (int(hi) << 64))
                for lo, hi in zip(ids_lo, ids_hi)
            )
            self.stats.add("spilled", k)
            self.stats.add("t_stage", _time.perf_counter() - t0)
            t0 = _time.perf_counter()

        # 2. Rebuild: fresh table, reinsert the hot tail (device-to-device;
        #    hot rows never visit the host).
        cap1 = self.kernels.t_dump + 1
        new_rows = jnp.zeros((cap1, ROW_WORDS), dtype=U32)
        new_ful = jnp.zeros(cap1, dtype=U32)
        new_claim = jnp.full(cap1, ht.CLAIM_FREE, dtype=U32)
        new_used = jnp.uint64(0)
        new_fault = jnp.uint32(0)
        for start in range(0, n_hot, CHUNK):
            k = min(CHUNK, n_hot - start)
            rows_d, ful_d = self.kernels.gather(
                st["xfer_rows"], st["fulfill"],
                hot_idx[start : start + CHUNK],
            )
            active = np.zeros(CHUNK, dtype=bool)
            active[:k] = True
            new_rows, new_ful, new_claim, new_used, new_fault, _ = (
                self.kernels.reload(
                    new_rows, new_ful, new_claim, new_used, new_fault,
                    rows_d, ful_d, jnp.asarray(active),
                )
            )
        new_fault_host = int(np.asarray(new_fault))
        if new_fault_host:
            raise_on_fault(new_fault_host, "spill rebuild")
        st["xfer_rows"] = new_rows
        st["fulfill"] = new_ful
        st["xfer_claim"] = new_claim
        st["xfer_used_slots"] = new_used
        led._xfer_used = n_hot
        led._occupancy_epoch += 1
        self._lo = np.sort(
            np.array([x & ((1 << 64) - 1) for x in self.spilled], dtype=np.uint64)
        )
        self.stats.add("t_rebuild", _time.perf_counter() - t0)
        self.stats.add("cycles")

    # ------------------------------------------------------------------
    # lookup / extract merging
    # ------------------------------------------------------------------

    def merge_lookup_rows(self, ids: list[int], found: np.ndarray,
                          rows: np.ndarray) -> bytes:
        """Reply body: wire rows in request order, HBM hits from the device
        lookup, spilled hits from the LSM store, misses skipped (_fetch
        barriers internally when it must read the forest)."""
        out = []
        for i, id_ in enumerate(ids):
            if found[i]:
                out.append(rows[i].tobytes())
            elif id_ in self.spilled:
                out.append(self._fetch(id_)[0])
        return b"".join(out)

    def extract_into(self, transfers: dict, posted: dict) -> None:
        """Merge spilled rows into extract() results (parity surface).
        Sorted: dict insertion order is part of the extract surface
        (parity dumps serialize it), and set order is not stable."""
        self.io_drain()
        for id_ in sorted(self.spilled):
            row, ful = self._fetch(id_)
            t = types.Transfer.from_np(
                np.frombuffer(row, dtype=types.TRANSFER_DTYPE)[0]
            )
            transfers[t.id] = t
            if ful:
                posted[t.timestamp] = ful

    # ------------------------------------------------------------------
    # durability
    # ------------------------------------------------------------------

    def checkpoint_meta(self) -> dict:
        """Persist the spill store: the spilled-id set goes into a grid
        block chain (it can exceed the superblock copy size; the forest's
        IdTree holds a superset — this exact set exists to exclude
        reloaded-and-stale LSM entries), then the forest checkpoint flushes
        trees, writes the manifest log, and encodes the free set LAST (so
        the id blocks created here are covered, and the previous chain's
        staged releases apply)."""
        from tigerbeetle_tpu.lsm.grid import BLOCK_PAYLOAD_MAX

        self.io_drain()  # queued inserts are part of this checkpoint
        g = self.forest.grid
        for address in self._id_chain:
            g.release(address)  # staged until the encode below
        payload = b"".join(
            x.to_bytes(16, "little") for x in sorted(self.spilled)
        )
        per_block = BLOCK_PAYLOAD_MAX // 16 * 16
        self._id_chain = [
            g.create_block(payload[i : i + per_block])
            for i in range(0, len(payload), per_block)
        ]
        manifest = self.forest.checkpoint()
        return {
            "manifest": manifest,
            "spilled_blocks": list(self._id_chain),
            "spilled_count": len(self.spilled),
        }

    def restore(self, meta: dict) -> None:
        self.io_drain()
        with self._staged_lock:
            self._staged.clear()
        self._prefetch = None  # gathered against the pre-restore store
        self.forest.restore(meta["manifest"])
        self._id_chain = list(meta["spilled_blocks"])
        self.spilled = set()
        for address in self._id_chain:
            raw = self.forest.grid.read_block(address)
            for i in range(0, len(raw), 16):
                self.spilled.add(int.from_bytes(raw[i : i + 16], "little"))
        assert len(self.spilled) == int(meta["spilled_count"])
        self._lo = np.sort(
            np.array([x & ((1 << 64) - 1) for x in self.spilled], dtype=np.uint64)
        )

    def overlap_report(self) -> dict:
        """The bench's overlap-accounting artifact (analogous to PR 1's
        shadow_upload_overlap): spill_overlap = fraction of prefetch-
        gather seconds hidden behind commits (1.0 = admit never waited);
        spill_lookup_batch = mean ids per batched LSM multi-lookup."""
        s = self.stats
        worker = s["t_prefetch_worker"]
        overlap = (
            round(max(0.0, 1.0 - s["t_prefetch_wait"] / worker), 4)
            if worker > 0 else None
        )
        batch = (
            round(s["lookup_ids"] / s["lookup_batches"], 1)
            if s["lookup_batches"] else None
        )
        return {"spill_overlap": overlap, "spill_lookup_batch": batch}


def _next_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p
