"""DualLedger: the native C++ engine serves replies, the TPU applies the
same committed ops asynchronously — the dual-commit backend
(``--backend dual``).

The native engine (native/ledger.cc) computes reply codes at host speed.
The REPLICA drives the apply queue: each committed create op is enqueued
at commit FINALIZE (reply built, WAL durable) via apply_commit(op, ...),
and a background thread applies the SAME events, same timestamps, same
order, to the JAX DeviceLedger — host->device uploads and kernel launches
only, nothing read back until shutdown. Device state is REAL state,
maintained batch by batch by the same commit kernels the `device` backend
replies from. Following the committed op stream with an explicit
watermark buys: a rolling per-op hash-log ring on BOTH sides (the first
divergent op is named exactly, not just "the digests differ"),
bounded-lag admission backpressure (`apply_lag_excess` feeds
Replica.ingress_occupancy and the credit regulator), checkpoint/state-sync
drains, and restart recovery — restore_bytes re-seeds the device from the
native snapshot's row images through DeviceLedger.install_snapshot_rows
(h2d only).

Verification (hash_log semantics, testing/hash_log.py):
- every batch's dense reply codes are folded into a chained u64 digest on
  BOTH sides — on device (fold_reply_codes, no d2h) and on the apply
  thread over the native engine's codes (same stream order);
- each op's post-fold chain value is also written into a rolling ring
  (host-side ring + device-side ring updated inside the fold kernel), so
  the end-of-run check can walk the rings and fail AT the first divergent
  op — the reference's -Dhash-log-mode check applied across heterogeneous
  engines (src/testing/hash_log.zig);
- at shutdown, finalize() drains the apply queue and does the applier's
  device->host reads: the fold scalars must match, the rings must match
  entry for entry, and state_fingerprint — an order-independent digest
  over every live account/transfer row's 128-byte wire image, implemented
  identically in C++ (tb_ledger_fingerprint) and JAX (models/ledger.py
  state_fingerprint) — must match row-set for row-set.

Reference seam: src/state_machine.zig:508-540 — commit determinism is the
consensus invariant; the dual backend extends it across heterogeneous
engines (the reference's simulator cross-checks replicas the same way,
src/testing/cluster/state_checker.zig).
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from tigerbeetle_tpu.constants import ConfigProcess
from tigerbeetle_tpu.latency import (
    DLEG_BUSY,
    DLEG_COALESCE,
    DLEG_DISPATCH,
    DLEG_H2D,
    DeviceAnatomy,
)
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.models.native_ledger import NativeLedger
from tigerbeetle_tpu.testing.hash_log import HashLogDivergence
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import Operation

_STOP = object()
_INSTALL = "__install__"  # control item: re-seed the device from a snapshot
_PROBE = "__probe__"  # control item: checkpoint-commitment fingerprint probe

# Rolling per-op digest ring: one chained-fold value per committed create
# op, op % RING. 4096 ops cover well over a full WAL ring of divergence
# localization without unbounded memory on either side.
APPLY_RING = 1 << 12

_FOLD_RING_CACHE: dict = {}


def _fold_group_ring_fn(k: int, n_pad: int):
    """Jitted chained fold over a fused group's flat results: one dispatch
    folds up to k batches' code streams (active-masked — padding slots
    must NOT advance the chain, the native side folds only real batches)
    and scatters each batch's post-fold chain value into the rolling
    device ring at its op's slot. Digest-identical to k sequential
    fold_reply_codes calls: the per-batch mix only sums lanes < n, so the
    trailing fault word / slot layout never contributes. The ring carries
    a DUMP slot at index APPLY_RING and inactive lanes are routed there by
    the caller — scattering a stale read-back at a real slot instead
    would race an active lane that maps to the same slot (duplicate-index
    .at[].set is order-undefined) and fabricate a divergence. The ring
    write rides the same dispatch, so the apply loop stays one launch per
    fused group with no d2h."""
    fn = _FOLD_RING_CACHE.get(("group", k, n_pad))
    if fn is None:
        import jax
        import jax.numpy as jnp

        from tigerbeetle_tpu.models.ledger import fold_reply_codes, sentinel_jit

        def f(chk, ring, idxs, flat, ns, active):
            flat2 = flat[: k * n_pad].reshape(k, n_pad)

            def body(c, x):
                res, n, a = x
                c2 = jnp.where(a, fold_reply_codes(c, res, n), c)
                return c2, c2

            c2, chain = jax.lax.scan(body, chk, (flat2, ns, active))
            return c2, ring.at[idxs].set(chain)

        fn = _FOLD_RING_CACHE[("group", k, n_pad)] = sentinel_jit(
            f"fold_group_ring_{k}x{n_pad}", f, donate_argnums=(1,)
        )
    return fn


def _fold_ring_fn():
    """Solo-batch fold: chain + one ring write, one dispatch."""
    fn = _FOLD_RING_CACHE.get("solo")
    if fn is None:
        import jax

        from tigerbeetle_tpu.models.ledger import fold_reply_codes, sentinel_jit

        def f(chk, ring, idx, results, n):
            c2 = fold_reply_codes(chk, results, n)
            return c2, ring.at[idx].set(c2)

        fn = _FOLD_RING_CACHE["solo"] = sentinel_jit(
            "fold_ring_solo", f, donate_argnums=(1,)
        )
    return fn


def raise_on_parity_divergence(report: dict) -> None:
    """Hash-log check mode over a finalize() report: a failed run raises
    HashLogDivergence AT the first divergent op when the rings localized
    one (testing/hash_log.py semantics), else a plain AssertionError."""
    if report.get("verified") is not False:
        return
    hl = report.get("hash_log") or {}
    op = hl.get("first_divergent_op")
    if op is not None:
        raise HashLogDivergence(
            op, "device-apply", hl.get("want", 0), hl.get("got", 0)
        )
    raise AssertionError(f"dual-commit parity failed: {report}")


class DualLedger:
    """Replica backend: NativeLedger semantics + an asynchronous device
    apply loop. All reply-serving calls delegate to the native engine; the
    device never blocks (or touches) the reply path."""

    zero_copy_events = True  # both consumers only read the event rows
    # the replica enqueues ops at commit finalize via apply_commit; the
    # execute paths never enqueue. Replica detects the plan by this.
    dual_follower = True

    SHADOW_KEYS = (
        "batches", "groups", "solo", "stage_s", "idle_s", "overlapped",
    )

    def instrument(self, metrics, tracer) -> None:
        """Re-bind onto a shared registry/tracer (the replica's).
        Accumulated values carry over; the apply loop reads
        self.shadow_stats/self.tracer per use. A loop update racing
        the carry-over/rebind window lands in the discarded old group
        and is DROPPED from the new registry — at most one update, and
        instrument() runs at setup before commits flow, so nothing of
        record is lost."""
        for key in self.SHADOW_KEYS:
            metrics.counter(f"shadow.{key}").add(self.shadow_stats[key])
        # rebound on the event loop while the apply thread reads per
        # use — a GIL-atomic reference swap, never a torn value; see the
        # docstring for the (setup-time-only) dropped-update window
        self.metrics = metrics  # vet: handoff
        self.tracer = tracer  # vet: handoff
        # registry-backed StatGroup; Counter.add serializes internally
        self.shadow_stats = metrics.group(  # vet: handoff
            "shadow", self.SHADOW_KEYS
        )
        # gauges bound ONCE (a registry lookup per committed op would
        # tax the hot paths the counters observe — the PR-6 bus
        # lesson); the APPLY thread is the only writer
        self._lag_gauge = metrics.gauge(  # vet: handoff
            "shadow.device_lag_ops"
        )
        self._overlap_gauge = metrics.gauge(  # vet: handoff
            "shadow.device_apply_overlap"
        )
        # device-apply lag lane (latency.py parallel-lane contract):
        # bound once; observed from the APPLY thread only (the
        # Histogram serializes internally)
        self._h_apply_lag = metrics.histogram(  # vet: handoff
            "latency.device_apply_lag_us"
        )
        # device anatomy: opened/stamped/finished on the APPLY thread
        # only (the enqueue stamp arrives by value in the apply
        # tuple); rebinding swaps the whole object — a GIL-atomic
        # reference swap read per run
        self.device_anatomy = DeviceAnatomy(metrics)  # vet: handoff
        # applier throughput surfaces (flight-recorder device columns);
        # written by the apply thread only
        self._g_qdepth = metrics.gauge("device.queue_depth")  # vet: handoff
        self._c_dispatch = metrics.counter("device.dispatches")  # vet: handoff
        # the device ledger's own instrumentation (group staging
        # fence waits + h2d byte counting) reports into the same store
        self.device.instrument(metrics, tracer)

    def __init__(
        self,
        acct_slots_log2: int = 16,
        xfer_slots_log2: int = 20,
        queue_max: int = 256,
        warm_kernels: bool = False,
        lag_window: int = 128,
    ):
        self.native = NativeLedger(acct_slots_log2, xfer_slots_log2)
        # Bounded-lag admission window (ops): apply lag beyond it feeds
        # Replica.ingress_occupancy, so the PR-6 credit regulator (and
        # the bare _on_request cap) throttles ADMISSION instead of the
        # bounded queue's put() eventually stalling the event loop.
        self.lag_window = lag_window
        from tigerbeetle_tpu.models.ledger import DeviceLedger

        process = ConfigProcess(
            account_slots_log2=acct_slots_log2,
            transfer_slots_log2=xfer_slots_log2,
        )
        # Warm the device kernels BEFORE serving (the server path sets
        # warm_kernels): an in-window compile would stall the apply loop
        # until the bounded queue fills and then block the reply path
        # (measured: a 2M-transfer run collapsed from ~960k to ~108k TPS
        # exactly this way). Warming runs BEFORE the real ledger is
        # allocated so the scratch tables never double device memory; with
        # the persistent compilation cache (package __init__) only the
        # first-ever server pays real compiles here — later boots load
        # from disk in seconds.
        if warm_kernels:
            self._warm_device_kernels(process)
        self.device = DeviceLedger(process=process, mode="auto")
        self.device.prefetch_results = False  # NO d2h until finalize()
        self.process = None  # replica duck-typing (native backend shape)
        self.spill = None
        self.hazards = self.device.hazards  # [stats] observability
        # written only by the apply thread; finalize() joins the thread
        # before reading either (join-before-read)
        self._shadow_error: Exception | None = None  # vet: handoff
        self._shadow_batches = 0  # vet: handoff
        # watermarks: _enqueued_op/_enq_ops written by the event
        # loop at apply_commit, read by the apply thread for the lag
        # gauge; _applied_op/_done_ops/_consumed_seq written by the apply
        # thread, read by the event loop (lag/backpressure/drain). All
        # GIL-atomic int flips whose one-iteration staleness only skews a
        # gauge reading. Lag counts ITEMS (one item == one committed
        # create op), not op-number distance — committed non-create ops
        # (lookups, registers) and the op-number jump after a restart
        # never enter the queue and must not read as phantom lag.
        self._enqueued_op = 0  # vet: handoff
        self._applied_op = 0  # vet: handoff
        self._enq_ops = 0  # vet: handoff
        self._done_ops = 0  # vet: handoff
        self._put_seq = 0  # event-loop-only (apply_commit/restore_bytes)
        self._consumed_seq = 0  # vet: handoff
        self._apply_cond = threading.Condition()
        # hash-log rings (APPLY_RING entries): the host ring
        # holds (op, prepare_checksum, native chain value) per applied
        # op; the device ring is its on-device twin, fetched ONCE at
        # finalize. Written only by the apply thread; finalize joins
        # before reading (join-before-read).
        self._op_ring: list = [None] * APPLY_RING  # vet: handoff
        self._dev_ring_out = None  # vet: handoff
        self._chk_native_thread = 0  # vet: handoff
        # test hooks (seeded fault injection for the hash-log check-mode
        # tests): set before traffic flows, read by the apply thread
        self._test_corrupt_apply_op: int | None = None  # vet: handoff
        self._test_apply_delay_s = 0.0  # vet: handoff
        # commitment probes (federation/commitment.py): (op, host
        # fingerprint, LAZY device fingerprint) per checkpoint boundary,
        # appended by the apply thread, materialized + compared at
        # finalize (join-before-read)
        self._probe_out: list = []  # vet: handoff
        # loop cost accounting (the h2d/staging tax shares the core
        # with the reply-serving event loop): stage_s = host time spent
        # staging + dispatching apply work; idle_s = blocked on an empty
        # queue; overlapped = groups whose staging/dispatch completed
        # while the PREVIOUS group's kernel was still executing (the
        # double-buffer pipeline working as intended). Registry-backed
        # (metrics.py StatGroup under `shadow.`): instrument() re-binds
        # onto the replica's shared registry so the [stats] line and the
        # benchmark read the same store.
        self.metrics = Metrics()
        self.tracer = NULL_TRACER
        self.shadow_stats = self.metrics.group("shadow", self.SHADOW_KEYS)
        self._g_qdepth = self.metrics.gauge("device.queue_depth")
        self._c_dispatch = self.metrics.counter("device.dispatches")
        self._lag_gauge = self.metrics.gauge("shadow.device_lag_ops")
        self._overlap_gauge = self.metrics.gauge(
            "shadow.device_apply_overlap"
        )
        self._h_apply_lag = self.metrics.histogram(
            "latency.device_apply_lag_us"
        )
        self.device_anatomy = DeviceAnatomy(self.metrics)
        # the device cannot follow a snapshot that exceeds its geometry:
        # _apply_install sets this and the apply loop drains without
        # applying (finalize reports skipped)
        self._restored = False  # vet: handoff
        # the queue IS the cross-thread handoff (bounded, blocking put)
        self._q: queue.Queue = queue.Queue(maxsize=queue_max)  # vet: handoff
        self._thread = threading.Thread(
            target=self._apply_loop,
            name="device-applier",
            daemon=True,
        )
        self._thread.start()

    def _warm_device_kernels(self, process: ConfigProcess) -> None:
        """Compile the kernel set the apply loop will hit, against a
        SCRATCH ledger of the same geometry (kernels are shared per
        ConfigProcess — models.ledger.get_kernels — so the real ledger
        reuses every compile; scratch state is freed before the real
        tables allocate). Covers: accounts commit, transfers fast tier,
        fast_pv (posts), group steppers (both fused capacities), the
        results summarizer, and the ring fold kernels, all at the wire
        batch pad. Rare tiers (serial residue at odd pads) compile on
        demand — the 256-slot queue absorbs those stalls."""
        import jax
        import jax.numpy as jnp

        from tigerbeetle_tpu import types
        from tigerbeetle_tpu.constants import BATCH_PAD, BENCH_BATCH
        from tigerbeetle_tpu.models.ledger import DeviceLedger

        scratch = DeviceLedger(process=process, mode="auto")
        scratch.prefetch_results = False
        # ~10n transfer rows + n accounts land in the scratch tables; the
        # warm batch shrinks for small-table configs (then it warms a
        # smaller pad — still useful, and the guard never trips)
        n = min(
            BENCH_BATCH,
            scratch._xfer_limit // 12,
            scratch._acct_limit // 2,
        )
        if n < 2:
            return  # simple() needs two distinct accounts (mod n-1)
        # full wire batches pad to BATCH_PAD (the driver's steady state);
        # odd tail sizes compile on demand behind the queue
        if n == BENCH_BATCH:
            scratch.pad_to = BATCH_PAD  # the wire-batch pad the real
            # ledger resolves to for full 8190-event batches
        pad = scratch._pad_for(n)
        ts = 1 << 40

        acct = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
        acct["id_lo"] = np.arange(1, n + 1, dtype=np.uint64)
        acct["ledger"] = 1
        acct["code"] = 1
        ts += n
        scratch.execute_async(Operation.create_accounts, ts, acct)

        def simple(base):
            x = np.zeros(n, dtype=types.TRANSFER_DTYPE)
            x["id_lo"] = np.arange(base, base + n, dtype=np.uint64)
            x["debit_account_id_lo"] = 1 + np.arange(n) % (n - 1)
            x["credit_account_id_lo"] = 1 + (np.arange(n) + 1) % (n - 1)
            x["amount_lo"] = 1
            x["ledger"] = 1
            x["code"] = 1
            return x

        # fast tier + summarizer
        ts += n
        scratch.execute_async(
            Operation.create_transfers, ts, simple(1_000_000)
        )
        # pending batch, then a full post batch -> the fast_pv tier
        pend = simple(2_000_000)
        pend["flags"] = 2
        ts += n
        scratch.execute_async(Operation.create_transfers, ts, pend)
        post = np.zeros(n, dtype=types.TRANSFER_DTYPE)
        post["id_lo"] = np.arange(3_000_000, 3_000_000 + n, dtype=np.uint64)
        post["pending_id_lo"] = pend["id_lo"]
        post["flags"] = 4
        ts += n
        scratch.execute_async(Operation.create_transfers, ts, post)
        # conflict-wave scheduler: a same-batch pend->post batch compiles
        # the scanned 2-wave stepper (the smallest _WAVE_BUCKETS shape) so
        # a dependent-transfer burst doesn't stall the apply loop on a
        # compile; deeper buckets compile on demand behind the queue
        half = n // 2
        if half >= 2:
            wav = simple(5_000_000)
            wav["flags"][:half] = 2  # pendings
            wav["pending_id_lo"][half : 2 * half] = wav["id_lo"][:half]
            wav["debit_account_id_lo"][half : 2 * half] = 0
            wav["credit_account_id_lo"][half : 2 * half] = 0
            wav["amount_lo"][half : 2 * half] = 0
            wav["flags"][half : 2 * half] = 4  # posts of same-batch pendings
            ts += n
            scratch.execute_async(Operation.create_transfers, ts, wav)
        # both fused group capacities (the replica's group commit) + the
        # fused group-fold kernel over each
        scratch_ring = jnp.zeros(APPLY_RING + 1, dtype=jnp.uint64)
        for k in (5, 2):  # 5 -> the 16-slot stepper, 2 -> the 4-slot
            items = []
            for j in range(k):
                ts += n
                items.append((ts, simple(4_000_000 + j * n)))
            pendings = scratch.try_execute_group_async(items)
            if pendings is not None:
                g = pendings[0].group
                ns = np.zeros(g.k, dtype=np.int32)
                ns[:k] = [len(a) for _, a in items]
                active = np.zeros(g.k, dtype=bool)
                active[:k] = True
                idxs = np.arange(g.k, dtype=np.int32)
                _, scratch_ring = _fold_group_ring_fn(g.k, g.n_pad)(
                    jnp.uint64(0), scratch_ring, jnp.asarray(idxs),
                    g.results, jnp.asarray(ns), jnp.asarray(active),
                )
        # the solo fold kernel
        chk, scratch_ring = _fold_ring_fn()(
            jnp.uint64(0), scratch_ring, jnp.int32(0),
            jnp.zeros(pad + 1, dtype=jnp.uint32), jnp.int32(1),
        )
        # block WITHOUT fetching: the applier reads nothing back from
        # the device before finalize
        jax.block_until_ready(chk)
        # compiles past this point are hot-path events (rare tiers and
        # odd pads compile on demand behind the queue — exactly the
        # stalls the sentinel exists to name)
        from tigerbeetle_tpu.models.ledger import COMPILE_SENTINEL

        COMPILE_SENTINEL.mark_warm()

    # -- the device apply loop --------------------------------------------

    def _apply_loop(self) -> None:
        """Items are (op, operation, ts, arr, codes, prepare_checksum,
        trace, lat_ns): the committed op number, the native dense codes,
        the prepare checksum, the op's cluster-causal trace id (tags the
        shadow.upload span) and the sampled enqueue stamp. Control items
        (first element a str) re-seed/reset the device between runs."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from tigerbeetle_tpu.models.ledger import (
            DeviceLedger,
            fold_reply_codes_np,
        )

        chk = jnp.uint64(0)
        chk_nat = 0
        # +1: the DUMP slot inactive group lanes scatter into (see
        # _fold_group_ring_fn); real ops land in [0, APPLY_RING)
        dev_ring = jnp.zeros(APPLY_RING + 1, dtype=jnp.uint64)
        group_max = DeviceLedger.GROUP_KS[0]
        prev_flat = None  # previous fused group's results (overlap probe)
        stop = False

        def note_applied(op: int, n_items: int) -> None:
            self._applied_op = op
            self._done_ops += n_items
            self._lag_gauge.set(max(0, self._enq_ops - self._done_ops))

        def fold_native_run(items) -> None:
            """Chain the native codes + ring entries for a run, in op
            order (runs are consumed in queue order so the chain matches
            the commit stream)."""
            nonlocal chk_nat
            for op2, _o, _t, _a, codes, prep, *_rest in items:
                chk_nat = fold_reply_codes_np(chk_nat, codes)
                self._op_ring[op2 % APPLY_RING] = (op2, prep, chk_nat)

        while not stop:
            t_wait = _time.perf_counter()
            with self.tracer.span("applier.wait_work"):
                run = [self._q.get()]
            self.shadow_stats.add("idle_s", _time.perf_counter() - t_wait)
            if run[0] is _STOP:
                break
            if isinstance(run[0][0], str):  # control item
                kind = run[0][0]
                if kind == _INSTALL:
                    try:
                        chk, chk_nat, dev_ring = self._apply_install(
                            run[0][1], dev_ring
                        )
                    except Exception as e:
                        self._shadow_error = e
                elif kind == _PROBE:
                    try:
                        self._apply_probe(run[0][1], run[0][2])
                    except Exception as e:
                        self._shadow_error = e
                self._consumed_seq += 1
                with self._apply_cond:
                    self._apply_cond.notify_all()
                continue
            # device anatomy: open a record per SAMPLED item (slot 8, the
            # commit path's enqueue stamp) as it leaves the queue — the
            # open closes queue_wait at this item's true dequeue time.
            # Keyed by the cluster trace id when one flows (slot 7), else
            # the op number — trace-id sampling is its own knob, and a
            # live server with tracing off must still decompose (open
            # rejects tid 0). Unsampled items cost one truthiness test.
            anat = self.device_anatomy
            toks = [
                anat.open(run[0][6] or run[0][0], run[0][7])
                if run[0][7] else 0
            ]
            self._g_qdepth.set(self._q.qsize())
            # drain a run of queued create_transfers batches: one fused
            # group dispatch covers up to GROUP_KS[0] of them — per-batch
            # host work (hazard analysis, upload, launch) is the loop's
            # dominant cost on a single-core host, and it shares that core
            # with the reply-serving event loop
            deferred_control = None
            while (
                len(run) < group_max
                and run[-1][1] == Operation.create_transfers
            ):
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is _STOP:
                    stop = True
                    break
                if isinstance(nxt[0], str):
                    # a control item partitions the run: apply the run
                    # first, then handle it below — queue order preserved
                    deferred_control = nxt
                    break
                run.append(nxt)
                toks.append(
                    anat.open(nxt[6] or nxt[0], nxt[7]) if nxt[7] else 0
                )
            if self._test_apply_delay_s:
                _time.sleep(self._test_apply_delay_s)
            if self._shadow_error is not None or self._restored:
                for t in toks:
                    anat.discard(t)
                self._consumed_seq += len(run) + (
                    1 if deferred_control is not None else 0
                )
                note_applied(run[-1][0], len(run))
                with self._apply_cond:
                    self._apply_cond.notify_all()
                continue  # drain without applying; finalize reports why
            any_tok = any(toks)
            try:
                if self._test_corrupt_apply_op is not None:
                    # seeded divergence injection (hash-log check tests):
                    # corrupt the DEVICE applier's view of one op's rows
                    run = [
                        (
                            item
                            if item[0] != self._test_corrupt_apply_op
                            else self._corrupt_item(item)
                        )
                        for item in run
                    ]
                i = 0
                while i < len(run):
                    # longest create_transfers stretch from i
                    j = i
                    while (
                        j < len(run)
                        and run[j][1] == Operation.create_transfers
                    ):
                        j += 1
                    # coalesce_hold closes here for this stretch's sampled
                    # items: the run is assembled and staging begins (a
                    # refused fusion's hazard re-probe counts into the
                    # following dispatch sub-leg)
                    stretch_toks = ()
                    if any_tok:
                        stretch_toks = [
                            t for t in toks[i:j if j > i else i + 1] if t
                        ]
                        if stretch_toks:
                            t_co = _time.perf_counter_ns()
                            for t in stretch_toks:
                                anat.stamp(t, DLEG_COALESCE, t_co)
                    pendings = None
                    if j - i >= 2:
                        t_stage = _time.perf_counter()
                        with self.tracer.span("shadow.upload",
                                              batches=j - i,
                                              trace=run[i][6]):
                            pendings = self.device.try_execute_group_async(
                                [(t, a) for _, _, t, a, *_ in run[i:j]]
                            )
                    if pendings is not None:
                        g = pendings[0].group
                        m = j - i
                        ns = np.zeros(g.k, dtype=np.int32)
                        ns[:m] = [len(a) for _, _, _, a, *_ in run[i:j]]
                        active = np.zeros(g.k, dtype=bool)
                        active[:m] = True
                        # inactive lanes -> the dump slot
                        idxs = np.full(g.k, APPLY_RING, dtype=np.int32)
                        idxs[:m] = [it[0] % APPLY_RING for it in run[i:j]]
                        # two ACTIVE ops in one run congruent mod
                        # APPLY_RING (>4096 non-create ops between them):
                        # duplicate-index scatter is order-undefined, so
                        # route all but the LAST to the dump slot — the
                        # host ring keeps the last op per slot too
                        seen_slots: dict[int, int] = {}
                        for lane in range(m):
                            s_prev = seen_slots.get(int(idxs[lane]))
                            if s_prev is not None:
                                idxs[s_prev] = APPLY_RING
                            seen_slots[int(idxs[lane])] = lane
                        chk, dev_ring = _fold_group_ring_fn(g.k, g.n_pad)(
                            chk, dev_ring, jnp.asarray(idxs), g.results,
                            jnp.asarray(ns), jnp.asarray(active),
                        )
                        fold_native_run(run[i:j])
                        self._shadow_batches += m
                        self._c_dispatch.add()
                        stats = self.shadow_stats
                        stats.add("batches", m)
                        stats.add("groups")
                        stats.add("stage_s", _time.perf_counter() - t_stage)
                        if prev_flat is not None and not prev_flat.is_ready():
                            # this group's staging + dispatch finished
                            # while the previous kernel was still running:
                            # the upload pipeline overlapped execution
                            stats.add("overlapped")
                        self._overlap_gauge.set(round(
                            stats["overlapped"] / stats["groups"], 4
                        ))
                        prev_flat = g.results
                        if stretch_toks:
                            # h2d_stage closes at the ledger's upload-
                            # issued seam; device_busy is fenced on the
                            # fold kernel's chain scalar — blocking is
                            # allowed (no fetch), but it serializes this
                            # sampled run against the device, so the
                            # overlap probe reads ready for ~1/16 of
                            # groups (the sampling tax)
                            h2d_ns = self.device.last_h2d_done_ns
                            t_disp = _time.perf_counter_ns()
                            for t in stretch_toks:
                                if h2d_ns:
                                    anat.stamp(t, DLEG_H2D, h2d_ns)
                                anat.stamp(t, DLEG_DISPATCH, t_disp)
                            with self.tracer.span("applier.fence"):
                                jax.block_until_ready(chk)
                            t_busy = _time.perf_counter_ns()
                            for t in stretch_toks:
                                anat.stamp(t, DLEG_BUSY, t_busy)
                    else:
                        # fusion refused (a batch failed the fast-tier
                        # proof) or too short: run the stretch per-batch —
                        # re-probing fusion at every offset would redo the
                        # vectorized hazard analysis O(k^2) times on the
                        # core the event loop needs. j == i means run[i]
                        # is not create_transfers (accounts): one batch.
                        end = j if j > i else i + 1
                        t_stage = _time.perf_counter()
                        with self.tracer.span("shadow.upload",
                                              batches=end - i, solo=True,
                                              trace=run[i][6]):
                            for op2, opn2, ts2, arr2, *_rest in run[i:end]:
                                pending = self.device.execute_async(
                                    opn2, ts2, arr2
                                )
                                self.device._c_h2d.add(arr2.nbytes)
                                chk, dev_ring = _fold_ring_fn()(
                                    chk, dev_ring,
                                    jnp.int32(op2 % APPLY_RING),
                                    pending.results,
                                    jnp.int32(len(arr2)),
                                )
                                self._shadow_batches += 1
                                self.shadow_stats.add("batches")
                                self.shadow_stats.add("solo")
                        fold_native_run(run[i:end])
                        self.shadow_stats.add(
                            "stage_s", _time.perf_counter() - t_stage)
                        self._c_dispatch.add(end - i)
                        if stretch_toks:
                            # no h2d seam on the per-batch path (the
                            # upload rides the dispatch): h2d_stage folds
                            # as uncrossed, dispatch absorbs it
                            t_disp = _time.perf_counter_ns()
                            for t in stretch_toks:
                                anat.stamp(t, DLEG_DISPATCH, t_disp)
                            with self.tracer.span("applier.fence"):
                                jax.block_until_ready(chk)
                            t_busy = _time.perf_counter_ns()
                            for t in stretch_toks:
                                anat.stamp(t, DLEG_BUSY, t_busy)
                        j = end
                    i = j
            except Exception as e:  # divergence surfaces at finalize
                self._shadow_error = e
            # latency anatomy's device-apply LANE: enqueue (commit
            # finalize, event loop) -> dispatched to the device (all
            # of this run's uploads issued). Sampled ops only (slot 8
            # is 0 otherwise); same perf_counter_ns domain both sides.
            t_done = _time.perf_counter_ns()
            for item in run:
                if item[7]:
                    self._h_apply_lag.observe(
                        (t_done - item[7]) / 1000.0
                    )
            self._consumed_seq += len(run)
            note_applied(run[-1][0], len(run))
            if any_tok:
                # finalize_visible: watermarks/lag gauge updated — the
                # applied op is observable to the event loop
                t_fin = _time.perf_counter_ns()
                for t in toks:
                    if t:
                        anat.finish(t, t_fin)
            if deferred_control is not None:
                if deferred_control[0] == _INSTALL:
                    try:
                        chk, chk_nat, dev_ring = self._apply_install(
                            deferred_control[1], dev_ring
                        )
                    except Exception as e:
                        self._shadow_error = e
                elif deferred_control[0] == _PROBE:
                    try:
                        self._apply_probe(
                            deferred_control[1], deferred_control[2]
                        )
                    except Exception as e:
                        self._shadow_error = e
                self._consumed_seq += 1
            with self._apply_cond:
                self._apply_cond.notify_all()
        # written once at apply-loop exit; finalize() joins before reading
        self._chk_device_scalar = chk  # vet: handoff
        self._chk_native_thread = chk_nat
        self._dev_ring_out = dev_ring

    @staticmethod
    def _corrupt_item(item):
        """Test hook payload: reroute EVERY lane's debit account (or
        ledger) to a nonexistent/invalid value so any valid lane's DEVICE
        reply code diverges from the native engine's (the exact failure
        the hash-log ring must localize). Whole-batch corruption — a
        single-lane flip could land on an event that was already invalid
        and change nothing."""
        op2, opn2, ts2, arr2, codes, prep, tr, lat = item
        bad = arr2.copy()
        if opn2 == Operation.create_transfers:
            bad["debit_account_id_lo"][:] = 0xDEAD_BEEF_DEAD_BEEF
            bad["debit_account_id_hi"][:] = 0xDEAD_BEEF_DEAD_BEEF
        else:
            bad["ledger"][:] = 0  # ledger_must_not_be_zero on valid lanes
        return (op2, opn2, ts2, bad, codes, prep, tr, lat)

    def _apply_install(self, raw: bytes, dev_ring):
        """Handle an _INSTALL control item ON the apply thread: re-seed
        the device tables from a native snapshot's row images
        (DeviceLedger.install_snapshot_rows — h2d only) and reset both
        digest chains/rings: the chains cover the op stream SINCE this
        state, exactly like the native side's restored tables."""
        import jax.numpy as jnp

        # both exits restart the chains/rings from the installed state
        fresh_chains = (
            jnp.uint64(0), 0, jnp.zeros(APPLY_RING + 1, dtype=jnp.uint64),
        )
        accounts, transfers, fulfill, commit_ts = _parse_native_snapshot(raw)
        if (
            len(accounts) > self.device._acct_limit
            or len(transfers) > self.device._xfer_limit
        ):
            # snapshot exceeds the device geometry: stand down (finalize
            # reports skipped) rather than overflow the probe windows
            self._restored = True
            return fresh_chains
        # a mid-run state-sync jump installs onto a device that already
        # holds applied rows: reset to fresh first (claim_slots would
        # otherwise give every already-present key a SECOND slot and the
        # occupancy trackers would double-count)
        self.device.reset_state()
        self.hazards = self.device.hazards  # vet: handoff
        self.device.install_snapshot_rows(
            accounts, transfers, fulfill, commit_ts
        )
        for i in range(APPLY_RING):
            self._op_ring[i] = None
        return fresh_chains

    def _apply_probe(self, op: int, fp_host: dict) -> None:
        """Handle a _PROBE control item ON the apply thread: stash the
        DEVICE state fingerprint at a checkpoint-commitment boundary.
        The probe item was enqueued at the boundary op's commit finalize
        — finalizes run in op order, so every create <= op is already in
        the queue ahead of it and none after it — which makes the lazy
        fingerprint exact for the boundary. Dispatch-only (no d2h): the
        scalars materialize at finalize() alongside the digest rings."""
        if self._restored:
            return
        self._probe_out.append((op, fp_host, self.device.fingerprint_lazy()))

    def _commitment_probe_check(self) -> dict:
        """Materialize the probed device fingerprints (finalize-time d2h,
        a handful of scalars per checkpoint) and compare each against the
        host engine's fingerprint recorded in the commitment chain —
        names the FIRST checkpoint where the device twin's state diverged
        from the committed history."""
        from tigerbeetle_tpu.federation.commitment import FP_FIELDS

        first = None
        detail = {}
        for op, fp_host, fp_dev_lazy in self._probe_out:
            fp_dev = {k: int(np.asarray(v)) for k, v in fp_dev_lazy.items()}
            for k in FP_FIELDS:
                if int(fp_host[k]) != int(fp_dev[k]):
                    if first is None:
                        first = op
                        detail = {
                            "field": k,
                            "host": int(fp_host[k]),
                            "device": int(fp_dev[k]),
                        }
                    break
        return {
            "checked": len(self._probe_out),
            "ok": first is None,
            "first_divergent_op": first,
            **detail,
        }

    # -- apply seam (driven by the replica at commit finalize) -------------

    def apply_commit(
        self,
        op: int,
        operation: Operation,
        timestamp: int,
        arr: np.ndarray,
        codes: np.ndarray,
        prepare_checksum: int = 0,
        trace: int = 0,
        lat_ns: int = 0,
    ) -> None:
        """Enqueue one COMMITTED op for the device applier: called by
        the replica at commit finalize, in op order,
        with the event rows (a read-only view over the prepare body) and
        the native engine's dense reply codes. The bounded queue
        backpressures the event loop only as a last resort — admission
        throttling via apply_lag_excess() engages first. `trace` is the
        op's cluster-causal trace id (vsr/header.py): the apply loop tags
        its shadow.upload span with the run's first id, so the device
        hop joins the op's Perfetto flow. `lat_ns` is the latency
        anatomy's enqueue stamp for SAMPLED ops (perf_counter_ns on the
        event loop): the apply loop observes enqueue->device-dispatch
        into latency.device_apply_lag_us — the dual mode's parallel
        lane, never part of the reply's critical-path legs."""
        self._enqueued_op = op
        self._enq_ops += 1
        self._put_seq += 1
        self._q.put(
            (op, operation, timestamp, arr, codes, prepare_checksum,
             trace, lat_ns)
        )

    def commitment_probe(self, op: int, fp_host: dict) -> None:
        """Enqueue a checkpoint-commitment fingerprint probe: called by
        the replica at the boundary op's commit
        finalize with the HOST engine's fingerprint from the commitment
        chain. The apply thread stashes the device twin's lazy
        fingerprint at the matching point in its queue; finalize()
        compares them per checkpoint."""
        self._put_seq += 1
        self._q.put((_PROBE, op, fp_host))

    def apply_lag_ops(self) -> int:
        """Committed-but-not-yet-device-applied CREATE ops (enqueued
        items minus consumed items — one item per committed create op;
        op-number distance would misread interleaved lookups/registers
        and the post-restart op jump as phantom lag). Applied means
        dispatched to the device: the kernels execute in stream order
        behind it, and nothing on the host ever waits on them."""
        return max(0, self._enq_ops - self._done_ops)

    def apply_lag_excess(self) -> int:
        """Lag beyond the admission window — the saturation signal
        Replica.ingress_occupancy adds to its pipeline occupancy so the
        credit regulator sheds before the apply queue's put() blocks."""
        return max(0, self.apply_lag_ops() - self.lag_window)

    def drain_applier(self, timeout: float = 600.0) -> bool:
        """Block until every enqueued item (ops and control items) has
        been consumed by the apply loop — the checkpoint/state-sync
        barrier. Returns False on timeout or a dead apply thread."""
        import time as _time

        deadline = _time.monotonic() + timeout
        with self._apply_cond:
            while self._consumed_seq < self._put_seq:
                if not self._thread.is_alive():
                    return False
                left = deadline - _time.monotonic()
                if left <= 0 or not self._apply_cond.wait(timeout=min(left, 1.0)):
                    if _time.monotonic() >= deadline:
                        return False
        return True

    # -- backend protocol (reply path: native) ----------------------------

    @property
    def prepare_timestamp(self) -> int:
        return self.native.prepare_timestamp

    @prepare_timestamp.setter
    def prepare_timestamp(self, value: int) -> None:
        self.native.prepare_timestamp = value

    def prepare(self, operation: Operation, event_count: int) -> None:
        self.native.prepare(operation, event_count)

    def execute_async(self, operation, timestamp: int, events):
        # the replica enqueues for the device at commit finalize
        return self.native.execute_async(operation, timestamp, events)

    def try_execute_group_async(self, items):
        return self.native.try_execute_group_async(items)

    def drain(self, pending):
        return self.native.drain(pending)

    def drain_many(self, pendings) -> None:
        self.native.drain_many(pendings)

    def drain_reply(self, pending, operation) -> bytes:
        return self.native.drain_reply(pending, operation)

    def execute_dense(self, operation, timestamp: int, events):
        return self.drain(self.execute_async(operation, timestamp, events))

    def execute(self, operation, timestamp: int, events):
        dense = self.execute_dense(operation, timestamp, events)
        return [(i, c) for i, c in enumerate(dense) if c]

    def lookup_rows(self, operation: Operation, ids) -> bytes:
        return self.native.lookup_rows(operation, ids)

    def lookup_accounts(self, ids):
        return self.native.lookup_accounts(ids)

    def lookup_transfers(self, ids):
        return self.native.lookup_transfers(ids)

    def counts(self) -> dict:
        return self.native.counts()

    @property
    def commit_timestamp(self) -> int:
        return self.native.commit_timestamp

    def fingerprint(self) -> dict:
        """The host engine's state digest (commitment chain input). The
        device twin's fingerprint is compared per checkpoint at
        finalize() via the commitment_probe seam."""
        return self.native.fingerprint()

    def snapshot_bytes(self) -> bytes:
        return self.native.snapshot_bytes()

    def restore_bytes(self, raw: bytes) -> None:
        self.native.restore_bytes(raw)
        # Re-seed the device from the SAME snapshot's row images (the
        # row-level upload path: h2d staging + insert kernels, no
        # d2h) — queued as a control item so it serializes with any
        # in-flight applies; the replica drains the applier before
        # any state-replacing restore (checkpoint/state-sync
        # contract). Digest chains/rings reset with the state.
        if len(raw) <= 64:
            return  # fresh/empty snapshot: nothing to install
        self._put_seq += 1
        self._q.put((_INSTALL, raw))

    # -- shutdown verification --------------------------------------------

    def _shadow_report(self) -> dict:
        """Apply-loop cost/overlap summary for the [stats] line. The
        upload_overlap ratio is the fraction of fused groups whose staging
        + dispatch completed while the previous group's kernel was still
        executing — 1.0 means the h2d path never waited on the device."""
        s = dict(self.shadow_stats)
        s["stage_s"] = round(s["stage_s"], 3)
        s["idle_s"] = round(s["idle_s"], 3)
        s["upload_overlap"] = (
            round(s["overlapped"] / s["groups"], 4) if s["groups"] else None
        )
        s["applied_op"] = self._applied_op
        s["lag_ops"] = self.apply_lag_ops()
        # worst sampled apply items with their sub-leg breakdowns
        # (the commit_wait decomposition, latency.py DeviceAnatomy)
        ds = self.device_anatomy.slowest(4)
        if ds:
            s["device_slowest"] = ds
        return s

    def _hash_ring_check(self) -> dict:
        """Walk the host/device per-op digest rings (one ring fetch — the
        finalize-time d2h) and name the FIRST divergent op, the
        -Dhash-log-mode check across engines."""
        dev = np.asarray(self._dev_ring_out)
        entries = sorted(
            (e for e in self._op_ring if e is not None), key=lambda e: e[0]
        )
        first = None
        want = got = prep = 0
        for op, prep_chk, nat_chk in entries:
            dv = int(dev[op % APPLY_RING])
            if dv != nat_chk:
                first, want, got, prep = op, nat_chk, dv, prep_chk
                break
        return {
            "ops": len(entries),
            "ok": first is None,
            "first_divergent_op": first,
            **(
                # the op's PREPARE checksum ties the divergence back to
                # the consensus stream (hash_log's prepare half): grep it
                # in a --hash-log recording / the WAL to find the exact
                # batch both engines executed
                {"want": want, "got": got, "prepare": f"{prep:#x}"}
                if first is not None else {}
            ),
        }

    def finalize(self, timeout: float = 600.0) -> dict:
        """Drain the apply queue, then do the applier's d2h reads:
        compare the two reply-code digests, the per-op digest rings and
        the two state fingerprints. Returns the verification report the
        server prints on its [stats] line."""
        self._q.put(_STOP)
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            return {"verified": False, "error": "shadow drain timed out",
                    "shadow": self._shadow_report()}
        if self._restored:
            return {
                "verified": None,
                "skipped": "snapshot restore: shadow stood down",
            }
        if self._shadow_error is not None:
            return {
                "verified": False,
                "error": f"{type(self._shadow_error).__name__}: "
                f"{self._shadow_error}",
            }
        try:
            self.device.check_fault()  # deferred fault word: report, not
        except Exception as e:         # crash — the [stats] line must land
            return {
                "verified": False,
                "error": f"{type(e).__name__}: {e}",
            }
        chk_dev = int(np.asarray(self._chk_device_scalar))
        chk_nat = self._chk_native_thread
        fp_nat = self.native.fingerprint()
        fp_dev = self.device.fingerprint()
        ok = (
            chk_nat == chk_dev
            and fp_nat["accounts_fp"] == fp_dev["accounts_fp"]
            and fp_nat["transfers_fp"] == fp_dev["transfers_fp"]
            and fp_nat["accounts"] == fp_dev["accounts"]
            and fp_nat["transfers"] == fp_dev["transfers"]
            and fp_nat["commit_timestamp"] == fp_dev["commit_timestamp"]
        )
        report = {
            "verified": bool(ok),
            "shadow_batches": self._shadow_batches,
            "shadow": self._shadow_report(),
            "code_stream_digest": {"native": chk_nat, "device": chk_dev},
            "fingerprint_native": fp_nat,
            "fingerprint_device": fp_dev,
        }
        report["hash_log"] = self._hash_ring_check()
        if not report["hash_log"]["ok"]:
            report["verified"] = False
        if self._probe_out:
            report["commitments"] = self._commitment_probe_check()
            if not report["commitments"]["ok"]:
                report["verified"] = False
        return report


def _parse_native_snapshot(raw: bytes):
    """Decode the native engine's snapshot blob (native/ledger.cc
    tb_ledger_snapshot layout: 64-byte header, live account rows, live
    transfer rows, posted {ts, val} pairs) into the wire-row arrays +
    per-transfer fulfill column DeviceLedger.install_snapshot_rows
    ingests. Host-side numpy only."""
    from tigerbeetle_tpu import types

    head = np.frombuffer(raw[:64], dtype=np.uint64)
    n_a, n_t, n_p = int(head[0]), int(head[1]), int(head[2])
    commit_ts = int(head[3])
    off = 64
    accounts = np.frombuffer(
        raw[off : off + n_a * 128], dtype=types.ACCOUNT_DTYPE
    )
    off += n_a * 128
    transfers = np.frombuffer(
        raw[off : off + n_t * 128], dtype=types.TRANSFER_DTYPE
    )
    off += n_t * 128
    posted = np.frombuffer(
        raw[off : off + n_p * 16], dtype=np.uint64
    ).reshape(n_p, 2)
    # posted pairs key the PENDING transfer by its timestamp; the device
    # keeps the same fact in the fulfill column 1:1 with transfer rows
    fulfill = np.zeros(n_t, dtype=np.uint32)
    if n_p and n_t:
        order = np.argsort(posted[:, 0])
        pts = posted[order, 0]
        pvals = posted[order, 1]
        idx = np.searchsorted(pts, transfers["timestamp"])
        idxc = np.minimum(idx, len(pts) - 1)
        match = pts[idxc] == transfers["timestamp"]
        fulfill = np.where(match, pvals[idxc], 0).astype(np.uint32)
    return accounts, transfers, fulfill, commit_ts
