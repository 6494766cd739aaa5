"""The device ledger: TigerBeetle's state machine as JAX kernels over HBM.

This is the TPU-native redesign of the reference's hot path (reference:
src/state_machine.zig:508-698 commit/execute): the account and transfer
stores are HBM-resident open-addressing hash tables whose rows ARE the
128-byte wire format (one [capacity+1, 32] u32 array per table — see
ops/hashtable.py for the probe design and why u32 rows are the fast layout
on TPU), and a whole prepare batch commits in one jitted step. Host batches
upload as a single bitcast of the wire bytes.

Two execution tiers, selected ON THE HOST before dispatch (the commit
kernels are straight-line programs — no lax.cond dispatch, no while_loops;
see ops/hashtable.py for why data-dependent control flow is banned. The
one loop whose trip count is not a constant sits AROUND the kernel: the
group stepper runs it once per batch a launch carries, a count the host
passes in — never a value read from the tables):

- **Fast tier (vectorized)**: all lookups, validation, and application run
  data-parallel over the batch. Sound only when the batch is free of serial
  hazards — no linked chains, no post/void or balancing events, no duplicate
  ids, no touched account with balance-limit flags, and no u128 overflow even
  at the batch-final balances (all fast-tier balance deltas are non-negative,
  so per-prefix overflow is impossible iff final overflow is). The HOST
  proves every one of these conditions before choosing this tier — see
  DeviceLedger._transfers_hazard (flags/dups from the batch itself, a
  limit-account id set, and an exact running amount-sum bound for overflow).
  Balance deltas accumulate as 16-bit digits in a persistent
  [capacity+1, 32] u32 scratch (4 balance fields x 8 digits; digit sums of
  <= 2^13 events stay < 2^30), and a touched-slot digit-carry pass folds
  them into the u128 balances — all in u32, no big-array traffic.
- **Serial tier (lax.scan)**: an exact, event-at-a-time kernel with the full
  semantics — linked-chain rollback via an undo log (reference:
  src/state_machine.zig:612-698 + src/lsm/groove.zig:990-1010 scopes),
  two-phase post/void (reference: :907-1014), balancing clamps, in-batch
  duplicate ids.

Between the two sits **conflict-wave scheduling** (HazardTracker.plan +
DeviceLedger._execute_waves): a batch with TRUE dependencies — duplicate
ids, post/voids of same-batch pendings, touches of balance-limit
accounts — is partitioned into dependency-ordered waves, each a masked
fast/fast_pv pass over the same uploaded batch, dispatched in one scanned
launch; only lanes the masked kernels cannot express (linked chains,
balancing, unresolvable pending refs against order-sensitive accounts,
chains deeper than WAVE_CAP) fall to a compacted serial residue. The wave
layout is a deterministic pure function of the batch bytes + tracker
state, so replicas and the simulator plan identically.

Both tiers call the same validation ladders (models/validate.py), so result
codes are bit-exact against the oracle (models/oracle.py) on every path.

**Fault protocol**: probe windows are finite (ops/hashtable.py), so a probe
chain or claim contention can — with ~2^-32 probability per op at the
enforced <= 1/2 load factor — exceed the window. The fast kernel detects
every such case BEFORE writing anything, turns the whole commit into a
no-op, and sets a sticky `fault` word in the state; once fault != 0, every
subsequent commit is also a no-op, so the device state stays exactly as of
the last good batch. The host checks the fault word (per batch on the sync
path, amortized on the async path) and raises. The serial kernel applies
as it scans and cannot un-apply, so its unresolved probes mark the fault
word as corrupting (FAULT_SERIAL) — with the 64-probe scalar window this is
a ~2^-64 event. The reference's analog is its assert-dense ReleaseSafe
discipline (reference: src/tigerbeetle.zig:263-266): fail loudly, never
corrupt silently.

The reference's `posted` groove (reference: src/state_machine.zig:185-198) is
the `fulfill` column alongside the transfer rows (1:1 by construction).
"""

from __future__ import annotations

import threading
from collections import deque
from contextlib import contextmanager
from time import perf_counter_ns, time  # vet: observability-only (compile sentinel)

import jax
import jax.numpy as jnp
import numpy as np

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import (
    DEFAULT_CLUSTER,
    DEFAULT_PROCESS,
    ConfigCluster,
    ConfigProcess,
)
from tigerbeetle_tpu.lsm import groove as groove_fields
from tigerbeetle_tpu.metrics import COMMIT_TIERS, NULL_METRICS
from tigerbeetle_tpu.models import validate
from tigerbeetle_tpu.models.validate import (
    F_BAL_CR,
    F_BAL_DR,
    F_LINKED,
    F_PENDING,
    F_POST,
    F_VOID,
)
from tigerbeetle_tpu.ops import hashtable as ht
from tigerbeetle_tpu.ops import u128
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import Operation

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

# Flags that force the serial tier in the ALL-OR-NOTHING hazard check
# (sharded ledger): linked | post | void | balancing_debit |
# balancing_credit. Only no-flag and pending-only events are fast-tier-safe.
_SLOW_FLAGS = 0b111101


# ----------------------------------------------------------------------
# compile sentinel (every jit entry point in this module and
# dual_ledger.py routes through sentinel_jit)
# ----------------------------------------------------------------------

class CompileSentinel:
    """Process-wide XLA compile observer: every jit entry point wraps in
    a probe that detects executable-cache growth (a compile) and times
    it. A compile landing AFTER `mark_warm()` is a hot-path event — the
    long-documented `.jax_cache` sandbox pathology (a poisoned or absent
    persistent cache recompiling mid-serving) becomes a named counter
    (`device.compiles_post_warmup`) plus a bounded event log the SIGQUIT
    dump and flight recorder surface, instead of an inferred abort.

    Counts accumulate process-wide from import time; `instrument()`
    (called by DeviceLedger/DualLedger.instrument at setup) rebinds onto
    the replica's shared registry and carries the accumulated totals in,
    so warm-up compiles that predate the registry still show. Compiles
    can land on any thread (warm path on main, group steppers on the
    apply thread), hence the lock.  # vet: guarded-by=_lock
    """

    _EVENTS_MAX = 64  # bounded event log (SIGQUIT dump section)

    def __init__(self):
        self._lock = threading.Lock()
        self.metrics = NULL_METRICS
        self.warm = False
        self.total = 0
        self.post_warmup = 0
        self.per_name: dict[str, int] = {}
        self.events: deque = deque(maxlen=self._EVENTS_MAX)
        # persistent-cache story (jax's own monitoring events): of the
        # compile requests that consulted the cache, how many it SERVED
        # (hits) and how many the backend compiled and STORED (misses:
        # programs over the persistence threshold — the ones a warm
        # cache turns into hits; `total` cannot show that, it counts
        # traces, and every process traces anew). The rest of the
        # requests are programs too small to be worth storing.
        self.cache_requests = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._bind(NULL_METRICS)
        jax.monitoring.register_event_listener(self._on_jax_event)

    def _on_jax_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            with self._lock:
                self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.cache_misses += 1

    def _bind(self, m) -> None:
        self._c_total = m.counter("device.compiles")
        self._c_post = m.counter("device.compiles_post_warmup")
        self._h_ms = m.histogram("device.compile_ms")

    def instrument(self, metrics) -> None:
        """Re-bind onto a shared registry (the replica's); process-wide
        totals carry over because the fresh registry starts at zero and
        warm-up compiles predate it."""
        with self._lock:
            self.metrics = metrics
            self._bind(metrics)
            if self.total:
                self._c_total.add(self.total)
            if self.post_warmup:
                self._c_post.add(self.post_warmup)

    def mark_warm(self) -> None:
        """Everything compiled past this point is a hot-path event
        (called after kernel warm-up / at serving start)."""
        with self._lock:
            self.warm = True

    def note(self, name: str, ms: float) -> None:
        with self._lock:
            self.total += 1
            self.per_name[name] = self.per_name.get(name, 0) + 1
            self._c_total.add()
            self._h_ms.observe(ms)
            post = self.warm
            if post:
                self.post_warmup += 1
                self._c_post.add()
            self.events.append({
                "t": round(time(), 3),
                "fn": name,
                "ms": round(ms, 3),
                "post_warmup": post,
            })

    def snapshot(self) -> dict:
        """The [stats]/SIGQUIT section: totals + per-signature counts +
        the bounded event log (newest last)."""
        with self._lock:
            return {
                "total": self.total,
                "post_warmup": self.post_warmup,
                "warm": self.warm,
                "cache_requests": self.cache_requests,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "per_fn": dict(self.per_name),
                "events": list(self.events),
            }


COMPILE_SENTINEL = CompileSentinel()


class _SentinelJit:
    """One jit entry point under the sentinel. The steady-state cost is
    two executable-cache-size probes and one clock read per dispatch —
    noise against a kernel launch. A call that grew the cache compiled:
    its wall duration (trace + lower + compile + first dispatch) is the
    observed compile time."""

    __slots__ = ("fn", "name")

    def __init__(self, fn, name: str):
        self.fn = fn
        self.name = name

    def __call__(self, *args, **kwargs):
        fn = self.fn
        try:
            before = fn._cache_size()
        except Exception:  # not a PjitFunction (test double) — pass through
            return fn(*args, **kwargs)
        t0 = perf_counter_ns()
        out = fn(*args, **kwargs)
        if fn._cache_size() > before:
            COMPILE_SENTINEL.note(self.name, (perf_counter_ns() - t0) / 1e6)
        return out


def sentinel_jit(name: str, fn, **jit_kwargs):
    """jax.jit + compile sentinel — the only way this repo jits."""
    return _SentinelJit(jax.jit(fn, **jit_kwargs), name)

# ----------------------------------------------------------------------
# conflict-wave scheduling (HazardTracker.plan / DeviceLedger._execute_waves)
# ----------------------------------------------------------------------
# Deepest dependency chain the wave path executes; lanes past the cap fall
# to the serial residue (each wave costs a full-batch kernel pass, so past
# ~this depth the exact scan is cheaper anyway).
WAVE_CAP = 24
# Longest-path propagation sweeps before the planner gives up and takes
# the whole-batch serial escape hatch (multi-key entanglement deeper than
# this is adversarial, not a workload).
_WAVE_SWEEPS = 8
# Compiled wave-count variants: a plan's wave count pads up to the next
# bucket with all-false (no-op) masks so the scanned wave stepper compiles
# a handful of shapes, not one per observed depth.
_WAVE_BUCKETS = (2, 3, 4, 6, 8, 12, 16, WAVE_CAP)
_WAVE_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
# Distinct multiplier for the order-sensitive ACCOUNT key namespace; a
# cross-namespace hash collision with an id/pending-id key only ADDS a
# conflict edge (conservative), never drops one.
_WAVE_GOLDEN2 = np.uint64(0xC2B2AE3D27D4EB4F)

ROW_WORDS = 32  # 128-byte wire rows as u32 words

# Equality-query field specs: name -> (first u32 word, word count, halfword)
# — derived from the ONE declaration of the indexed field layouts
# (lsm/groove.py, mirroring the reference's secondary index trees,
# src/state_machine.zig:103-206 ids 1-24) so the device filter scan and the
# LSM index scan can never drift apart per field name.


def _query_words(index_fields) -> dict:
    out = {}
    for name, off, w in index_fields:
        assert off % 4 == 0 and w in (2, 4, 8, 16), (name, off, w)
        out[name] = (off // 4, max(w // 4, 1), w == 2)
    return out


_ACCOUNT_QUERY_WORDS = _query_words(groove_fields.ACCOUNT_INDEX_FIELDS)
_TRANSFER_QUERY_WORDS = _query_words(groove_fields.TRANSFER_INDEX_FIELDS)
# Query replies are message-bounded like every other reply (reference:
# src/state_machine.zig:59-64 — results must fit one message).
QUERY_LIMIT = 8192

# Sticky fault bits (see module docstring "Fault protocol").
FAULT_PROBE = 1  # fast-tier lookup window exhausted (batch was a no-op)
FAULT_CLAIM = 2  # fast-tier claim rounds exhausted (batch was a no-op)
FAULT_OVERFLOW = 4  # device-side overflow backstop tripped (batch was a no-op)
FAULT_SERIAL = 8  # serial-tier probe window exhausted — STATE IS CORRUPT
FAULT_CAPACITY = 16  # device-side load-factor guard tripped (batch no-op)

_FAULT_NAMES = (
    (FAULT_PROBE, "probe-window"),
    (FAULT_CLAIM, "claim-rounds"),
    (FAULT_OVERFLOW, "overflow-backstop"),
    (FAULT_SERIAL, "serial-probe"),
    (FAULT_CAPACITY, "capacity-guard"),
)


def raise_on_fault(fault: int, what: str) -> None:
    """Shared fault-word decoder (single-chip and sharded ledgers)."""
    if not fault:
        return
    bits = [name for bit, name in _FAULT_NAMES if fault & bit]
    corrupt = (
        " (serial tier: device state is CORRUPT)"
        if fault & FAULT_SERIAL
        else " (the faulting batch and everything after were no-ops)"
    )
    raise RuntimeError(
        f"{what} fault {fault:#x} [{', '.join(bits)}]{corrupt}: "
        "grow the table (slots_log2) or lower the load factor"
    )


# ----------------------------------------------------------------------
# state fingerprint + reply-code fold (the dual-commit parity seam)
#
# Order-independent digest over LIVE table rows: sum (mod 2^64) of a
# per-row hash of the 128-byte wire image. The native C++ engine implements
# the IDENTICAL function over its host tables (native/ledger.cc
# tb_ledger_fingerprint), so two parity-locked engines that processed the
# same prepares agree iff their logical row sets are bit-identical —
# regardless of slot layout (device open-addressing vs host table). Any
# constant below changes BOTH implementations or dual-commit verification
# breaks loudly.
# ----------------------------------------------------------------------

_FP_SEED = np.uint64(0x9E3779B97F4A7C15)
_FP_MUL = np.uint64(0xC2B2AE3D27D4EB4F)
_FP_ADD = np.uint64(0x165667B19E3779F9)
_FP_MIX1 = np.uint64(0xFF51AFD7ED558CCD)
_FP_MIX2 = np.uint64(0xC4CEB9FE1A85EC53)


def _fp_mix(x):
    x = (x ^ (x >> jnp.uint64(33))) * _FP_MIX1
    x = (x ^ (x >> jnp.uint64(33))) * _FP_MIX2
    return x ^ (x >> jnp.uint64(33))


def _fp_rows(rows):
    """[S, 32]-u32 table -> (u64 fp sum over live rows, u64 live count).
    Empty (key words all-0) and tombstone (all-0xFFFFFFFF) slots excluded,
    matching the native table's st[] == full predicate."""
    h = jnp.full(rows.shape[0], _FP_SEED, dtype=U64)
    for i in range(ROW_WORDS):
        h = h ^ (rows[:, i].astype(U64) * _FP_MUL)
        h = ((h << jnp.uint64(27)) | (h >> jnp.uint64(37))) * _FP_SEED + _FP_ADD
    h = _fp_mix(h)
    k4 = rows[:, :4]
    empty = (k4 == 0).all(axis=1)
    tomb = (k4 == 0xFFFFFFFF).all(axis=1)
    live = ~empty & ~tomb
    return (
        jnp.sum(jnp.where(live, h, jnp.uint64(0))),
        jnp.sum(live.astype(U64)),
    )


def state_fingerprint(state) -> dict:
    """Jittable digest of the device ledger (dual-commit verification).
    The trailing dump row (masked-scatter target, never read) is excluded —
    it holds garbage by design."""
    afp, alive = _fp_rows(state["acct_rows"][:-1])
    tfp, tlive = _fp_rows(state["xfer_rows"][:-1])
    return {
        "accounts_fp": afp,
        "transfers_fp": tfp,
        "accounts": alive,
        "transfers": tlive,
        "commit_timestamp": state["commit_ts"],
    }


def fold_reply_codes(chk, results, n):
    """Jittable running digest of the dense reply-code stream (the
    hash_log-style shadow check: the dual server folds every shadow batch's
    codes on DEVICE — no d2h — and compares one scalar at shutdown against
    the native engine's host-side fold). `results` is the packed
    [codes(n_pad), fault] vector from execute_async; lanes >= n are
    padding and excluded. Chained: order of batches is captured."""
    lane = jnp.arange(results.shape[0], dtype=jnp.int32)
    m = _fp_mix(
        results.astype(U64) * _FP_MUL
        + lane.astype(U64)
        + jnp.uint64(1)
    )
    batch_h = jnp.sum(jnp.where(lane < n, m, jnp.uint64(0)))
    return _fp_mix(chk ^ (batch_h + jnp.uint64(n).astype(U64)))


def fold_reply_codes_np(chk: int, codes: np.ndarray) -> int:
    """The numpy twin of fold_reply_codes for the native engine's dense
    codes (exact u64 wraparound semantics)."""
    with np.errstate(over="ignore"):
        def mix(x):
            x = (x ^ (x >> np.uint64(33))) * _FP_MIX1
            x = (x ^ (x >> np.uint64(33))) * _FP_MIX2
            return x ^ (x >> np.uint64(33))

        lane = np.arange(len(codes), dtype=np.uint64)
        m = mix(codes.astype(np.uint64) * _FP_MUL + lane + np.uint64(1))
        batch_h = np.sum(m, dtype=np.uint64)
        out = mix(np.uint64(chk) ^ (batch_h + np.uint64(len(codes))))
        return int(out)


def fp_rows_np(rows: np.ndarray) -> tuple:
    """The numpy twin of _fp_rows over 128-byte wire rows (structured
    ACCOUNT_DTYPE/TRANSFER_DTYPE arrays or raw [n, 32]-u32). The per-row
    hash is content-only and the reduction a commutative sum, so the
    oracle computes the same digest from its dict-ordered wire images as
    the device does from its open-addressed slots — this is what lets an
    external CDC consumer recompute checkpoint commitments."""
    if rows.dtype != np.uint32:
        rows = np.ascontiguousarray(rows).view(np.uint32)
    rows = rows.reshape(-1, ROW_WORDS)
    if len(rows) == 0:
        return 0, 0
    with np.errstate(over="ignore"):
        h = np.full(rows.shape[0], _FP_SEED, dtype=np.uint64)
        for i in range(ROW_WORDS):
            h = h ^ (rows[:, i].astype(np.uint64) * _FP_MUL)
            h = ((h << np.uint64(27)) | (h >> np.uint64(37))) * _FP_SEED + _FP_ADD
        h = (h ^ (h >> np.uint64(33))) * _FP_MIX1
        h = (h ^ (h >> np.uint64(33))) * _FP_MIX2
        h = h ^ (h >> np.uint64(33))
        k4 = rows[:, :4]
        live = ~(k4 == 0).all(axis=1) & ~(k4 == 0xFFFFFFFF).all(axis=1)
        return (
            int(np.sum(np.where(live, h, np.uint64(0)), dtype=np.uint64)),
            int(np.sum(live, dtype=np.uint64)),
        )


# ----------------------------------------------------------------------
# wire-row pack/unpack (word offsets = byte offsets / 4 of the extern
# structs, reference: src/tigerbeetle.zig:7-40 Account, :64-89 Transfer)
# ----------------------------------------------------------------------


def _w64(r, i: int):
    return r[..., i].astype(U64) | (r[..., i + 1].astype(U64) << jnp.uint64(32))


def _lohi(x):
    return (x & jnp.uint64(0xFFFFFFFF)).astype(U32), (x >> jnp.uint64(32)).astype(U32)


def unpack_transfer(r) -> dict:
    return {
        "id_lo": _w64(r, 0), "id_hi": _w64(r, 2),
        "dr_lo": _w64(r, 4), "dr_hi": _w64(r, 6),
        "cr_lo": _w64(r, 8), "cr_hi": _w64(r, 10),
        "amt_lo": _w64(r, 12), "amt_hi": _w64(r, 14),
        "pid_lo": _w64(r, 16), "pid_hi": _w64(r, 18),
        "ud128_lo": _w64(r, 20), "ud128_hi": _w64(r, 22),
        "ud64": _w64(r, 24),
        "ud32": r[..., 26],
        "timeout": r[..., 27],
        "ledger": r[..., 28],
        "code": r[..., 29] & jnp.uint32(0xFFFF),
        "flags": r[..., 29] >> jnp.uint32(16),
        "ts": _w64(r, 30),
    }


def pack_transfer(f) -> jnp.ndarray:
    words = []
    for key in ("id", "dr", "cr", "amt", "pid", "ud128"):
        lo0, lo1 = _lohi(f[key + "_lo"])
        hi0, hi1 = _lohi(f[key + "_hi"])
        words += [lo0, lo1, hi0, hi1]
    u0, u1 = _lohi(f["ud64"])
    words += [u0, u1, f["ud32"], f["timeout"], f["ledger"],
              (f["code"] & jnp.uint32(0xFFFF)) | (f["flags"] << jnp.uint32(16))]
    t0, t1 = _lohi(f["ts"])
    words += [t0, t1]
    return jnp.stack(words, axis=-1)


def unpack_account(r) -> dict:
    return {
        "id_lo": _w64(r, 0), "id_hi": _w64(r, 2),
        "dp_lo": _w64(r, 4), "dp_hi": _w64(r, 6),
        "dpo_lo": _w64(r, 8), "dpo_hi": _w64(r, 10),
        "cp_lo": _w64(r, 12), "cp_hi": _w64(r, 14),
        "cpo_lo": _w64(r, 16), "cpo_hi": _w64(r, 18),
        "ud128_lo": _w64(r, 20), "ud128_hi": _w64(r, 22),
        "ud64": _w64(r, 24),
        "ud32": r[..., 26],
        "reserved": r[..., 27],
        "ledger": r[..., 28],
        "code": r[..., 29] & jnp.uint32(0xFFFF),
        "flags": r[..., 29] >> jnp.uint32(16),
        "ts": _w64(r, 30),
    }


def pack_account(f) -> jnp.ndarray:
    words = []
    for key in ("id", "dp", "dpo", "cp", "cpo", "ud128"):
        lo0, lo1 = _lohi(f[key + "_lo"])
        hi0, hi1 = _lohi(f[key + "_hi"])
        words += [lo0, lo1, hi0, hi1]
    u0, u1 = _lohi(f["ud64"])
    words += [u0, u1, f["ud32"], f["reserved"], f["ledger"],
              (f["code"] & jnp.uint32(0xFFFF)) | (f["flags"] << jnp.uint32(16))]
    t0, t1 = _lohi(f["ts"])
    words += [t0, t1]
    return jnp.stack(words, axis=-1)


_TOMB_ROW = np.full(ROW_WORDS, 0xFFFFFFFF, dtype=np.uint32)


def key4_from_fields(f):
    lo0, lo1 = _lohi(f["id_lo"])
    hi0, hi1 = _lohi(f["id_hi"])
    return jnp.stack([lo0, lo1, hi0, hi1], axis=-1)


# ----------------------------------------------------------------------
# state
# ----------------------------------------------------------------------


def init_state(process: ConfigProcess = DEFAULT_PROCESS) -> dict:
    """Allocate the device ledger. Tables have capacity+1 rows: the last row
    is the write dump for masked scatters (never read). `bal_acc` is the
    persistent balance-digit accumulator (all-zero between commits). `fault`
    is the sticky fault word (0 = healthy; see module docstring)."""
    a_rows = (1 << process.account_slots_log2) + 1
    t_rows = (1 << process.transfer_slots_log2) + 1
    return {
        "acct_rows": jnp.zeros((a_rows, ROW_WORDS), dtype=U32),
        "xfer_rows": jnp.zeros((t_rows, ROW_WORDS), dtype=U32),
        "fulfill": jnp.zeros(t_rows, dtype=U32),
        "acct_claim": jnp.full(a_rows, ht.CLAIM_FREE, dtype=U32),
        "xfer_claim": jnp.full(t_rows, ht.CLAIM_FREE, dtype=U32),
        "bal_acc": jnp.zeros((a_rows, ROW_WORDS), dtype=U32),
        "commit_ts": jnp.uint64(0),
        "acct_count": jnp.uint64(0),
        "xfer_count": jnp.uint64(0),
        # ever-applied insert counters (rolled-back inserts INCLUDED: their
        # tombstones still lengthen probe chains) — the DEVICE-side
        # load-factor guard, independent of the host's estimate
        "acct_used_slots": jnp.uint64(0),
        "xfer_used_slots": jnp.uint64(0),
        "fault": jnp.uint32(0),
    }


# ----------------------------------------------------------------------
# host <-> device batch conversion (one bitcast upload)
# ----------------------------------------------------------------------


def _to_rows_np(arr: np.ndarray, n_pad: int) -> np.ndarray:
    out = np.zeros((n_pad, ROW_WORDS), dtype=np.uint32)
    out[: len(arr)] = arr.view(np.uint32).reshape(len(arr), ROW_WORDS)
    return out


def transfers_to_batch(arr: np.ndarray, n_pad: int) -> dict:
    """Wire-format structured array (types.TRANSFER_DTYPE) -> device batch."""
    return {"rows": jnp.asarray(_to_rows_np(arr, n_pad))}


def accounts_to_batch(arr: np.ndarray, n_pad: int) -> dict:
    return {"rows": jnp.asarray(_to_rows_np(arr, n_pad))}


def ids_to_batch(ids: list[int], n_pad: int) -> dict:
    k4 = np.zeros((n_pad, 4), dtype=np.uint32)
    for i, x in enumerate(ids):
        lo, hi = types.split_u128(x)
        k4[i] = (lo & 0xFFFFFFFF, lo >> 32, hi & 0xFFFFFFFF, hi >> 32)
    return {"key4": jnp.asarray(k4)}


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------


def _amount_digits(amt_lo, amt_hi):
    """u128 -> 8 x 16-bit digits (u32 lanes), little-endian."""
    ds = []
    for limb in (amt_lo, amt_hi):
        for j in range(4):
            ds.append(((limb >> jnp.uint64(16 * j)) & jnp.uint64(0xFFFF)).astype(U32))
    return jnp.stack(ds, axis=-1)  # [..., 8]


def _fold_digits(row32, acc32):
    """Fold a [.., 32] digit accumulator into a [.., 32] wire row's 4 balance
    fields (words 4..19) with 16-bit carry propagation. acc lanes: dp digits
    0..7, dpo 8..15, cp 16..23, cpo 24..31. Returns (new_row, overflow)."""
    new_words = [row32[..., i] for i in range(ROW_WORDS)]
    overflow = jnp.zeros(row32.shape[:-1], dtype=bool)
    for field in range(4):  # dp, dpo, cp, cpo at words 4+4f .. 7+4f
        w0 = 4 + 4 * field
        carry = jnp.zeros(row32.shape[:-1], dtype=U32)
        for k in range(4):  # 4 words x two 16-bit digits
            w = row32[..., w0 + k]
            d_lo = acc32[..., 8 * field + 2 * k]
            d_hi = acc32[..., 8 * field + 2 * k + 1]
            s_lo = (w & jnp.uint32(0xFFFF)) + d_lo + carry
            carry = s_lo >> jnp.uint32(16)
            s_hi = (w >> jnp.uint32(16)) + d_hi + carry
            carry = s_hi >> jnp.uint32(16)
            new_words[w0 + k] = (s_lo & jnp.uint32(0xFFFF)) | (s_hi << jnp.uint32(16))
        overflow = overflow | (carry != 0)
    return jnp.stack(new_words, axis=-1), overflow


def _fold_digits_signed(row32, acc32):
    """Signed variant of _fold_digits for the post/void fast tier: the
    accumulator lanes hold mod-2^32 sums of SIGNED 16-bit digits
    (subtractions contribute (-d) mod 2^32). |true sum| <= 8192*65535 <
    2^30, so bitcasting a lane to i32 recovers the exact signed value; the
    fold then runs in i64 with arithmetic-shift carries. A nonzero final
    carry means overflow (positive) or underflow (negative — impossible for
    host-proven batches: every subtraction is a distinct committed pending's
    amount already included in the balance; kept as the device backstop).
    Returns (new_row, bad)."""
    new_words = [row32[..., i] for i in range(ROW_WORDS)]
    bad = jnp.zeros(row32.shape[:-1], dtype=bool)
    I64 = jnp.int64
    for field in range(4):  # dp, dpo, cp, cpo at words 4+4f .. 7+4f
        w0 = 4 + 4 * field
        carry = jnp.zeros(row32.shape[:-1], dtype=I64)
        for k in range(4):
            w = row32[..., w0 + k]
            d_lo = jax.lax.bitcast_convert_type(
                acc32[..., 8 * field + 2 * k], jnp.int32
            ).astype(I64)
            d_hi = jax.lax.bitcast_convert_type(
                acc32[..., 8 * field + 2 * k + 1], jnp.int32
            ).astype(I64)
            s_lo = (w & jnp.uint32(0xFFFF)).astype(I64) + d_lo + carry
            carry = s_lo >> jnp.int64(16)
            s_hi = (w >> jnp.uint32(16)).astype(I64) + d_hi + carry
            carry = s_hi >> jnp.int64(16)
            new_words[w0 + k] = (
                (s_lo & jnp.int64(0xFFFF))
                | ((s_hi & jnp.int64(0xFFFF)) << jnp.int64(16))
            ).astype(U32)
        bad = bad | (carry != 0)
    return jnp.stack(new_words, axis=-1), bad


def _combined_overflow(new_rows_t):
    """Per-lane carry of the COMBINED debits_pending+debits_posted and
    credits_pending+credits_posted sums of folded account rows. Codes 51/52
    guard these sums (reference: src/state_machine.zig:856-861), not just each
    field: a batch mixing pending and posted amounts to one account can
    overflow dp+dpo with neither field's fold carrying. All fast-tier deltas
    are non-negative, so the batch-final combined sums overflow iff some
    prefix does — checking the folded rows is exact."""
    nr = unpack_account(new_rows_t)
    _, _, c_dr = u128.add(nr["dp_lo"], nr["dp_hi"], nr["dpo_lo"], nr["dpo_hi"])
    _, _, c_cr = u128.add(nr["cp_lo"], nr["cp_hi"], nr["cpo_lo"], nr["cpo_hi"])
    return c_dr | c_cr


def build_stored_transfer(e, p, is_pv, amt_lo, amt_hi, ts) -> dict:
    """The row a create_transfers event STORES: post/void events inherit the
    pending's routing fields, default their user data from it, and persist
    the resolved amount (reference: src/state_machine.zig:907-1014). Shared
    by the fast_pv kernel (batched) and the serial scan (per event) so the
    two tiers cannot drift."""

    def dflt128(t_lo, t_hi, q_lo, q_hi):
        z = u128.is_zero(t_lo, t_hi)
        return jnp.where(z, q_lo, t_lo), jnp.where(z, q_hi, t_hi)

    t2_ud128 = dflt128(e["ud128_lo"], e["ud128_hi"], p["ud128_lo"], p["ud128_hi"])
    return {
        "id_lo": e["id_lo"], "id_hi": e["id_hi"],
        "dr_lo": jnp.where(is_pv, p["dr_lo"], e["dr_lo"]),
        "dr_hi": jnp.where(is_pv, p["dr_hi"], e["dr_hi"]),
        "cr_lo": jnp.where(is_pv, p["cr_lo"], e["cr_lo"]),
        "cr_hi": jnp.where(is_pv, p["cr_hi"], e["cr_hi"]),
        "amt_lo": amt_lo, "amt_hi": amt_hi,
        "pid_lo": e["pid_lo"], "pid_hi": e["pid_hi"],
        "ud128_lo": jnp.where(is_pv, t2_ud128[0], e["ud128_lo"]),
        "ud128_hi": jnp.where(is_pv, t2_ud128[1], e["ud128_hi"]),
        "ud64": jnp.where(is_pv & (e["ud64"] == 0), p["ud64"], e["ud64"]),
        "ud32": jnp.where(is_pv & (e["ud32"] == 0), p["ud32"], e["ud32"]),
        "timeout": jnp.where(is_pv, jnp.uint32(0), e["timeout"]),
        "ledger": jnp.where(is_pv, p["ledger"], e["ledger"]),
        "code": jnp.where(is_pv, p["code"], e["code"]),
        "flags": e["flags"],
        "ts": ts,
    }


def _set_ts_words(rows, ts):
    t0, t1 = _lohi(ts)
    return jnp.concatenate(
        [rows[:, :30], t0[:, None], t1[:, None]], axis=1
    )


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------


_KERNELS_CACHE: dict = {}


def get_kernels(process: "ConfigProcess") -> "LedgerKernels":
    """One LedgerKernels per table geometry, process-wide. The kernels are
    stateless (closed over slot counts only); sharing them means a fresh
    DeviceLedger reuses every jitted function's compile cache — a
    median-of-N bench run or a 300-test CI session compiles each kernel
    ONCE instead of once per ledger."""
    k = _KERNELS_CACHE.get(process)
    if k is None:
        k = _KERNELS_CACHE[process] = LedgerKernels(process)
    return k


class LedgerKernels:
    """Compiled commit kernels closed over the table geometry.

    `mode` selects dispatch: "auto" (hazard-predicated lax.cond, production),
    "serial" (always the exact scan; parity testing), "fast" (always the
    vectorized tier; only sound on hazard-free batches — parity testing).
    """

    def __init__(self, process: ConfigProcess = DEFAULT_PROCESS):
        self.process = process
        self.a_log2 = process.account_slots_log2
        self.t_log2 = process.transfer_slots_log2
        # Python ints (embedded as literals) — capturing jnp scalars in the
        # kernels would poison dispatch (see ops/hashtable.py note).
        self.a_dump = 1 << self.a_log2
        self.t_dump = 1 << self.t_log2
        self.commit_transfers = sentinel_jit(
            "commit_transfers", self._commit_transfers,
            static_argnames=("mode",), donate_argnums=(0,),
        )
        self.commit_accounts = sentinel_jit(
            "commit_accounts", self._commit_accounts,
            static_argnames=("mode",), donate_argnums=(0,),
        )
        # Residue entry for the WAVE executor: the serial scan over a
        # compacted hazard residue with explicit per-event timestamps.
        self.commit_transfers_residue = sentinel_jit(
            "commit_transfers_residue",
            lambda state, ev, n: self._serial_transfers_core(
                state, ev["rows"], ev["ts"], n
            ),
            donate_argnums=(0,),
        )
        self.merge_results = sentinel_jit(
            "merge_results",
            lambda r_fast, r_res, idx: r_fast.at[idx].set(r_res, mode="drop"),
        )
        self.lookup_accounts = sentinel_jit("lookup_accounts", self._lookup_accounts)
        self.lookup_transfers = sentinel_jit("lookup_transfers", self._lookup_transfers)
        self._filters: dict = {}  # (table, field) -> jitted filter scan

    # ------------------------------------------------------------------
    # secondary-index queries: the TPU-native analog of the reference's
    # per-field index trees (reference: src/lsm/groove.zig:137-157) over
    # the RESIDENT store is a vectorized filter scan — the whole table is
    # in HBM, so an equality query is one fused compare+compact, no index
    # maintenance on the hot path. (Spilled rows use the LSM index trees,
    # lsm/groove.py; DeviceLedger.query_* merges the two.)
    # ------------------------------------------------------------------

    def filter_scan(self, table: str, field: str):
        """Jitted equality scan over a table: (rows, value_words u32[4]) ->
        (first QUERY_LIMIT matching rows in slot order, total match count)."""
        key = (table, field)
        if key in self._filters:
            return self._filters[key]
        spec = (_ACCOUNT_QUERY_WORDS if table == "acct" else
                _TRANSFER_QUERY_WORDS)[field]
        word0, nwords, halfword = spec
        dump = self.a_dump if table == "acct" else self.t_dump
        K = QUERY_LIMIT

        def scan(rows, val_words):
            occ = ht.occupied_mask(rows).at[dump].set(False)
            if halfword:
                m = (rows[:, word0] & jnp.uint32(0xFFFF)) == val_words[0]
            else:
                m = rows[:, word0] == val_words[0]
                for i in range(1, nwords):
                    m = m & (rows[:, word0 + i] == val_words[i])
            mask = occ & m
            total = jnp.sum(mask.astype(I32))
            rank = jnp.cumsum(mask.astype(I32)) - 1
            pos = jnp.where(mask & (rank < K), rank, K)
            idx = (
                jnp.full(K + 1, dump, dtype=I32)
                .at[pos]
                .set(jnp.arange(rows.shape[0], dtype=I32))[:K]
            )
            return rows[idx], total

        self._filters[key] = sentinel_jit(f"filter_{table}_{field}", scan)
        return self._filters[key]

    # ------------------------------------------------------------------
    # create_transfers
    # ------------------------------------------------------------------

    def _commit_transfers(self, state, ev, n, timestamp, mode: str = "fast"):
        """Returns (state', results u32 [B]). `mode` is chosen by the HOST:
        "fast" for host-proven hazard-free batches, "fast_pv" when the batch
        additionally carries fast-eligible post/void events (distinct,
        registry-known pendings, or waves ordered after their in-batch
        creators — see HazardTracker.plan), "serial" for the exact
        scan.

        What is probed: "fast" looks up each lane's debit and credit account
        (one 2B-lane window lookup) and its transfer id. "fast_pv" first
        looks up each lane's pending_id in the transfer table, then makes the
        SAME one account lookup over each lane's EFFECTIVE accounts — the
        pending's where the lane is a post/void, the event's own elsewhere —
        then the transfer id. A post/void lane never reads its event's own
        accounts: validate.validate_post_void takes no account row ("the
        pending transfer's accounts are not validated — only mutated on
        apply, exactly as the reference"), its codes 27/28 compare ids, and
        the balances it moves are the pending's accounts'."""
        if mode == "serial":
            return self._serial_transfers(state, ev, n, timestamp)
        assert mode in ("fast", "fast_pv"), mode
        pv_mode = mode == "fast_pv"

        rows_b = ev["rows"]
        B = rows_b.shape[0]
        e = unpack_transfer(rows_b)
        lane = jnp.arange(B, dtype=I32)
        valid = lane < n
        if "mask" in ev:  # wave executor: only this wave's lanes are live
            valid = valid & ev["mask"]
        ts_vec = timestamp - n.astype(U64) + lane.astype(U64) + jnp.uint64(1)
        e_a = {**e, "ts": ts_vec}

        acct_rows = state["acct_rows"]
        xfer_rows = state["xfer_rows"]
        dr_k4, cr_k4 = rows_b[:, 4:8], rows_b[:, 8:12]
        if pv_mode:
            # pending-transfer wave: probe the p row (+ its fulfill column)
            # first, because a post/void lane changes p's accounts and never
            # reads its own (validate_post_void's docstring): its account
            # keys below are p's ids. A lane whose p is not found reads the
            # row at p's insert target (empty or tombstone ids: never found,
            # code 25, no write).
            is_pv = (e["flags"] & jnp.uint32(F_POST | F_VOID)) != 0
            p_slot, p_found, p_res = ht.lookup(
                rows_b[:, 16:20], xfer_rows, self.t_log2
            )
            p = unpack_transfer(xfer_rows[p_slot])
            p["fulfill"] = state["fulfill"][p_slot]
            dr_k4 = jnp.where(
                is_pv[:, None],
                key4_from_fields({"id_lo": p["dr_lo"], "id_hi": p["dr_hi"]}),
                dr_k4,
            )
            cr_k4 = jnp.where(
                is_pv[:, None],
                key4_from_fields({"id_lo": p["cr_lo"], "id_hi": p["cr_hi"]}),
                cr_k4,
            )
        # dr and cr probe the same table: fuse into one 2B-lane lookup.
        both_k4 = jnp.concatenate([dr_k4, cr_k4], axis=0)
        both_slot, both_found, both_res = ht.lookup(both_k4, acct_rows, self.a_log2)
        both_rows = acct_rows[both_slot]
        dr_slot, cr_slot = both_slot[:B], both_slot[B:]
        dr_found, cr_found = both_found[:B], both_found[B:]
        dr_row, cr_row = both_rows[:B], both_rows[B:]
        ex_slot, ex_found, ex_res = ht.lookup(rows_b[:, :4], xfer_rows, self.t_log2)
        dr = unpack_account(dr_row)
        cr = unpack_account(cr_row)
        ex = unpack_transfer(xfer_rows[ex_slot])

        r0 = jnp.where(e["ts"] != 0, jnp.uint32(3), jnp.uint32(0))
        r0 = validate.transfer_common(e, r0)
        r, amt_lo, amt_hi = validate.validate_simple_transfer(
            r0, e_a, dr, cr, dr_found, cr_found, ex, ex_found
        )

        # Unresolved probes among lanes that matter -> abort the whole batch
        # (fault protocol; writes below are gated on `proceed`).
        valid2 = jnp.concatenate([valid, valid])
        probe_bad = jnp.any(valid2 & ~both_res) | jnp.any(valid & ~ex_res)

        if pv_mode:
            r_pv, amt_pv_lo, amt_pv_hi = validate.validate_post_void(
                r0, e_a, p, p_found, ex, ex_found
            )
            r = jnp.where(is_pv, r_pv, r)
            amt_lo = jnp.where(is_pv, amt_pv_lo, amt_lo)
            amt_hi = jnp.where(is_pv, amt_pv_hi, amt_hi)
            pvv = valid & is_pv
            probe_bad = probe_bad | jnp.any(pvv & ~p_res)
        else:
            is_pv = jnp.zeros(B, dtype=bool)

        r = jnp.where(valid, r, jnp.uint32(0))
        ok = valid & (r == 0)

        # Claim insert slots (pure claim phase; rows written below, after
        # gating). Keys are batch-unique and absent — host-proven.
        ins_slots, claim, ins_res = ht.claim_slots(
            rows_b[:, :4], ok, xfer_rows, state["xfer_claim"], self.t_log2
        )
        claim_bad = jnp.any(~ins_res)

        # Balance deltas: 16-bit digit scatter-add into the persistent
        # accumulator, then a touched-slot carry fold. acc lane layout:
        # dp 0..7 / dpo 8..15 / cp 16..23 / cpo 24..31.
        digits = _amount_digits(amt_lo, amt_hi)  # [B, 8]
        pending = ((e["flags"] & jnp.uint32(F_PENDING)) != 0)
        zeros8 = jnp.zeros_like(digits)
        if pv_mode:
            # signed digits: post/void SUBTRACTS the pending's amount from
            # the pending balances of the PENDING's accounts, and a post
            # adds the resolved amount to the posted balances
            is_post = is_pv & ((e["flags"] & jnp.uint32(F_POST)) != 0)
            p_digits = _amount_digits(p["amt_lo"], p["amt_hi"])
            neg_p = jnp.zeros_like(p_digits) - p_digits  # mod 2^32
            simple = ~is_pv
            pend8 = jnp.where((simple & pending)[:, None], digits, zeros8) + \
                jnp.where(is_pv[:, None], neg_p, zeros8)
            post8 = jnp.where((simple & ~pending)[:, None], digits, zeros8) + \
                jnp.where(is_post[:, None], digits, zeros8)
        else:
            pend8 = jnp.where(pending[:, None], digits, zeros8)
            post8 = jnp.where(pending[:, None], zeros8, digits)
        upd_dr = jnp.concatenate([pend8, post8, zeros8, zeros8], axis=-1)  # [B,32]
        upd_cr = jnp.concatenate([zeros8, zeros8, pend8, post8], axis=-1)
        slots_t = jnp.concatenate([
            jnp.where(ok, dr_slot, self.a_dump),
            jnp.where(ok, cr_slot, self.a_dump),
        ])
        upd = jnp.concatenate([upd_dr, upd_cr], axis=0)  # [2B, 32]
        acc = state["bal_acc"].at[slots_t].add(upd)
        acc_t = acc[slots_t]  # [2B, 32]
        old_rows_t = jnp.concatenate([dr_row, cr_row], axis=0)
        if pv_mode:
            new_rows_t, over_t = _fold_digits_signed(old_rows_t, acc_t)
        else:
            new_rows_t, over_t = _fold_digits(old_rows_t, acc_t)
        # Device-side backstop for the host's overflow bound (codes 51/52
        # combined-sum carries included — see _combined_overflow).
        over_bad = jnp.any(
            (over_t | _combined_overflow(new_rows_t)) & (slots_t != self.a_dump)
        )
        acc = acc.at[slots_t].set(jnp.zeros_like(upd))  # restore all-zero

        # Device-side load-factor guard (independent of the host estimate:
        # a desynced host must not re-expose unbounded probe densities).
        ok_n = jnp.sum(ok).astype(U64)
        cap_bad = state["xfer_used_slots"] + ok_n > np.uint64(self.t_dump // 2)
        fault = (
            state["fault"]
            | jnp.where(probe_bad, jnp.uint32(FAULT_PROBE), jnp.uint32(0))
            | jnp.where(claim_bad, jnp.uint32(FAULT_CLAIM), jnp.uint32(0))
            | jnp.where(over_bad, jnp.uint32(FAULT_OVERFLOW), jnp.uint32(0))
            | jnp.where(cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0))
        )
        proceed = fault == 0  # sticky: also no-ops every batch after a fault

        # --- application (every write gated on `proceed`) ---
        if pv_mode:
            ins_rows = pack_transfer(
                build_stored_transfer(e, p, is_pv, amt_lo, amt_hi, ts_vec)
            )
        else:
            ins_rows = _set_ts_words(rows_b, ts_vec)
        acct2 = acct_rows.at[jnp.where(proceed, slots_t, self.a_dump)].set(new_rows_t)
        w = jnp.where(proceed & ok, ins_slots, self.t_dump)
        xfer2 = xfer_rows.at[w].set(ins_rows)
        fulfill = state["fulfill"].at[w].set(jnp.uint32(0))
        if pv_mode:
            # mark the pendings resolved (distinct pendings: no conflicts)
            fw = jnp.where(proceed & ok & is_pv, p_slot, self.t_dump)
            fulfill = fulfill.at[fw].set(
                jnp.where(is_post, jnp.uint32(1), jnp.uint32(2))
            )
        applied = proceed & jnp.any(ok)
        # max, not set: wave execution dispatches this kernel out of lane
        # order (a later wave can hold EARLIER lanes), and the split-era
        # residue path already relied on max in the serial scan
        last_ts = jnp.maximum(
            state["commit_ts"], jnp.max(jnp.where(ok, ts_vec, jnp.uint64(0)))
        )
        return {
            **state,
            "acct_rows": acct2,
            "xfer_rows": xfer2,
            "fulfill": fulfill,
            "xfer_claim": claim,
            "bal_acc": acc,
            "commit_ts": jnp.where(applied, last_ts, state["commit_ts"]),
            "xfer_count": state["xfer_count"]
            + jnp.where(proceed, ok_n, jnp.uint64(0)),
            "xfer_used_slots": state["xfer_used_slots"]
            + jnp.where(proceed, ok_n, jnp.uint64(0)),
            "fault": fault,
        }, r

    # -- exact serial tier --

    def _serial_transfers(self, state, ev, n, timestamp):
        rows_b = ev["rows"]
        B = rows_b.shape[0]
        lane = jnp.arange(B, dtype=I32)
        ts_vec = timestamp - n.astype(U64) + lane.astype(U64) + jnp.uint64(1)
        return self._serial_transfers_core(state, rows_b, ts_vec, n)

    def _serial_transfers_core(self, state, rows_b, ts_vec, n):
        """The exact scan. Timestamps are EXPLICIT per event: the full-batch
        path passes timestamp-n+i+1; the wave executor passes the residue
        events' ORIGINAL batch timestamps (compaction must not change them).
        """
        B = rows_b.shape[0]
        lanes = jnp.arange(B, dtype=I32)
        a_dump, t_dump = self.a_dump, self.t_dump
        tomb_row = _TOMB_ROW  # numpy: embeds as a literal
        # Entry gates: sticky fault + the device-side load-factor guard
        # (conservative: charges all n events; the scan applies as it goes
        # and cannot un-apply, so it must not START near the limit).
        cap_bad = state["xfer_used_slots"] + n.astype(U64) > np.uint64(
            self.t_dump // 2
        )
        fault0 = state["fault"] | jnp.where(
            cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0)
        )
        n = jnp.where(fault0 == 0, n, jnp.int32(0))

        undo0 = {
            "kind": jnp.zeros(B, dtype=U32),
            "dr_slot": jnp.zeros(B, dtype=I32),
            "cr_slot": jnp.zeros(B, dtype=I32),
            "t_slot": jnp.zeros(B, dtype=I32),
            "p_slot": jnp.zeros(B, dtype=I32),
            "a_lo": jnp.zeros(B, dtype=U64),
            "a_hi": jnp.zeros(B, dtype=U64),
            "pa_lo": jnp.zeros(B, dtype=U64),
            "pa_hi": jnp.zeros(B, dtype=U64),
        }
        carry0 = (
            state["acct_rows"], state["xfer_rows"], state["fulfill"],
            jnp.zeros(B, dtype=U32),  # results
            undo0,
            jnp.int32(-1),  # chain_start
            jnp.zeros((), dtype=bool),  # chain_broken
            state["commit_ts"],
            jnp.zeros((), dtype=bool),  # unresolved-probe accumulator
        )

        def step(carry, x):
            (acct_rows, xfer_rows, fulfill, results, undo, chain_start,
             chain_broken, commit_ts, probe_bad) = carry
            i, row_e, ts = x
            e = unpack_transfer(row_e)
            active = i < n
            linked = active & ((e["flags"] & jnp.uint32(F_LINKED)) != 0)

            opening = linked & (chain_start < 0)
            chain_start = jnp.where(opening, i, chain_start)
            in_chain = chain_start >= 0
            is_last = i == (n - 1)

            e_a = {**e, "ts": ts}

            lad = validate.Ladder(jnp.uint32(0))
            lad.set(in_chain & is_last & linked, 2)  # linked_event_chain_open
            lad.set(active & chain_broken, 1)  # linked_event_failed
            lad.set(e["ts"] != 0, 3)  # timestamp_must_be_zero
            r0 = validate.transfer_common(e, lad.r)

            k4 = key4_from_fields
            W = ht.WINDOW_SCALAR
            dr_slot, dr_found, res1 = ht.lookup(
                k4({"id_lo": e["dr_lo"], "id_hi": e["dr_hi"]}), acct_rows,
                self.a_log2, window=W,
            )
            cr_slot, cr_found, res2 = ht.lookup(
                k4({"id_lo": e["cr_lo"], "id_hi": e["cr_hi"]}), acct_rows,
                self.a_log2, window=W,
            )
            ex_slot, ex_found, res3 = ht.lookup(
                row_e[:4], xfer_rows, self.t_log2, window=W
            )
            p_slot, p_found, res4 = ht.lookup(
                k4({"id_lo": e["pid_lo"], "id_hi": e["pid_hi"]}), xfer_rows,
                self.t_log2, window=W,
            )
            dr = unpack_account(acct_rows[dr_slot])
            cr = unpack_account(acct_rows[cr_slot])
            ex = unpack_transfer(xfer_rows[ex_slot])
            p = unpack_transfer(xfer_rows[p_slot])
            p["fulfill"] = fulfill[p_slot]
            # The pending transfer's accounts (post/void path); garbage rows
            # when ~p_found, gated by the validator.
            pdr_slot, _, res5 = ht.lookup(
                k4({"id_lo": p["dr_lo"], "id_hi": p["dr_hi"]}), acct_rows,
                self.a_log2, window=W,
            )
            pcr_slot, _, res6 = ht.lookup(
                k4({"id_lo": p["cr_lo"], "id_hi": p["cr_hi"]}), acct_rows,
                self.a_log2, window=W,
            )
            pdr = unpack_account(acct_rows[pdr_slot])
            pcr = unpack_account(acct_rows[pcr_slot])
            probe_bad = probe_bad | (
                active & ~(res1 & res2 & res3 & res4 & res5 & res6)
            )

            is_pv = (e["flags"] & jnp.uint32(F_POST | F_VOID)) != 0
            r_s, amt_s_lo, amt_s_hi = validate.validate_simple_transfer(
                r0, e_a, dr, cr, dr_found, cr_found, ex, ex_found
            )
            r_pv, amt_pv_lo, amt_pv_hi = validate.validate_post_void(
                r0, e_a, p, p_found, ex, ex_found
            )
            r = jnp.where(is_pv, r_pv, r_s)
            r = jnp.where(active, r, jnp.uint32(0))
            ok = active & (r == 0)

            amt_lo = jnp.where(is_pv, amt_pv_lo, amt_s_lo)
            amt_hi = jnp.where(is_pv, amt_pv_hi, amt_s_hi)
            is_post = is_pv & ((e["flags"] & jnp.uint32(F_POST)) != 0)
            is_pending = ~is_pv & ((e["flags"] & jnp.uint32(F_PENDING)) != 0)

            # --- build the row to insert (shared with the fast_pv tier) ---
            ins_row = pack_transfer(
                build_stored_transfer(e, p, is_pv, amt_lo, amt_hi, ts)
            )
            free_slot, free_ok = ht.probe_free(row_e[:4], xfer_rows, self.t_log2)
            probe_bad = probe_bad | (ok & ~free_ok)
            w = jnp.where(ok & free_ok, free_slot, t_dump)
            xfer_rows = xfer_rows.at[w].set(ins_row)
            fulfill = fulfill.at[w].set(jnp.uint32(0))
            fw = jnp.where(ok & is_pv, p_slot, t_dump)
            fulfill = fulfill.at[fw].set(
                jnp.where(is_post, jnp.uint32(1), jnp.uint32(2))
            )

            # --- balance application ---
            tgt_dr_slot = jnp.where(is_pv, pdr_slot, dr_slot)
            tgt_cr_slot = jnp.where(is_pv, pcr_slot, cr_slot)
            tdr = {k: jnp.where(is_pv, pdr[k], dr[k]) for k in dr}
            tcr = {k: jnp.where(is_pv, pcr[k], cr[k]) for k in cr}

            def upd(row_d, bal, add_cond, add_lo, add_hi, sub_cond, sub_lo, sub_hi):
                lo, hi = row_d[bal + "_lo"], row_d[bal + "_hi"]
                a_lo2, a_hi2, _ = u128.add(lo, hi, add_lo, add_hi)
                lo = jnp.where(add_cond, a_lo2, lo)
                hi = jnp.where(add_cond, a_hi2, hi)
                s_lo2, s_hi2, _ = u128.sub(lo, hi, sub_lo, sub_hi)
                lo = jnp.where(sub_cond, s_lo2, lo)
                hi = jnp.where(sub_cond, s_hi2, hi)
                return lo, hi

            false_ = jnp.zeros((), dtype=bool)
            zero64 = jnp.uint64(0)
            dpo_add = (~is_pv & ~is_pending) | is_post
            tdr["dp_lo"], tdr["dp_hi"] = upd(
                tdr, "dp", is_pending, amt_lo, amt_hi, is_pv, p["amt_lo"], p["amt_hi"]
            )
            tdr["dpo_lo"], tdr["dpo_hi"] = upd(
                tdr, "dpo", dpo_add, amt_lo, amt_hi, false_, zero64, zero64
            )
            tcr["cp_lo"], tcr["cp_hi"] = upd(
                tcr, "cp", is_pending, amt_lo, amt_hi, is_pv, p["amt_lo"], p["amt_hi"]
            )
            tcr["cpo_lo"], tcr["cpo_hi"] = upd(
                tcr, "cpo", dpo_add, amt_lo, amt_hi, false_, zero64, zero64
            )
            dw = jnp.where(ok, tgt_dr_slot, a_dump)
            cw = jnp.where(ok, tgt_cr_slot, a_dump)
            acct_rows = acct_rows.at[dw].set(pack_account(tdr))
            acct_rows = acct_rows.at[cw].set(pack_account(tcr))
            # max, not set: the wave executor's earlier waves may already
            # have committed later-lane timestamps
            commit_ts = jnp.where(ok, jnp.maximum(commit_ts, ts), commit_ts)

            # --- undo log entry ---
            kind = jnp.where(
                ~ok,
                jnp.uint32(0),
                jnp.where(
                    is_pv,
                    jnp.where(is_post, jnp.uint32(3), jnp.uint32(4)),
                    jnp.where(is_pending, jnp.uint32(2), jnp.uint32(1)),
                ),
            )
            undo = {
                "kind": undo["kind"].at[i].set(kind),
                "dr_slot": undo["dr_slot"].at[i].set(tgt_dr_slot),
                "cr_slot": undo["cr_slot"].at[i].set(tgt_cr_slot),
                "t_slot": undo["t_slot"].at[i].set(free_slot),
                "p_slot": undo["p_slot"].at[i].set(p_slot),
                "a_lo": undo["a_lo"].at[i].set(amt_lo),
                "a_hi": undo["a_hi"].at[i].set(amt_hi),
                "pa_lo": undo["pa_lo"].at[i].set(p["amt_lo"]),
                "pa_hi": undo["pa_hi"].at[i].set(p["amt_hi"]),
            }

            # --- chain break: roll back [chain_start, i) ---
            break_now = active & (r != 0) & in_chain & ~chain_broken
            lo_k = jnp.where(break_now, chain_start, i)

            def undo_body(k, tabs):
                acct_rows, xfer_rows, fulfill = tabs
                kd = undo["kind"][k]
                applied = kd != 0
                k1, k2 = kd == 1, kd == 2
                k3, k4_ = kd == 3, kd == 4
                drs = undo["dr_slot"][k]
                crs = undo["cr_slot"][k]
                ua_lo, ua_hi = undo["a_lo"][k], undo["a_hi"][k]
                up_lo, up_hi = undo["pa_lo"][k], undo["pa_hi"][k]
                add_p = k3 | k4_
                sub_pend = k2
                sub_post = k1 | k3

                def inv(fields, bal, addc, subc, s_lo, s_hi):
                    lo, hi = fields[bal + "_lo"], fields[bal + "_hi"]
                    a_lo2, a_hi2, _ = u128.add(lo, hi, up_lo, up_hi)
                    lo = jnp.where(addc, a_lo2, lo)
                    hi = jnp.where(addc, a_hi2, hi)
                    s_lo2, s_hi2, _ = u128.sub(lo, hi, s_lo, s_hi)
                    lo = jnp.where(subc, s_lo2, lo)
                    hi = jnp.where(subc, s_hi2, hi)
                    return lo, hi

                fdr = unpack_account(acct_rows[drs])
                fcr = unpack_account(acct_rows[crs])
                fdr["dp_lo"], fdr["dp_hi"] = inv(fdr, "dp", add_p, sub_pend, ua_lo, ua_hi)
                fdr["dpo_lo"], fdr["dpo_hi"] = inv(fdr, "dpo", false_, sub_post, ua_lo, ua_hi)
                fcr["cp_lo"], fcr["cp_hi"] = inv(fcr, "cp", add_p, sub_pend, ua_lo, ua_hi)
                fcr["cpo_lo"], fcr["cpo_hi"] = inv(fcr, "cpo", false_, sub_post, ua_lo, ua_hi)
                dwk = jnp.where(applied, drs, a_dump)
                cwk = jnp.where(applied, crs, a_dump)
                acct_rows = acct_rows.at[dwk].set(pack_account(fdr))
                acct_rows = acct_rows.at[cwk].set(pack_account(fcr))
                twk = jnp.where(applied, undo["t_slot"][k], t_dump)
                xfer_rows = xfer_rows.at[twk].set(tomb_row)
                fwk = jnp.where(k3 | k4_, undo["p_slot"][k], t_dump)
                fulfill = fulfill.at[fwk].set(jnp.uint32(0))
                return acct_rows, xfer_rows, fulfill

            acct_rows, xfer_rows, fulfill = jax.lax.fori_loop(
                lo_k, i, undo_body, (acct_rows, xfer_rows, fulfill)
            )

            results = jnp.where(
                break_now & (lanes >= chain_start) & (lanes < i), jnp.uint32(1), results
            )
            results = results.at[i].set(r)
            chain_broken = chain_broken | break_now
            chain_end = in_chain & (~linked | (r == 2))
            chain_start = jnp.where(chain_end, jnp.int32(-1), chain_start)
            chain_broken = jnp.where(chain_end, False, chain_broken)

            return (
                acct_rows, xfer_rows, fulfill, results, undo,
                chain_start, chain_broken, commit_ts, probe_bad,
            ), None

        (acct_rows, xfer_rows, fulfill, results, undo, _, _, commit_ts,
         probe_bad), _ = jax.lax.scan(step, carry0, (lanes, rows_b, ts_vec))
        ok_n = jnp.sum((results == 0) & (lanes < n)).astype(U64)
        # Ever-applied inserts (rolled-back ones leave tombstones): the
        # undo log's kind stays set through rollback — exactly the count
        # the device-side load guard needs.
        applied_n = jnp.sum((undo["kind"] != 0).astype(U64))
        # commit_ts advanced on at-the-time-ok events and, like the oracle's
        # scopes, is NOT restored by chain rollback — return the carry as-is.
        # An unresolved probe mid-scan cannot be rolled back: FAULT_SERIAL
        # marks the state corrupt (host must discard it).
        return {
            **state,
            "acct_rows": acct_rows,
            "xfer_rows": xfer_rows,
            "fulfill": fulfill,
            "commit_ts": commit_ts,
            "xfer_count": state["xfer_count"] + ok_n,
            "xfer_used_slots": state["xfer_used_slots"] + applied_n,
            "fault": fault0
            | jnp.where(probe_bad, jnp.uint32(FAULT_SERIAL), jnp.uint32(0)),
        }, results

    # ------------------------------------------------------------------
    # create_accounts
    # ------------------------------------------------------------------

    def _commit_accounts(self, state, ev, n, timestamp, mode: str = "fast"):
        if mode == "serial":
            return self._serial_accounts(state, ev, n, timestamp)
        assert mode == "fast", mode

        rows_b = ev["rows"]
        B = rows_b.shape[0]
        e = unpack_account(rows_b)
        lane = jnp.arange(B, dtype=I32)
        valid = lane < n
        ts_vec = timestamp - n.astype(U64) + lane.astype(U64) + jnp.uint64(1)

        ex_slot, ex_found, ex_res = ht.lookup(
            rows_b[:, :4], state["acct_rows"], self.a_log2
        )
        ex = unpack_account(state["acct_rows"][ex_slot])
        r0 = jnp.where(e["ts"] != 0, jnp.uint32(3), jnp.uint32(0))
        r = validate.validate_create_account(r0, e, ex, ex_found)
        r = jnp.where(valid, r, jnp.uint32(0))
        ok = valid & (r == 0)

        probe_bad = jnp.any(valid & ~ex_res)
        ins_slots, claim, ins_res = ht.claim_slots(
            rows_b[:, :4], ok, state["acct_rows"], state["acct_claim"], self.a_log2
        )
        claim_bad = jnp.any(~ins_res)

        ok_n = jnp.sum(ok).astype(U64)
        cap_bad = state["acct_used_slots"] + ok_n > np.uint64(self.a_dump // 2)
        fault = (
            state["fault"]
            | jnp.where(probe_bad, jnp.uint32(FAULT_PROBE), jnp.uint32(0))
            | jnp.where(claim_bad, jnp.uint32(FAULT_CLAIM), jnp.uint32(0))
            | jnp.where(cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0))
        )
        proceed = fault == 0

        ins_rows = _set_ts_words(rows_b, ts_vec)
        w = jnp.where(proceed & ok, ins_slots, self.a_dump)
        acct2 = state["acct_rows"].at[w].set(ins_rows)
        applied = proceed & jnp.any(ok)
        last_ts = jnp.max(jnp.where(ok, ts_vec, jnp.uint64(0)))
        return {
            **state,
            "acct_rows": acct2,
            "acct_claim": claim,
            "commit_ts": jnp.where(applied, last_ts, state["commit_ts"]),
            "acct_count": state["acct_count"]
            + jnp.where(proceed, ok_n, jnp.uint64(0)),
            "acct_used_slots": state["acct_used_slots"]
            + jnp.where(proceed, ok_n, jnp.uint64(0)),
            "fault": fault,
        }, r

    def _serial_accounts(self, state, ev, n, timestamp):
        rows_b = ev["rows"]
        B = rows_b.shape[0]
        lanes = jnp.arange(B, dtype=I32)
        a_dump = self.a_dump
        tomb_row = _TOMB_ROW  # numpy: embeds as a literal
        cap_bad = state["acct_used_slots"] + n.astype(U64) > np.uint64(
            self.a_dump // 2
        )
        fault0 = state["fault"] | jnp.where(
            cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0)
        )
        n = jnp.where(fault0 == 0, n, jnp.int32(0))

        undo0 = {
            "slot": jnp.zeros(B, dtype=I32),
            "kind": jnp.zeros(B, dtype=U32),
        }
        carry0 = (
            state["acct_rows"],
            jnp.zeros(B, dtype=U32),
            undo0,
            jnp.int32(-1),
            jnp.zeros((), dtype=bool),
            state["commit_ts"],
            jnp.zeros((), dtype=bool),  # unresolved-probe accumulator
        )

        def step(carry, x):
            (acct_rows, results, undo, chain_start, chain_broken, commit_ts,
             probe_bad) = carry
            i, row_e = x
            e = unpack_account(row_e)
            active = i < n
            linked = active & ((e["flags"] & jnp.uint32(F_LINKED)) != 0)
            opening = linked & (chain_start < 0)
            chain_start = jnp.where(opening, i, chain_start)
            in_chain = chain_start >= 0
            is_last = i == (n - 1)
            ts = timestamp - n.astype(U64) + i.astype(U64) + jnp.uint64(1)

            lad = validate.Ladder(jnp.uint32(0))
            lad.set(in_chain & is_last & linked, 2)
            lad.set(active & chain_broken, 1)
            lad.set(e["ts"] != 0, 3)

            ex_slot, ex_found, ex_res = ht.lookup(
                row_e[:4], acct_rows, self.a_log2, window=ht.WINDOW_SCALAR
            )
            ex = unpack_account(acct_rows[ex_slot])
            r = validate.validate_create_account(lad.r, e, ex, ex_found)
            r = jnp.where(active, r, jnp.uint32(0))
            ok = active & (r == 0)

            free_slot, free_ok = ht.probe_free(row_e[:4], acct_rows, self.a_log2)
            probe_bad = probe_bad | (active & ~ex_res) | (ok & ~free_ok)
            w = jnp.where(ok & free_ok, free_slot, a_dump)
            t0, t1 = _lohi(ts)
            ins_row = jnp.concatenate([row_e[:30], t0[None], t1[None]])
            acct_rows = acct_rows.at[w].set(ins_row)
            commit_ts = jnp.where(ok, ts, commit_ts)

            undo = {
                "kind": undo["kind"].at[i].set(jnp.where(ok, jnp.uint32(5), jnp.uint32(0))),
                "slot": undo["slot"].at[i].set(free_slot),
            }

            break_now = active & (r != 0) & in_chain & ~chain_broken
            lo_k = jnp.where(break_now, chain_start, i)

            def undo_body(k, acct_rows):
                applied = undo["kind"][k] != 0
                sl = jnp.where(applied, undo["slot"][k], a_dump)
                return acct_rows.at[sl].set(tomb_row)

            acct_rows = jax.lax.fori_loop(lo_k, i, undo_body, acct_rows)
            results = jnp.where(
                break_now & (lanes >= chain_start) & (lanes < i), jnp.uint32(1), results
            )
            results = results.at[i].set(r)
            chain_broken = chain_broken | break_now
            chain_end = in_chain & (~linked | (r == 2))
            chain_start = jnp.where(chain_end, jnp.int32(-1), chain_start)
            chain_broken = jnp.where(chain_end, False, chain_broken)
            return (acct_rows, results, undo, chain_start, chain_broken,
                    commit_ts, probe_bad), None

        (acct_rows, results, undo, _, _, commit_ts, probe_bad), _ = jax.lax.scan(
            step, carry0, (lanes, rows_b)
        )
        ok_n = jnp.sum((results == 0) & (lanes < n)).astype(U64)
        applied_n = jnp.sum((undo["kind"] != 0).astype(U64))
        return {
            **state,
            "acct_rows": acct_rows,
            "commit_ts": commit_ts,
            "acct_count": state["acct_count"] + ok_n,
            "acct_used_slots": state["acct_used_slots"] + applied_n,
            "fault": fault0
            | jnp.where(probe_bad, jnp.uint32(FAULT_SERIAL), jnp.uint32(0)),
        }, results

    # ------------------------------------------------------------------
    # lookups (reference: src/state_machine.zig:701-736)
    # ------------------------------------------------------------------

    def _lookup_accounts(self, state, ids):
        slot, found, res = ht.lookup(ids["key4"], state["acct_rows"], self.a_log2)
        # Per-lane resolve (NOT jnp.all): the padding lanes probe key 0,
        # whose single fixed window can fill with tombstones over time —
        # only the caller knows which lanes were requested.
        return found, state["acct_rows"][slot], res

    def _lookup_transfers(self, state, ids):
        slot, found, res = ht.lookup(ids["key4"], state["xfer_rows"], self.t_log2)
        return found, state["xfer_rows"][slot], res


# ----------------------------------------------------------------------
# Host-facing state machine (the oracle-compatible driver interface)
# ----------------------------------------------------------------------


def _next_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


class WavePlan:
    """Deterministic per-batch conflict-wave layout: `wave_of[i]` is event
    i's wave index (-1 = serial residue). Waves dispatch in index order
    through the masked fast/fast_pv kernel — wave w+1's table lookups see
    wave w's applied state, which is exactly the ordering the conflict
    edges demand — and the compacted residue runs the exact serial scan
    LAST (the entanglement closure proves it shares no ordering key with
    any wave lane, so last is as good as any position). The layout is a
    pure function of the batch bytes plus the tracker's committed-history
    state (no seeds, no wall clock, no unordered iteration), so every
    replica and the simulator plan the same batch identically."""

    __slots__ = ("wave_of", "n_waves", "has_pv", "residue_n")

    def __init__(self, wave_of: np.ndarray, n_waves: int, has_pv: bool):
        self.wave_of = wave_of
        self.n_waves = n_waves
        self.has_pv = has_pv  # any post/void among the wave lanes
        self.residue_n = int((wave_of < 0).sum())


class HazardTracker:
    """Host-side, EXACT fast-tier admission control. Tracks the two facts
    that cannot be read off a batch alone — balance-limit account ids and the
    running amount-sum overflow bound — plus the pending-accounts registry,
    and plans each batch's execution (fast / fast_pv / conflict waves /
    serial; see plan()). Shared by the single-chip DeviceLedger and the
    sharded ledger."""

    def __init__(self):
        # Ids of accounts created with balance-limit flags (account flags are
        # immutable after creation, so membership is stable). Kept as sorted
        # u64 limb columns so the hot-path membership test is vectorized.
        self.limit_account_ids: set[int] = set()
        self._limit_lo = np.empty(0, dtype=np.uint64)
        # Running sum of every transfer amount ever submitted. While this
        # exact upper bound on any balance stays < 2^127, no u128 balance sum
        # can overflow, so overflow codes 47-52 can only arise from per-event
        # validation against pre-batch balances — which the vectorized ladder
        # computes exactly.
        self.amount_sum = 0
        # Conservative superset of pending transfers ever submitted:
        # id -> (debit lo-limb, credit lo-limb). The wave planner needs
        # the accounts a post/void will touch (they are the PENDING's
        # accounts, not the event's own) to order them against
        # order-sensitive (limit/balancing) accounts.
        self.pending_accounts: dict[int, tuple[int, int]] = {}
        # Planner decision counters. New-style keys: fast / fast_pv /
        # serial / waves (batches through the wave path) /
        # wave_dispatches (total waves dispatched) / residue_events /
        # chain_len_max (deepest wave count seen). Legacy keys kept for
        # existing dashboards: every wave batch also counts as split /
        # split_pv (the retired split executor's partial-split buckets),
        # so fast + fast_pv + serial + split + split_pv still sums to
        # batches processed — DEPRECATED, read `waves` instead.
        self.plan_stats = {
            "fast": 0, "fast_pv": 0, "serial": 0, "waves": 0,
            "wave_dispatches": 0, "residue_events": 0, "chain_len_max": 0,
            "split": 0, "split_pv": 0,
        }
        self.bind_counters(NULL_METRICS)

    def bind_counters(self, metrics) -> None:
        """What plan() saw of linked chains, a call (a fuse probe that is
        rolled back counts like `ledger.plan_calls` does): lanes inside a
        chain, and chains (their terminators)."""
        self._c_linked_events = metrics.counter("ledger.linked_events")
        self._c_linked_chains = metrics.counter("ledger.linked_chains")

    @property
    def split_stats(self) -> dict:
        """DEPRECATED compat view: the pre-wave-planner stat surface.
        Same dict as plan_stats (a superset of the legacy keys), so
        `dict(hz.split_stats)` keeps working for every dashboard."""
        return self.plan_stats

    @staticmethod
    def has_dup_ids(arr: np.ndarray) -> bool:
        # Fast path: sort a 64-bit hash-fold of the u128 ids; if no two
        # hashes collide there are certainly no duplicate ids. Only on a
        # hash collision (~B^2/2^64 per batch) fall back to the exact
        # 16-byte comparison. Exact overall, ~15x cheaper than np.unique
        # over 16-byte voids on the hot path.
        with np.errstate(over="ignore"):
            h = arr["id_lo"] ^ (arr["id_hi"] * np.uint64(0x9E3779B97F4A7C15))
        h.sort()
        if not (h[1:] == h[:-1]).any():
            return False
        ids = np.ascontiguousarray(
            np.stack([arr["id_lo"], arr["id_hi"]], axis=1)
        ).view("V16")
        return len(np.unique(ids)) < len(arr)

    @staticmethod
    def _batch_amount_sum(arr: np.ndarray) -> int:
        """Exact u128 sum of every amount in the batch (u64 column sums
        cannot wrap: 2^13 values < 2^32 per 32-bit half)."""
        lo, hi = arr["amount_lo"], arr["amount_hi"]
        return (
            int(np.sum(lo & np.uint64(0xFFFFFFFF), dtype=np.uint64))
            + (int(np.sum(lo >> np.uint64(32), dtype=np.uint64)) << 32)
            + ((int(np.sum(hi & np.uint64(0xFFFFFFFF), dtype=np.uint64))
                + (int(np.sum(hi >> np.uint64(32), dtype=np.uint64)) << 32)) << 64)
        )

    def transfers_hazard(self, arr: np.ndarray) -> bool:
        """True if the batch needs the serial tier (all-or-nothing variant;
        the sharded ledger uses this — the single-chip ledger uses plan()).
        The running amount sum is an upper bound on any balance the store
        can hold: posts move pending to posted, voids remove, balancing
        clamps to available <= sum — counted for EVERY batch."""
        self.amount_sum += self._batch_amount_sum(arr)
        if self.amount_sum >= (1 << 127):
            return True  # conservative: overflow no longer provably impossible
        if (arr["flags"] & _SLOW_FLAGS).any():
            return True
        if self.has_dup_ids(arr):
            return True
        if self.limit_account_ids and self._touches_limit(arr).any():
            return True
        return False

    def accounts_hazard(self, arr: np.ndarray) -> bool:
        if (arr["flags"] & validate.A_LINKED).any():
            return True
        return self.has_dup_ids(arr)

    # ------------------------------------------------------------------
    # the WAVE decision (middle tier): order a batch's TRUE dependencies
    # into waves and close the serial residue under shared ORDERING KEYS
    # only — plain shared accounts commute and create no edges (the
    # split-era account-disjointness invariant is deliberately relaxed);
    # running waves-then-residue preserves exact semantics (see plan())
    # ------------------------------------------------------------------

    def note_pending(self, arr: np.ndarray) -> None:
        pen = (arr["flags"] & np.uint16(F_PENDING)) != 0
        if pen.any():
            for idl, idh, dl, cl in zip(
                arr["id_lo"][pen], arr["id_hi"][pen],
                arr["debit_account_id_lo"][pen],
                arr["credit_account_id_lo"][pen],
            ):
                self.pending_accounts[int(idl) | (int(idh) << 64)] = (
                    int(dl), int(cl),
                )
        # Bound the registry: a pending referenced by a post/void cannot be
        # meaningfully referenced again (idempotency paths fail without
        # touching balances) — evict it; a later stray reference moves that
        # lane to the residue (or the batch to serial), always sound.
        pv = (arr["flags"] & np.uint16(F_POST | F_VOID)) != 0
        if pv.any():
            for pl, ph in zip(
                arr["pending_id_lo"][pv], arr["pending_id_hi"][pv]
            ):
                self.pending_accounts.pop(int(pl) | (int(ph) << 64), None)

    def plan(self, arr: np.ndarray):
        """Per-batch tier decision, the conflict-wave planner: returns
        ("fast"|"fast_pv"|"serial", None) or ("waves", WavePlan).

        A deterministic (seed-free, sorted — a pure function of the batch
        bytes and this tracker's committed-history state) conflict index
        orders only the TRUE dependencies of a batch:

        - same-id groups (duplicate creates: exists-check order);
        - pending-id references (post/void after its in-batch creator;
          competing resolves of one pending in first-wins order);
        - order-sensitive ACCOUNTS: balance-limit accounts (their
          validation reads the running balance) and the accounts of
          balancing lanes (their clamp reads the running balance), so
          every touch of such an account is ordered. Plain hot accounts
          create NO edges — balance adds commute and non-limit validation
          never reads a balance, which is what lets a one-hot-account
          batch run in ~dependency-chain-length waves instead of a
          whole-batch serial scan.

        Lanes the masked fast/fast_pv kernels cannot express — linked
        chains (rollback), balancing (balance-dependent amount), and
        unresolvable pending references when order-sensitive accounts
        exist — form the serial RESIDUE, closed so it shares no ordering
        key with any wave lane (then running it after the waves preserves
        every cross ordering). Post/voids perform no limit checks
        themselves (reference: src/state_machine.zig:907-1014)."""
        # exact overflow bound, counted once per batch (see transfers_hazard)
        self.amount_sum += self._batch_amount_sum(arr)
        st = self.plan_stats
        if self.amount_sum >= (1 << 127):
            st["serial"] += 1
            return "serial", None

        B = len(arr)
        flags = arr["flags"]
        pv = (flags & np.uint16(F_POST | F_VOID)) != 0
        any_pv = bool(pv.any())
        bal = (flags & np.uint16(F_BAL_DR | F_BAL_CR)) != 0
        linked = (flags & np.uint16(F_LINKED)) != 0
        # whole chain runs: a linked run's terminator is the event AFTER it
        in_chain = linked.copy()
        in_chain[1:] |= linked[:-1]
        n_in_chain = int(in_chain.sum())
        self._c_linked_events.add(n_in_chain)
        # a chain's terminator is its one lane that is not itself linked
        self._c_linked_chains.add(n_in_chain - int(linked.sum()))
        residue = in_chain | bal

        with np.errstate(over="ignore"):
            h_id = arr["id_lo"] ^ (arr["id_hi"] * _WAVE_GOLDEN)
        dup = self._dup_groups(h_id)

        # -- fast exits: hazard-free batches pay only what they always paid
        if not residue.any() and not dup.any():
            limit_touch = (
                self._touches_limit(arr)
                if self.limit_account_ids
                else None
            )
            if not any_pv:
                if limit_touch is None or not limit_touch.any():
                    st["fast"] += 1
                    return "fast", None
            else:
                with np.errstate(over="ignore"):
                    hp = arr["pending_id_lo"] ^ (
                        arr["pending_id_hi"] * _WAVE_GOLDEN
                    )
                # distinct pending refs, none created in this batch, no
                # limit-account touches by simple lanes: the whole batch
                # is one fast_pv wave (the kernel reads each pending's
                # truth — row, accounts, fulfill — from the table)
                hpc = hp.copy()
                hpc[~pv] = np.uint64(0) - np.arange(1, B + 1)[~pv].astype(
                    np.uint64
                )
                if (
                    not (self._dup_groups(hpc) & pv).any()
                    and not np.isin(hp[pv], h_id).any()
                    and (limit_touch is None or not (limit_touch & ~pv).any())
                ):
                    st["fast_pv"] += 1
                    return "fast_pv", None

        # -- general path: conflict index over ordering keys --
        with np.errstate(over="ignore"):
            h_pid = arr["pending_id_lo"] ^ (
                arr["pending_id_hi"] * _WAVE_GOLDEN
            )
        pv_idx = np.nonzero(pv)[0]

        # order-sensitive accounts (lo limbs; a collision only ADDS edges)
        sens = [self._limit_lo]
        if bal.any():
            sens.append(arr["debit_account_id_lo"][bal].astype(np.uint64))
            sens.append(arr["credit_account_id_lo"][bal].astype(np.uint64))
        sens_lo = np.unique(np.concatenate(sens))

        # pv lanes mutate their PENDING's accounts, not their own: resolve
        # those targets (registry, else the in-batch creator) so the
        # order-sensitive account edges are complete. Only needed when
        # order-sensitive accounts exist at all — otherwise pv balance
        # effects commute with everything and need no account edges.
        eff_dr = arr["debit_account_id_lo"].astype(np.uint64).copy()
        eff_cr = arr["credit_account_id_lo"].astype(np.uint64).copy()
        if len(pv_idx) and len(sens_lo):
            for i in pv_idx:
                pid = int(arr["pending_id_lo"][i]) | (
                    int(arr["pending_id_hi"][i]) << 64
                )
                if pid in (0, (1 << 128) - 1):
                    eff_dr[i] = 0  # invalid ref: fails with no effect
                    eff_cr[i] = 0
                    continue
                known = self.pending_accounts.get(pid)
                if known is not None:
                    eff_dr[i] = known[0] & ((1 << 64) - 1)
                    eff_cr[i] = known[1] & ((1 << 64) - 1)
                    continue
                cre = np.nonzero(h_id == h_pid[i])[0]
                if len(cre):
                    # in-batch creator(s): take the first's accounts; id-dup
                    # creators that disagree are unresolvable -> residue
                    eff_dr[i] = int(arr["debit_account_id_lo"][cre[0]])
                    eff_cr[i] = int(arr["credit_account_id_lo"][cre[0]])
                    if len(cre) > 1 and (
                        (arr["debit_account_id_lo"][cre] != eff_dr[i]).any()
                        or (arr["credit_account_id_lo"][cre] != eff_cr[i]).any()
                    ):
                        residue[i] = True
                else:
                    # unknown pending (e.g. registry evicted, or created
                    # before a restart): its balance targets cannot be
                    # proven clear of the order-sensitive set
                    eff_dr[i] = 0
                    eff_cr[i] = 0
                    residue[i] = True

        # (lane, key) conflict-edge list. Id keys only for lanes in a
        # duplicate group or referenced by a pv's pending id (a unique,
        # unreferenced id orders nothing).
        dup_or_ref = dup
        if len(pv_idx):
            dup_or_ref = dup | np.isin(h_id, h_pid[pv_idx])
        idk = np.nonzero(dup_or_ref)[0]
        lanes_e = [idk]
        keys_e = [h_id[idk]]
        if len(pv_idx):
            lanes_e.append(pv_idx)
            keys_e.append(h_pid[pv_idx])
        if len(sens_lo):
            with np.errstate(over="ignore"):
                for side in (eff_dr, eff_cr):
                    t_idx = np.nonzero(np.isin(side, sens_lo))[0]
                    if len(t_idx):
                        lanes_e.append(t_idx)
                        keys_e.append(side[t_idx] * _WAVE_GOLDEN2 + np.uint64(1))
        lane_e = np.concatenate(lanes_e)
        key_e = np.concatenate(keys_e)

        # -- residue entanglement closure: a wave lane sharing ANY ordering
        # key with a residue lane joins the residue (it runs LAST; a shared
        # key across that boundary would reorder a true dependency). Plain
        # account collisions never propagate — this closure is what keeps
        # hot accounts on the wave path.
        for _ in range(64):
            if not len(lane_e) or residue.all():
                break
            on_res = residue[lane_e]
            if not on_res.any():
                break
            tainted = np.unique(key_e[on_res])
            move = ~on_res & np.isin(key_e, tainted)
            if not move.any():
                break
            residue[lane_e[move]] = True
        else:
            st["serial"] += 1
            return "serial", None

        wl = ~residue
        if int(wl.sum()) < max(8, B // 8):
            # too little wave work to pay for the extra dispatches
            st["serial"] += 1
            return "serial", None

        # -- wave assignment: longest dependency chain ending at each lane.
        # Within one key group the lanes (in index order) form a chain
        # w'_t = max(w_t, w'_{t-1} + 1) = rank_t + cummax(w_s - rank_s);
        # a sweep applies every group's scan at once and scatter-maxes the
        # results back per lane; sweeps iterate to the multi-key fixpoint.
        wave = np.zeros(B, dtype=np.int64)
        m = wl[lane_e]
        el, ek = lane_e[m], key_e[m]
        if len(el):
            ko = np.lexsort((el, ek))
            el_k, ek_k = el[ko], ek[ko]
            E = len(el_k)
            grp_start = np.ones(E, dtype=bool)
            grp_start[1:] = ek_k[1:] != ek_k[:-1]
            gid = np.cumsum(grp_start) - 1
            pos = np.arange(E, dtype=np.int64)
            rank = pos - pos[grp_start][gid]
            off = gid * np.int64(2 * B + WAVE_CAP + 8)  # isolates groups
            lo_ = np.argsort(el_k, kind="stable")
            el_l = el_k[lo_]
            lane_start = np.ones(E, dtype=bool)
            lane_start[1:] = el_l[1:] != el_l[:-1]
            starts = np.nonzero(lane_start)[0]
            lanes_u = el_l[starts]
            for _ in range(_WAVE_SWEEPS):
                w_k = wave[el_k]
                w2 = rank + np.maximum.accumulate(w_k - rank + off) - off
                red = np.maximum.reduceat(w2[lo_], starts)
                if (red <= wave[lanes_u]).all():
                    break
                wave[lanes_u] = np.maximum(wave[lanes_u], red)
            else:
                st["serial"] += 1  # adversarial entanglement: escape hatch
                return "serial", None
            # depth cap: capped lanes fall to the residue. Sound without
            # re-running the closure — wave numbers are monotone along
            # every key chain, so any lane ordered AFTER a capped lane is
            # itself capped (also residue, in original order), and lanes
            # ordered before run in earlier waves, before the residue.
            over = wl & (wave >= WAVE_CAP)
            if over.any():
                residue |= over
                wl = ~residue
                if int(wl.sum()) < max(8, B // 8):
                    st["serial"] += 1
                    return "serial", None

        n_waves = int(wave[wl].max()) + 1 if wl.any() else 1
        has_res = bool(residue.any())
        if not has_res and n_waves == 1:
            name = "fast_pv" if any_pv else "fast"
            st[name] += 1
            return name, None
        wave_of = np.where(wl, wave, -1).astype(np.int32)
        plan = WavePlan(wave_of, n_waves, bool(pv[wl].any()))
        st["waves"] += 1
        st["wave_dispatches"] += n_waves
        st["residue_events"] += plan.residue_n
        st["chain_len_max"] = max(st["chain_len_max"], n_waves)
        # legacy dashboard keys (deprecated, see plan_stats): EVERY wave
        # batch counts toward split/split_pv so the legacy identity
        # fast + fast_pv + serial + split + split_pv == batches still
        # holds (a residue-free multi-wave batch is still a "partial
        # split" to an old reader: not whole-batch fast, not serial)
        st["split_pv" if plan.has_pv else "split"] += 1
        return "waves", plan

    @staticmethod
    def _dup_groups(h: np.ndarray) -> np.ndarray:
        """Lanes whose hash value occurs more than once (conservative)."""
        B = len(h)
        order = np.argsort(h, kind="stable")
        hs = h[order]
        dup_sorted = np.zeros(B, dtype=bool)
        if B > 1:
            eq = hs[1:] == hs[:-1]
            dup_sorted[1:] |= eq
            dup_sorted[:-1] |= eq
        dup = np.zeros(B, dtype=bool)
        dup[order] = dup_sorted
        return dup

    def _touches_limit(self, arr: np.ndarray) -> np.ndarray:
        lo2 = np.stack([arr["debit_account_id_lo"], arr["credit_account_id_lo"]])
        hi2 = np.stack([arr["debit_account_id_hi"], arr["credit_account_id_hi"]])
        pos = np.searchsorted(self._limit_lo, lo2)
        pos_c = np.minimum(pos, len(self._limit_lo) - 1)
        cand = self._limit_lo[pos_c] == lo2
        out = np.zeros(arr.shape[0], dtype=bool)
        if cand.any():
            for side in range(2):
                for i in np.nonzero(cand[side])[0]:
                    key = int(lo2[side][i]) | (int(hi2[side][i]) << 64)
                    if key in self.limit_account_ids:
                        out[i] = True
        return out

    def note_limit_accounts(self, arr: np.ndarray) -> None:
        limit_bits = validate.A_DR_LIMIT | validate.A_CR_LIMIT
        sel = (arr["flags"] & limit_bits) != 0
        if not sel.any():
            return
        new_lo = []
        for lo, hi in zip(arr["id_lo"][sel], arr["id_hi"][sel]):
            key = int(lo) | (int(hi) << 64)
            if key not in self.limit_account_ids:  # dedup: retries re-submit
                self.limit_account_ids.add(key)
                new_lo.append(lo)
        if new_lo:
            self._limit_lo = np.sort(
                np.concatenate([self._limit_lo, np.array(new_lo, dtype=np.uint64)])
            )


class PendingLookup:
    """Handle for a lookup launched against the tables and not yet read
    back: the kernel's three device outputs and how many lanes were asked
    for. The replica keeps it in its in-flight queue like a PendingBatch
    and finalizes it when `is_ready()` (DeviceLedger.lookup_finish)."""

    __slots__ = ("n", "found", "rows", "resolved")

    def __init__(self, n: int, found, rows, resolved):
        self.n = n
        self.found = found
        self.rows = rows
        self.resolved = resolved

    def is_ready(self) -> bool:
        # one program wrote all three: its largest output stands for them
        return self.rows.is_ready()


class HostLedgerBase:
    """Shared host-side driver surface of the single-chip and sharded
    ledgers: prepare-timestamp bookkeeping (reference:
    src/state_machine.zig:336-343), the lookup wrappers (reference:
    src/state_machine.zig:701-736), the launch bookkeeping (what a commit
    launch carried, its device time, the blocking reply read) and a
    launched batch's way home (`_summarize` at dispatch, `drain` /
    `drain_reply` / `drain_many` later). Subclasses provide `state`,
    `kernels.lookup_accounts/lookup_transfers`, `execute_async` returning a
    PendingBatch, `_uncharge`, optionally `pad_to` and `fault_name`, and
    call `_bind_counters(self.metrics)` when constructed."""

    pad_to: int | None = None
    prepare_timestamp: int = 0

    # observability seams (tigerbeetle_tpu/metrics.py, tracer.py);
    # instrument() re-points them at a shared registry
    metrics = NULL_METRICS
    tracer = NULL_TRACER
    # metrics.LaunchClock, installed by the serving process only
    # (cli.cmd_start): books each launch's device time from a
    # completion thread. None everywhere else — the simulator's
    # seeded runs stay single-threaded.
    launch_clock = None
    # Start each batch's device->host result copy AT DISPATCH so a
    # reply-serving driver (the VSR replica) drains landed buffers
    # instead of paying sync round trips. OPT-IN: a fetch-free
    # driver (the dual backend's applier) must never trigger it.
    prefetch_results = False
    # what a raised fault word calls this ledger
    fault_name = "device ledger"

    def instrument(self, metrics, tracer) -> None:
        self.metrics = metrics
        self.tracer = tracer
        # the compile sentinel rides the same registry rebind (warm-up
        # totals carry over; see CompileSentinel.instrument)
        COMPILE_SENTINEL.instrument(metrics)
        self._bind_counters(metrics)

    def _bind_counters(self, metrics) -> None:
        # the ONE place every backend launches commits through (the dual
        # applier, the device backend's replica, the sharded ledger): what
        # a launch carried, counted where it is made
        self._c_launches = metrics.counter("device.commit_launches")
        self._c_batches = metrics.counter("device.commit_batches")
        self._c_slots = metrics.counter("device.commit_slots")
        self._c_fetch = metrics.counter("loop.fetch_s")
        # which way a drained batch took: the two-word summary proved it
        # all-success, or its dense codes were read
        self._c_drain_all_ok = metrics.counter("ledger.drain_all_ok")
        self._c_drain_dense = metrics.counter("ledger.drain_dense")
        # the tier of each create_transfers batch that is LAUNCHED
        self._c_tier = {
            tier: metrics.counter(f"ledger.tier.{tier}") for tier in COMMIT_TIERS
        }

    def _note_launch(self, handle, t_launch_ns: int, batches: int,
                     slots: int, tier: str | None = None) -> None:
        """`tier`: the planner's decision for a create_transfers launch
        (a fused group is `fast` by construction); None for accounts."""
        self._c_launches.add()
        self._c_batches.add(batches)
        self._c_slots.add(slots)
        if tier is not None:
            self._c_tier[tier].add(batches)
        clock = self.launch_clock
        if clock is not None:
            clock.launched(handle, t_launch_ns, batches, tier)

    def _fetch(self, dev) -> np.ndarray:
        """The blocking device->host read of a commit's reply words: the
        one site where whoever drains (the device backend's event loop)
        waits for the chip."""
        t0 = perf_counter_ns()
        with self.tracer.span("ledger.fetch_replies"):
            host = np.asarray(dev)
        self._c_fetch.add((perf_counter_ns() - t0) / 1e9)
        return host

    # -- a launched batch's way home: summary at dispatch, drain later --

    def _summarize_fn(self):
        """Jitted (results, fault, n) -> (packed results+fault, [count,
        fault]): ONE dispatch for the post-kernel bookkeeping (the previous
        out-of-jit concatenate was its own XLA launch per batch). Cached on
        the SHARED kernels object so fresh ledgers reuse the compile."""
        fn = getattr(self.kernels, "_summarize_cache", None)
        if fn is None:
            def s(results, fault, n):
                res = results.astype(jnp.uint32)
                lane = jnp.arange(res.shape[0], dtype=jnp.int32)
                cnt = jnp.sum(
                    ((res != 0) & (lane < n)).astype(jnp.uint32)
                )
                f = fault.reshape(1).astype(jnp.uint32)
                packed = jnp.concatenate([res, f])
                return packed, jnp.concatenate([cnt.reshape(1), f])

            fn = self.kernels._summarize_cache = sentinel_jit("summarize", s)
        return fn

    def _summarize(self, results, nn):
        """Pack the fault word onto a launch's results, compute the
        device-side failure count, and START the summary's device->host
        copy now: the all-success steady state drains TWO words per batch
        (count + fault) off an already-landed buffer — no dense-codes
        transfer, no per-event host loop, no sync round trip."""
        results, summary = self._summarize_fn()(
            results, self.state["fault"], nn
        )
        if self.prefetch_results:
            try:
                summary.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # no async copy: drain pays the sync cost
        return results, summary

    def _uncharge(self, pending: PendingBatch, not_applied: np.ndarray) -> None:
        """Take a drained batch's not-applied lanes off the occupancy
        charge its dispatch made (+n, conservative). The one thing the two
        ledgers' drains differ in: a scalar on one chip, a count per owner
        shard on a mesh."""
        raise NotImplementedError

    def drain(self, pending: PendingBatch) -> list[int]:
        """Materialize a pending batch's dense result codes; reconciles the
        conservative occupancy charge to the exact ever-applied insert count
        (rolled-back inserts leave tombstones, which still occupy probe
        slots — see applied_insert_mask). Idempotent: a second drain returns
        the cached codes without double-reconciling.

        Fast path: the device-side summary (failure count + fault word —
        a few words, prefetched at dispatch) proves the batch all-success,
        in which case every event applied (applied == n, reconcile is a
        no-op) and the dense codes are all zeros — no codes transfer, no
        per-event host loop."""
        if pending.dense is not None:
            return pending.dense
        if pending.group is not None:
            g = pending.group
            if g.summary is not None:
                s = g.fetch_summary(self._fetch)  # [k counts..., fault]
                fault = int(s[-1])
                if int(s[pending.group_idx]) == 0:
                    return self._drain_all_ok(pending, fault)
            arr = g.fetch(self._fetch)  # one transfer a group (cached)
            off = pending.group_idx * g.n_pad
            codes = arr[off : off + pending.n]
            return self._drain_from_host(pending, codes, int(arr[-1]))
        if pending.summary is not None:
            s = self._fetch(pending.summary)  # [count, fault]
            if int(s[0]) == 0:
                return self._drain_all_ok(pending, int(s[1]))
        arr = self._fetch(pending.results)  # one transfer: results + fault
        return self._drain_from_host(pending, arr[: pending.n], int(arr[-1]))

    def _drain_all_ok(self, pending: PendingBatch, fault: int) -> list[int]:
        raise_on_fault(fault, self.fault_name)
        self._c_drain_all_ok.add()
        pending.failures = 0
        pending.dense = [0] * pending.n
        return pending.dense

    def drain_reply(self, pending: PendingBatch, operation) -> bytes:
        """The reply body bytes (sparse non-ok result structs, reference:
        src/tigerbeetle.zig:231-249) without any per-event Python loop:
        all-success replies are empty by construction, and the failure path
        encodes via vectorized nonzero."""
        self.drain(pending)
        if not pending.failures:
            return b""
        from tigerbeetle_tpu.state_machine import encode_sparse_results

        return encode_sparse_results(pending.codes_np, operation)

    def drain_many(self, pendings) -> None:
        """Materialize a window of pending batches. Each batch's
        device->host copy was started AT DISPATCH (it pipelines right
        behind the commit kernel), so draining the window costs one
        wait for the oldest in-flight copy and the rest read landed
        buffers — NOT one transport round trip per batch. (A device-side
        concat would be worse: a fresh launch + fetch that ignores the
        prefetched copies.)"""
        for p in pendings:
            if p is not None:
                self.drain(p)

    def _drain_from_host(self, pending: PendingBatch, codes,
                         fault: int) -> list[int]:
        raise_on_fault(fault, self.fault_name)
        self._c_drain_dense.add()
        pending.codes_np = np.asarray(codes, dtype=np.uint32)
        pending.failures = int(np.count_nonzero(pending.codes_np))
        dense = [int(x) for x in codes]
        self._uncharge(pending, ~applied_insert_mask(dense, pending.flags))
        # Cache only AFTER the fault check and reconcile: a drain retried
        # after a fault exception must re-raise, not return unsound codes.
        pending.dense = dense
        return dense

    def execute_dense(self, operation, timestamp: int, events) -> list[int]:
        return self.drain(self.execute_async(operation, timestamp, events))

    def prepare(self, operation: Operation, event_count: int) -> None:
        if operation in (Operation.create_accounts, Operation.create_transfers):
            self.prepare_timestamp += event_count

    def _pad_for(self, n: int) -> int:
        return self.pad_to if self.pad_to is not None else _next_pow2(n)

    def _lookup_kernel(self, operation: Operation):
        return (
            self.kernels.lookup_accounts
            if operation == Operation.lookup_accounts
            else self.kernels.lookup_transfers
        )

    def _lookup_launch(self, kernel, ids: list[int]) -> PendingLookup:
        """Launch a lookup against the tables as they stand (after every
        commit dispatched before it, before any dispatched after: one
        device stream). The outputs stay on the device; nothing waits."""
        n = len(ids)
        return PendingLookup(
            n, *kernel(self.state, ids_to_batch(ids, self._pad_for(n)))
        )

    @staticmethod
    def _lookup_finish(pending: PendingLookup):
        """Materialize a launched lookup's requested lanes (blocks until
        the chip has run it)."""
        n = pending.n
        # resolved is a scalar (device kernel: jnp.all over its lanes) or
        # per-lane (sharded kernel) — only the REQUESTED lanes matter: the
        # padding lanes probe key 0, whose single fixed window can fill with
        # tombstones over time.
        res = np.asarray(pending.resolved).reshape(-1)
        if not (res if res.size == 1 else res[:n]).all():
            raise RuntimeError("lookup probe-window overflow: grow the table")
        return np.asarray(pending.found)[:n], np.asarray(pending.rows)[:n]

    def _lookup(self, kernel, ids: list[int]):
        return self._lookup_finish(self._lookup_launch(kernel, ids))

    def lookup_finish(self, pending: PendingLookup) -> bytes:
        """A launched lookup's reply body: found objects' 128-byte wire
        rows, request order, missing skipped (reference:
        src/state_machine.zig:701-736), with no per-row Python object
        round-trip. Blocks until the chip has run it; the probe-window
        overflow raises here."""
        found, rows = self._lookup_finish(pending)
        return rows[found].tobytes()

    def lookup_rows(self, operation: Operation, ids: list[int]) -> bytes:
        """The reply body of a lookup, launched and read back at once."""
        return self.lookup_finish(
            self._lookup_launch(self._lookup_kernel(operation), ids)
        )

    def lookup_accounts(self, ids: list[int]) -> list[types.Account]:
        found, rows = self._lookup(self.kernels.lookup_accounts, ids)
        arr = np.frombuffer(rows.tobytes(), dtype=types.ACCOUNT_DTYPE)
        return [types.Account.from_np(arr[i]) for i in range(len(ids)) if found[i]]

    def lookup_transfers(self, ids: list[int]) -> list[types.Transfer]:
        found, rows = self._lookup(self.kernels.lookup_transfers, ids)
        arr = np.frombuffer(rows.tobytes(), dtype=types.TRANSFER_DTYPE)
        return [types.Transfer.from_np(arr[i]) for i in range(len(ids)) if found[i]]


def applied_insert_mask(dense: list[int], flags: np.ndarray) -> np.ndarray:
    """Which events inserted a row at their turn — INCLUDING inserts later
    rolled back by a chain break (rollback tombstones the slot, and
    tombstones still extend probe chains, so they count toward the non-empty
    slot density that the probe-window math bounds; see the load guard).

    Reconstructs the chain outcomes from the dense result codes: code 1
    (linked_event_failed) is only ever assigned by chain relabel/skip, and a
    broken chain reads [1, 1, .., breaker-code, 1, ..] — members strictly
    before the breaker were applied then rolled back."""
    n = len(dense)
    mask = np.zeros(n, dtype=bool)
    i = 0
    while i < n:
        if not (int(flags[i]) & 1):  # standalone event
            mask[i] = dense[i] == 0
            i += 1
            continue
        j = i  # chain: linked run + its first non-linked member (if any)
        while j < n and (int(flags[j]) & 1):
            j += 1
        end = min(j + 1, n)
        chain = dense[i:end]
        breaker = next((k for k, c in enumerate(chain) if c not in (0, 1)), None)
        if breaker is None:
            for k, c in enumerate(chain):
                mask[i + k] = c == 0
        else:
            mask[i : i + breaker] = True  # applied, then rolled back
        i = end
    return mask


class PendingGroup:
    """One fused device dispatch covering several batches (group commit):
    a single flat results array [k * n_pad + 1] (last word = fault),
    fetched ONCE for the whole group — the per-batch launch + transfer
    latency that dominates a high-latency transport is paid 1/k times.

    `summary` [k + 1] = per-slot failure counts + fault word, computed on
    device: the all-success steady state fetches THESE few words per group
    and never materializes the dense codes at all (the reply body for
    all-ok is empty; reference: src/tigerbeetle.zig:231-249 sparse
    results)."""

    __slots__ = ("results", "n_pad", "k", "host", "summary", "host_summary")

    def __init__(self, results, n_pad: int, k: int, summary=None):
        self.results = results
        self.n_pad = n_pad
        self.k = k
        self.host = None
        self.summary = summary
        self.host_summary = None

    def fetch(self, read=np.asarray):
        if self.host is None:
            self.host = read(self.results)
        return self.host

    def fetch_summary(self, read=np.asarray):
        if self.host_summary is None:
            self.host_summary = read(self.summary)
        return self.host_summary


class PendingBatch:
    """Handle for an asynchronously dispatched commit (results still on
    device). The driver's pipelining unit — the analog of one in-flight
    prepare in the reference's pipeline (reference:
    src/vsr/replica.zig:5102-5186, pipeline_prepare_queue_max=8)."""

    __slots__ = ("operation", "n", "results", "flags", "id_limbs", "dense",
                 "epoch", "group", "group_idx", "summary", "failures",
                 "codes_np", "plan")

    def __init__(self, operation, n, results, flags=None, id_limbs=None,
                 epoch=0, group=None, group_idx=0, summary=None, plan=None):
        self.operation = operation
        self.n = n
        self.results = results  # device u32 [n_pad + 1]; last = fault word
        self.flags = flags  # host u16 [n] (occupancy reconciliation)
        self.id_limbs = id_limbs  # host (lo, hi) u64 [n] (sharded reconcile)
        self.dense = None  # cached drain() result (drain is idempotent)
        self.epoch = epoch  # occupancy epoch at dispatch (spill reconcile)
        self.group = group  # PendingGroup when part of a fused dispatch
        self.group_idx = group_idx  # this batch's row within the group
        self.summary = summary  # device [count, fault]: the cheap drain
        self.failures = None  # failure count once drained
        self.codes_np = None  # dense codes np array (failure path only)
        # wave-planner decision plumbed to the commit dispatcher:
        # (decision str, wave count) for create_transfers, else None
        self.plan = plan


class DeviceLedger(HostLedgerBase):
    """Host wrapper: owns the device state and mirrors the oracle's execute()
    API so the two are drop-in interchangeable in parity tests and in the VSR
    commit path (reference lifecycle: src/state_machine.zig:336-540
    prepare/commit; prefetch is subsumed by HBM residency).

    `mode`:
    - "auto" (production): the host PROVES each batch hazard-free (see
      _transfers_hazard) and dispatches the vectorized kernel, else the exact
      serial kernel. Nothing data-dependent runs on device.
    - "fast" / "serial": force one tier (parity testing).
    """

    def instrument(self, metrics, tracer) -> None:
        super().instrument(metrics, tracer)
        if getattr(self, "spill", None) is not None:
            self.spill.instrument(metrics, tracer)

    def _bind_counters(self, metrics) -> None:
        super()._bind_counters(metrics)
        self._c_h2d = metrics.counter("device.h2d_bytes")
        # the planner: every HazardTracker.plan call (a fuse probe that is
        # rolled back and the solo path's second call count alike) and the
        # pending registry the planner keeps
        self._c_plan_calls = metrics.counter("ledger.plan_calls")
        self._h_plan = metrics.histogram("ledger.plan_us")
        self._c_probe_rejected = metrics.counter("ledger.group_probe_rejected")
        self._g_registry = metrics.gauge("ledger.pending_registry_rows")
        # which way a lookup took: launched and left in the caller's
        # in-flight queue (lookup_async), or answered before returning
        self._c_lookup_deferred = metrics.counter("ledger.lookup_deferred")
        self._c_lookup_inline = metrics.counter("ledger.lookup_inline")
        # a solo launch's jit call alone: on a busy chip it returns only
        # when the launch before it is done, and the event loop waits in it
        self._c_solo_dispatch_us = metrics.counter("ledger.solo_dispatch_us")
        self._c_solo_dispatches = metrics.counter("ledger.solo_dispatches")
        self.hazards.bind_counters(metrics)

    @contextmanager
    def _solo_dispatch(self, tier: str, t_launch_ns: int):
        """Span and clock around ONE solo launch's jit call, nothing else
        of `_solo_launch` inside: what the caller waits for the runtime,
        apart from its own planning, converting and uploading. Timed from
        the launch's dispatch stamp, taken the line before."""
        with self.tracer.span("ledger.solo_dispatch", tier=tier):
            yield
        self._c_solo_dispatch_us.add((perf_counter_ns() - t_launch_ns) / 1e3)
        self._c_solo_dispatches.add()

    def __init__(
        self,
        cluster: ConfigCluster = DEFAULT_CLUSTER,
        process: ConfigProcess = DEFAULT_PROCESS,
        mode: str = "auto",
        forest=None,
        spill_keep_frac: float = 0.25,
        spill_async_io: bool = True,
        spill_io=None,
    ):
        self.cluster = cluster
        self.process = process
        self.mode = mode
        self.kernels = get_kernels(process)
        self.state = init_state(process)
        self.prepare_timestamp = 0
        self.pad_to: int | None = None  # fix the batch pad (bench: 8192)
        # Optional LSM backing store: with a forest attached, the transfer
        # table spills its cold tail instead of raising at the load-factor
        # limit (models/spill.py — the bounded-memory story).
        self.spill = None
        self._occupancy_epoch = 0  # bumped by spill cycles (drain reconcile)
        if forest is not None:
            from tigerbeetle_tpu.models.spill import SpillManager

            # spill_io selects the IO executor behind the spill store:
            # None/"threaded" = real worker thread (production overlap),
            # "deferred" = deterministic event-loop-paced queue (the VSR
            # replica / simulator — see models/spill.py DeferredSpillIO),
            # or an executor instance.
            self.spill = SpillManager(self, forest, keep_frac=spill_keep_frac,
                                      async_io=spill_async_io, io=spill_io)
        # Host-tracked occupancy for the load-factor guard (1/2 max — the
        # probe-window unresolve probability is ~alpha^window, so alpha <= 1/2
        # with window 32 makes window overflow a ~2^-32 event; see
        # ops/hashtable.py). The reference sizes its object pools statically
        # for the same class of reason (reference: src/static_allocator.zig,
        # src/message_pool.zig:18-41).
        self._acct_used = 0
        self._xfer_used = 0
        self._acct_limit = (1 << process.account_slots_log2) // 2
        self._xfer_limit = (1 << process.transfer_slots_log2) // 2
        self.hazards = HazardTracker()
        # device-anatomy h2d seam: try_execute_group_async stamps the
        # upload-issued boundary here; the dual applier reads it to close
        # its h2d_stage sub-leg. Written and read on whichever thread
        # drives dispatch (the apply thread in dual mode), between the
        # dispatch call and its return — never concurrently.
        # vet: owner=device-shadow
        self.last_h2d_done_ns = 0
        self._bind_counters(self.metrics)

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def execute(self, operation, timestamp: int, events: list) -> list[tuple[int, int]]:
        dense = self.execute_dense(operation, timestamp, events)
        return [(i, c) for i, c in enumerate(dense) if c]

    def execute_async(self, operation, timestamp: int, events) -> PendingBatch:
        """Dispatch a commit without any device->host synchronization.
        The caller materializes results later (results stay on device) and
        MUST call check_fault() at least once after the last drain.

        The occupancy guard charges the batch conservatively (+n, an upper
        bound on inserted rows); calling drain() reconciles it to the exact
        ever-applied count. An async driver that never drains keeps the
        conservative estimate — safe (guard can only fire early, never
        late)."""
        with self.tracer.span("ledger.solo_launch", events=len(events),
                              xfer_used=self._xfer_used):
            return self._solo_launch(operation, timestamp, events)

    def _solo_launch(self, operation, timestamp: int, events) -> PendingBatch:
        n = len(events)
        n_pad = self._pad_for(n)
        assert n <= n_pad
        ts = jnp.uint64(timestamp)
        nn = jnp.int32(n)
        decision = None  # the planner's tier: create_transfers only
        if operation == Operation.create_transfers:
            arr = events if isinstance(events, np.ndarray) else types.transfers_to_np(events)
            if self.spill is not None:
                # spill the cold tail / reload referenced spilled rows so
                # the kernels' HBM lookups see the full store (spill.py)
                self.spill.admit(arr, n)
            if self._xfer_used + n > self._xfer_limit:
                raise RuntimeError(
                    f"transfer table at load-factor limit "
                    f"({self._xfer_used}+{n} > {self._xfer_limit}): "
                    "grow ConfigProcess.transfer_slots_log2"
                )
            with self._h_plan.time():
                tok = self.tracer.start("ledger.plan", batches=1)
                try:
                    if self.mode == "auto":
                        decision, wave_plan = self.hazards.plan(arr)
                        self._c_plan_calls.add()
                    else:  # forced tier (parity tests); the amount bound is unused
                        decision, wave_plan = self.mode, None
                    self.hazards.note_pending(arr)
                    self.tracer.annotate(tok, tier=decision)
                finally:
                    self.tracer.stop(tok)
            self._g_registry.set(len(self.hazards.pending_accounts))
            if decision == "waves":
                t_launch = perf_counter_ns()  # the waves upload their own rows
                with self._solo_dispatch(decision, t_launch):
                    results = self._execute_waves(
                        arr, n, n_pad, nn, ts, timestamp, wave_plan
                    )
            else:
                batch = transfers_to_batch(arr, n_pad)
                t_launch = perf_counter_ns()  # rows on their way: kernel next
                with self._solo_dispatch(decision, t_launch):
                    self.state, results = self.kernels.commit_transfers(
                        self.state, batch, nn, ts, mode=decision
                    )
            plan_info = (
                decision, wave_plan.n_waves if wave_plan is not None else 1
            )
            self._xfer_used += n
        elif operation == Operation.create_accounts:
            if self._acct_used + n > self._acct_limit:
                raise RuntimeError(
                    f"account table at load-factor limit "
                    f"({self._acct_used}+{n} > {self._acct_limit}): "
                    "grow ConfigProcess.account_slots_log2"
                )
            arr = events if isinstance(events, np.ndarray) else types.accounts_to_np(events)
            mode = self.mode
            if mode == "auto":
                mode = "serial" if self.hazards.accounts_hazard(arr) else "fast"
            self.hazards.note_limit_accounts(arr)
            batch = accounts_to_batch(arr, n_pad)
            t_launch = perf_counter_ns()
            with self._solo_dispatch("accounts", t_launch):
                self.state, results = self.kernels.commit_accounts(
                    self.state, batch, nn, ts, mode=mode
                )
            plan_info = None
            self._acct_used += n
        else:
            raise AssertionError(operation)
        results, summary = self._summarize(results, nn)
        self._note_launch(results, t_launch, 1, 1, decision)
        return PendingBatch(
            operation, n, results, flags=arr["flags"].copy(),
            epoch=self._occupancy_epoch, summary=summary, plan=plan_info,
        )

    def _wave_stepper(self, W: int, n_pad: int, mode: str):
        """Jitted dispatch of W dependency-ordered waves over ONE uploaded
        batch: a lax.scan over the wave masks traces the commit kernel
        ONCE regardless of W (the _group_stepper lesson), so a multi-wave
        batch pays a single launch, not one per wave. Each lane is active
        in exactly one wave and inactive lanes return code 0, so the
        per-wave results fold with an elementwise max. Cached on the
        SHARED kernels object; W is bucketed by the caller
        (_WAVE_BUCKETS) so only a handful of shapes ever compile."""
        cache = getattr(self.kernels, "_wave_cache", None)
        if cache is None:
            cache = self.kernels._wave_cache = {}
        fn = cache.get((W, n_pad, mode))
        if fn is None:
            kernels = self.kernels

            def step(state, rows, masks, n, timestamp):
                def body(st, mask):
                    st, r = kernels._commit_transfers(
                        st, {"rows": rows, "mask": mask}, n, timestamp,
                        mode=mode,
                    )
                    return st, r.astype(jnp.uint32)

                state, rs = jax.lax.scan(body, state, masks)
                return state, jnp.max(rs, axis=0)

            fn = cache[(W, n_pad, mode)] = sentinel_jit(
                f"wave_stepper_{W}x{n_pad}_{mode}", step, donate_argnums=(0,)
            )
        return fn

    def _execute_waves(self, arr, n, n_pad, nn, ts, timestamp: int, plan):
        """Conflict-scheduled wave execution (the HazardTracker.plan
        layout): the batch uploads ONCE, then the waves dispatch in
        dependency order through the masked fast/fast_pv kernel — wave
        w+1's table lookups see wave w's applied rows, the exact ordering
        the plan's conflict edges require — and the serial residue (if
        any) runs the exact scan COMPACTED (cost scales with residue
        size, not batch size) with its events' ORIGINAL timestamps;
        results scatter back to original lanes."""
        wave_of = plan.wave_of
        W = plan.n_waves
        mode = "fast_pv" if plan.has_pv else "fast"
        rows_dev = jnp.asarray(_to_rows_np(arr, n_pad))
        wl = wave_of[:n] >= 0
        m = self.metrics
        m.counter("waves.batches").add()
        m.histogram("waves.per_batch").observe(W)
        g = m.gauge("waves.chain_len_max")
        g.set(max(g.value, W))
        m.gauge("waves.occupancy").set(
            round(float(wl.sum()) / max(1, W * n), 4)
        )
        if W == 1:
            mask_np = np.zeros(n_pad, dtype=bool)
            mask_np[:n] = wl
            self.state, results = self.kernels.commit_transfers(
                self.state, {"rows": rows_dev, "mask": jnp.asarray(mask_np)},
                nn, ts, mode=mode,
            )
        else:
            Wp = next(b for b in _WAVE_BUCKETS if b >= W)
            masks = np.zeros((Wp, n_pad), dtype=bool)  # pad waves: no-ops
            masks[wave_of[:n][wl], np.nonzero(wl)[0]] = True
            self.state, results = self._wave_stepper(Wp, n_pad, mode)(
                self.state, rows_dev, jnp.asarray(masks), nn, ts
            )
        if plan.residue_n:
            m.counter("waves.residue_events").add(plan.residue_n)
            idx = np.nonzero(~wl)[0]
            n2 = len(idx)
            pad2 = _next_pow2(n2)
            rows2 = np.zeros((pad2, ROW_WORDS), dtype=np.uint32)
            rows2[:n2] = arr.view(np.uint32).reshape(len(arr), ROW_WORDS)[idx]
            ts2 = np.zeros(pad2, dtype=np.uint64)
            base = timestamp - n + 1  # first event's ts (host int: no sync)
            ts2[:n2] = np.uint64(base) + idx.astype(np.uint64)
            self.state, r_res = self.kernels.commit_transfers_residue(
                self.state,
                {"rows": jnp.asarray(rows2), "ts": jnp.asarray(ts2)},
                jnp.int32(n2),
            )
            idx_pad = np.full(pad2, n_pad, dtype=np.int32)  # OOB -> dropped
            idx_pad[:n2] = idx
            results = self.kernels.merge_results(
                results, r_res, jnp.asarray(idx_pad)
            )
        return results

    # Fixed fused-group capacities: a loop over the slots traces the
    # commit kernel ONCE regardless of K (an unrolled K multiplies the
    # graph and has broken the remote compiler). A smaller run fills the
    # first slots and the loop stops after them: an empty slot costs its
    # share of the upload, nothing on the chip. Two capacities bound the
    # padded-upload waste.
    GROUP_KS = (16, 4)

    def _group_staging_slot(self, k: int, n_pad: int) -> dict:
        """One of TWO alternating preallocated host staging buffers per
        (k, n_pad): group N+1 packs into buffer B while buffer A's kernel
        (group N) still runs — upload staging double-buffers against
        device execution, and the per-group 16 MiB zeros+alloc (a measured
        host-side tax on the core the event loop shares) disappears.
        `used` tracks per-slot row counts so only stale tails are zeroed;
        `fence` is the flat results of the last group dispatched from the
        buffer (see the reuse fence at the call site)."""
        pool = getattr(self, "_group_staging", None)
        if pool is None:
            pool = self._group_staging = {}
        key = (k, n_pad)
        entry = pool.get(key)
        if entry is None:
            entry = pool[key] = {"i": 0, "slots": [None, None]}
        i = entry["i"]
        entry["i"] = 1 - i
        slot = entry["slots"][i]
        if slot is None:
            slot = entry["slots"][i] = {
                "rows": np.zeros((k, n_pad, ROW_WORDS), dtype=np.uint32),
                "used": np.zeros(k, dtype=np.int64),
                "fence": None,
            }
        return slot

    def _group_stepper(self, k: int, n_pad: int):
        """Jitted fused commit of up to k fast-tier batches in ONE launch
        (group commit: the replica coalesces its pipeline the way the
        flagship benchmark K-fuses device-generated batches). The loop
        runs the `m` batches the group carries (slots 0..m-1), not the k
        slots of its capacity: its trip count is an operand of the launch,
        so one program serves every fill and an empty slot costs the chip
        nothing. Returns (state', flat results [k * n_pad + 1]; last
        word = fault, slots >= m all zero, summary [k + 1])."""
        cache = getattr(self.kernels, "_group_cache", None)
        if cache is None:
            cache = self.kernels._group_cache = {}
        fn = cache.get((k, n_pad))
        if fn is None:
            kernels = self.kernels

            def step(state, rows, ns, tss, m):
                lane = jnp.arange(n_pad, dtype=jnp.int32)

                def body(i, carry):
                    st, results, cnts = carry
                    r, n, t = (
                        jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
                        for x in (rows, ns, tss)
                    )
                    st, res = kernels._commit_transfers(
                        st, {"rows": r}, n, t, mode="fast"
                    )
                    res = res.astype(jnp.uint32)
                    cnt = jnp.sum((res != 0) & (lane < n), dtype=U64)
                    return (
                        st,
                        jax.lax.dynamic_update_index_in_dim(results, res, i, 0),
                        jax.lax.dynamic_update_index_in_dim(cnts, cnt, i, 0),
                    )

                state, results, cnts = jax.lax.fori_loop(
                    0, m, body,
                    (
                        state,
                        jnp.zeros((k, n_pad), dtype=jnp.uint32),
                        jnp.zeros(k, dtype=U64),
                    ),
                )
                fault = state["fault"].reshape(1).astype(jnp.uint32)
                flat = jnp.concatenate([results.reshape(-1), fault])
                # summary = per-slot failure counts + fault: the only words
                # the all-success drain ever transfers
                return state, flat, jnp.concatenate([cnts, fault])

            fn = cache[(k, n_pad)] = sentinel_jit(
                f"group_stepper_{k}x{n_pad}", step, donate_argnums=(0,)
            )
        return fn

    def try_execute_group_async(self, items) -> list[PendingBatch] | None:
        """Fuse `items` = [(timestamp, transfers ndarray), ...] into one
        device dispatch, or return None when fusion is unsound — spill
        store active (reloads mutate state between batches), forced mode,
        or any batch not proven fast-tier. The caller falls back to
        per-batch execute_async."""
        if self.mode != "auto" or self.spill is not None or len(items) < 2:
            return None
        if getattr(self, "_group_disabled", False):
            return None
        # never truncate silently: callers zip the returned pendings with
        # their items — a shorter list would drop batches without a trace
        assert len(items) <= self.GROUP_KS[0], (len(items), self.GROUP_KS)
        total = sum(len(arr) for _, arr in items)
        if self._xfer_used + total > self._xfer_limit:
            return None  # per-batch path raises the descriptive guard
        # Probe tier decisions with rollback: plan() advances the
        # monotone amount_sum overflow bound (and plan_stats), and a
        # rejected fusion falls back to per-batch execute_async which
        # calls plan() AGAIN — without rollback every mixed-tier window
        # double-counts toward the 2^127 serial cutoff.
        sum_before = self.hazards.amount_sum
        stats_before = dict(self.hazards.plan_stats)
        with self.tracer.span("ledger.plan", batches=len(items), tier="probe"), \
                self._h_plan.time():
            decisions = [self.hazards.plan(arr) for _, arr in items]
        self._c_plan_calls.add(len(items))
        if any(d != "fast" for d, _plan in decisions):
            self.hazards.amount_sum = sum_before
            self.hazards.plan_stats = stats_before
            self._c_probe_rejected.add()
            return None
        k = next(g for g in reversed(self.GROUP_KS) if g >= len(items))
        with self.tracer.span("ledger.group_launch", slots=k,
                              batches=len(items), xfer_used=self._xfer_used):
            n_pad = self._pad_for(max(len(arr) for _, arr in items))
            slot = self._group_staging_slot(k, n_pad)
            if slot["fence"] is not None:
                # Double-buffer fence: this buffer last fed the group dispatched
                # TWO groups ago — wait for that kernel before mutating it (on
                # backends where device_put aliases host memory, e.g. CPU,
                # reuse mid-flight would corrupt the in-flight rows). In steady
                # state the fence is long retired and this is free; when the
                # device is more than two groups behind, it is exactly the
                # backpressure we want.
                with self.tracer.span("ledger.staging_wait"), \
                        self.metrics.histogram("ledger.staging_wait_us").time():
                    jax.block_until_ready(slot["fence"])
                slot["fence"] = None
            rows = slot["rows"]
            used = slot["used"]
            # batches fill slots 0..m-1; the stepper never runs the rest
            ns = np.zeros(k, dtype=np.int32)
            tss = np.zeros(k, dtype=np.uint64)
            for i, (ts, arr) in enumerate(items):
                na = len(arr)
                rows[i, :na] = arr.view(np.uint32).reshape(na, ROW_WORDS)
                if used[i] > na:
                    rows[i, na : used[i]] = 0  # zero only the stale tail
                used[i] = na
                ns[i] = na
                tss[i] = ts
            for i in range(len(items), k):
                if used[i]:
                    rows[i, : used[i]] = 0
                    used[i] = 0
            dev_rows = jax.device_put(rows)
            # upload-issued boundary for the device anatomy's h2d_stage
            # sub-leg (device_put returns once the transfer is initiated; on
            # aliasing backends it is the staging copy itself)
            self.last_h2d_done_ns = perf_counter_ns()
            self._c_h2d.add(rows.nbytes)
            try:
                # the trip count is the batches carried, never `ns > 0`:
                # an empty batch inside a group is a batch, not padding
                state, flat, summary = self._group_stepper(k, n_pad)(
                    self.state, dev_rows, jnp.asarray(ns),
                    jnp.asarray(tss), np.int32(len(items)),
                )
            except Exception:
                # A broken/flaky (remote) compile must not take the server
                # down: fall back to per-batch dispatch. But the stepper
                # donates self.state — a RUNTIME failure after donation leaves
                # deleted buffers, and no fallback is sound; re-raise then.
                for buf in self.state.values():
                    if getattr(buf, "is_deleted", lambda: False)():
                        raise
                self._group_disabled = True
                return None
        slot["fence"] = flat  # this buffer is consumed once `flat` resolves
        self.state = state
        # the registry half of the group's planning, booked with the probe
        with self.tracer.span("ledger.plan", batches=len(items),
                              tier="fast"), self._h_plan.time():
            for _ts, arr in items:
                self.hazards.note_pending(arr)
        self._g_registry.set(len(self.hazards.pending_accounts))
        if self.prefetch_results:
            try:
                summary.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
        self._xfer_used += total
        # the kernel can start once the rows are on their way
        self._note_launch(flat, self.last_h2d_done_ns, len(items), k, "fast")
        group = PendingGroup(flat, n_pad, k, summary=summary)
        return [
            PendingBatch(
                Operation.create_transfers, len(arr), flat,
                flags=arr["flags"].copy(), epoch=self._occupancy_epoch,
                group=group, group_idx=i,
            )
            for i, (_ts, arr) in enumerate(items)
        ]

    def fingerprint_lazy(self) -> dict:
        """state_fingerprint as DEVICE scalars (dispatch only, no d2h):
        the dual applier's commitment probe stashes these at each
        checkpoint boundary and materializes them once, at finalize."""
        fn = getattr(self, "_fingerprint_cache", None)
        if fn is None:
            fn = self._fingerprint_cache = sentinel_jit("fingerprint", state_fingerprint)
        return fn(self.state)

    def fingerprint(self) -> dict:
        """Materialized state_fingerprint (ONE scalar-only d2h — the dual
        server calls this once, after its clock stops)."""
        return {k: int(np.asarray(v)) for k, v in self.fingerprint_lazy().items()}

    def check_fault(self) -> None:
        """Raise if the device hit the fault protocol (see module docstring).
        Synchronizes with the device — amortize on the hot path."""
        raise_on_fault(int(np.asarray(self.state["fault"])), self.fault_name)

    # ------------------------------------------------------------------
    # snapshot row install (the dual follower's restore path)
    # ------------------------------------------------------------------

    INSTALL_CHUNK = 8192  # rows per install upload (one compile per table)

    def reset_state(self) -> None:
        """Drop every table back to fresh (the install path's
        precondition): a state-sync jump installs a snapshot onto a
        device that already holds applied rows — claim_slots would give
        each already-present key a SECOND slot and the occupancy
        trackers would double-count. In-flight kernels keep their
        references to the old arrays (functional updates), so this is
        safe to run between dispatches."""
        self.state = init_state(self.process)
        self._acct_used = 0
        self._xfer_used = 0
        self.hazards = HazardTracker()
        self.hazards.bind_counters(self.metrics)

    def _install_fn(self, table: str):
        """Jitted chunk installer for one table: claim slots for `n` wire
        rows and scatter them in (h2d upload + insert kernels ONLY — no
        device->host read; install failures set the sticky fault word and
        surface at the caller's next check_fault). `ful` carries the
        per-row posted/voided resolution for transfers (ignored for
        accounts — the column is scattered into the dump slot)."""
        cache = getattr(self.kernels, "_install_cache", None)
        if cache is None:
            cache = self.kernels._install_cache = {}
        fn = cache.get(table)
        if fn is None:
            log2 = self.kernels.a_log2 if table == "acct" else self.kernels.t_log2
            dump = jnp.int32(1 << log2)
            rows_key = f"{table}_rows"
            claim_key = f"{table}_claim"
            count_key = "acct_count" if table == "acct" else "xfer_count"
            used_key = (
                "acct_used_slots" if table == "acct" else "xfer_used_slots"
            )
            is_xfer = table == "xfer"

            def f(state, rows_b, ful, n):
                active = jnp.arange(rows_b.shape[0], dtype=jnp.int32) < n
                slots, claim, resolved = ht.claim_slots(
                    rows_b[:, :4], active, state[rows_key],
                    state[claim_key], log2,
                )
                ok = active & resolved
                w = jnp.where(ok, slots, dump)
                out = dict(state)
                out[rows_key] = state[rows_key].at[w].set(rows_b)
                out[claim_key] = claim
                if is_xfer:
                    out["fulfill"] = state["fulfill"].at[w].set(ful)
                nn = jnp.sum(ok.astype(jnp.uint64))
                out[count_key] = state[count_key] + nn
                out[used_key] = state[used_key] + nn
                # an unresolved active lane (probe-window overflow) is an
                # unrecoverable install: sticky fault, checked at finalize
                out["fault"] = state["fault"] | jnp.where(
                    jnp.any(active & ~resolved), jnp.uint32(1 << 30),
                    jnp.uint32(0),
                )
                return out

            fn = cache[table] = sentinel_jit(
                f"install_{table}", f, donate_argnums=(0,)
            )
        return fn

    def install_snapshot_rows(
        self,
        accounts: np.ndarray,
        transfers: np.ndarray,
        fulfill: np.ndarray,
        commit_timestamp: int,
    ) -> None:
        """Rebuild the device tables from host-side 128-byte wire row
        images (the native engine's snapshot format parses to exactly
        these) — the row-level upload path the dual follower uses to
        re-seed the device after a checkpoint restore or state-sync jump.
        Precondition: fresh (empty) device state. `fulfill` is the
        per-transfer posted/voided column (0 = unresolved), aligned with
        `transfers`. H2d staging and insert kernels only: no d2h."""
        assert len(fulfill) == len(transfers)
        ch = self.INSTALL_CHUNK
        for table, arr, ful in (
            ("acct", accounts, None),
            ("xfer", transfers, fulfill),
        ):
            fn = self._install_fn(table)
            for i in range(0, len(arr), ch):
                part = arr[i : i + ch]
                n = len(part)
                rows_b = jnp.asarray(_to_rows_np(part, ch))
                fv = np.zeros(ch, dtype=np.uint32)
                if ful is not None:
                    fv[:n] = ful[i : i + n]
                self.state = fn(
                    self.state, rows_b, jnp.asarray(fv), jnp.int32(n)
                )
        # device-side commit clock + host-side occupancy/hazard rebuild
        self.state["commit_ts"] = jnp.uint64(commit_timestamp)
        self._acct_used += len(accounts)
        self._xfer_used += len(transfers)
        self.hazards.note_limit_accounts(accounts)
        if len(transfers):
            # conservative superset of live pendings (extra entries only
            # degrade later post/void batches to the serial tier)
            pen = (transfers["flags"] & np.uint16(F_PENDING)) != 0
            for idl, idh, dl, cl in zip(
                transfers["id_lo"][pen], transfers["id_hi"][pen],
                transfers["debit_account_id_lo"][pen],
                transfers["credit_account_id_lo"][pen],
            ):
                self.hazards.pending_accounts[
                    int(idl) | (int(idh) << 64)
                ] = (int(dl), int(cl))
        # amount_sum is the proof bound "no balance can exceed this": the
        # sum of every restored posted+pending balance is an upper bound
        # on any restored balance, and future batches keep adding theirs
        for col in (
            "debits_posted", "credits_posted",
            "debits_pending", "credits_pending",
        ):
            if len(accounts):
                lo = accounts[col + "_lo"]
                hi = accounts[col + "_hi"]
                self.hazards.amount_sum += (
                    int(np.sum(lo & np.uint64(0xFFFFFFFF), dtype=np.uint64))
                    + (int(np.sum(lo >> np.uint64(32), dtype=np.uint64)) << 32)
                    + ((int(np.sum(hi & np.uint64(0xFFFFFFFF), dtype=np.uint64))
                        + (int(np.sum(hi >> np.uint64(32), dtype=np.uint64)) << 32)) << 64)
                )

    def _uncharge(self, pending: PendingBatch, not_applied: np.ndarray) -> None:
        dec = int(not_applied.sum())
        if pending.operation == Operation.create_transfers:
            # A spill cycle after dispatch rebuilt the table and recounted
            # occupancy exactly — this batch's effect is already measured;
            # reconciling again would double-count the correction.
            if pending.epoch == self._occupancy_epoch:
                self._xfer_used -= dec
        else:
            self._acct_used -= dec

    # -- lookups (spill-aware: HBM miss falls back to the LSM store) --

    def lookup_async(self, operation: Operation,
                     ids: list[int]) -> PendingLookup | None:
        """Launch a lookup and return at once; `lookup_finish` reads it
        back. None = this lookup must be answered inline: a transfers
        lookup over a spill store merges rows from the LSM tree, which may
        raise GridBlockCorrupt, and the replica's stall-and-retry for that
        lives at dispatch."""
        if self.spill is not None and operation == Operation.lookup_transfers:
            return None
        self._c_lookup_deferred.add()
        pending = self._lookup_launch(self._lookup_kernel(operation), ids)
        if self.prefetch_results:
            try:  # the reply's rows start home right behind the kernel
                pending.rows.copy_to_host_async()
                pending.found.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # no async copy: lookup_finish pays the sync cost
        return pending

    def lookup_rows(self, operation: Operation, ids: list[int]) -> bytes:
        self._c_lookup_inline.add()
        if self.spill is None or operation == Operation.lookup_accounts:
            return super().lookup_rows(operation, ids)
        found, rows = self._lookup(self.kernels.lookup_transfers, ids)
        return self.spill.merge_lookup_rows(ids, found, rows)

    def lookup_transfers(self, ids: list[int]) -> list[types.Transfer]:
        if self.spill is None:
            return super().lookup_transfers(ids)
        body = self.lookup_rows(Operation.lookup_transfers, ids)
        arr = np.frombuffer(body, dtype=types.TRANSFER_DTYPE)
        return [types.Transfer.from_np(arr[i]) for i in range(len(arr))]

    # -- secondary-index equality queries (device filter scan + LSM tail) --

    def _query_scan(self, table: str, field: str, value: int) -> np.ndarray:
        words = _ACCOUNT_QUERY_WORDS if table == "acct" else _TRANSFER_QUERY_WORDS
        _, nwords, halfword = words[field]
        width_bits = 16 if halfword else nwords * 32
        if not 0 <= value < (1 << width_bits):
            raise ValueError(f"{field} value out of range: {value}")
        vw = np.frombuffer(value.to_bytes(16, "little"), dtype=np.uint32).copy()
        rows_key = "acct_rows" if table == "acct" else "xfer_rows"
        rows_d, total_d = self.kernels.filter_scan(table, field)(
            self.state[rows_key], jnp.asarray(vw)
        )
        total = int(np.asarray(total_d))
        if total > QUERY_LIMIT:
            raise RuntimeError(
                f"query matches {total} rows > QUERY_LIMIT {QUERY_LIMIT}"
            )
        return np.asarray(rows_d)[:total]

    def query_accounts(self, field: str, value: int) -> list[types.Account]:
        """Accounts whose `field` equals `value`, ascending timestamp (the
        analog of a reference index-tree range query; accounts never spill,
        so the device scan is the whole store)."""
        rows = self._query_scan("acct", field, value)
        arr = np.frombuffer(rows.tobytes(), dtype=types.ACCOUNT_DTYPE)
        out = [types.Account.from_np(arr[i]) for i in range(len(arr))]
        return sorted(out, key=lambda a: a.timestamp)

    def query_transfers(self, field: str, value: int) -> list[types.Transfer]:
        """Transfers whose `field` equals `value`, ascending timestamp:
        device filter scan over HBM merged with the LSM index trees over the
        spilled tail (lsm/groove.py query)."""
        rows = self._query_scan("xfer", field, value)
        arr = np.frombuffer(rows.tobytes(), dtype=types.TRANSFER_DTYPE)
        by_ts = {
            int(arr[i]["timestamp"]): types.Transfer.from_np(arr[i])
            for i in range(len(arr))
        }
        if self.spill is not None and self.spill.spilled:
            self.spill.io_drain()  # queued inserts must land before scans
            g = self.spill.forest.transfers
            for ts in g.query(field, value):
                if ts in by_ts:
                    continue  # HBM wins (stale LSM rows of reloaded ids)
                row = g.get_by_timestamp(ts)
                t = types.Transfer.from_np(
                    np.frombuffer(row, dtype=types.TRANSFER_DTYPE)[0]
                )
                if t.id in self.spill.spilled:
                    by_ts[ts] = t
            if len(by_ts) > QUERY_LIMIT:
                raise RuntimeError(
                    f"query matches {len(by_ts)} rows > QUERY_LIMIT"
                )
        return [by_ts[ts] for ts in sorted(by_ts)]

    # -- parity extraction --

    def extract(self):
        """Pull the full device state to host dicts (accounts, transfers,
        posted) for bit-exact comparison against the oracle."""
        acct_rows = np.asarray(self.state["acct_rows"])[:-1]
        xfer_rows = np.asarray(self.state["xfer_rows"])[:-1]
        fulfill = np.asarray(self.state["fulfill"])[:-1]

        accounts: dict[int, types.Account] = {}
        transfers: dict[int, types.Transfer] = {}
        posted: dict[int, int] = {}

        occ = _occupied_rows(acct_rows)
        arr = np.frombuffer(acct_rows[occ].tobytes(), dtype=types.ACCOUNT_DTYPE)
        for i in range(len(arr)):
            a = types.Account.from_np(arr[i])
            accounts[a.id] = a
        occ = _occupied_rows(xfer_rows)
        arr = np.frombuffer(xfer_rows[occ].tobytes(), dtype=types.TRANSFER_DTYPE)
        ful = fulfill[occ]
        for i in range(len(arr)):
            t = types.Transfer.from_np(arr[i])
            transfers[t.id] = t
            if ful[i]:
                posted[t.timestamp] = int(ful[i])
        if self.spill is not None:
            self.spill.extract_into(transfers, posted)
        return accounts, transfers, posted

    @property
    def commit_timestamp(self) -> int:
        return int(self.state["commit_ts"])


def _occupied_rows(rows: np.ndarray) -> np.ndarray:
    k4 = rows[:, :4]
    empty = (k4 == 0).all(axis=1)
    tomb = (k4 == 0xFFFFFFFF).all(axis=1)
    return ~empty & ~tomb
