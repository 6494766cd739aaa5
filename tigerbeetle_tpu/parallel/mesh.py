"""Multi-chip ledger: the HBM tables sharded over a jax.sharding.Mesh.

The reference scales one replica's state machine only vertically (one core,
one NUMA node — reference: src/vsr/replica.zig single-threaded event loop).
The TPU-native design shards the account and transfer hash tables across
chips of ONE replica over ICI — consensus replication between replicas stays
host-level and is orthogonal (SURVEY.md §5.8).

Layout: wire-row tables of [n_shards, local_rows, 32] u32 sharded on axis 0
over mesh axis "shard". A key's owner shard is a second, independent hash
(owner_of_key4); within the owner it probes that shard's local table with
the same windowed double-hash probes as the single-chip ledger
(ops/hashtable.py). A commit step runs under shard_map:

1. Each shard probes its local tables for ALL lanes, masks hits by
   ownership, and the per-lane 128-byte rows are combined with one psum over
   ICI (exactly one shard contributes non-zero data per found lane).
2. Validation (models/validate.py ladders) is computed replicated — it is
   pure elementwise math over the psum'd rows, identical on every shard.
3. Application is local: each shard updates balances and inserts rows only
   for keys it owns.

Tier selection is HOST-side, exactly like the single-chip ledger
(models/ledger.py HazardTracker): hazard-free batches dispatch the
vectorized kernel; hazard batches (linked chains, post/void, balancing,
duplicate ids, limit accounts, overflow risk) dispatch the sharded SERIAL
kernel — an exact event-at-a-time scan where every store lookup is a
(local probe -> ownership mask -> fused psum) and every write is masked to
the owning shard. Validation and the undo log's replicated fields are
identical on all shards by construction; per-shard undo slots roll back each
shard's own writes on linked-chain breaks.

The fault protocol matches the single-chip ledger: unresolved probes abort
the batch (fast tier: whole-batch no-op + sticky fault; serial tier:
FAULT_SERIAL marks corruption) — the fault word is replicated via psum so
every shard agrees.
"""

from __future__ import annotations

from time import perf_counter_ns

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tigerbeetle_tpu.constants import ConfigProcess
from tigerbeetle_tpu.models import validate
from tigerbeetle_tpu.models.ledger import (
    FAULT_CAPACITY,
    FAULT_CLAIM,
    FAULT_OVERFLOW,
    FAULT_PROBE,
    FAULT_SERIAL,
    ROW_WORDS,
    raise_on_fault,
    _TOMB_ROW,
    _amount_digits,
    _combined_overflow,
    _fold_digits,
    _lohi,
    _next_pow2,
    _set_ts_words,
    _to_rows_np,
    HazardTracker,
    HostLedgerBase,
    PendingBatch,
    build_stored_transfer,
    key4_from_fields,
    pack_account,
    pack_transfer,
    sentinel_jit,
    unpack_account,
    unpack_transfer,
)
from tigerbeetle_tpu.models.validate import F_LINKED, F_PENDING, F_POST, F_VOID
from tigerbeetle_tpu.ops import hashtable as ht
from tigerbeetle_tpu.ops import u128
from tigerbeetle_tpu.types import Operation

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

# Owner-hash constants — the SINGLE source of truth for both the device hash
# (owner_of_key4) and its host mirror (owner_of_ids_np); a parity test ties
# the two (tests/test_mesh.py). numpy scalars: see ops/hashtable.py note.
_OWNER_MIX = np.uint64(0xD6E8FEB86659FD93)
_OWNER_XOR = np.uint64(0xA5A5A5A5A5A5A5A5)
_OWNER_MUL2 = np.uint64(0x94D049BB133111EB)
_OWNER_SHIFT1 = 29
_OWNER_SHIFT2 = 32


def owner_of_key4(key4, n_shards: int):
    """Owner shard of a key — an independent hash from the slot hash."""
    k = key4.astype(U64)
    lo = k[..., 0] | (k[..., 1] << jnp.uint64(32))
    hi = k[..., 2] | (k[..., 3] << jnp.uint64(32))
    x = (lo ^ _OWNER_XOR) * _OWNER_MIX
    x = x ^ (hi * _OWNER_MIX) ^ (x >> jnp.uint64(_OWNER_SHIFT1))
    x = x * _OWNER_MUL2
    x = x ^ (x >> jnp.uint64(_OWNER_SHIFT2))
    return (x % jnp.uint64(n_shards)).astype(I32)


def owner_of_ids_np(id_lo: np.ndarray, id_hi: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side mirror of owner_of_key4 (for the per-shard occupancy guard).
    Same constants by construction; parity-tested against the device hash."""
    lo = id_lo.astype(np.uint64)
    hi = id_hi.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (lo ^ _OWNER_XOR) * _OWNER_MIX
        x = x ^ (hi * _OWNER_MIX) ^ (x >> np.uint64(_OWNER_SHIFT1))
        x = x * _OWNER_MUL2
        x = x ^ (x >> np.uint64(_OWNER_SHIFT2))
    return (x % np.uint64(n_shards)).astype(np.int64)


def sharded_state_program(mesh: Mesh, process: ConfigProcess):
    """The jitted allocator of the sharded state: tables of [n_shards,
    local_rows, 32] sharded over mesh axis "shard", local capacity =
    2^account_slots_log2 etc. PER SHARD. Allocated SHARDED from the start
    (out_shardings): every device fills only its own slice. Building each
    table whole on the default device and then device_put-ing it across
    the mesh would land n_shards x the per-shard state on chip 0 first."""
    n = mesh.devices.size
    a_rows = (1 << process.account_slots_log2) + 1
    t_rows = (1 << process.transfer_slots_log2) + 1
    sh = NamedSharding(mesh, P("shard"))
    sc = NamedSharding(mesh, P())

    def build():
        return {
            "acct_rows": jnp.zeros((n, a_rows, ROW_WORDS), dtype=U32),
            "xfer_rows": jnp.zeros((n, t_rows, ROW_WORDS), dtype=U32),
            "fulfill": jnp.zeros((n, t_rows), dtype=U32),
            "acct_claim": jnp.full((n, a_rows), ht.CLAIM_FREE, dtype=U32),
            "xfer_claim": jnp.full((n, t_rows), ht.CLAIM_FREE, dtype=U32),
            "bal_acc": jnp.zeros((n, a_rows, ROW_WORDS), dtype=U32),
            # per-shard ever-applied insert counters (device load guard)
            "acct_used_slots": jnp.zeros((n,), dtype=jnp.uint64),
            "xfer_used_slots": jnp.zeros((n,), dtype=jnp.uint64),
            "commit_ts": jnp.uint64(0),
            "acct_count": jnp.uint64(0),
            "xfer_count": jnp.uint64(0),
            "fault": jnp.uint32(0),
        }

    replicated = ("commit_ts", "acct_count", "xfer_count", "fault")
    shardings = {
        k: sc if k in replicated else sh for k in jax.eval_shape(build)
    }
    return jax.jit(build, out_shardings=shardings)


def init_sharded_state(mesh: Mesh, process: ConfigProcess) -> dict:
    return sharded_state_program(mesh, process)()


class ShardedLedgerKernels:
    """shard_map commit kernels over a 1-D "shard" mesh axis. Mode ("fast" /
    "serial") is selected by the HOST per batch — both kernels are
    straight-line programs (the serial one a lax.scan), no on-device
    dispatch."""

    def __init__(self, mesh: Mesh, process: ConfigProcess):
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.process = process
        self.a_log2 = process.account_slots_log2
        self.t_log2 = process.transfer_slots_log2
        # Python ints (embedded as literals) — capturing jnp scalars in the
        # kernels would poison dispatch (see ops/hashtable.py note).
        self.a_dump = 1 << self.a_log2
        self.t_dump = 1 << self.t_log2

        sharded_keys = (
            "acct_rows", "xfer_rows", "fulfill", "acct_claim", "xfer_claim",
            "bal_acc", "acct_used_slots", "xfer_used_slots",
        )
        state_spec = {k: P("shard") for k in sharded_keys}
        state_spec.update(
            {k: P() for k in ("commit_ts", "acct_count", "xfer_count", "fault")}
        )

        def wrap(fn, out_state=True):
            out_specs = (state_spec, P()) if out_state else (P(), P(), P())
            in_specs = (state_spec, P(), P(), P()) if out_state else (state_spec, P())
            return sentinel_jit(
                "sharded" + fn.__name__,
                shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                          check_vma=False),
                donate_argnums=(0,) if out_state else (),
            )

        self.commit_transfers_fast = wrap(self._commit_transfers_fast)
        self.commit_transfers_serial = wrap(self._commit_transfers_serial)
        self.commit_accounts_fast = wrap(self._commit_accounts_fast)
        self.commit_accounts_serial = wrap(self._commit_accounts_serial)
        self.lookup_accounts = wrap(self._lookup_accounts_shard, out_state=False)
        self.lookup_transfers = wrap(self._lookup_transfers_shard, out_state=False)

    # ------------------------------------------------------------------
    # sharded lookup: local probe + ownership mask + one fused psum
    # ------------------------------------------------------------------

    def _find(self, rows_local, key4, log2, my_shard, window=ht.WINDOW):
        """Batched sharded probe. Returns (slot local-i32, mine bool,
        found bool, row [.., 32], resolved bool) — found/row/resolved are
        replicated (psum'd); slot/mine are local."""
        own = owner_of_key4(key4, self.n_shards) == my_shard
        slot, found_l, res_l = ht.lookup(key4, rows_local, log2, window=window)
        mine = own & found_l
        # Owner shards must resolve their probes; non-owners don't matter.
        bad_local = own & ~res_l
        row_c = jnp.where(mine[..., None], rows_local[slot], jnp.uint32(0))
        found_c, bad_c, row = jax.lax.psum(
            (mine.astype(U32), bad_local.astype(U32), row_c), "shard"
        )
        return slot, mine, found_c > 0, row, bad_c == 0

    # ------------------------------------------------------------------
    # fast tier
    # ------------------------------------------------------------------

    def _commit_transfers_fast(self, state, ev, n, timestamp):
        my = jax.lax.axis_index("shard")
        acct_rows = state["acct_rows"][0]
        xfer_rows = state["xfer_rows"][0]

        rows_b = ev["rows"]
        B = rows_b.shape[0]
        e = unpack_transfer(rows_b)
        lane = jnp.arange(B, dtype=I32)
        valid = lane < n
        ts_vec = timestamp - n.astype(U64) + lane.astype(U64) + jnp.uint64(1)
        e_a = {**e, "ts": ts_vec}

        dr_k4 = key4_from_fields({"id_lo": e["dr_lo"], "id_hi": e["dr_hi"]})
        cr_k4 = key4_from_fields({"id_lo": e["cr_lo"], "id_hi": e["cr_hi"]})
        both_k4 = jnp.concatenate([dr_k4, cr_k4], axis=0)
        b_slot, b_mine, b_found, b_row, b_res = self._find(
            acct_rows, both_k4, self.a_log2, my
        )
        dr_slot, cr_slot = b_slot[:B], b_slot[B:]
        dr_mine, cr_mine = b_mine[:B], b_mine[B:]
        dr_found, cr_found = b_found[:B], b_found[B:]
        dr_row, cr_row = b_row[:B], b_row[B:]
        _, _, ex_found, ex_row, ex_res = self._find(
            xfer_rows, rows_b[:, :4], self.t_log2, my
        )
        dr = unpack_account(dr_row)
        cr = unpack_account(cr_row)
        ex = unpack_transfer(ex_row)

        r0 = jnp.where(e["ts"] != 0, jnp.uint32(3), jnp.uint32(0))
        r0 = validate.transfer_common(e, r0)
        r, amt_lo, amt_hi = validate.validate_simple_transfer(
            r0, e_a, dr, cr, dr_found, cr_found, ex, ex_found
        )
        r = jnp.where(valid, r, jnp.uint32(0))
        ok = valid & (r == 0)

        valid2 = jnp.concatenate([valid, valid])
        probe_bad = jnp.any(valid2 & ~b_res) | jnp.any(valid & ~ex_res)

        # Claim insert slots on the id's owner shard (pure claim phase).
        own_id = owner_of_key4(rows_b[:, :4], self.n_shards) == my
        ins = ok & own_id
        ins_slots, claim, ins_res = ht.claim_slots(
            rows_b[:, :4], ins, xfer_rows, state["xfer_claim"][0], self.t_log2
        )
        claim_bad_l = jnp.any(~ins_res)

        # Local balance-delta accumulation for owned accounts only.
        digits = _amount_digits(amt_lo, amt_hi)
        pending = (e["flags"] & jnp.uint32(F_PENDING)) != 0
        zeros8 = jnp.zeros_like(digits)
        pend8 = jnp.where(pending[:, None], digits, zeros8)
        post8 = jnp.where(pending[:, None], zeros8, digits)
        upd_dr = jnp.concatenate([pend8, post8, zeros8, zeros8], axis=-1)
        upd_cr = jnp.concatenate([zeros8, zeros8, pend8, post8], axis=-1)
        slots_t = jnp.concatenate([
            jnp.where(ok & dr_mine, dr_slot, self.a_dump),
            jnp.where(ok & cr_mine, cr_slot, self.a_dump),
        ])
        upd = jnp.concatenate([upd_dr, upd_cr], axis=0)
        acc = state["bal_acc"][0].at[slots_t].add(upd)
        acc_t = acc[slots_t]
        old_rows_t = acct_rows[slots_t]  # local rows (valid where mine)
        new_rows_t, over_t = _fold_digits(old_rows_t, acc_t)
        over_bad_l = jnp.any(
            (over_t | _combined_overflow(new_rows_t)) & (slots_t != self.a_dump)
        )
        acc = acc.at[slots_t].set(jnp.zeros_like(upd))

        # per-shard device load guard over owned inserts
        ins_n = jnp.sum(ins).astype(jnp.uint64)
        cap_bad_l = state["xfer_used_slots"][0] + ins_n > np.uint64(
            self.t_dump // 2
        )
        claim_bad, over_bad, cap_bad = jax.lax.psum(
            (claim_bad_l.astype(U32), over_bad_l.astype(U32),
             cap_bad_l.astype(U32)), "shard"
        )
        fault = (
            state["fault"]
            | jnp.where(probe_bad, jnp.uint32(FAULT_PROBE), jnp.uint32(0))
            | jnp.where(claim_bad > 0, jnp.uint32(FAULT_CLAIM), jnp.uint32(0))
            | jnp.where(over_bad > 0, jnp.uint32(FAULT_OVERFLOW), jnp.uint32(0))
            | jnp.where(cap_bad > 0, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0))
        )
        proceed = fault == 0

        # --- application (gated on proceed) ---
        acct2 = acct_rows.at[jnp.where(proceed, slots_t, self.a_dump)].set(new_rows_t)
        ins_rows = _set_ts_words(rows_b, ts_vec)
        w = jnp.where(proceed & ins, ins_slots, self.t_dump)
        xfer2 = xfer_rows.at[w].set(ins_rows)
        fulfill = state["fulfill"][0].at[w].set(jnp.uint32(0))

        applied = proceed & jnp.any(ok)
        last_ts = jnp.max(jnp.where(ok, ts_vec, jnp.uint64(0)))
        new_state = {
            "acct_rows": acct2[None],
            "xfer_rows": xfer2[None],
            "fulfill": fulfill[None],
            "acct_claim": state["acct_claim"],
            "xfer_claim": claim[None],
            "bal_acc": acc[None],
            "acct_used_slots": state["acct_used_slots"],
            "xfer_used_slots": state["xfer_used_slots"]
            + jnp.where(proceed, ins_n, jnp.uint64(0))[None],
            "commit_ts": jnp.where(applied, last_ts, state["commit_ts"]),
            "acct_count": state["acct_count"],
            "xfer_count": state["xfer_count"]
            + jnp.where(proceed, jnp.sum(ok).astype(U64), jnp.uint64(0)),
            "fault": fault,
        }
        return new_state, r

    def _commit_accounts_fast(self, state, ev, n, timestamp):
        my = jax.lax.axis_index("shard")
        acct_rows = state["acct_rows"][0]

        rows_b = ev["rows"]
        B = rows_b.shape[0]
        e = unpack_account(rows_b)
        lane = jnp.arange(B, dtype=I32)
        valid = lane < n
        ts_vec = timestamp - n.astype(U64) + lane.astype(U64) + jnp.uint64(1)

        _, _, ex_found, ex_row, ex_res = self._find(
            acct_rows, rows_b[:, :4], self.a_log2, my
        )
        ex = unpack_account(ex_row)
        r0 = jnp.where(e["ts"] != 0, jnp.uint32(3), jnp.uint32(0))
        r = validate.validate_create_account(r0, e, ex, ex_found)
        r = jnp.where(valid, r, jnp.uint32(0))
        ok = valid & (r == 0)

        probe_bad = jnp.any(valid & ~ex_res)
        own_id = owner_of_key4(rows_b[:, :4], self.n_shards) == my
        ins = ok & own_id
        ins_slots, claim, ins_res = ht.claim_slots(
            rows_b[:, :4], ins, acct_rows, state["acct_claim"][0], self.a_log2
        )
        ins_n = jnp.sum(ins).astype(jnp.uint64)
        cap_bad_l = state["acct_used_slots"][0] + ins_n > np.uint64(
            self.a_dump // 2
        )
        claim_bad_c, cap_bad_c = jax.lax.psum(
            (jnp.any(~ins_res).astype(U32), cap_bad_l.astype(U32)), "shard"
        )

        fault = (
            state["fault"]
            | jnp.where(probe_bad, jnp.uint32(FAULT_PROBE), jnp.uint32(0))
            | jnp.where(claim_bad_c > 0, jnp.uint32(FAULT_CLAIM), jnp.uint32(0))
            | jnp.where(cap_bad_c > 0, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0))
        )
        proceed = fault == 0

        ins_rows = _set_ts_words(rows_b, ts_vec)
        w = jnp.where(proceed & ins, ins_slots, self.a_dump)
        acct2 = acct_rows.at[w].set(ins_rows)

        applied = proceed & jnp.any(ok)
        last_ts = jnp.max(jnp.where(ok, ts_vec, jnp.uint64(0)))
        new_state = {
            "acct_rows": acct2[None],
            "xfer_rows": state["xfer_rows"],
            "fulfill": state["fulfill"],
            "acct_claim": claim[None],
            "xfer_claim": state["xfer_claim"],
            "bal_acc": state["bal_acc"],
            "acct_used_slots": state["acct_used_slots"]
            + jnp.where(proceed, ins_n, jnp.uint64(0))[None],
            "xfer_used_slots": state["xfer_used_slots"],
            "commit_ts": jnp.where(applied, last_ts, state["commit_ts"]),
            "acct_count": state["acct_count"]
            + jnp.where(proceed, jnp.sum(ok).astype(U64), jnp.uint64(0)),
            "xfer_count": state["xfer_count"],
            "fault": fault,
        }
        return new_state, r

    # ------------------------------------------------------------------
    # serial tier (exact; hazard batches)
    # ------------------------------------------------------------------

    def _find1(self, rows_local, fulfill_local, keys, log2, my):
        """Fused scalar-step probe of k stacked keys [k, 4]. Returns
        (slot [k] local, mine [k] local, found [k] repl, rows [k, 32] repl,
        fulfill [k] repl, bad repl-bool)."""
        own = owner_of_key4(keys, self.n_shards) == my
        slot, found_l, res_l = ht.lookup(
            keys, rows_local, log2, window=ht.WINDOW_SCALAR
        )
        mine = own & found_l
        bad_l = jnp.any(own & ~res_l)
        row_c = jnp.where(mine[:, None], rows_local[slot], jnp.uint32(0))
        ful_c = (
            jnp.where(mine, fulfill_local[slot], jnp.uint32(0))
            if fulfill_local is not None
            else jnp.zeros(keys.shape[0], dtype=U32)
        )
        found_c, row, ful, bad_c = jax.lax.psum(
            (mine.astype(U32), row_c, ful_c, bad_l.astype(U32)), "shard"
        )
        return slot, mine, found_c > 0, row, ful, bad_c > 0

    def _commit_transfers_serial(self, state, ev, n, timestamp):
        my = jax.lax.axis_index("shard")
        rows_b = ev["rows"]
        B = rows_b.shape[0]
        lanes = jnp.arange(B, dtype=I32)
        a_dump, t_dump = self.a_dump, self.t_dump
        tomb_row = _TOMB_ROW  # numpy: embeds as a literal
        # entry gates: sticky fault + per-shard device load guard
        # (conservative: all n events charged against every shard)
        cap_bad_l = state["xfer_used_slots"][0] + n.astype(U64) > np.uint64(
            self.t_dump // 2
        )
        cap_bad = jax.lax.psum(cap_bad_l.astype(U32), "shard") > 0
        fault0 = state["fault"] | jnp.where(
            cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0)
        )
        n = jnp.where(fault0 == 0, n, jnp.int32(0))

        undo0 = {
            "kind": jnp.zeros(B, dtype=U32),
            "dr_mine": jnp.zeros(B, dtype=bool),
            "cr_mine": jnp.zeros(B, dtype=bool),
            "t_mine": jnp.zeros(B, dtype=bool),
            "p_mine": jnp.zeros(B, dtype=bool),
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
            state["acct_rows"][0], state["xfer_rows"][0], state["fulfill"][0],
            jnp.zeros(B, dtype=U32),  # results (replicated)
            undo0,
            jnp.int32(-1),  # chain_start (replicated)
            jnp.zeros((), dtype=bool),  # chain_broken (replicated)
            state["commit_ts"],
            jnp.zeros((), dtype=bool),  # unresolved accumulator (replicated)
        )

        def step(carry, x):
            (acct_rows, xfer_rows, fulfill, results, undo, chain_start,
             chain_broken, commit_ts, probe_bad) = carry
            i, row_e = x
            e = unpack_transfer(row_e)
            active = i < n
            linked = active & ((e["flags"] & jnp.uint32(F_LINKED)) != 0)

            opening = linked & (chain_start < 0)
            chain_start = jnp.where(opening, i, chain_start)
            in_chain = chain_start >= 0
            is_last = i == (n - 1)

            ts = timestamp - n.astype(U64) + i.astype(U64) + jnp.uint64(1)
            e_a = {**e, "ts": ts}

            lad = validate.Ladder(jnp.uint32(0))
            lad.set(in_chain & is_last & linked, 2)  # linked_event_chain_open
            lad.set(active & chain_broken, 1)  # linked_event_failed
            lad.set(e["ts"] != 0, 3)  # timestamp_must_be_zero
            r0 = validate.transfer_common(e, lad.r)

            k4 = key4_from_fields
            # Fused probes: accounts (dr, cr) and transfers (ex, p).
            a_keys = jnp.stack([
                k4({"id_lo": e["dr_lo"], "id_hi": e["dr_hi"]}),
                k4({"id_lo": e["cr_lo"], "id_hi": e["cr_hi"]}),
            ])
            a_slot, a_mine, a_found, a_rows_g, _, bad_a = self._find1(
                acct_rows, None, a_keys, self.a_log2, my
            )
            t_keys = jnp.stack([
                row_e[:4],
                k4({"id_lo": e["pid_lo"], "id_hi": e["pid_hi"]}),
            ])
            t_slot, t_mine, t_found, t_rows_g, t_ful, bad_t = self._find1(
                xfer_rows, fulfill, t_keys, self.t_log2, my
            )
            dr = unpack_account(a_rows_g[0])
            cr = unpack_account(a_rows_g[1])
            dr_found, cr_found = a_found[0], a_found[1]
            ex = unpack_transfer(t_rows_g[0])
            p = unpack_transfer(t_rows_g[1])
            ex_found, p_found = t_found[0], t_found[1]
            p["fulfill"] = t_ful[1]
            # The pending transfer's accounts (post/void path); garbage rows
            # when ~p_found, gated by the validator.
            pa_keys = jnp.stack([
                k4({"id_lo": p["dr_lo"], "id_hi": p["dr_hi"]}),
                k4({"id_lo": p["cr_lo"], "id_hi": p["cr_hi"]}),
            ])
            pa_slot, pa_mine, _, pa_rows_g, _, bad_pa = self._find1(
                acct_rows, None, pa_keys, self.a_log2, my
            )
            pdr = unpack_account(pa_rows_g[0])
            pcr = unpack_account(pa_rows_g[1])
            probe_bad = probe_bad | (active & (bad_a | bad_t | bad_pa))

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

            # --- build the row to insert (replicated; shared helper) ---
            ins_row = pack_transfer(
                build_stored_transfer(e, p, is_pv, amt_lo, amt_hi, ts)
            )
            # Insert on the id's owner shard only.
            id_own = owner_of_key4(row_e[:4], self.n_shards) == my
            free_slot, free_ok = ht.probe_free(row_e[:4], xfer_rows, self.t_log2)
            probe_bad = probe_bad | jnp.any(
                jax.lax.psum((ok & id_own & ~free_ok).astype(U32), "shard") > 0
            )
            t_write = ok & id_own & free_ok
            w = jnp.where(t_write, free_slot, t_dump)
            xfer_rows = xfer_rows.at[w].set(ins_row)
            fulfill = fulfill.at[w].set(jnp.uint32(0))
            # fulfill update at the pending transfer (p's owner shard).
            p_mine_l = t_mine[1]
            fw = jnp.where(ok & is_pv & p_mine_l, t_slot[1], t_dump)
            fulfill = fulfill.at[fw].set(
                jnp.where(is_post, jnp.uint32(1), jnp.uint32(2))
            )

            # --- balance application (masked to owning shards) ---
            tgt_dr_mine = jnp.where(is_pv, pa_mine[0], a_mine[0])
            tgt_cr_mine = jnp.where(is_pv, pa_mine[1], a_mine[1])
            tgt_dr_slot = jnp.where(is_pv, pa_slot[0], a_slot[0])
            tgt_cr_slot = jnp.where(is_pv, pa_slot[1], a_slot[1])
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
            dw = jnp.where(ok & tgt_dr_mine, tgt_dr_slot, a_dump)
            cw = jnp.where(ok & tgt_cr_mine, tgt_cr_slot, a_dump)
            acct_rows = acct_rows.at[dw].set(pack_account(tdr))
            acct_rows = acct_rows.at[cw].set(pack_account(tcr))
            commit_ts = jnp.where(ok, ts, commit_ts)

            # --- undo log entry (kinds/amounts replicated; slots local) ---
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
                "dr_mine": undo["dr_mine"].at[i].set(tgt_dr_mine),
                "cr_mine": undo["cr_mine"].at[i].set(tgt_cr_mine),
                "t_mine": undo["t_mine"].at[i].set(id_own),
                "p_mine": undo["p_mine"].at[i].set(p_mine_l),
                "dr_slot": undo["dr_slot"].at[i].set(tgt_dr_slot),
                "cr_slot": undo["cr_slot"].at[i].set(tgt_cr_slot),
                "t_slot": undo["t_slot"].at[i].set(free_slot),
                "p_slot": undo["p_slot"].at[i].set(t_slot[1]),
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
                applied_k = kd != 0
                k1, k2 = kd == 1, kd == 2
                k3, k4_ = kd == 3, kd == 4
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

                dwk = jnp.where(
                    applied_k & undo["dr_mine"][k], undo["dr_slot"][k], a_dump
                )
                cwk = jnp.where(
                    applied_k & undo["cr_mine"][k], undo["cr_slot"][k], a_dump
                )
                fdr = unpack_account(acct_rows[dwk])
                fcr = unpack_account(acct_rows[cwk])
                fdr["dp_lo"], fdr["dp_hi"] = inv(fdr, "dp", add_p, sub_pend, ua_lo, ua_hi)
                fdr["dpo_lo"], fdr["dpo_hi"] = inv(fdr, "dpo", false_, sub_post, ua_lo, ua_hi)
                fcr["cp_lo"], fcr["cp_hi"] = inv(fcr, "cp", add_p, sub_pend, ua_lo, ua_hi)
                fcr["cpo_lo"], fcr["cpo_hi"] = inv(fcr, "cpo", false_, sub_post, ua_lo, ua_hi)
                acct_rows = acct_rows.at[dwk].set(pack_account(fdr))
                acct_rows = acct_rows.at[cwk].set(pack_account(fcr))
                twk = jnp.where(
                    applied_k & undo["t_mine"][k], undo["t_slot"][k], t_dump
                )
                xfer_rows = xfer_rows.at[twk].set(tomb_row)
                fwk = jnp.where(
                    (k3 | k4_) & undo["p_mine"][k], undo["p_slot"][k], t_dump
                )
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
         probe_bad), _ = jax.lax.scan(step, carry0, (lanes, rows_b))
        ok_n = jnp.sum((results == 0) & (lanes < n)).astype(U64)
        applied_l = jnp.sum(((undo["kind"] != 0) & undo["t_mine"]).astype(U64))
        new_state = {
            "acct_rows": acct_rows[None],
            "xfer_rows": xfer_rows[None],
            "fulfill": fulfill[None],
            "acct_claim": state["acct_claim"],
            "xfer_claim": state["xfer_claim"],
            "bal_acc": state["bal_acc"],
            "acct_used_slots": state["acct_used_slots"],
            "xfer_used_slots": state["xfer_used_slots"] + applied_l[None],
            "commit_ts": commit_ts,
            "acct_count": state["acct_count"],
            "xfer_count": state["xfer_count"] + ok_n,
            "fault": fault0
            | jnp.where(probe_bad, jnp.uint32(FAULT_SERIAL), jnp.uint32(0)),
        }
        return new_state, results

    def _commit_accounts_serial(self, state, ev, n, timestamp):
        my = jax.lax.axis_index("shard")
        rows_b = ev["rows"]
        B = rows_b.shape[0]
        lanes = jnp.arange(B, dtype=I32)
        a_dump = self.a_dump
        tomb_row = _TOMB_ROW  # numpy: embeds as a literal
        cap_bad_l = state["acct_used_slots"][0] + n.astype(U64) > np.uint64(
            self.a_dump // 2
        )
        cap_bad = jax.lax.psum(cap_bad_l.astype(U32), "shard") > 0
        fault0 = state["fault"] | jnp.where(
            cap_bad, jnp.uint32(FAULT_CAPACITY), jnp.uint32(0)
        )
        n = jnp.where(fault0 == 0, n, jnp.int32(0))

        undo0 = {
            "slot": jnp.zeros(B, dtype=I32),
            "kind": jnp.zeros(B, dtype=U32),
            "mine": jnp.zeros(B, dtype=bool),
        }
        carry0 = (
            state["acct_rows"][0],
            jnp.zeros(B, dtype=U32),
            undo0,
            jnp.int32(-1),
            jnp.zeros((), dtype=bool),
            state["commit_ts"],
            jnp.zeros((), dtype=bool),
        )

        def step(carry, x):
            (acct_rows, results, undo, chain_start, chain_broken, commit_ts,
             probe_bad) = carry
            i, row_e = x
            e = unpack_account(row_e)
            active = i < n
            linked = active & ((e["flags"] & jnp.uint32(validate.A_LINKED)) != 0)
            opening = linked & (chain_start < 0)
            chain_start = jnp.where(opening, i, chain_start)
            in_chain = chain_start >= 0
            is_last = i == (n - 1)
            ts = timestamp - n.astype(U64) + i.astype(U64) + jnp.uint64(1)

            lad = validate.Ladder(jnp.uint32(0))
            lad.set(in_chain & is_last & linked, 2)
            lad.set(active & chain_broken, 1)
            lad.set(e["ts"] != 0, 3)

            _, _, ex_found_v, ex_row, _, bad = self._find1(
                acct_rows, None, row_e[None, :4], self.a_log2, my
            )
            ex = unpack_account(ex_row[0])
            r = validate.validate_create_account(lad.r, e, ex, ex_found_v[0])
            r = jnp.where(active, r, jnp.uint32(0))
            ok = active & (r == 0)

            id_own = owner_of_key4(row_e[:4], self.n_shards) == my
            free_slot, free_ok = ht.probe_free(row_e[:4], acct_rows, self.a_log2)
            probe_bad = probe_bad | (active & bad) | jnp.any(
                jax.lax.psum((ok & id_own & ~free_ok).astype(U32), "shard") > 0
            )
            do_write = ok & id_own & free_ok
            w = jnp.where(do_write, free_slot, a_dump)
            t0, t1 = _lohi(ts)
            ins_row = jnp.concatenate([row_e[:30], t0[None], t1[None]])
            acct_rows = acct_rows.at[w].set(ins_row)
            commit_ts = jnp.where(ok, ts, commit_ts)

            undo = {
                "kind": undo["kind"].at[i].set(jnp.where(ok, jnp.uint32(5), jnp.uint32(0))),
                "slot": undo["slot"].at[i].set(free_slot),
                "mine": undo["mine"].at[i].set(id_own),
            }

            break_now = active & (r != 0) & in_chain & ~chain_broken
            lo_k = jnp.where(break_now, chain_start, i)

            def undo_body(k, acct_rows):
                applied_k = (undo["kind"][k] != 0) & undo["mine"][k]
                sl = jnp.where(applied_k, undo["slot"][k], a_dump)
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
        applied_l = jnp.sum(((undo["kind"] != 0) & undo["mine"]).astype(U64))
        new_state = {
            "acct_rows": acct_rows[None],
            "xfer_rows": state["xfer_rows"],
            "fulfill": state["fulfill"],
            "acct_claim": state["acct_claim"],
            "xfer_claim": state["xfer_claim"],
            "bal_acc": state["bal_acc"],
            "acct_used_slots": state["acct_used_slots"] + applied_l[None],
            "xfer_used_slots": state["xfer_used_slots"],
            "commit_ts": commit_ts,
            "acct_count": state["acct_count"] + ok_n,
            "xfer_count": state["xfer_count"],
            "fault": fault0
            | jnp.where(probe_bad, jnp.uint32(FAULT_SERIAL), jnp.uint32(0)),
        }
        return new_state, results

    # ------------------------------------------------------------------
    # lookups
    # ------------------------------------------------------------------

    def _lookup_accounts_shard(self, state, ids):
        my = jax.lax.axis_index("shard")
        _, _, found, row, res = self._find(
            state["acct_rows"][0], ids["key4"], self.a_log2, my
        )
        return found, row, res

    def _lookup_transfers_shard(self, state, ids):
        my = jax.lax.axis_index("shard")
        _, _, found, row, res = self._find(
            state["xfer_rows"][0], ids["key4"], self.t_log2, my
        )
        return found, row, res


class ShardedLedger(HostLedgerBase):
    """Host wrapper over the sharded kernels. Mirrors DeviceLedger's
    execute_async / drain contract (HostLedgerBase: prepare, lookups, the
    launch bookkeeping and the whole drain): a commit is dispatched and
    left in flight, its fault word and failure count come home as two
    words, and only a batch with failures has its dense codes read. Tier
    selection is the same host-side HazardTracker, all-or-nothing (fast /
    serial). Lookups are answered inline."""

    fault_name = "sharded ledger"

    def __init__(self, mesh: Mesh, process: ConfigProcess, mode: str = "auto"):
        self.mesh = mesh
        self.process = process
        self.mode = mode
        self.n_shards = mesh.devices.size
        self.kernels = ShardedLedgerKernels(mesh, process)
        self.state = init_sharded_state(mesh, process)
        self.hazards = HazardTracker()
        # Per-shard occupancy guard (conservative: a dispatch charges every
        # submission, the drain takes the not-applied ones off again).
        # Owner-hash skew means one shard can fill well before aggregate
        # capacity.
        self._acct_used = np.zeros(self.n_shards, dtype=np.int64)
        self._xfer_used = np.zeros(self.n_shards, dtype=np.int64)
        self._acct_limit = (1 << process.account_slots_log2) // 2
        self._xfer_limit = (1 << process.transfer_slots_log2) // 2
        self._replicated = NamedSharding(mesh, P())
        self._bind_counters(self.metrics)

    def _bind_counters(self, metrics) -> None:
        super()._bind_counters(metrics)
        # the owner hash's skew: rows charged to the fullest transfer
        # shard and the mean over shards (host counts; no device read)
        self._g_rows_max = metrics.gauge("sharded.xfer_rows_max")
        self._g_rows_mean = metrics.gauge("sharded.xfer_rows_mean")

    def _shard_counts(self, id_lo: np.ndarray, id_hi: np.ndarray) -> np.ndarray:
        owners = owner_of_ids_np(id_lo, id_hi, self.n_shards)
        return np.bincount(owners, minlength=self.n_shards)

    def _note_rows(self) -> None:
        self._g_rows_max.set(int(self._xfer_used.max()))
        self._g_rows_mean.set(float(self._xfer_used.mean()))

    def execute_async(self, operation, timestamp: int, events) -> PendingBatch:
        """Dispatch a commit without any device->host synchronization: the
        host work of one batch up to its dispatch (shard counts, load
        guard, hazard test, rows to the device, the jit call), then the
        summary's copy home started. `drain` reads it later and takes the
        not-applied lanes off the per-shard charge made here."""
        with self.tracer.span("ledger.sharded_launch", events=len(events)):
            return self._launch(operation, timestamp, events)

    def _launch(self, operation, timestamp: int, events) -> PendingBatch:
        from tigerbeetle_tpu import types as t

        n = len(events)
        n_pad = _next_pow2(n)
        # A launch's inputs go from the host to EVERY chip (numpy scalars,
        # the rows put with the mesh's replicated sharding). An array that
        # lands on chip 0 first is copied on by chip 0's own stream, behind
        # the commit program running there, and the launch queued for the
        # other chips then starts ~2 ms after its predecessor ended.
        nn = np.int32(n)
        tier = None  # the tier of a create_transfers launch
        if operation == Operation.create_transfers:
            arr = events if isinstance(events, np.ndarray) else t.transfers_to_np(events)
            id_limbs = (arr["id_lo"].copy(), arr["id_hi"].copy())
            counts = self._shard_counts(*id_limbs)
            if ((self._xfer_used + counts) > self._xfer_limit).any():
                raise RuntimeError(
                    "a transfer shard is at its load-factor limit: grow "
                    "ConfigProcess.transfer_slots_log2 (per-shard capacity)"
                )
            tier = self.mode
            if tier == "auto":
                tier = "serial" if self.hazards.transfers_hazard(arr) else "fast"
            # the [stats] `split` surface: which tier each batch took
            self.hazards.plan_stats[tier] += 1
            fn = (
                self.kernels.commit_transfers_fast
                if tier == "fast"
                else self.kernels.commit_transfers_serial
            )
            rows = _to_rows_np(arr, n_pad)
            self._xfer_used += counts
            self._note_rows()
        elif operation == Operation.create_accounts:
            arr = events if isinstance(events, np.ndarray) else t.accounts_to_np(events)
            id_limbs = (arr["id_lo"].copy(), arr["id_hi"].copy())
            counts = self._shard_counts(*id_limbs)
            if ((self._acct_used + counts) > self._acct_limit).any():
                raise RuntimeError(
                    "an account shard is at its load-factor limit: grow "
                    "ConfigProcess.account_slots_log2 (per-shard capacity)"
                )
            mode = self.mode
            if mode == "auto":
                mode = "serial" if self.hazards.accounts_hazard(arr) else "fast"
            self.hazards.note_limit_accounts(arr)
            fn = (
                self.kernels.commit_accounts_fast
                if mode == "fast"
                else self.kernels.commit_accounts_serial
            )
            rows = _to_rows_np(arr, n_pad)
            self._acct_used += counts
        else:
            raise AssertionError(operation)
        batch = {"rows": jax.device_put(rows, self._replicated)}
        t_launch = perf_counter_ns()  # rows on their way: kernel next
        self.state, results = fn(self.state, batch, nn, np.uint64(timestamp))
        results, summary = self._summarize(results, nn)
        self._note_launch(results, t_launch, 1, 1, tier)
        return PendingBatch(
            operation, n, results, flags=arr["flags"].copy(),
            id_limbs=id_limbs, summary=summary,
        )

    def _uncharge(self, pending: PendingBatch, not_applied: np.ndarray) -> None:
        # rolled-back inserts tombstone their slot on the owner shard and
        # still occupy it (models.ledger.applied_insert_mask): only lanes
        # that never inserted come off their owner's charge
        if not_applied.any():
            lo, hi = pending.id_limbs
            dec = self._shard_counts(lo[not_applied], hi[not_applied])
            if pending.operation == Operation.create_transfers:
                self._xfer_used -= dec
                self._note_rows()
            else:
                self._acct_used -= dec

    def check_fault(self) -> None:
        """Raise if the mesh hit the fault protocol. Synchronizes with the
        devices: for the snapshot and direct callers; a commit's own fault
        word comes home on its results and raises at drain."""
        raise_on_fault(int(np.asarray(self.state["fault"])), self.fault_name)

    # -- parity extraction (lookups come from HostLedgerBase) --

    def extract(self):
        """Pull the full sharded state to host dicts (accounts, transfers,
        posted) for bit-exact comparison against the oracle."""
        from tigerbeetle_tpu import types as t
        from tigerbeetle_tpu.models.ledger import _occupied_rows

        accounts: dict[int, object] = {}
        transfers: dict[int, object] = {}
        posted: dict[int, int] = {}
        acct = np.asarray(self.state["acct_rows"])
        xfer = np.asarray(self.state["xfer_rows"])
        ful = np.asarray(self.state["fulfill"])
        for s in range(self.n_shards):
            rows = acct[s][:-1]
            occ = _occupied_rows(rows)
            arr = np.frombuffer(rows[occ].tobytes(), dtype=t.ACCOUNT_DTYPE)
            for i in range(len(arr)):
                a = t.Account.from_np(arr[i])
                accounts[a.id] = a
            rows = xfer[s][:-1]
            occ = _occupied_rows(rows)
            arr = np.frombuffer(rows[occ].tobytes(), dtype=t.TRANSFER_DTYPE)
            fu = ful[s][:-1][occ]
            for i in range(len(arr)):
                x = t.Transfer.from_np(arr[i])
                transfers[x.id] = x
                if fu[i]:
                    posted[x.timestamp] = int(fu[i])
        return accounts, transfers, posted

    @property
    def commit_timestamp(self) -> int:
        return int(np.asarray(self.state["commit_ts"]))

    # -- checkpoint / state sync (the replica's blob snapshot seam) --

    _SNAP_SHARDED = (
        "acct_rows", "xfer_rows", "fulfill", "acct_claim", "xfer_claim",
        "bal_acc", "acct_used_slots", "xfer_used_slots",
    )
    _SNAP_REPLICATED = ("commit_ts", "acct_count", "xfer_count", "fault")

    def snapshot_bytes(self) -> bytes:
        """Serialize the full sharded state (one host pull per leaf) plus
        the host-side admission state — the replica checkpoints this as its
        snapshot blob, and state sync ships the same bytes. Byte-identical
        across replicas with identical histories (the determinism
        contract)."""
        import json

        self.check_fault()
        parts = [
            np.asarray(self.state[k]).tobytes()
            for k in self._SNAP_SHARDED + self._SNAP_REPLICATED
        ]
        h = self.hazards
        head = json.dumps({
            "n_shards": self.n_shards,
            "acct_slots_log2": self.process.account_slots_log2,
            "xfer_slots_log2": self.process.transfer_slots_log2,
            "sizes": [len(p) for p in parts],
            "acct_used": self._acct_used.tolist(),
            "xfer_used": self._xfer_used.tolist(),
            "amount_sum": str(h.amount_sum),
            "limit_account_ids": [str(x) for x in sorted(h.limit_account_ids)],
        }, sort_keys=True).encode()
        return len(head).to_bytes(4, "little") + head + b"".join(parts)

    def restore_bytes(self, raw: bytes) -> None:
        import json

        hn = int.from_bytes(raw[:4], "little")
        head = json.loads(raw[4 : 4 + hn])
        if (
            head["n_shards"] != self.n_shards
            or head["acct_slots_log2"] != self.process.account_slots_log2
            or head["xfer_slots_log2"] != self.process.transfer_slots_log2
        ):
            raise RuntimeError(
                "sharded checkpoint geometry mismatch: snapshot is "
                f"{head['n_shards']} shards @ 2^{head['acct_slots_log2']}/"
                f"2^{head['xfer_slots_log2']}, this mesh is "
                f"{self.n_shards} @ 2^{self.process.account_slots_log2}/"
                f"2^{self.process.transfer_slots_log2}"
            )
        # Each leaf goes host -> its own shards directly (device_put of the
        # numpy array with the leaf's sharding): no whole-table staging on
        # the default device. The outgoing state is released FIRST — only
        # its metadata is kept — so a restore never holds two full states.
        layout = {
            k: (v.dtype, v.shape, v.sharding) for k, v in self.state.items()
        }
        self.state = {}
        off = 4 + hn
        names = self._SNAP_SHARDED + self._SNAP_REPLICATED
        for name, size in zip(names, head["sizes"]):
            dtype, shape, sharding = layout[name]
            host = np.frombuffer(raw[off : off + size], dtype=dtype)
            self.state[name] = jax.device_put(host.reshape(shape), sharding)
            off += size
        self._acct_used = np.array(head["acct_used"], dtype=np.int64)
        self._xfer_used = np.array(head["xfer_used"], dtype=np.int64)
        h = self.hazards
        h.amount_sum = int(head["amount_sum"])
        h.limit_account_ids = {int(x) for x in head["limit_account_ids"]}
        h._limit_lo = np.sort(np.array(
            [int(x) & ((1 << 64) - 1) for x in head["limit_account_ids"]],
            dtype=np.uint64,
        ))
