"""The plain reference the benchmark decides `correct` against.

`ReferenceLedger` replays a cell's committed requests in commit order, with
the timestamps the cluster assigned, through the scalar state machine of
scalar.py. A run of plain (or of pending) transfers between accounts
without balance limits commutes, so such a run inside a batch takes a numpy
path that gives the same answers as the scalar loop (benchmarks/tests checks
the two against each other); everything else (linked chains, post/void, a
limit account, a duplicate id, any field out of the ordinary) runs event by
event, in the order of the batch.

It imports nothing of the program and takes nothing the program has made
but the commit order and timestamps, which only consensus can assign.

`fault` turns it into the CONTROL: the reference put in the program's
place with one stated guarantee broken (see CONTROLS).
"""

from __future__ import annotations

import bisect

import numpy as np

from .scalar import OracleStateMachine
from .wire_types import (
    ACCOUNT_DTYPE,
    TRANSFER_DTYPE,
    Account,
    AccountFlags,
    Operation,
    Transfer,
    TransferFlags,
    transfers_to_np,
)

# what each control breaks, of the guarantees the configurations state
CONTROLS = {
    # "result codes equal the reference": balance limits are not enforced
    # (every add treated as commuting, the step a fused kernel tempts)
    "ignore_limits": "debits_must_not_exceed_credits is not enforced",
    # "acknowledged => durable and read back" / "the chip's state equals
    # the engine's": one acknowledged batch never reaches the tables
    "lost_ack": "one acknowledged create_transfers batch is not applied",
}

_LIMIT_FLAGS = int(
    AccountFlags.debits_must_not_exceed_credits
    | AccountFlags.credits_must_not_exceed_debits
)
_HI_FIELDS = (
    "id_hi", "debit_account_id_hi", "credit_account_id_hi", "amount_hi",
    "pending_id_lo", "pending_id_hi", "timeout", "timestamp",
)
_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
MIN_RUN = 32  # shorter runs of plain events are not worth a numpy pass


class TransferStore:
    """The scalar machine's `transfers` mapping, with bulk batches kept as
    the arrays they arrived in. Bulk rows are immutable (post/void inserts
    a new transfer, it never rewrites the pending one)."""

    def __init__(self) -> None:
        self.objs: dict[int, Transfer] = {}
        self._starts: list[int] = []  # sorted first ids of bulk batches
        self._bulk: list[tuple] = []  # aligned: (lo, hi, sorted ids, order, rows)
        self._overlap = False
        self.bulk_rows = 0

    def add_bulk(self, rows: np.ndarray) -> None:
        ids = rows["id_lo"]
        order = np.argsort(ids, kind="stable")
        sorted_ids = ids[order]
        lo, hi = int(sorted_ids[0]), int(sorted_ids[-1])
        k = bisect.bisect_left(self._starts, lo)
        if (k > 0 and self._bulk[k - 1][1] >= lo) or (
            k < len(self._bulk) and self._bulk[k][0] <= hi
        ):
            self._overlap = True
        self._starts.insert(k, lo)
        self._bulk.insert(k, (lo, hi, sorted_ids, order, rows))
        self.bulk_rows += len(rows)

    def _bulk_row(self, key: int):
        if key > 0xFFFFFFFFFFFFFFFF or not self._bulk:
            return None
        if self._overlap:
            cands = [b for b in self._bulk if b[0] <= key <= b[1]]
        else:
            k = bisect.bisect_right(self._starts, key) - 1
            cands = [self._bulk[k]] if k >= 0 and self._bulk[k][1] >= key else []
        for _lo, _hi, sorted_ids, order, rows in cands:
            j = int(np.searchsorted(sorted_ids, np.uint64(key)))
            if j < len(sorted_ids) and int(sorted_ids[j]) == key:
                return rows[int(order[j])]
        return None

    def get(self, key: int, default=None):
        t = self.objs.get(key)
        if t is not None:
            return t
        row = self._bulk_row(key)
        if row is None:
            return default
        return _records(Operation.create_transfers, row.reshape(1))[0]

    def __getitem__(self, key: int) -> Transfer:
        t = self.get(key)
        if t is None:
            raise KeyError(key)
        return t

    def __contains__(self, key: int) -> bool:
        return key in self.objs or self._bulk_row(key) is not None

    def __setitem__(self, key: int, t: Transfer) -> None:
        self.objs[key] = t

    def __delitem__(self, key: int) -> None:
        del self.objs[key]

    def __len__(self) -> int:
        return len(self.objs) + self.bulk_rows

    def contains_any(self, ids: np.ndarray) -> bool:
        """Does any of these (hi == 0) ids exist already?"""
        lo, hi = int(ids.min()), int(ids.max())
        for b_lo, b_hi, sorted_ids, _order, _rows in self._bulk:
            if b_lo <= hi and b_hi >= lo and np.isin(ids, sorted_ids).any():
                return True
        objs = self.objs
        return bool(objs) and any(i in objs for i in ids.tolist())

    def rows_for(self, ids: list[int]) -> bytes:
        """Wire rows of the ids that exist, in the order asked."""
        out = []
        for i in ids:
            t = self.objs.get(i)
            if t is not None:
                out.append(transfers_to_np([t]).tobytes())
            else:
                row = self._bulk_row(i)
                if row is not None:
                    out.append(row.tobytes())
        return b"".join(out)

    def all_rows(self) -> np.ndarray:
        parts = [b[4] for b in self._bulk]
        if self.objs:
            parts.append(transfers_to_np(list(self.objs.values())))
        if not parts:
            return np.zeros(0, dtype=TRANSFER_DTYPE)
        return np.concatenate(parts)


class _NoLimitAccount(Account):
    """ignore_limits control: the limit checks never fire."""

    def debits_exceed_credits(self, amount: int) -> bool:
        return False

    def credits_exceed_debits(self, amount: int) -> bool:
        return False


class ReferenceLedger(OracleStateMachine):
    def __init__(self, fault: str | None = None, fault_at: int = 0) -> None:
        super().__init__()
        if fault is not None and fault not in CONTROLS:
            raise ValueError(f"unknown control {fault!r}")
        self.fault = fault
        self.fault_at = fault_at  # lost_ack: which create_transfers batch
        self.transfers = TransferStore()
        self.fast_batches = self.scalar_batches = 0  # batches with no / some scalar events
        self.fast_events = self.scalar_events = 0
        self._transfer_batches = 0
        self._index_n = -1
        self._balance_bound = 0

    # -- the numpy path ---------------------------------------------------

    def _account_index(self):
        if self._index_n != len(self.accounts):
            accts = [a for a in self.accounts.values() if a.id <= 0xFFFFFFFFFFFFFFFF]
            accts.sort(key=lambda a: a.id)
            self._idx_objs = accts
            self._idx_ids = np.array([a.id for a in accts], dtype=np.uint64)
            self._idx_ledger = np.array([a.ledger for a in accts], dtype=np.uint32)
            self._idx_limited = np.array(
                [bool(a.flags & _LIMIT_FLAGS) for a in accts], dtype=bool
            )
            self._index_n = len(self.accounts)
        return self._idx_ids

    def _plain_mask(self, rows: np.ndarray) -> np.ndarray:
        """Events that may join a numpy run: plain or pending, every field
        ordinary, both accounts present, unlimited and of the transfer's
        ledger, and not the tail of a linked chain."""
        flags = rows["flags"]
        ok = (flags == 0) | (flags == int(TransferFlags.pending))
        for f in _HI_FIELDS:
            ok &= rows[f] == 0
        ids = rows["id_lo"]
        dr, cr = rows["debit_account_id_lo"], rows["credit_account_id_lo"]
        ok &= ((ids != 0) & (ids != _U64_MAX) & (dr != 0) & (cr != 0)
               & (dr != cr) & (rows["amount_lo"] != 0) & (rows["ledger"] != 0)
               & (rows["code"] != 0))
        linked = (flags & int(TransferFlags.linked)) != 0
        ok[1:] &= ~linked[:-1]
        acct_ids = self._account_index()
        if len(acct_ids) == 0:
            return np.zeros(len(rows), dtype=bool)
        last = len(acct_ids) - 1
        di = np.minimum(np.searchsorted(acct_ids, dr), last)
        ci = np.minimum(np.searchsorted(acct_ids, cr), last)
        ok &= (acct_ids[di] == dr) & (acct_ids[ci] == cr)
        ok &= ~(self._idx_limited[di] | self._idx_limited[ci])
        ledger = rows["ledger"]
        ok &= (self._idx_ledger[di] == ledger) & (self._idx_ledger[ci] == ledger)
        return ok

    def _apply_run(self, rows: np.ndarray, first_timestamp: int) -> bool:
        """Apply a run of events that _plain_mask passed and that share one
        flag value: all of them succeed. False (nothing done) when an id
        repeats or exists, or an overflow code is possible."""
        n = len(rows)
        ids, amount = rows["id_lo"], rows["amount_lo"]
        if len(np.unique(ids)) != n or self.transfers.contains_any(ids):
            return False
        total = int(amount.sum(dtype=object))
        if self._balance_bound + total >= 1 << 63:
            return False
        self._balance_bound += total
        acct_ids = self._account_index()
        di = np.searchsorted(acct_ids, rows["debit_account_id_lo"])
        ci = np.searchsorted(acct_ids, rows["credit_account_id_lo"])
        stored = rows.copy()
        stored["timestamp"] = np.arange(
            first_timestamp, first_timestamp + n, dtype=np.uint64)
        self.transfers.add_bulk(stored)
        dsum = np.zeros(len(acct_ids), dtype=np.uint64)
        csum = np.zeros(len(acct_ids), dtype=np.uint64)
        np.add.at(dsum, di, amount)
        np.add.at(csum, ci, amount)
        d_field, c_field = (
            ("debits_pending", "credits_pending") if int(rows["flags"][0])
            else ("debits_posted", "credits_posted"))
        objs = self._idx_objs
        for i in np.flatnonzero(dsum).tolist():
            a = objs[i]
            setattr(a, d_field, getattr(a, d_field) + int(dsum[i]))
        for i in np.flatnonzero(csum).tolist():
            a = objs[i]
            setattr(a, c_field, getattr(a, c_field) + int(csum[i]))
        self.commit_timestamp = first_timestamp + n - 1
        return True

    def _execute_transfers(self, timestamp: int, rows: np.ndarray) -> list:
        """scalar.execute's loop for create_transfers, with every long run
        of commuting events (see _plain_mask) applied whole. A run never
        starts inside a linked chain, so the chain bookkeeping below is the
        scalar loop's, unchanged."""
        n = len(rows)
        plain = self._plain_mask(rows)
        flags = rows["flags"]
        # maximal runs of plain events with one flag value
        runs: dict[int, int] = {}
        edges = np.flatnonzero(
            np.r_[True, (plain[1:] != plain[:-1]) | (flags[1:] != flags[:-1]), True])
        for a, b in zip(edges[:-1].tolist(), edges[1:].tolist()):
            if plain[a] and b - a >= MIN_RUN:
                runs[a] = b
        in_run = np.zeros(n, dtype=bool)
        for a, b in runs.items():
            in_run[a:b] = True
        scalar_idx = np.flatnonzero(~in_run)
        recs = dict(zip(scalar_idx.tolist(),
                        _records(Operation.create_transfers, rows[scalar_idx])))
        self.fast_events += int(in_run.sum())
        self.scalar_events += len(scalar_idx)

        results: list[tuple[int, int]] = []
        chain = None
        chain_broken = False
        index = 0
        while index < n:
            end = runs.get(index)
            if end is not None:
                assert chain is None
                if self._apply_run(rows[index:end], timestamp - n + index + 1):
                    index = end
                    continue
                recs.update(zip(range(index, end), _records(
                    Operation.create_transfers, rows[index:end])))
                for k in range(index, end):
                    runs.pop(k, None)
            event = recs[index]
            result = None
            if event.flags & 0x1:  # linked
                if chain is None:
                    chain = index
                    assert not chain_broken
                    self._scope_open()
                if index == n - 1:
                    result = 2  # linked_event_chain_open
            if result is None and chain_broken:
                result = 1  # linked_event_failed
            if result is None and event.timestamp != 0:
                result = 3  # timestamp_must_be_zero
            if result is None:
                event.timestamp = timestamp - n + index + 1
                result = int(self.create_transfer(event))
            if result != 0:
                if chain is not None:
                    if not chain_broken:
                        chain_broken = True
                        self._scope_close(persist=False)
                        for chain_index in range(chain, index):
                            results.append((chain_index, 1))
                    else:
                        assert result in (1, 2)
                results.append((index, result))
            if chain is not None and (not (event.flags & 0x1) or result == 2):
                if not chain_broken:
                    self._scope_close(persist=True)
                chain = None
                chain_broken = False
            index += 1
        assert chain is None and not chain_broken
        return results

    # -- the replay entry -------------------------------------------------

    def execute(self, operation, timestamp: int, events) -> list[tuple[int, int]]:
        operation = Operation(int(operation))
        if operation == Operation.create_transfers:
            k = self._transfer_batches
            self._transfer_batches += 1
            if self.fault == "lost_ack" and k == self.fault_at:
                # answers as the sound machine would, keeps nothing
                probe = _clone_for_probe(self)
                return probe.execute(operation, timestamp, events)
            if len(events) == 0 or self._scope is not None:
                return super().execute(operation, timestamp, [])
            self._balance_bound += (
                1 << 63 if events["amount_hi"].any()
                else int(events["amount_lo"].sum(dtype=object))
            )
            before = self.scalar_events
            results = self._execute_transfers(timestamp, events)
            if self.scalar_events == before:
                self.fast_batches += 1
            else:
                self.scalar_batches += 1
            return results
        results = super().execute(operation, timestamp, _records(operation, events))
        if operation == Operation.create_accounts and self.fault == "ignore_limits":
            for key, a in list(self.accounts.items()):
                if type(a) is Account:
                    self.accounts[key] = _NoLimitAccount(**vars(a))
            self._index_n = -1
        return results

    def _put_account(self, a: Account) -> None:
        # the scalar loop replaces account objects; the index holds objects
        super()._put_account(a)
        self._index_n = -1

    def _scope_close(self, persist: bool) -> None:
        super()._scope_close(persist)
        self._index_n = -1

    # -- what the comparison reads ---------------------------------------

    def lookup_account_rows(self, ids: list[int]) -> bytes:
        accounts = self.accounts
        return _account_rows([accounts[i] for i in ids if i in accounts]).tobytes()

    def lookup_transfer_rows(self, ids: list[int]) -> bytes:
        return self.transfers.rows_for(ids)

    def fingerprint(self) -> dict:
        """The state digest the server's finalize reports for the chip's
        tables (fp_rows below), computed over the reference's rows."""
        afp, alive = fp_rows(_account_rows(list(self.accounts.values())))
        tfp, tlive = fp_rows(self.transfers.all_rows())
        return {
            "accounts_fp": afp, "transfers_fp": tfp, "accounts": alive,
            "transfers": tlive, "commit_timestamp": self.commit_timestamp,
        }


_M64 = 0xFFFFFFFFFFFFFFFF


def _records(operation, rows: np.ndarray) -> list:
    """Wire rows -> record objects, through one tolist() (field by field
    reads of numpy scalars cost ten times the state machine itself)."""
    out = []
    if operation == Operation.create_accounts:
        for (id_lo, id_hi, dp_lo, dp_hi, dpo_lo, dpo_hi, cp_lo, cp_hi, cpo_lo,
             cpo_hi, ud128_lo, ud128_hi, ud64, ud32, reserved, ledger, code,
             flags, ts) in rows.tolist():
            out.append(Account(
                id_lo | id_hi << 64, dp_lo | dp_hi << 64, dpo_lo | dpo_hi << 64,
                cp_lo | cp_hi << 64, cpo_lo | cpo_hi << 64,
                ud128_lo | ud128_hi << 64, ud64, ud32, reserved, ledger, code,
                flags, ts))
    else:
        for (id_lo, id_hi, dr_lo, dr_hi, cr_lo, cr_hi, am_lo, am_hi, pe_lo,
             pe_hi, ud128_lo, ud128_hi, ud64, ud32, timeout, ledger, code,
             flags, ts) in rows.tolist():
            out.append(Transfer(
                id_lo | id_hi << 64, dr_lo | dr_hi << 64, cr_lo | cr_hi << 64,
                am_lo | am_hi << 64, pe_lo | pe_hi << 64,
                ud128_lo | ud128_hi << 64, ud64, ud32, timeout, ledger, code,
                flags, ts))
    return out


def _account_rows(accounts: list) -> np.ndarray:
    """accounts_to_np by columns (one pass a field, not a row)."""
    out = np.zeros(len(accounts), dtype=ACCOUNT_DTYPE)
    for f in ("id", "debits_pending", "debits_posted", "credits_pending",
              "credits_posted", "user_data_128"):
        vals = [getattr(a, f) for a in accounts]
        out[f + "_lo"] = [v & _M64 for v in vals]
        out[f + "_hi"] = [v >> 64 for v in vals]
    for f in ("user_data_64", "user_data_32", "reserved", "ledger", "code",
              "flags", "timestamp"):
        out[f] = [getattr(a, f) for a in accounts]
    return out


def _clone_for_probe(ref: ReferenceLedger) -> OracleStateMachine:
    """A throwaway scalar machine over copies of the stores: the answers
    of a batch without its effects."""
    import copy

    probe = OracleStateMachine()
    probe.accounts = {k: copy.copy(v) for k, v in ref.accounts.items()}
    shadow = TransferStore()  # shares the bulk rows; writes land in its own dict
    shadow.objs = dict(ref.transfers.objs)
    shadow._starts, shadow._bulk = ref.transfers._starts, ref.transfers._bulk
    shadow._overlap, shadow.bulk_rows = ref.transfers._overlap, ref.transfers.bulk_rows
    probe.transfers = shadow
    probe.posted = dict(ref.posted)
    probe.commit_timestamp = ref.commit_timestamp
    return probe


# ---------------------------------------------------------------------------
# the row-set digest (formula of the program's state_fingerprint, written
# down here: per-row content hash, commutative sum, so slot order is free)
# ---------------------------------------------------------------------------

_FP_SEED = np.uint64(0x9E3779B97F4A7C15)
_FP_MUL = np.uint64(0xC2B2AE3D27D4EB4F)
_FP_ADD = np.uint64(0x165667B19E3779F9)
_FP_MIX1 = np.uint64(0xFF51AFD7ED558CCD)
_FP_MIX2 = np.uint64(0xC4CEB9FE1A85EC53)
ROW_WORDS = 32


def fp_rows(rows: np.ndarray) -> tuple[int, int]:
    """(sum of row hashes mod 2^64, live rows) over 128-byte wire rows."""
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


__all__ = ["ReferenceLedger", "TransferStore", "CONTROLS", "fp_rows",
           "ACCOUNT_DTYPE", "TRANSFER_DTYPE"]
