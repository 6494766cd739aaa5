"""The plain reference the benchmark decides `correct` against.

`ReferenceLedger` replays a cell's committed requests in commit order, with
the timestamps the cluster assigned, through the scalar state machine of
scalar.py. Where a batch's events provably all succeed, and commute, it
applies them in numpy passes that give the same answers and the same state
as the scalar loop (benchmarks/tests checks each against it):

- a batch of ordinary create_accounts (new distinct ids, no high limb,
  valid flags, every field in range) lands in the account table whole;
- a run of plain (or of pending) transfers between accounts of its ledger,
  whole linked chains of such transfers among them; over accounts with a
  balance limit it forms each account's running debits and credits in
  event order and checks the limit at every event; the run is cut before
  the chain of the first event that crosses one, and the events behind it
  are a run again while the cut run applied MIN_RUN events or more;
- a run of posts and voids of distinct, unresolved, unexpired pending
  transfers that exist before it.

Everything else (a post of a resolved pending transfer, a broken chain, a
duplicate id, any field out of the ordinary) runs event by event, in the
order of the batch, on account objects read out of the table; they are
written back when the batch ends.

It imports nothing of the program and takes nothing the program has made
but the commit order and timestamps, which only consensus can assign.

`fault` turns it into the CONTROL: the reference put in the program's
place with one stated guarantee broken (see CONTROLS).
"""

from __future__ import annotations

import bisect
import copy

import numpy as np

from .scalar import POSTED, VOIDED, OracleStateMachine
from .wire_types import (
    ACCOUNT_DTYPE,
    NS_PER_S,
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

_DEBITS_LIMIT = int(AccountFlags.debits_must_not_exceed_credits)
_CREDITS_LIMIT = int(AccountFlags.credits_must_not_exceed_debits)
_LIMIT_FLAGS = _DEBITS_LIMIT | _CREDITS_LIMIT
_LINKED = int(TransferFlags.linked)
_PENDING = int(TransferFlags.pending)
_POST = int(TransferFlags.post_pending_transfer)
_VOID = int(TransferFlags.void_pending_transfer)
_HI_FIELDS = (
    "id_hi", "debit_account_id_hi", "credit_account_id_hi", "amount_hi",
    "pending_id_lo", "pending_id_hi", "timeout", "timestamp",
)
_PV_ZERO = ("id_hi", "debit_account_id_hi", "credit_account_id_hi",
            "amount_hi", "pending_id_hi", "timeout", "timestamp")
_BALANCES = ("debits_pending", "debits_posted", "credits_pending",
             "credits_posted")
_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_M64 = 0xFFFFFFFFFFFFFFFF
# shorter runs are not worth a numpy pass; a run cut by a limit crossing
# is taken up again behind it only if the cut run applied this many events,
# so a batch makes at most len / MIN_RUN passes and one that crosses
# densely goes event by event
MIN_RUN = 32
# numpy run kinds of an event
_SCALAR, _PLAIN, _PEND, _RESOLVE = 0, 1, 2, 3
PATHS = ("accounts", "plain", "limited", "chains", "resolve")


def _rows_for(ids, find, objs: dict, encode) -> bytes:
    """Wire rows of the ids that exist, in the order asked. `ids` is a
    (lo, hi) pair of limb arrays, as a lookup's body holds them, or ints;
    `find(lo)` gives (rows, found) of a store's table, and an object of
    `objs` (by id) stands for its row."""
    if not isinstance(ids, tuple):
        ids = list(ids)
        ids = (np.array([i & _M64 for i in ids], dtype=np.uint64),
               np.array([i >> 64 for i in ids], dtype=np.uint64))
    lo, hi = ids
    out, found = find(lo)
    found &= hi == 0
    if objs:
        for i, key in enumerate(zip(lo.tolist(), hi.tolist())):
            o = objs.get(key[0] | key[1] << 64)
            if o is not None:
                out[i] = encode([o])[0]
                found[i] = True
    return out[found].tobytes()


class AccountStore:
    """The scalar machine's `accounts` mapping, held as one table of wire
    rows (a bulk batch is appended whole, its ids sorted) with an index on
    the low limb. An account a scalar event reads is taken out as an
    object, and the object is the truth until `flush` writes it back at the
    end of the batch (or before a numpy run). Ids with a high limb stay
    objects. A probe's copy (`read_only`) never writes the table."""

    def __init__(self) -> None:
        self.rows = np.zeros(0, dtype=ACCOUNT_DTYPE)
        self._ids = np.zeros(0, dtype=np.uint64)  # rows' id_lo, contiguous
        self.n = 0
        self.objs: dict[int, Account] = {}
        self.account_cls = Account
        self.read_only = False
        self._in_order = True  # table rows ascending by id: the index is free
        self._sorted = None  # (sorted ids, row of each), once out of order

    # -- the table ----------------------------------------------------------

    def append(self, rows: np.ndarray) -> None:
        """New accounts (distinct ids not in the table, high limb 0)."""
        rows = rows[np.argsort(rows["id_lo"], kind="stable")]
        need = self.n + len(rows)
        if need > len(self.rows):
            cap = max(need, 2 * len(self.rows))
            grown = np.zeros(cap, dtype=ACCOUNT_DTYPE)
            grown[:self.n] = self.rows[:self.n]
            ids = np.zeros(cap, dtype=np.uint64)
            ids[:self.n] = self._ids[:self.n]
            self.rows, self._ids = grown, ids
        if self.n and rows["id_lo"][0] <= self._ids[self.n - 1]:
            self._in_order = False
        self.rows[self.n:need] = rows
        self._ids[self.n:need] = rows["id_lo"]
        self.n = need
        self._sorted = None

    def find(self, ids: np.ndarray) -> np.ndarray:
        """The table row of each (high limb 0) id, -1 where none."""
        ids = np.asarray(ids, dtype=np.uint64)
        if self.n == 0:
            return np.full(len(ids), -1, dtype=np.int64)
        if self._in_order:
            sid, order = self._ids[:self.n], None
        else:
            if self._sorted is None:
                order = np.argsort(self._ids[:self.n], kind="stable")
                self._sorted = (self._ids[:self.n][order], order)
            sid, order = self._sorted
        j = np.minimum(np.searchsorted(sid, ids), self.n - 1)
        pos = j if order is None else order[j]
        return np.where(sid[j] == ids, pos, -1)

    def _row(self, key: int) -> int:
        if key > _M64 or self.n == 0:
            return -1
        return int(self.find(np.array([key], dtype=np.uint64))[0])

    def flush(self) -> None:
        """Every object back into the table (outside a linked chain)."""
        if not self.objs or self.read_only:
            return
        back = [a for k, a in self.objs.items() if k <= _M64]
        self.objs = {k: a for k, a in self.objs.items() if k > _M64}
        if not back:
            return
        rows = _account_rows(back)
        pos = self.find(rows["id_lo"])
        old = pos >= 0
        self.rows[pos[old]] = rows[old]
        if not old.all():
            self.append(rows[~old])

    def all_rows(self) -> np.ndarray:
        rows = self.rows[:self.n]
        if not self.objs:
            return rows
        rows = rows.copy()
        objs = list(self.objs.values())
        obj_rows = _account_rows(objs)
        pos = np.array([self._row(a.id) for a in objs], dtype=np.int64)
        rows[pos[pos >= 0]] = obj_rows[pos >= 0]
        return np.concatenate([rows, obj_rows[pos < 0]])

    def find_rows(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(rows, found) of the table rows of these (high limb 0) ids."""
        pos = self.find(ids)
        found = pos >= 0
        out = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
        out[found] = self.rows[pos[found]]
        return out, found

    def rows_for(self, ids) -> bytes:
        return _rows_for(ids, self.find_rows, self.objs, _account_rows)

    # -- the mapping the scalar machine reads and writes -----------------

    def get(self, key: int, default=None):
        a = self.objs.get(key)
        if a is not None:
            return a
        pos = self._row(key)
        if pos < 0:
            return default
        a = _records(Operation.create_accounts, self.rows[pos:pos + 1],
                     self.account_cls)[0]
        self.objs[key] = a
        return a

    def __getitem__(self, key: int) -> Account:
        a = self.get(key)
        if a is None:
            raise KeyError(key)
        return a

    def __contains__(self, key: int) -> bool:
        return key in self.objs or self._row(key) >= 0

    def __setitem__(self, key: int, a: Account) -> None:
        if type(a) is not self.account_cls:
            a = self.account_cls(**vars(a))
        self.objs[key] = a

    def __delitem__(self, key: int) -> None:
        del self.objs[key]  # only an account created inside a chain

    def keys(self) -> list[int]:
        extra = [k for k in self.objs if self._row(k) < 0]
        return self._ids[:self.n].tolist() + extra

    def __iter__(self):
        return iter(self.keys())

    def values(self) -> list[Account]:
        return _records(Operation.create_accounts, self.all_rows(),
                        self.account_cls)


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

    def _segments(self, lo: int, hi: int) -> list:
        """The bulk batches whose id range meets [lo, hi]."""
        if self._overlap:
            return [b for b in self._bulk if b[0] <= hi and b[1] >= lo]
        out = []
        k = bisect.bisect_right(self._starts, hi) - 1
        while k >= 0 and self._bulk[k][1] >= lo:  # ranges disjoint: hi ascends too
            out.append(self._bulk[k])
            k -= 1
        return out

    def find(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(rows, found) of the bulk rows of these (high limb 0) ids."""
        out = np.zeros(len(keys), dtype=TRANSFER_DTYPE)
        found = np.zeros(len(keys), dtype=bool)
        if len(keys) == 0:
            return out, found
        for _lo, _hi, sorted_ids, order, rows in self._segments(
                int(keys.min()), int(keys.max())):
            j = np.minimum(np.searchsorted(sorted_ids, keys), len(sorted_ids) - 1)
            hit = sorted_ids[j] == keys
            out[hit] = rows[order[j[hit]]]
            found |= hit
        return out, found

    def _bulk_row(self, key: int):
        if key > _M64:
            return None
        for _lo, _hi, sorted_ids, order, rows in self._segments(key, key):
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
        for _lo, _hi, sorted_ids, _order, _rows in self._segments(
                int(ids.min()), int(ids.max())):
            if np.isin(ids, sorted_ids).any():
                return True
        return bool(self.objs) and not self.objs.keys().isdisjoint(ids.tolist())

    def rows_for(self, ids) -> bytes:
        return _rows_for(ids, self.find, self.objs, transfers_to_np)

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
        self.accounts = AccountStore()
        self._limits = fault != "ignore_limits"
        if not self._limits:
            self.accounts.account_cls = _NoLimitAccount
        self.transfers = TransferStore()
        self.fast_batches = self.scalar_batches = 0  # batches with no / some scalar events
        self.fast_events = self.scalar_events = 0
        self.path_events = dict.fromkeys(PATHS, 0)  # events each numpy path applied
        self._transfer_batches = 0
        self._balance_bound = 0  # no balance can pass it (< 2**63: u64 sums are exact)

    # -- create_accounts --------------------------------------------------

    def _create_accounts_bulk(self, timestamp: int, rows: np.ndarray) -> bool:
        """Append a batch whose every event is ordinary and new: all of
        them succeed. False (nothing done) otherwise."""
        n = len(rows)
        flags = rows["flags"]
        ids = rows["id_lo"]
        ok = (((flags & np.uint16(0xFFFF ^ _LIMIT_FLAGS)) == 0)
              & ((flags & _LIMIT_FLAGS) != _LIMIT_FLAGS)
              & (ids != 0) & (rows["id_hi"] == 0) & (rows["reserved"] == 0)
              & (rows["ledger"] != 0) & (rows["code"] != 0)
              & (rows["timestamp"] == 0))
        for f in _BALANCES:
            ok &= (rows[f + "_lo"] == 0) & (rows[f + "_hi"] == 0)
        if not ok.all() or len(np.unique(ids)) != n or (
                self.accounts.find(ids) >= 0).any():
            return False
        stored = rows.copy()
        stored["timestamp"] = np.arange(timestamp - n + 1, timestamp + 1,
                                        dtype=np.uint64)
        self.accounts.append(stored)
        self.commit_timestamp = timestamp
        return True

    # -- create_transfers: which events may join a numpy run --------------

    def _kinds(self, rows: np.ndarray):
        """Each event's run kind (_SCALAR where the numpy paths cannot
        vouch for it), its debit and credit accounts' table rows, and the
        bounds [start, end) of the linked chain it is in (itself alone
        outside a chain). A chain keeps a kind only when every event of it
        has that kind, and it is closed."""
        n = len(rows)
        flags = rows["flags"]
        base = flags & np.uint16(0xFFFF ^ _LINKED)
        ids = rows["id_lo"]
        dr, cr = rows["debit_account_id_lo"], rows["credit_account_id_lo"]
        acc = self.accounts
        dpos, cpos = acc.find(dr), acc.find(cr)

        plain = (base == 0) | (base == _PENDING)
        for f in _HI_FIELDS:
            plain &= rows[f] == 0
        plain &= ((ids != 0) & (ids != _U64_MAX) & (dr != 0) & (cr != 0)
                  & (dr != cr) & (rows["amount_lo"] != 0) & (rows["ledger"] != 0)
                  & (rows["code"] != 0) & (dpos >= 0) & (cpos >= 0))
        if acc.n:
            ledger = acc.rows["ledger"]
            plain &= ((ledger[dpos] == rows["ledger"])
                      & (ledger[cpos] == rows["ledger"]))
        pend = rows["pending_id_lo"]
        resolve = ((flags == _POST) | (flags == _VOID)) & (ids != 0) & (
            pend != 0) & (pend != ids)
        for f in _PV_ZERO:
            resolve &= rows[f] == 0
        kind = np.where(plain, np.where(base == 0, _PLAIN, _PEND),
                        np.where(resolve, _RESOLVE, _SCALAR)).astype(np.int8)

        idx = np.arange(n)
        linked = (flags & _LINKED) != 0
        prev = np.r_[False, linked[:-1]]
        in_chain = linked | prev
        start = np.maximum.accumulate(np.where(in_chain & ~prev, idx, 0))
        start = np.where(in_chain, start, idx)
        last = np.where(~linked, idx + 1, n + 1)  # n + 1: a chain left open
        end = np.minimum.accumulate(last[::-1])[::-1]
        bad = in_chain & ((kind != kind[start]) | (kind == _SCALAR)
                          | (kind == _RESOLVE) | (end > n))
        bad_chain = np.zeros(n, dtype=bool)
        bad_chain[start[bad]] = True
        kind[in_chain & bad_chain[start]] = _SCALAR
        return kind, dpos, cpos, start, np.minimum(end, n), in_chain

    # -- create_transfers: the numpy runs ---------------------------------

    def _first_crossing(self, dpos, cpos, amount, pending: bool):
        """The first event (index in the run) that a balance limit fails,
        with every event before it applied; None if none does."""
        fl = self.accounts.rows["flags"]
        dl = (fl[dpos] & _LIMIT_FLAGS) != 0
        cl = (fl[cpos] & _LIMIT_FLAGS) != 0
        if not (dl.any() or cl.any()):
            return None
        ev = np.arange(len(dpos))
        nd = int(dl.sum())
        acct = np.concatenate([dpos[dl], cpos[cl]])
        evt = np.concatenate([ev[dl], ev[cl]])
        zero = np.zeros(len(acct), dtype=np.uint64)
        deb, cred = zero.copy(), zero.copy()
        deb[:nd], cred[nd:] = amount[dl], amount[cl]
        is_deb = np.arange(len(acct)) < nd
        order = np.lexsort((evt, acct))  # by account, then event
        acct, evt, deb, cred, is_deb = (
            x[order] for x in (acct, evt, deb, cred, is_deb))
        # each account's running sums over the run, this event included
        # (an event never debits and credits one account)
        cd, cc = np.cumsum(deb), np.cumsum(cred)
        first = np.r_[True, acct[1:] != acct[:-1]]
        head = np.maximum.accumulate(np.where(first, np.arange(len(acct)), 0))
        run_d = cd - (cd[head] - deb[head])
        run_c = cc - (cc[head] - cred[head])
        r = self.accounts.rows
        dp, dpo, cp, cpo = (r[f + "_lo"][acct] for f in _BALANCES)
        f = r["flags"][acct]
        posted_d = zero if pending else run_d  # what the run adds to posted
        posted_c = zero if pending else run_c
        over = (is_deb & ((f & _DEBITS_LIMIT) != 0)
                & (dp + dpo + run_d > cpo + posted_c))
        over |= (~is_deb & ((f & _CREDITS_LIMIT) != 0)
                 & (cp + cpo + run_c > dpo + posted_d))
        return int(evt[over].min()) if over.any() else None

    def _plain_run(self, rows, a: int, b: int, dpos, cpos):
        """Apply events [a, b) of one plain or pending kind: all of them
        succeed, up to the first limit crossing. Returns (applied up to,
        first crossing or None); (a, None) when an id repeats or exists or
        a sum could overflow (nothing done)."""
        part = rows[a:b]
        ids, amount = part["id_lo"], part["amount_lo"]
        if len(np.unique(ids)) != b - a or self.transfers.contains_any(ids):
            return a, None
        total = int(amount.sum(dtype=object))
        if self._balance_bound + total >= 1 << 63:
            return a, None
        pending = (int(part["flags"][0]) & _PENDING) != 0
        di, ci = dpos[a:b], cpos[a:b]
        cross = self._first_crossing(di, ci, amount, pending) if self._limits else None
        return b if cross is None else a + cross, cross

    def _commit_plain(self, rows, a: int, b: int, dpos, cpos, ts0: int) -> None:
        part = rows[a:b]
        amount = part["amount_lo"]
        self._balance_bound += int(amount.sum(dtype=object))
        stored = part.copy()
        stored["timestamp"] = np.arange(ts0 + a, ts0 + b, dtype=np.uint64)
        self.transfers.add_bulk(stored)
        d_field, c_field = (
            ("debits_pending", "credits_pending")
            if int(part["flags"][0]) & _PENDING
            else ("debits_posted", "credits_posted"))
        r = self.accounts.rows
        np.add.at(r[d_field + "_lo"], dpos[a:b], amount)
        np.add.at(r[c_field + "_lo"], cpos[a:b], amount)
        self.commit_timestamp = ts0 + b - 1

    def _resolve_run(self, rows, a: int, b: int, ts0: int) -> bool:
        """Posts and voids [a, b) of distinct pending transfers that exist
        in bulk, are pending, unresolved and unexpired: all succeed (post
        and void check no balance limit). False (nothing done) otherwise."""
        part = rows[a:b]
        ids, pids = part["id_lo"], part["pending_id_lo"]
        m = b - a
        tr = self.transfers
        if (len(np.unique(ids)) != m or len(np.unique(pids)) != m
                or self._balance_bound >= 1 << 63 or tr.contains_any(ids)
                or (tr.objs and not tr.objs.keys().isdisjoint(pids.tolist()))):
            return False
        p, found = tr.find(pids)
        if not found.all():
            return False
        ts = np.arange(ts0 + a, ts0 + b, dtype=np.uint64)
        dr, cr = part["debit_account_id_lo"], part["credit_account_id_lo"]
        led, code, t_amt = part["ledger"], part["code"], part["amount_lo"]
        p_amt = p["amount_lo"]
        amount = np.where(t_amt > 0, t_amt, p_amt)
        void = part["flags"] == _VOID
        p_timeout = p["timeout"].astype(np.uint64)
        ok = (((p["flags"] & _PENDING) != 0)
              & ((dr == 0) | (dr == p["debit_account_id_lo"]))
              & ((cr == 0) | (cr == p["credit_account_id_lo"]))
              & ((led == 0) | (led == p["ledger"]))
              & ((code == 0) | (code == p["code"]))
              & (amount <= p_amt) & (~void | (amount == p_amt))
              & ((p_timeout == 0)
                 | (ts < p["timestamp"] + p_timeout * np.uint64(NS_PER_S))))
        if not ok.all():
            return False
        p_ts = p["timestamp"].tolist()
        if self.posted and not self.posted.keys().isdisjoint(p_ts):
            return False
        acc = self.accounts
        dpos, cpos = acc.find(p["debit_account_id_lo"]), acc.find(p["credit_account_id_lo"])
        if (dpos < 0).any() or (cpos < 0).any():
            return False
        new = np.zeros(m, dtype=TRANSFER_DTYPE)
        for f in ("id_lo", "flags", "pending_id_lo"):
            new[f] = part[f]
        for f in ("debit_account_id_lo", "credit_account_id_lo", "ledger", "code"):
            new[f] = p[f]
        new["amount_lo"] = amount
        new["timestamp"] = ts
        own = (part["user_data_128_lo"] != 0) | (part["user_data_128_hi"] != 0)
        for f in ("user_data_128_lo", "user_data_128_hi"):
            new[f] = np.where(own, part[f], p[f])
        for f in ("user_data_64", "user_data_32"):
            new[f] = np.where(part[f] != 0, part[f], p[f])
        tr.add_bulk(new)
        self.posted.update(zip(p_ts, np.where(void, VOIDED, POSTED).tolist()))
        r = acc.rows
        np.subtract.at(r["debits_pending_lo"], dpos, p_amt)
        np.subtract.at(r["credits_pending_lo"], cpos, p_amt)
        post = ~void
        np.add.at(r["debits_posted_lo"], dpos[post], amount[post])
        np.add.at(r["credits_posted_lo"], cpos[post], amount[post])
        self._balance_bound += int(amount.sum(dtype=object))
        self.commit_timestamp = ts0 + b - 1
        return True

    def _count(self, kind, in_chain, a: int, b: int, limited: bool) -> None:
        chained = int(in_chain[a:b].sum())
        self.path_events["chains"] += chained
        if kind[a] == _RESOLVE:
            self.path_events["resolve"] += b - a
        else:
            self.path_events["limited" if limited else "plain"] += b - a - chained

    def _execute_transfers(self, timestamp: int, rows: np.ndarray) -> list:
        """scalar.execute's loop for create_transfers, with every long run
        of events the numpy paths vouch for (see _kinds) applied whole. A
        run holds whole chains only, so the chain bookkeeping below is the
        scalar loop's, unchanged."""
        n = len(rows)
        ts0 = timestamp - n + 1  # the timestamp of event 0
        kind, dpos, cpos, c_start, c_end, in_chain = self._kinds(rows)
        runs: dict[int, int] = {}
        edges = np.flatnonzero(np.r_[True, kind[1:] != kind[:-1], True])
        for a, b in zip(edges[:-1].tolist(), edges[1:].tolist()):
            if kind[a] != _SCALAR and b - a >= MIN_RUN:
                runs[a] = b
        in_run = np.zeros(n, dtype=bool)
        for a, b in runs.items():
            in_run[a:b] = True
        scalar_idx = np.flatnonzero(~in_run)
        recs = dict(zip(scalar_idx.tolist(),
                        _records(Operation.create_transfers, rows[scalar_idx])))
        fl = self.accounts.rows["flags"]
        limited = ((fl[dpos] | fl[cpos]) & _LIMIT_FLAGS) != 0 if self.accounts.n else None
        applied = 0

        results: list[tuple[int, int]] = []
        chain = None
        chain_broken = False
        index = 0
        while index < n:
            end = runs.get(index)
            if end is not None:
                assert chain is None
                self.accounts.flush()  # the objects of scalar events back first
                if kind[index] == _RESOLVE:
                    done = end if self._resolve_run(rows, index, end, ts0) else index
                    cross = None
                else:
                    done, cross = self._plain_run(rows, index, end, dpos, cpos)
                    if cross is not None:
                        done = max(index, int(c_start[done]))  # whole chains only
                    if done > index:
                        self._commit_plain(rows, index, done, dpos, cpos, ts0)
                if done > index:
                    self._count(kind, in_chain, index, done,
                                limited is not None and limited[index:done].any())
                    applied += done - index
                if done == end:
                    index = end
                    continue
                # the rest goes event by event: after a crossing that the
                # run met MIN_RUN events or more into it, only the crossing
                # chain; the events behind it are a run again
                stop = end
                if cross is not None and done - index >= MIN_RUN:
                    after = int(c_end[index + cross])
                    if end - after >= MIN_RUN:
                        runs[after] = end
                        stop = after
                recs.update(zip(range(done, stop), _records(
                    Operation.create_transfers, rows[done:stop])))
                index = done
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
        self.fast_events += applied
        self.scalar_events += n - applied
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
            scalar = self.scalar_events > before
        elif (operation == Operation.create_accounts and len(events)
              and self._scope is None
              and self._create_accounts_bulk(timestamp, events)):
            self.fast_events += len(events)
            self.path_events["accounts"] += len(events)
            results, scalar = [], False
        else:
            results = super().execute(operation, timestamp, _records(operation, events))
            self.scalar_events += len(events)
            scalar = len(events) > 0
        if scalar:
            self.scalar_batches += 1
        else:
            self.fast_batches += 1
        self.accounts.flush()
        return results

    # -- what the comparison reads ---------------------------------------

    def lookup_account_rows(self, ids) -> bytes:
        """Wire rows of the ids that exist (limb arrays or ints)."""
        return self.accounts.rows_for(ids)

    def lookup_transfer_rows(self, ids) -> bytes:
        """As lookup_account_rows."""
        return self.transfers.rows_for(ids)

    def fingerprint(self) -> dict:
        """The state digest the server's finalize reports for the chip's
        tables (fp_rows below), computed over the reference's rows."""
        afp, alive = fp_rows(self.accounts.all_rows())
        tfp, tlive = fp_rows(self.transfers.all_rows())
        return {
            "accounts_fp": afp, "transfers_fp": tfp, "accounts": alive,
            "transfers": tlive, "commit_timestamp": self.commit_timestamp,
        }


def _records(operation, rows: np.ndarray, account_cls=Account) -> list:
    """Wire rows -> record objects, through one tolist() (field by field
    reads of numpy scalars cost ten times the state machine itself)."""
    out = []
    if operation == Operation.create_accounts:
        for (id_lo, id_hi, dp_lo, dp_hi, dpo_lo, dpo_hi, cp_lo, cp_hi, cpo_lo,
             cpo_hi, ud128_lo, ud128_hi, ud64, ud32, reserved, ledger, code,
             flags, ts) in rows.tolist():
            out.append(account_cls(
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
    probe = OracleStateMachine()
    accounts = copy.copy(ref.accounts)  # shares the table; writes land in objs
    accounts.objs = {k: copy.copy(v) for k, v in ref.accounts.objs.items()}
    accounts.read_only = True
    probe.accounts = accounts
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
_FP_BLOCK = 1 << 13


def fp_rows(rows: np.ndarray) -> tuple[int, int]:
    """(sum of row hashes mod 2^64, live rows) over 128-byte wire rows."""
    if rows.dtype != np.uint32:
        rows = np.ascontiguousarray(rows).view(np.uint32)
    rows = rows.reshape(-1, ROW_WORDS)
    total, alive = np.uint64(0), 0
    # in blocks of rows, each block's words transposed once: a pass over a
    # word is then a pass over one contiguous column that stays in cache
    for start in range(0, len(rows), _FP_BLOCK):
        block = rows[start:start + _FP_BLOCK]
        cols = np.ascontiguousarray(block.T).astype(np.uint64)
        with np.errstate(over="ignore"):
            h = np.full(len(block), _FP_SEED, dtype=np.uint64)
            for word in cols:
                h = h ^ (word * _FP_MUL)
                h = ((h << np.uint64(27)) | (h >> np.uint64(37))) * _FP_SEED + _FP_ADD
            h = (h ^ (h >> np.uint64(33))) * _FP_MIX1
            h = (h ^ (h >> np.uint64(33))) * _FP_MIX2
            h = h ^ (h >> np.uint64(33))
            k4 = block[:, :4]
            live = ~(k4 == 0).all(axis=1) & ~(k4 == 0xFFFFFFFF).all(axis=1)
            total += np.sum(np.where(live, h, np.uint64(0)), dtype=np.uint64)
        alive += int(live.sum())
    return int(total), alive


__all__ = ["ReferenceLedger", "AccountStore", "TransferStore", "CONTROLS",
           "PATHS", "fp_rows", "ACCOUNT_DTYPE", "TRANSFER_DTYPE"]
