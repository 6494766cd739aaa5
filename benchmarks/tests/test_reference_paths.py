"""Each numpy path of the reference equals the scalar loop (run: `python
-m pytest benchmarks/tests -q`, on the CPU): the same result codes, the
same account and transfer rows read back, the same state digest and commit
timestamp, on seeded streams that also hold what each path must hand to the
scalar loop. Each case asserts that its path was taken."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import traffic  # noqa: E402
from benchmarks.reference.ledger_ref import ReferenceLedger, fp_rows  # noqa: E402
from benchmarks.reference.scalar import OracleStateMachine  # noqa: E402
from benchmarks.reference.wire_types import (  # noqa: E402
    ACCOUNT_DTYPE,
    TRANSFER_DTYPE,
    AccountFlags,
    Operation,
    TransferFlags as TF,
    accounts_to_np,
    transfers_to_np,
)

ACC, XFER = Operation.create_accounts, Operation.create_transfers
D_LIMIT = int(AccountFlags.debits_must_not_exceed_credits)
C_LIMIT = int(AccountFlags.credits_must_not_exceed_debits)
HERE = os.path.dirname(os.path.abspath(__file__))


def accounts(ids, flags=0) -> np.ndarray:
    arr = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
    arr["id_lo"] = ids
    arr["ledger"] = 1
    arr["code"] = 1
    arr["flags"] = flags
    arr["user_data_64"] = np.arange(len(ids)) * 7
    return arr


def transfers(rng, first_id, n, n_accounts, flags=0, amount=None) -> np.ndarray:
    arr = np.zeros(n, dtype=TRANSFER_DTYPE)
    arr["id_lo"] = np.arange(first_id, first_id + n)[::-1]
    dr = rng.integers(1, n_accounts + 1, n)
    arr["debit_account_id_lo"] = dr
    arr["credit_account_id_lo"] = (dr - 1 + rng.integers(1, n_accounts, n)) % n_accounts + 1
    arr["amount_lo"] = rng.integers(1, 1001, n) if amount is None else amount
    arr["user_data_64"] = rng.integers(0, 1 << 40, n)
    arr["ledger"] = 1
    arr["code"] = 1
    arr["flags"] = flags
    return arr


def resolve(pending: np.ndarray, first_id: int, flags=int(TF.post_pending_transfer)):
    out = np.zeros(len(pending), dtype=TRANSFER_DTYPE)
    out["id_lo"] = np.arange(first_id, first_id + len(pending))
    out["pending_id_lo"] = pending["id_lo"]
    out["flags"] = flags
    return out


def replay(batches):
    """Both machines over the same batches; every batch's results equal."""
    fast, slow = ReferenceLedger(), OracleStateMachine()
    ts = 10**15
    codes = []
    for op, rows in batches:
        ts += len(rows) + 3
        got = fast.execute(op, ts, rows)
        assert got == slow.execute(op, ts, rows)
        codes += [c for _i, c in got]
    return fast, slow, codes


def limbs(ids) -> tuple[np.ndarray, np.ndarray]:
    """Ids as the (lo, hi) limb arrays a lookup request's body holds."""
    return (np.array([i & (1 << 64) - 1 for i in ids], dtype=np.uint64),
            np.array([i >> 64 for i in ids], dtype=np.uint64))


def assert_same_state(fast: ReferenceLedger, slow: OracleStateMachine) -> None:
    acct = sorted(slow.accounts)
    assert sorted(fast.accounts) == acct
    want_a = accounts_to_np(slow.lookup_accounts(acct)).tobytes()
    assert fast.lookup_account_rows(limbs(acct)) == want_a
    ids = sorted(slow.transfers)
    want_t = transfers_to_np(slow.lookup_transfers(ids)).tobytes()
    assert fast.lookup_transfer_rows(limbs(ids)) == want_t
    afp, alive = fp_rows(np.frombuffer(want_a, dtype=ACCOUNT_DTYPE))
    tfp, tlive = fp_rows(np.frombuffer(want_t, dtype=TRANSFER_DTYPE))
    assert fast.fingerprint() == {
        "accounts_fp": afp, "transfers_fp": tfp, "accounts": alive,
        "transfers": tlive, "commit_timestamp": slow.commit_timestamp}
    assert fast.posted == slow.posted


def stream_accounts():
    """Ordinary batches (one out of id order), a duplicate id, a bad field,
    an existing id with other flags, an id with a high limb; transfers over
    all of them."""
    rng = np.random.default_rng(41)
    flags = np.where(np.arange(1, 201) % 5 == 0, D_LIMIT, 0)
    dup = accounts(np.arange(201, 301))
    dup["id_lo"][50] = 230
    bad = accounts(np.arange(301, 401))
    bad["ledger"][17] = 0
    again = accounts(np.arange(1, 11))
    again["flags"][3] = C_LIMIT
    high = accounts(np.arange(600, 632))
    high["id_hi"][5] = 1
    batches = [(ACC, accounts(np.arange(1, 201), flags)), (ACC, dup), (ACC, bad),
               (ACC, accounts(np.arange(1064, 1000, -1))),
               (ACC, accounts(np.arange(500, 401, -1))), (ACC, again), (ACC, high)]
    funding = transfers(rng, 9000, 64, 300, amount=100)
    funding["credit_account_id_lo"] = np.arange(5, 5 * 64 + 1, 5) % 200 + 5
    funding["debit_account_id_lo"] = 1
    batches.append((XFER, funding))
    for k in range(6):
        batches.append((XFER, transfers(rng, 10_000 + 100 * k, 96, 500)))
    to_high = transfers(rng, 20_000, 40, 500)
    to_high["credit_account_id_hi"][7] = 1
    to_high["credit_account_id_lo"][7] = 605
    batches.append((XFER, to_high))
    return batches, "accounts", None


def stream_limited():
    """The test configuration limits_funded.json (every account limited and
    funded, a few with the credit-side limit), plain and pending batches of
    uniform traffic until limits are crossed."""
    with open(os.path.join(HERE, "limits_funded.json")) as f:
        cfg = json.load(f)
    cfg.update(accounts=300, batch_events=256)
    cfg["account_flags"] = [
        {"flag": "debits_must_not_exceed_credits", "accounts": {"from": 1, "to": 280}},
        {"flag": "credits_must_not_exceed_debits", "accounts": {"from": 281, "to": 290}},
        {"flag": "debits_must_not_exceed_credits", "accounts": {"every": 50}},
    ]
    mix = {"loop": "open", "sessions": 1, "cycle": ["plain", "pending", "plain"],
           "classes": {"plain": [], "pending": [{"do": "flags", "flags": "pending"}]},
           "warm_cycles": 1, "client": {}, "account_draw": {"name": "uniform"}}
    st = traffic.Stream(mix, cfg, 2**31 + 9)
    batches = [(ACC, a) for a in st.account_batches()]
    batches += [(XFER, a) for a in st.opening_batches()]
    batches += [(XFER, st.next_create()[1]) for _ in range(45)]
    return batches, "limited", 54  # exceeds_credits


def stream_resolve():
    """Pending batches, then posts (full and partial) and voids of them, a
    post of an already-resolved pending id, a post of a missing one, and
    two posts of one pending id in one batch."""
    rng = np.random.default_rng(43)
    batches = [(ACC, accounts(np.arange(1, 101), np.where(np.arange(1, 101) == 100, D_LIMIT, 0)))]
    fund = transfers(rng, 500, 40, 99, amount=1000)
    fund["credit_account_id_lo"] = 100
    fund["debit_account_id_lo"] = np.arange(1, 41)
    batches.append((XFER, fund))
    pend = [transfers(rng, 1000 * (k + 1), 64, 100, flags=int(TF.pending))
            for k in range(5)]
    for p in pend:
        batches.append((XFER, p))
    post = resolve(pend[0], 50_000)
    post["user_data_64"][::3] = 77
    batches.append((XFER, post))
    half = resolve(pend[1], 51_000)
    half["flags"][32:] = int(TF.void_pending_transfer)
    half["amount_lo"][:32] = pend[1]["amount_lo"][:32] // 2 + 1
    batches.append((XFER, half))
    short_void = resolve(pend[2][:40], 51_500, int(TF.void_pending_transfer))
    short_void["amount_lo"][35] = 1  # a void must name the whole amount
    batches.append((XFER, short_void))
    batches.append((XFER, resolve(pend[0], 52_000)))  # already posted
    missing = resolve(pend[2][40:], 53_000)
    missing["pending_id_lo"][20] = 999_999
    batches.append((XFER, missing))
    twice = resolve(pend[3], 54_000)
    twice["pending_id_lo"][50] = twice["pending_id_lo"][10]
    batches.append((XFER, twice))
    batches.append((XFER, resolve(pend[4], 55_000, int(TF.void_pending_transfer))))
    return batches, "resolve", 33  # pending_transfer_already_posted


def stream_chains():
    """Whole chains of three (one payer, one in 16 broken), chains of
    pending transfers, a chain open at the batch's end, a chain that mixes
    plain and pending, and chains through limited accounts that cross."""
    rng = np.random.default_rng(47)
    flags = np.where(np.arange(1, 301) > 280, D_LIMIT, 0)
    batches = [(ACC, accounts(np.arange(1, 301), flags))]
    fund = transfers(rng, 700, 20, 280, amount=300)
    fund["credit_account_id_lo"] = np.arange(281, 301)
    batches.append((XFER, fund))

    class St:
        plain_accounts = 279

    def chains(first_id, pending=False, brk=16):
        arr = transfers(rng, first_id, 240, 279, flags=int(TF.pending) if pending else 0)
        for mod in ({"do": "linked_chains", "chains": "all", "length": 3, "break_every": brk},
                    {"do": "chain_payer", "length": 3}):
            arr = traffic.named("modifiers", mod["do"]).apply(St, mod, arr, first_id)
        return arr

    for k in range(4):
        batches.append((XFER, chains(10_000 * (k + 1))))
    batches.append((XFER, chains(60_000, pending=True)))
    open_end = chains(70_000)
    open_end["flags"][-1] |= int(TF.linked)
    batches.append((XFER, open_end))
    mixed = chains(80_000)
    mixed["flags"][31] |= int(TF.pending)  # the middle of chain 10
    batches.append((XFER, mixed))
    crossing = chains(90_000, brk=0)
    # a limited payer in six legs from the middle of chain 20: the fourth
    # (the middle of chain 21) crosses, and chain 21 rolls back whole
    crossing["debit_account_id_lo"][61:67] = 290
    crossing["amount_lo"][61:67] = 100
    batches.append((XFER, crossing))
    return batches, "chains", 54


@pytest.mark.parametrize("make", [stream_accounts, stream_limited, stream_resolve,
                                  stream_chains], ids=lambda f: f.__name__[7:])
def test_numpy_path_equals_the_scalar_loop(make):
    batches, path, code = make()
    fast, slow, codes = replay(batches)
    assert fast.path_events[path] > 0, fast.path_events
    assert fast.scalar_events > 0  # the stream hands the scalar loop its share
    if code is not None:
        assert code in codes
    assert_same_state(fast, slow)


def test_a_run_that_crosses_a_limit_is_cut_before_it():
    """A limited account credited early in a run may be debited later in
    it; the first debit past its credits ends the numpy part of the run."""
    acc = accounts(np.arange(1, 65), np.where(np.arange(1, 65) == 64, D_LIMIT, 0))
    run = transfers(np.random.default_rng(5), 100, 200, 63, amount=10)
    run["credit_account_id_lo"][20] = 64  # +10
    run["debit_account_id_lo"][150] = 64  # -10: fits
    run["debit_account_id_lo"][160] = 64  # -10: crosses
    fast, slow, codes = replay([(ACC, acc), (XFER, run)])
    assert codes == [54]
    assert fast.path_events["limited"] == 160  # the events before the crossing
    assert fast.scalar_events == 1  # the crossing event
    assert fast.path_events["plain"] == 39  # the events behind it: a run again
    assert_same_state(fast, slow)
