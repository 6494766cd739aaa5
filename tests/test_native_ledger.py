"""Native C++ ledger engine (native/ledger.cc) parity + lifecycle.

The durable server's commit backend must match the Python oracle (itself
pinned to the reference's own test tables, tests/test_golden.py) code for
code and row for row — randomized differential runs over the full workload
space (two-phase, linked chains, balancing, duplicates, invalid events),
plus snapshot/restore and the Replica integration seam.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.models.native_ledger import NativeLedger
from tigerbeetle_tpu.models.oracle import OracleStateMachine
from tigerbeetle_tpu.testing.workload import WorkloadGenerator
from tigerbeetle_tpu.types import Operation


def _run_differential(seed: int, n_batches: int = 12, batch: int = 64):
    gen = WorkloadGenerator(seed)
    oracle = OracleStateMachine()
    nat = NativeLedger(12, 14)
    ids_seen: list[int] = []
    for b in range(n_batches):
        if b % 3 == 0:
            op, events = gen.gen_accounts_batch(batch)
        else:
            op, events = gen.gen_transfers_batch(batch)
            ids_seen.extend(t.id for t in events)
        oracle.prepare(op, len(events))
        nat.prepare(op, len(events))
        assert nat.prepare_timestamp == oracle.prepare_timestamp
        ts = oracle.prepare_timestamp
        dense_o = oracle.execute_dense(op, ts, list(events))
        dense_n = nat.execute_dense(op, ts, list(events))
        assert dense_n == dense_o, (
            f"seed {seed} batch {b}: first diff at "
            f"{next(i for i in range(len(dense_o)) if dense_o[i] != dense_n[i])}"
        )
    return oracle, nat, ids_seen


@pytest.mark.parametrize("seed", [1, 7, 23, 91])
def test_native_matches_oracle_random_workload(seed):
    oracle, nat, ids_seen = _run_differential(seed)
    # full state parity: every account and transfer row, via lookups
    acct_ids = sorted(oracle.accounts)
    assert nat.lookup_accounts(acct_ids) == oracle.lookup_accounts(acct_ids)
    probe = sorted(set(ids_seen))[:512]
    assert nat.lookup_transfers(probe) == oracle.lookup_transfers(probe)
    got = nat.counts()
    assert got["accounts"] == len(oracle.accounts)
    assert got["transfers"] == len(oracle.transfers)
    assert got["posted"] == len(oracle.posted)
    assert got["commit_timestamp"] == oracle.commit_timestamp


def test_native_snapshot_restore_roundtrip():
    oracle, nat, ids_seen = _run_differential(5, n_batches=9)
    snap = nat.snapshot_bytes()
    nat2 = NativeLedger(4, 4)  # restore grows tables as needed
    nat2.restore_bytes(snap)
    nat2.prepare_timestamp = nat.prepare_timestamp
    acct_ids = sorted(oracle.accounts)
    assert nat2.lookup_accounts(acct_ids) == oracle.lookup_accounts(acct_ids)
    assert nat2.counts() == nat.counts()

    # both continue identically after restore
    gen = WorkloadGenerator(99)
    op, events = gen.gen_transfers_batch(48)
    for led in (nat, nat2):
        led.prepare(op, len(events))
    ts = nat.prepare_timestamp
    assert nat.execute_dense(op, ts, list(events)) == nat2.execute_dense(
        op, ts, list(events)
    )
    assert nat.snapshot_bytes() == nat2.snapshot_bytes()


def test_native_two_phase_and_chains_explicit():
    """Deterministic two-phase + chain scenario (not seed-dependent)."""
    oracle = OracleStateMachine()
    nat = NativeLedger(8, 10)
    A = [types.Account(id=i, ledger=1, code=1) for i in (1, 2, 3)]
    for led in (oracle, nat):
        led.prepare(Operation.create_accounts, 3)
    ts = oracle.prepare_timestamp
    assert oracle.execute_dense(Operation.create_accounts, ts, list(A)) == \
        nat.execute_dense(Operation.create_accounts, ts, list(A)) == [0, 0, 0]

    F = types.TransferFlags
    T = [
        types.Transfer(id=10, debit_account_id=1, credit_account_id=2,
                       amount=100, ledger=1, code=1, flags=int(F.pending),
                       timeout=60),
        # linked chain that breaks (same-account transfer is invalid)
        types.Transfer(id=11, debit_account_id=1, credit_account_id=3,
                       amount=5, ledger=1, code=1, flags=int(F.linked)),
        types.Transfer(id=12, debit_account_id=2, credit_account_id=2,
                       amount=5, ledger=1, code=1),
        # standalone ok
        types.Transfer(id=13, debit_account_id=3, credit_account_id=1,
                       amount=7, ledger=1, code=1),
    ]
    for led in (oracle, nat):
        led.prepare(Operation.create_transfers, len(T))
    ts = oracle.prepare_timestamp
    d_o = oracle.execute_dense(Operation.create_transfers, ts, list(T))
    d_n = nat.execute_dense(Operation.create_transfers, ts, list(T))
    assert d_n == d_o
    assert d_o[1] == 1 and d_o[2] != 0 and d_o[3] == 0  # chain broke

    # post the pending, then double-post (already_posted), then void
    P = [types.Transfer(id=20, pending_id=10, ledger=1, code=1,
                        flags=int(F.post_pending_transfer))]
    for led in (oracle, nat):
        led.prepare(Operation.create_transfers, 1)
    ts = oracle.prepare_timestamp
    assert oracle.execute_dense(Operation.create_transfers, ts, list(P)) == \
        nat.execute_dense(Operation.create_transfers, ts, list(P)) == [0]
    P2 = [types.Transfer(id=21, pending_id=10, ledger=1, code=1,
                         flags=int(F.void_pending_transfer))]
    for led in (oracle, nat):
        led.prepare(Operation.create_transfers, 1)
    ts = oracle.prepare_timestamp
    d_o = oracle.execute_dense(Operation.create_transfers, ts, list(P2))
    d_n = nat.execute_dense(Operation.create_transfers, ts, list(P2))
    assert d_n == d_o  # pending_transfer_already_posted
    ids = [1, 2, 3]
    assert nat.lookup_accounts(ids) == oracle.lookup_accounts(ids)


def test_native_reply_encoding_matches_state_machine():
    """drain_reply's vectorized sparse encoding == the wire format."""
    from tigerbeetle_tpu.state_machine import StateMachine, decode_results

    nat = NativeLedger(8, 10)
    sm = StateMachine(nat)
    acc = types.accounts_to_np([
        types.Account(id=1, ledger=1, code=1),
        types.Account(id=0, ledger=1, code=1),  # id_must_not_be_zero
        types.Account(id=2, ledger=0, code=1),  # ledger_must_not_be_zero
    ]).tobytes()
    sm.prepare(Operation.create_accounts, acc)
    reply = sm.commit_finish(
        sm.commit_async(Operation.create_accounts, sm.prepare_timestamp, acc)
    )
    assert decode_results(reply, Operation.create_accounts) == [(1, 6), (2, 13)]


def test_native_throughput_sanity():
    """Sanity floor, not a benchmark: the engine must stay orders of
    magnitude above the Python oracle (~50k TPS). The threshold is set
    far below the measured ~2.8M TPS so loaded/slow CI hosts stay green."""
    import time

    nat = NativeLedger(16, 22)
    n_acc = 10_000
    arr = np.zeros(n_acc, dtype=types.ACCOUNT_DTYPE)
    arr["id_lo"] = np.arange(1, n_acc + 1)
    arr["ledger"] = 1
    arr["code"] = 1
    nat.prepare(Operation.create_accounts, n_acc)
    assert not any(nat.execute_dense(
        Operation.create_accounts, nat.prepare_timestamp, arr
    ))
    rng = np.random.default_rng(1)
    batches = []
    for g in range(12):
        t = np.zeros(8190, dtype=types.TRANSFER_DTYPE)
        t["id_lo"] = np.arange(1_000_000 + g * 8190, 1_000_000 + (g + 1) * 8190)
        dr = rng.integers(1, n_acc + 1, size=8190, dtype=np.uint64)
        off = rng.integers(1, n_acc, size=8190, dtype=np.uint64)
        t["debit_account_id_lo"] = dr
        t["credit_account_id_lo"] = (dr - 1 + off) % n_acc + 1
        t["amount_lo"] = 1
        t["ledger"] = 1
        t["code"] = 1
        batches.append(t)
    t0 = time.perf_counter()
    last = None
    for b in batches:
        nat.prepare(Operation.create_transfers, len(b))
        last = nat.execute_async(
            Operation.create_transfers, nat.prepare_timestamp, b
        )
    last.wait()  # engine worker FIFO: the last done => all done
    assert last.failures == 0
    dt = time.perf_counter() - t0
    tps = 12 * 8190 / dt
    assert tps > 250_000, f"native engine too slow: {tps:,.0f} TPS"
