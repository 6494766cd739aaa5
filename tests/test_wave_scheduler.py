"""Conflict-wave scheduler (HazardTracker.plan + _execute_waves):
deterministic wave layout, bit-exact parity vs the scalar oracle on
adversarial hot-account workloads, the executor's passes (each wave's
live lanes packed into passes of a fixed width, wave_chunks) against the
serial scan and the oracle, and the decision plumbing.

The determinism contract under test: the wave layout is a PURE FUNCTION
of the batch bytes plus the tracker's committed-history state — no
seeds, no wall clock, no unordered iteration — so every replica and the
simulator plan (and execute) a batch identically. The parity contract:
whatever the layout, the committed result codes and the full state are
bit-exact against the oracle's strictly-serial semantics.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tigerbeetle_tpu.constants import TEST_PROCESS
from tigerbeetle_tpu.metrics import CATALOG, Metrics
from tigerbeetle_tpu.models import ledger as L
from tigerbeetle_tpu.models.ledger import (
    FAULT_CAPACITY,
    WAVE_CAP,
    DeviceLedger,
    HazardTracker,
    wave_chunks,
)
from tigerbeetle_tpu.models.oracle import OracleStateMachine
from tigerbeetle_tpu.types import (
    Account,
    AccountFlags,
    Operation,
    Transfer,
    TransferFlags,
    transfers_to_np,
)
from tigerbeetle_tpu.types import CreateTransferResult as R

F_PENDING = int(TransferFlags.pending)
F_POST = int(TransferFlags.post_pending_transfer)
F_VOID = int(TransferFlags.void_pending_transfer)
F_LINKED = int(TransferFlags.linked)


def _pair(n_accounts=24, limit_accounts=(), funded=200, mode="auto"):
    """(oracle, device, ts): n accounts; `limit_accounts` get
    debits_must_not_exceed_credits and `funded` of credit headroom."""
    oracle = OracleStateMachine()
    dev = DeviceLedger(process=TEST_PROCESS, mode=mode)
    ts = 10_000
    accounts = [
        Account(
            id=i, ledger=1, code=1,
            flags=int(AccountFlags.debits_must_not_exceed_credits)
            if i in limit_accounts else 0,
        )
        for i in range(1, n_accounts + 1)
    ]
    ts += len(accounts)
    assert oracle.execute_dense(Operation.create_accounts, ts, accounts) == \
        dev.execute_dense(Operation.create_accounts, ts, accounts)
    if limit_accounts:
        fund = [
            Transfer(id=900_000 + a, debit_account_id=n_accounts,
                     credit_account_id=a, amount=funded, ledger=1, code=1)
            for a in limit_accounts
        ]
        ts += len(fund)
        assert oracle.execute_dense(Operation.create_transfers, ts, fund) == \
            dev.execute_dense(Operation.create_transfers, ts, fund)
    return oracle, dev, ts


def _check(oracle, dev, ts, transfers):
    ts += len(transfers)
    dense_o = oracle.execute_dense(Operation.create_transfers, ts, transfers)
    dense_d = dev.execute_dense(Operation.create_transfers, ts, transfers)
    assert dense_d == dense_o, [
        (i, d, o) for i, (d, o) in enumerate(zip(dense_d, dense_o)) if d != o
    ][:6]
    oracle.assert_parity(dev)
    return ts


# ----------------------------------------------------------------------
# determinism of the layout itself
# ----------------------------------------------------------------------


def _adversarial_batch():
    tr = []
    for i in range(10):  # same-batch pend->post pairs on a hot account
        tr.append(Transfer(id=1000 + i, debit_account_id=1,
                           credit_account_id=2 + i % 5, amount=10, ledger=1,
                           code=1, flags=F_PENDING))
    for i in range(10):
        tr.append(Transfer(id=2000 + i, pending_id=1000 + i, amount=5,
                           flags=F_POST))
    for _ in range(3):  # duplicate-id chain
        tr.append(Transfer(id=3000, debit_account_id=3, credit_account_id=4,
                           amount=1, ledger=1, code=1))
    for i in range(12):  # limit-account touches (order-sensitive)
        tr.append(Transfer(id=4000 + i, debit_account_id=7,
                           credit_account_id=8 + i % 4, amount=3, ledger=1,
                           code=1))
    return transfers_to_np(tr)


def _tracker(reverse_registry=False):
    t = HazardTracker()
    t.limit_account_ids = {7}
    t._limit_lo = np.array([7], dtype=np.uint64)
    pend = [(500 + i, (11 + i, 12 + i)) for i in range(6)]
    for pid, acc in (reversed(pend) if reverse_registry else pend):
        t.pending_accounts[pid] = acc
    return t


def test_wave_layout_is_a_pure_function_of_batch_and_state():
    """Same batch bytes + same tracker state => byte-identical layout,
    including with the pending registry built in a different insertion
    order (layout must not depend on dict ordering)."""
    arr = _adversarial_batch()
    d1, p1 = _tracker().plan(arr.copy())
    d2, p2 = _tracker().plan(arr.copy())
    d3, p3 = _tracker(reverse_registry=True).plan(arr.copy())
    assert d1 == d2 == d3 == "waves"
    assert p1.wave_of.tobytes() == p2.wave_of.tobytes() == p3.wave_of.tobytes()
    assert p1.n_waves == p2.n_waves == p3.n_waves
    assert p1.has_pv == p2.has_pv == p3.has_pv
    # the layout is genuinely multi-wave: posts after creators, dup ids
    # and limit touches chained
    assert p1.n_waves >= 3


def test_wave_layout_identical_across_replica_instances():
    """Two independent device ledgers fed the same committed op stream
    plan every batch identically AND produce byte-identical state — the
    cross-replica half of the determinism contract."""
    devs = [DeviceLedger(process=TEST_PROCESS, mode="auto") for _ in range(2)]
    ts = 10_000
    accounts = [Account(id=i, ledger=1, code=1) for i in range(1, 25)]
    ts += len(accounts)
    for d in devs:
        d.execute_dense(Operation.create_accounts, ts, accounts)
    rng = np.random.default_rng(9)
    for batch in range(4):
        tr = []
        base = 10_000 * (batch + 1)
        for i in range(8):
            tr.append(Transfer(id=base + i, debit_account_id=1,
                               credit_account_id=2 + i % 6, amount=4,
                               ledger=1, code=1, flags=F_PENDING))
        for i in range(8):
            tr.append(Transfer(id=base + 100 + i, pending_id=base + i,
                               flags=F_POST if i % 2 else F_VOID))
        for i in range(16):
            a = int(rng.integers(2, 24))
            tr.append(Transfer(id=base + 200 + i, debit_account_id=1,
                               credit_account_id=a, amount=1, ledger=1,
                               code=1))
        arr = transfers_to_np(tr)
        plans = []
        for d in devs:
            probe = HazardTracker()
            probe.pending_accounts = dict(d.hazards.pending_accounts)
            probe.limit_account_ids = set(d.hazards.limit_account_ids)
            probe._limit_lo = d.hazards._limit_lo.copy()
            plans.append(probe.plan(arr.copy()))
        (d1, p1), (d2, p2) = plans
        assert d1 == d2
        if p1 is not None:
            assert p1.wave_of.tobytes() == p2.wave_of.tobytes()
        ts += len(tr)
        dense = [d.execute_dense(Operation.create_transfers, ts, arr.copy())
                 for d in devs]
        assert dense[0] == dense[1]
    f1, f2 = devs[0].fingerprint(), devs[1].fingerprint()
    assert f1 == f2


# ----------------------------------------------------------------------
# parity on adversarial hot-account workloads
# ----------------------------------------------------------------------


def test_one_account_in_every_event_stays_single_wave():
    """1 hot PLAIN account in 100% of events: balance adds commute and
    non-limit validation never reads a balance, so the planner must keep
    the whole batch on ONE wave (no edges), bit-exact."""
    oracle, dev, ts = _pair()
    tr = [
        Transfer(id=5000 + i, debit_account_id=1,
                 credit_account_id=2 + i % 20, amount=1 + i % 3, ledger=1,
                 code=1)
        for i in range(64)
    ]
    probe = HazardTracker()
    decision, plan = probe.plan(transfers_to_np(tr))
    assert decision == "fast" and plan is None
    _check(oracle, dev, ts, tr)


def test_hot_limit_account_exhaustion_order():
    """A hot LIMIT account whose credit headroom runs out mid-batch: each
    touch is one wave deep (validation must see every prior touch), and
    the exact lane where exceeds_credits starts firing must match the
    strictly-serial oracle."""
    oracle, dev, ts = _pair(limit_accounts=(5,), funded=50)
    tr = []
    for i in range(12):  # 12 x 6 = 72 > 50: later lanes must fail
        tr.append(Transfer(id=6000 + i, debit_account_id=5,
                           credit_account_id=6 + i % 8, amount=6, ledger=1,
                           code=1))
        tr.append(Transfer(id=6100 + i, debit_account_id=2 + i % 3,
                           credit_account_id=10 + i % 8, amount=1, ledger=1,
                           code=1))
    probe = HazardTracker()
    probe.limit_account_ids = set(oracle.accounts) and {5}
    probe._limit_lo = np.array([5], dtype=np.uint64)
    decision, plan = probe.plan(transfers_to_np(tr))
    assert decision == "waves"
    assert plan.n_waves == 12  # one wave per limit touch
    ts = _check(oracle, dev, ts, tr)
    assert dev.hazards.plan_stats["waves"] >= 1


def test_hot_limit_chain_deeper_than_cap_falls_to_residue():
    """More touches of one limit account than WAVE_CAP: the tail falls to
    the serial residue (the escape hatch), results still bit-exact."""
    n = WAVE_CAP + 8
    oracle, dev, ts = _pair(n_accounts=48, limit_accounts=(5,),
                            funded=3 * n)
    tr = []
    for i in range(n):
        tr.append(Transfer(id=7000 + i, debit_account_id=5,
                           credit_account_id=6 + i % 8, amount=2, ledger=1,
                           code=1))
        tr.append(Transfer(id=7500 + i, debit_account_id=10 + i % 20,
                           credit_account_id=31 + i % 16, amount=1,
                           ledger=1, code=1))
    probe = HazardTracker()
    probe.limit_account_ids = {5}
    probe._limit_lo = np.array([5], dtype=np.uint64)
    decision, plan = probe.plan(transfers_to_np(tr))
    assert decision == "waves"
    assert plan.n_waves == WAVE_CAP
    assert plan.residue_n == 8  # the capped tail, in original order
    _check(oracle, dev, ts, tr)


def test_same_batch_pend_post_void_races():
    """post AND void of the same same-batch pending (first resolve wins),
    a post of a pending created LATER in the batch (not_found, creator
    still succeeds), and a void-then-post pair — all order semantics the
    waves must preserve exactly."""
    oracle, dev, ts = _pair()
    tr = [
        Transfer(id=8000, debit_account_id=1, credit_account_id=2,
                 amount=30, ledger=1, code=1, flags=F_PENDING),
        Transfer(id=8001, pending_id=8000, amount=30, flags=F_POST),
        Transfer(id=8002, pending_id=8000, flags=F_VOID),  # already posted
        # post BEFORE its creator: must fail not_found; creator succeeds
        Transfer(id=8003, pending_id=8010, amount=5, flags=F_POST),
        Transfer(id=8010, debit_account_id=3, credit_account_id=4,
                 amount=5, ledger=1, code=1, flags=F_PENDING),
        # void then post of another same-batch pending
        Transfer(id=8020, debit_account_id=5, credit_account_id=6,
                 amount=7, ledger=1, code=1, flags=F_PENDING),
        Transfer(id=8021, pending_id=8020, flags=F_VOID),
        Transfer(id=8022, pending_id=8020, amount=7, flags=F_POST),
    ] + [
        Transfer(id=8100 + i, debit_account_id=7 + i % 8,
                 credit_account_id=15 + i % 8, amount=1, ledger=1, code=1)
        for i in range(16)
    ]
    _check(oracle, dev, ts, tr)


def test_linked_chains_next_to_waves():
    """Linked chains (serial residue) coexisting with same-batch two-phase
    waves; a post referencing a CHAIN-created pending must be pulled into
    the residue with its creator (entanglement closure)."""
    oracle, dev, ts = _pair()
    tr = [
        # chain creating a pending, then failing -> rollback
        Transfer(id=9000, debit_account_id=1, credit_account_id=2,
                 amount=5, ledger=1, code=1,
                 flags=F_LINKED | F_PENDING),
        Transfer(id=9001, debit_account_id=1, credit_account_id=2,
                 amount=0, ledger=1, code=1),  # breaks the chain
        # post of the rolled-back pending: must see not_found
        Transfer(id=9002, pending_id=9000, amount=5, flags=F_POST),
        # a healthy chain
        Transfer(id=9010, debit_account_id=3, credit_account_id=4,
                 amount=2, ledger=1, code=1, flags=F_LINKED),
        Transfer(id=9011, debit_account_id=3, credit_account_id=4,
                 amount=2, ledger=1, code=1),
    ] + [
        t
        for i in range(8)
        for t in (
            Transfer(id=9100 + i, debit_account_id=5 + i % 6,
                     credit_account_id=11 + i % 6, amount=9, ledger=1,
                     code=1, flags=F_PENDING),
            Transfer(id=9200 + i, pending_id=9100 + i, amount=4,
                     flags=F_POST),
        )
    ]
    probe = HazardTracker()
    decision, plan = probe.plan(transfers_to_np(tr))
    assert decision == "waves"
    assert plan.wave_of[2] < 0  # the chain-pending post joined the residue
    assert plan.n_waves >= 2  # the healthy pairs still wave
    _check(oracle, dev, ts, tr)


def test_duplicate_id_first_occurrence_fails():
    """Duplicate-id group where occurrence 1 FAILS validation: occurrence
    2 must then succeed, occurrence 3 must see exists — the waves walk
    the group in lane order."""
    oracle, dev, ts = _pair()
    tr = [
        Transfer(id=9500, debit_account_id=1, credit_account_id=1,
                 amount=1, ledger=1, code=1),  # accounts equal: fails
        Transfer(id=9500, debit_account_id=1, credit_account_id=2,
                 amount=1, ledger=1, code=1),  # now succeeds
        Transfer(id=9500, debit_account_id=1, credit_account_id=2,
                 amount=2, ledger=1, code=1),  # exists_with_different...
        Transfer(id=9500, debit_account_id=1, credit_account_id=2,
                 amount=1, ledger=1, code=1),  # exists
    ] + [
        Transfer(id=9600 + i, debit_account_id=3 + i % 10,
                 credit_account_id=13 + i % 10, amount=1, ledger=1, code=1)
        for i in range(12)
    ]
    _check(oracle, dev, ts, tr)


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_zipfian_hot_mix_randomized_parity(seed):
    """Randomized zipfian hot-account batches with same-batch two-phase
    pairs, duplicate ids, limit-account traffic and occasional chains —
    run through auto dispatch; every batch bit-exact and the wave path
    demonstrably engaged."""
    rng = np.random.default_rng(seed)
    oracle, dev, ts = _pair(n_accounts=40, limit_accounts=(3,),
                            funded=10_000)
    next_id = 20_000
    for _ in range(5):
        tr = []
        n_pairs = 6
        for i in range(n_pairs):
            tr.append(Transfer(
                id=next_id + i, debit_account_id=1,
                credit_account_id=int(rng.integers(4, 40)), amount=3,
                ledger=1, code=1, flags=F_PENDING,
            ))
        for i in range(n_pairs):
            tr.append(Transfer(
                id=next_id + 100 + i, pending_id=next_id + i,
                amount=0 if i % 2 else 3, flags=F_POST if i % 3 else F_VOID,
            ))
        for i in range(36):
            # zipf-ish: most traffic on accounts 1-3 (3 is limited)
            u = float(rng.random())
            a = 1 + int(39 * u**4)
            b = int(rng.integers(1, 41))
            if b == a:
                b = a % 40 + 1
            tr.append(Transfer(
                id=next_id + 200 + i, debit_account_id=a,
                credit_account_id=b, amount=1 + int(rng.integers(0, 3)),
                ledger=1, code=1,
            ))
        if rng.random() < 0.6:  # occasional duplicate id
            tr.append(Transfer(id=next_id + 200, debit_account_id=2,
                               credit_account_id=5, amount=1, ledger=1,
                               code=1))
        if rng.random() < 0.5:  # occasional chain
            tr.append(Transfer(id=next_id + 300, debit_account_id=6,
                               credit_account_id=7, amount=2, ledger=1,
                               code=1, flags=F_LINKED))
            tr.append(Transfer(id=next_id + 301, debit_account_id=6,
                               credit_account_id=7, amount=2, ledger=1,
                               code=1))
        ts = _check(oracle, dev, ts, tr)
        next_id += 1000
    assert dev.hazards.plan_stats["waves"] >= 3, dev.hazards.plan_stats


# ----------------------------------------------------------------------
# the executor's passes: each wave's live lanes packed into passes of a
# fixed width, run in wave order by one program
# ----------------------------------------------------------------------

PASS = 8  # the pass width these tests run, so a 64-lane batch needs several


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wave_chunks_packs_every_wave_lane_once_in_wave_then_lane_order(seed):
    rng = np.random.default_rng(seed)
    n, n_pad, width = 61, 64, min(L.WAVE_CHUNK, 64)
    wave_of = rng.integers(-1, 5, size=n_pad).astype(np.int32)
    wave_of[n:] = -1
    wave_of[:5] = np.arange(5)  # every wave holds a lane
    idx, k = wave_chunks(wave_of, n, n_pad)
    assert idx.shape == (-(-n_pad // width) + WAVE_CAP, width)
    counts = np.bincount(wave_of[:n][wave_of[:n] >= 0])
    assert k == int((-(-counts // width)).sum())
    assert (idx[k:] == n_pad).all()  # no pass past the k-th holds a lane
    ran = idx[:k][idx[:k] < n_pad]
    want = np.concatenate([np.flatnonzero(wave_of[:n] == w)
                           for w in range(len(counts))])
    assert (ran == want).all()  # each live lane once, waves in order
    for row in idx[:k]:  # a pass holds one wave, its pads at the end
        live = row[row < n_pad]
        assert len(set(wave_of[live])) == 1
        assert (row[len(live):] == n_pad).all()


def _probe(dev) -> HazardTracker:
    """A tracker that plans as `dev`'s does, without booking the plan."""
    probe = HazardTracker()
    probe.limit_account_ids = set(dev.hazards.limit_account_ids)
    probe._limit_lo = dev.hazards._limit_lo.copy()
    return probe


def _trio(limit_accounts=(), funded=200, n_accounts=48):
    """(oracle, the auto-planned ledger, the serial-scan ledger, ts)."""
    oracle, dev, ts = _pair(n_accounts, limit_accounts, funded)
    _oracle, serial, _ts = _pair(n_accounts, limit_accounts, funded, mode="serial")
    return oracle, dev, serial, ts


def _plain(n, first_id=20_000, accounts=range(20, 40)):
    acc = list(accounts)
    return [Transfer(id=first_id + i, debit_account_id=acc[i % len(acc)],
                     credit_account_id=acc[(i + 7) % len(acc)], amount=1 + i % 3,
                     ledger=1, code=1) for i in range(n)]


def _touches(limited, times, first_id, amount=3):
    """`times` debits of each limited account, to plain accounts 41-47."""
    return [Transfer(id=first_id + 100 * t + j, debit_account_id=a,
                     credit_account_id=41 + (j + t) % 7, amount=amount,
                     ledger=1, code=1)
            for t in range(times) for j, a in enumerate(limited)]


def _case(name):
    """(limited accounts, funding, the batch, waves, lanes a wave, residue)."""
    if name == "a_wave_wider_than_a_pass":
        # wave 0: 40 plain lanes + the first touch (6 passes); waves 1-2: 1 lane
        return (5,), 200, _touches([5], 3, 7000)[:1] + _plain(40) + \
            _touches([5], 3, 7000)[1:], 3, [41, 1, 1], 0
    if name == "a_wave_of_exactly_a_pass_and_one_of_one_lane":
        lim = list(range(5, 5 + PASS))
        tr = _touches(lim, 2, 7000) + _touches([5], 3, 7500)[2:] + _plain(20)
        return tuple(lim), 200, tr, 3, [PASS + 20, PASS, 1], 0
    if name == "one_wave_and_a_residue":
        chain = [Transfer(id=7900, debit_account_id=2, credit_account_id=3,
                          amount=2, ledger=1, code=1, flags=F_LINKED),
                 Transfer(id=7901, debit_account_id=2, credit_account_id=3,
                          amount=2, ledger=1, code=1)]
        return (5,), 200, _touches([5], 1, 7000) + chain + _plain(40), \
            1, [41], 2
    if name == "waves_at_the_cap_and_a_residue":
        return (5,), 3 * (WAVE_CAP + 3), _touches([5], WAVE_CAP + 3, 7000, 2) + \
            _plain(20), WAVE_CAP, [21] + [1] * (WAVE_CAP - 1), 3
    if name == "a_pending_and_its_post_in_one_batch":
        pend = [Transfer(id=7000 + i, debit_account_id=1 + i % 10,
                         credit_account_id=11 + i % 8, amount=10 + i, ledger=1,
                         code=1, flags=F_PENDING) for i in range(14)]
        post = [Transfer(id=7100 + i, pending_id=7000 + i,
                         amount=0 if i % 3 else 4 + i,
                         flags=F_VOID if i % 4 == 3 else F_POST) for i in range(14)]
        return (), 0, pend + _plain(10) + post, 2, [24, 14], 0
    if name == "declines_on_empty_limited_accounts":
        # 5 of 3 each side: the second touch of every account declines
        lim = list(range(5, 15))
        return tuple(lim), 5, _touches(lim, 2, 7000) + _plain(20), 2, \
            [30, 10], 0
    raise AssertionError(name)


CASES = ["a_wave_wider_than_a_pass",
         "a_wave_of_exactly_a_pass_and_one_of_one_lane",
         "one_wave_and_a_residue", "waves_at_the_cap_and_a_residue",
         "a_pending_and_its_post_in_one_batch",
         "declines_on_empty_limited_accounts"]


@pytest.mark.parametrize("name", CASES)
def test_the_waves_in_passes_equal_the_serial_scan_and_the_oracle(name, monkeypatch):
    """Codes, every account, every transfer row, the posted set and the
    commit timestamp of the passes equal the whole-batch serial scan's and
    the oracle's."""
    monkeypatch.setattr(L, "WAVE_CHUNK", PASS)
    limited, funded, tr, waves, lanes, residue = _case(name)
    oracle, dev, serial, ts = _trio(limited, funded)
    arr = transfers_to_np(tr)
    decision, plan = _probe(dev).plan(arr)
    assert decision == "waves" and plan.n_waves == waves
    assert plan.residue_n == residue
    assert np.bincount(plan.wave_of[plan.wave_of >= 0]).tolist() == lanes
    idx, k = wave_chunks(plan.wave_of, len(tr), 64)
    assert k == sum(-(-c // PASS) for c in lanes) and idx.shape[1] == PASS
    ts += len(tr)
    dense_o = oracle.execute_dense(Operation.create_transfers, ts, tr)
    dense_s = serial.execute_dense(Operation.create_transfers, ts, tr)
    dense_d = dev.execute_dense(Operation.create_transfers, ts, tr)
    assert dense_d == dense_s == dense_o
    if name == "declines_on_empty_limited_accounts":
        assert dense_d.count(int(R.exceeds_credits)) == 10
    assert dev.extract() == serial.extract() == \
        (oracle.accounts, oracle.transfers, oracle.posted)
    assert dev.commit_timestamp == serial.commit_timestamp == oracle.commit_timestamp
    assert dev.hazards.plan_stats["waves"] == 1


def test_a_fault_in_a_pass_stops_every_later_pass(monkeypatch):
    """The device's load guard trips in the second pass of a wave: the first
    pass stays applied (its rows with their original timestamps), the
    faulting pass and every later one apply nothing, and the host raises."""
    monkeypatch.setattr(L, "WAVE_CHUNK", PASS)
    _oracle, dev, ts = _pair(48, (5,), 200)
    tr = _touches([5], 3, 7000)[:1] + _plain(40) + _touches([5], 3, 7000)[1:]
    arr = transfers_to_np(tr)
    _decision, plan = _probe(dev).plan(arr)
    idx, k = wave_chunks(plan.wave_of, len(tr), 64)
    assert k == 8
    limit = (1 << TEST_PROCESS.transfer_slots_log2) // 2
    # room for exactly one pass more
    dev.state = {**dev.state, "xfer_used_slots": jnp.uint64(limit - PASS)}
    ts += len(tr)
    pending = dev.execute_async(Operation.create_transfers, ts, arr)
    assert pending.plan == ("waves", 3)
    with pytest.raises(RuntimeError, match="capacity"):
        dev.check_fault()
    assert int(np.asarray(dev.state["fault"])) & FAULT_CAPACITY
    assert int(np.asarray(dev.state["xfer_used_slots"])) == limit
    _accounts, transfers, _posted = dev.extract()
    first = [tr[i] for i in idx[0]]
    assert set(transfers) - {900_005} == {t.id for t in first}  # and the funding
    for i in idx[0]:
        assert transfers[tr[i].id].timestamp == ts - len(tr) + int(i) + 1


# ----------------------------------------------------------------------
# plumbing: decision on the handle, metrics catalog, stats compat
# ----------------------------------------------------------------------


def test_handle_plan_and_wave_metrics():
    """The wave decision rides the commit_async handle (replica surfaces
    it as commit.group.wave_*), the waves.* metrics are registered under
    CATALOG'd names, and split_stats stays a readable compat view."""
    from tigerbeetle_tpu.state_machine import StateMachine

    dev = DeviceLedger(process=TEST_PROCESS, mode="auto")
    metrics = Metrics()
    dev.instrument(metrics, dev.tracer)
    sm = StateMachine(dev)
    ts = 10_000
    accounts = [Account(id=i, ledger=1, code=1) for i in range(1, 12)]
    ts += len(accounts)
    dev.execute_dense(Operation.create_accounts, ts, accounts)
    tr = [
        Transfer(id=100 + i, debit_account_id=1, credit_account_id=2 + i % 9,
                 amount=2, ledger=1, code=1, flags=F_PENDING)
        for i in range(8)
    ] + [
        Transfer(id=200 + i, pending_id=100 + i, flags=F_POST)
        for i in range(8)
    ] + [
        Transfer(id=300 + i, debit_account_id=2 + i % 9,
                 credit_account_id=3 + i % 8 if 3 + i % 8 != 2 + i % 9
                 else 11, amount=1, ledger=1, code=1)
        for i in range(16)
    ]
    body = transfers_to_np(tr).tobytes()
    ts += len(tr)
    handle = sm.commit_async(Operation.create_transfers, ts, body)
    plan = sm.handle_plan(handle)
    assert plan is not None and plan[0] == "waves" and plan[1] >= 2
    assert sm.commit_finish(handle) == b""  # all-success
    # waves.* metrics live under CATALOG'd names
    names = {
        c.name for c in metrics._counters.values()
    } | {g.name for g in metrics._gauges.values()} | {
        h.name for h in metrics._histograms.values()
    }
    wave_names = {n for n in names if n.startswith("waves.")}
    assert {"waves.batches", "waves.per_batch", "waves.chain_len_max",
            "waves.occupancy"} <= wave_names
    assert all(n in CATALOG for n in wave_names), wave_names - set(CATALOG)
    # legacy stat surface: same dict, legacy keys present
    s = dict(dev.hazards.split_stats)
    for key in ("fast", "fast_pv", "serial", "split", "split_pv", "waves"):
        assert key in s, s


def test_simulator_seed_matrix_with_waves():
    """Same seed, conflict-heavy workload, REAL device backend: two runs
    are byte-identical (the full stats dict, which folds in the committed
    history via the checker) — the wave planner introduces no
    nondeterminism under consensus, crashes included."""
    from tigerbeetle_tpu.testing.simulator import run_simulation

    kwargs = dict(
        ticks=220,
        backend_factory=None,  # the DeviceLedger (wave planner live)
        n_clients=1,
        crash_probability=0.002,
        workload_knobs={
            "conflict_rate": 0.3,
            "two_phase_rate": 0.35,
            "chain_rate": 0.1,
            "limit_account_rate": 0.2,
        },
    )
    a = run_simulation(17, **kwargs)
    b = run_simulation(17, **kwargs)
    assert a == b
    assert a["committed_ops"] > 3
