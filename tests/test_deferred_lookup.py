"""A reply leaves when its own result is ready (PR 33).

Two halves of one mechanism: `DeviceLedger.lookup_async` launches a lookup
and leaves it in the replica's in-flight queue (StateMachine.commit_async /
commit_finish), and the non-blocking `Replica.flush_commits(only_ready=
True)` finalizes the longest prefix of that queue whose own results are
ready — so a create's reply does not wait for the lookup dispatched behind
it. The CPU computes a lookup at once, so the tests that need a lookup
still in flight gate its handle's `is_ready`."""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tigerbeetle_tpu import benchmark, types
from tigerbeetle_tpu.constants import TEST_CLUSTER, TEST_PROCESS
from tigerbeetle_tpu.io.storage import MemoryStorage, ZoneLayout
from tigerbeetle_tpu.lsm.grid import Grid
from tigerbeetle_tpu.lsm.groove import Forest
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.models.ledger import DeviceLedger, PendingLookup
from tigerbeetle_tpu.models.oracle import OracleStateMachine
from tigerbeetle_tpu.state_machine import StateMachine, encode_ids
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOOKUP_OF = {
    Operation.create_accounts: Operation.lookup_accounts,
    Operation.create_transfers: Operation.lookup_transfers,
}


def _accounts(first: int, n: int) -> np.ndarray:
    acc = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(first, first + n)
    acc["ledger"] = 1
    acc["code"] = 1
    return acc


def _transfers(first: int, n: int, accounts: int) -> np.ndarray:
    arr = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    arr["id_lo"] = np.arange(first, first + n)
    arr["debit_account_id_lo"] = 1 + np.arange(n) % accounts
    arr["credit_account_id_lo"] = 1 + (np.arange(n) + 3) % accounts
    arr["amount_lo"] = 7
    arr["ledger"] = 1
    arr["code"] = 1
    return arr


class _Gate:
    """Stands in front of `backend.lookup_async`: the handles it hands out
    report not-ready until `open` is set, whatever the CPU has computed."""

    def __init__(self, backend):
        self.open = False
        gate = self

        class Gated(PendingLookup):
            __slots__ = ()

            def is_ready(self) -> bool:
                return gate.open and super().is_ready()

        real = backend.lookup_async

        def gated(operation, ids):
            p = real(operation, ids)
            return Gated(p.n, p.found, p.rows, p.resolved)

        backend.lookup_async = gated


def _window_replica(commit_window: int = 4):
    """One replica with a commit window and two registered clients (the
    window goes on after the registers: `add_client` expects its reply at
    once)."""
    cluster = Cluster(replica_count=1)
    r = cluster.replicas[0]
    c1, c2 = cluster.add_client(), cluster.add_client()
    r.commit_window = commit_window
    return cluster, r, c1, c2


def _dispatch_create_then_lookup(cluster, r, creator, reader):
    """One create_accounts and, behind it, a lookup of its ids: both
    dispatched, neither finalized. Returns (base op, the accounts)."""
    acc = _accounts(1, 16)
    base = r.commit_min
    creator.request(Operation.create_accounts, acc.tobytes())
    cluster.network.run()
    reader.request(
        Operation.lookup_accounts, encode_ids([int(x) for x in acc["id_lo"]])
    )
    cluster.network.run()
    cluster.pump_commits_ahead_of_results()
    assert r.commit_min == base + 2
    assert len(r._inflight) == 2
    create, lookup = (e["handle"] for e in r._inflight)
    assert isinstance(lookup, tuple) and isinstance(lookup[1], PendingLookup)
    jax.block_until_ready(create[1].summary)  # the create's own result
    assert creator.reply is None and reader.reply is None
    return base, acc


def test_create_reply_leaves_while_the_lookup_behind_it_is_in_flight():
    cluster, r, c1, c2 = _window_replica()
    gate = _Gate(r.sm.backend)
    base, acc = _dispatch_create_then_lookup(cluster, r, c1, c2)

    # the idle loop's call: the create is ready, the lookup is not
    assert r.flush_commits(only_ready=True) == 1
    cluster.network.run()
    h1, body1 = c1.take_reply()
    assert h1.op == base + 1 and body1 == b""
    assert c2.reply is None, "the lookup's reply left before its result"
    assert len(r._inflight) == 1
    assert r.group_stats["replies_ahead"] == 1
    assert r.flush_commits(only_ready=True) == 0  # nothing else is ready

    gate.open = True
    assert r.flush_commits(only_ready=True) == 1
    cluster.network.run()
    h2, body2 = c2.take_reply()
    assert h2.op == base + 2  # op order
    rows = np.frombuffer(body2, dtype=types.ACCOUNT_DTYPE)
    assert list(rows["id_lo"]) == list(acc["id_lo"])
    # the last entry had nothing younger to leave ahead of
    assert r.group_stats["replies_ahead"] == 1
    snap = r.metrics.snapshot()["counters"]
    assert snap["ledger.lookup_deferred"] == 1
    assert snap.get("ledger.lookup_inline", 0) == 0


def test_the_solo_dispatch_path_hands_a_ready_reply_to_the_wire():
    """PR 37: the solo dispatch path finalizes the ready prefix behind the
    op it just dispatched and flushes the transport at once
    (`Network.flush_pending`: the TCP bus buffers its sends until the loop
    pumps, and under a dispatch that blocks for a whole launch the next
    pump is launches away). The op just dispatched is never finalized
    there, ready or not. The recorder stands in for the in-process
    network's no-op flush."""
    cluster, r, c1, c2 = _window_replica()
    flushes = []
    cluster.network.flush_pending = lambda: flushes.append(len(r._inflight))
    gate = _Gate(r.sm.backend)
    acc = _accounts(1, 16)
    base = r.commit_min
    c1.request(Operation.create_accounts, acc.tobytes())
    cluster.network.run()
    r.pump_commits()
    assert len(r._inflight) == 1 and not flushes  # the newest is kept
    jax.block_until_ready(r._inflight[0]["handle"][1].summary)
    c2.request(Operation.lookup_accounts, encode_ids(list(range(1, 17))))
    cluster.network.run()
    r.pump_commits()  # dispatches the lookup, then releases the create
    assert flushes == [1] and len(r._inflight) == 1
    assert r.group_stats["replies_ahead"] == 1
    cluster.network.run()
    h1, body1 = c1.take_reply()
    assert h1.op == base + 1 and body1 == b""
    assert c2.reply is None
    jax.block_until_ready(r._inflight[0]["handle"][1].rows)
    gate.open = True
    assert r.flush_commits(only_ready=True) == 1
    cluster.network.run()
    assert c2.take_reply()[0].op == base + 2
    assert flushes == [1]  # the idle loop's flush leaves the wire to the pump


def test_a_lookup_at_the_head_holds_the_ready_create_behind_it():
    """Replies leave in op order: a create whose result is ready does not
    overtake the not-ready lookup ahead of it in the queue."""
    cluster, r, c1, c2 = _window_replica()
    c1.request(Operation.create_accounts, _accounts(1, 16).tobytes())
    cluster.network.run()
    r.pump_commits()
    assert r.flush_commits() == 1
    cluster.network.run()
    c1.take_reply()
    gate = _Gate(r.sm.backend)
    c2.request(Operation.lookup_accounts, encode_ids(list(range(1, 17))))
    cluster.network.run()
    c1.request(Operation.create_accounts, _accounts(100, 8).tobytes())
    cluster.network.run()
    r.pump_commits()
    assert len(r._inflight) == 2
    jax.block_until_ready(r._inflight[1]["handle"][1].summary)
    assert r.flush_commits(only_ready=True) == 0
    assert c1.reply is None and c2.reply is None
    gate.open = True
    assert r.flush_commits(only_ready=True) == 2
    cluster.network.run()
    assert c2.take_reply()[0].op + 1 == c1.take_reply()[0].op
    assert r.group_stats["replies_ahead"] == 0


@pytest.mark.parametrize("site", ["flush_commits", "checkpoint"])
def test_blocking_flush_drains_a_deferred_lookup(site):
    """Checkpoint, restore and status change drain the whole queue with the
    blocking flush_commits(): a deferred lookup goes through commit_finish
    like any other handle, ready or not."""
    cluster, r, c1, c2 = _window_replica()
    _Gate(r.sm.backend)  # never opened: the blocking flush does not ask
    base, acc = _dispatch_create_then_lookup(cluster, r, c1, c2)
    if site == "checkpoint":
        r.checkpoint()
    else:
        assert r.flush_commits() == 2
    assert not r._inflight
    cluster.network.run()
    assert c1.take_reply()[0].op == base + 1
    h2, body2 = c2.take_reply()
    assert h2.op == base + 2
    assert len(body2) == 16 * 128
    assert r.group_stats["replies_ahead"] == 0  # only the idle path counts


def _device_sm():
    ledger = DeviceLedger(TEST_CLUSTER, TEST_PROCESS)
    ledger.instrument(Metrics(), NULL_TRACER)
    return ledger, StateMachine(ledger, TEST_CLUSTER)


@pytest.mark.parametrize(
    "create_op", [Operation.create_accounts, Operation.create_transfers],
    ids=["accounts", "transfers"],
)
def test_deferred_lookups_between_creates_match_the_oracle(create_op):
    """create A, lookup(A's ids + B's ids), create B — all three dispatched
    before any is finished, the state donated to B's launch while the
    lookup is in flight: the lookup sees all of A and none of B."""
    ledger, dev = _device_sm()
    ref = StateMachine(OracleStateMachine(), TEST_CLUSTER)
    lookup_op = LOOKUP_OF[create_op]
    ts = 1_000_000_000

    def both(op, body):
        nonlocal ts
        ts += 64
        return dev.commit_async(op, ts, body), ref.commit(op, ts, body)

    if create_op == Operation.create_transfers:
        h, want = both(Operation.create_accounts, _accounts(1, 24).tobytes())
        assert dev.commit_finish(h) == want
        a, b = _transfers(1000, 32, 24), _transfers(2000, 32, 24)
    else:
        a, b = _accounts(1, 32), _accounts(500, 32)
    ids = [int(x) for x in a["id_lo"]] + [int(x) for x in b["id_lo"]]

    handles = [
        both(create_op, a.tobytes()),
        both(lookup_op, encode_ids(ids)),
        both(create_op, b.tobytes()),
        both(lookup_op, encode_ids(ids)),
    ]
    for h, _ in handles:
        assert isinstance(h, tuple), "answered at dispatch"
    replies = [(dev.commit_finish(h), want) for h, want in handles]
    for got, want in replies:
        assert got == want
    assert len(replies[1][0]) == 32 * 128  # all of A, none of B
    assert len(replies[3][0]) == 64 * 128
    # the blocking call reads the same bytes
    assert ledger.lookup_rows(lookup_op, ids) == replies[3][1]
    snap = ledger.metrics.snapshot()["counters"]
    assert snap["ledger.lookup_deferred"] == 2
    assert snap["ledger.lookup_inline"] == 1


def test_probe_window_overflow_raises_at_finish(monkeypatch):
    ledger, dev = _device_sm()
    h = dev.commit_async(
        Operation.create_accounts, 1_000_000_000, _accounts(1, 8).tobytes()
    )
    assert dev.commit_finish(h) == b""
    real = ledger._lookup_kernel

    def unresolved(operation):
        kernel = real(operation)

        def run(state, ids):
            found, rows, resolved = kernel(state, ids)
            return found, rows, jnp.zeros_like(resolved)

        return run

    monkeypatch.setattr(ledger, "_lookup_kernel", unresolved)
    body = encode_ids(list(range(1, 9)))
    handle = dev.commit_async(Operation.lookup_accounts, 0, body)  # no raise
    assert isinstance(handle, tuple)
    with pytest.raises(RuntimeError, match="probe-window overflow"):
        dev.commit_finish(handle)
    with pytest.raises(RuntimeError, match="probe-window overflow"):
        ledger.lookup_rows(Operation.lookup_accounts, list(range(1, 9)))


def test_transfers_lookup_over_a_spill_store_stays_inline():
    """`merge_lookup_rows` may raise GridBlockCorrupt and the replica's
    stall-and-retry lives at dispatch: the ledger decides by what it holds
    (a spill store), not by a flag."""
    layout = ZoneLayout(TEST_CLUSTER, grid_size=96 * 1024 * 1024)
    forest = Forest(
        Grid(MemoryStorage(layout), offset=0, block_count=640, cache_blocks=64)
    )
    ledger = DeviceLedger(TEST_CLUSTER, TEST_PROCESS, forest=forest)
    ledger.instrument(Metrics(), NULL_TRACER)
    assert ledger.spill is not None
    dev = StateMachine(ledger, TEST_CLUSTER)
    ref = StateMachine(OracleStateMachine(), TEST_CLUSTER)
    ts = 1_000_000_000
    for op, arr in (
        (Operation.create_accounts, _accounts(1, 24)),
        (Operation.create_transfers, _transfers(1000, 32, 24)),
    ):
        ts += 64
        want = ref.commit(op, ts, arr.tobytes())
        assert dev.commit_finish(dev.commit_async(op, ts, arr.tobytes())) == want

    def counters():
        snap = ledger.metrics.snapshot()["counters"]
        return (snap.get("ledger.lookup_deferred", 0),
                snap.get("ledger.lookup_inline", 0))

    body = encode_ids(list(range(1000, 1040)))
    handle = dev.commit_async(Operation.lookup_transfers, 0, body)
    assert isinstance(handle, bytes)
    assert handle == ref.commit(Operation.lookup_transfers, 0, body)
    assert len(handle) == 32 * 128
    assert counters() == (0, 1)
    # an accounts lookup never touches the spill store: deferred as ever
    body = encode_ids(list(range(1, 25)))
    handle = dev.commit_async(Operation.lookup_accounts, 0, body)
    assert isinstance(handle, tuple)
    assert dev.commit_finish(handle) == ref.commit(
        Operation.lookup_accounts, 0, body
    )
    assert counters() == (1, 1)


def test_backends_without_the_deferred_call_answer_a_lookup_at_dispatch():
    sm = StateMachine(OracleStateMachine(), TEST_CLUSTER)
    sm.commit(Operation.create_accounts, 1_000_000_000, _accounts(1, 4).tobytes())
    handle = sm.commit_async(Operation.lookup_accounts, 0, encode_ids([1, 2, 9]))
    assert isinstance(handle, bytes) and len(handle) == 2 * 128
    assert sm.commit_finish(handle) is handle


# -- the served process: the event loop's idle branch -------------------

SMALL = ("--account-slots-log2", "10", "--transfer-slots-log2", "12",
         "--grid-mb", "8")


@pytest.mark.parametrize("backend", ["device", "dual"])
def test_served_loop_answers_a_create_and_the_lookup_behind_it(
        backend, tmp_path):
    """`start --backend device`: every lookup of the run is deferred and
    none inline; `dual`: the C++ engine answers lookups as bytes, its
    handles are ready at dispatch, so neither counter moves and no reply
    ever leaves ahead of a younger op's result. Both: every reply arrives,
    the lookup reads what the create before it wrote."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(PYTHONPATH=REPO, TB_PARENT_WATCHDOG="1", TB_JAX_PLATFORM="cpu")
    path = str(tmp_path / "d.tigerbeetle")
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster", "0",
         "--replica", "0", "--replica-count", "1", "--grid-mb", "8", path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert fmt.returncode == 0, fmt.stderr
    port = benchmark.free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{port}", "--backend", backend, *SMALL,
         path],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    rounds = 6
    try:
        benchmark.wait_listening(proc, backend, deadline_s=240)
        writer = benchmark._BenchClient(0xE0101, port)
        reader = benchmark._BenchClient(0xE0102, port)
        writer.register()
        reader.register()
        writer.client.request(Operation.create_accounts,
                              benchmark._accounts_body(1, 8))
        assert writer.wait_reply()[1] == b""
        rng = np.random.default_rng(1)
        for i in range(rounds):
            ids = list(range(1000 + 16 * i, 1016 + 16 * i))
            # both on the wire before either reply is awaited: the lookup
            # is dispatched behind the create, as a tick of a rate cell
            writer.client.request(
                Operation.create_transfers,
                benchmark._transfers_body(rng, ids[0], 16, 8))
            writer.pump()
            time.sleep(0.05)  # the create's frame first (two connections)
            reader.client.request(Operation.lookup_transfers, encode_ids(ids))
            h_c, body_c = writer.wait_reply()
            h_l, body_l = reader.wait_reply()
            assert body_c == b""
            assert h_l.op > h_c.op
            rows = np.frombuffer(body_l, dtype=types.TRANSFER_DTYPE)
            assert [int(x) for x in rows["id_lo"]] == ids
        writer.bus.drop_connections()
        reader.bus.drop_connections()
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        benchmark.kill_process_group(proc)
    assert proc.returncode == 0, out[-3000:]
    stats = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("[stats] "))[8:])
    c = stats["metrics"]["counters"]
    if backend == "device":
        assert c["ledger.lookup_deferred"] == rounds
        assert c.get("ledger.lookup_inline", 0) == 0
    else:
        assert c.get("ledger.lookup_deferred", 0) == 0
        assert c.get("ledger.lookup_inline", 0) == 0
        assert c.get("commit.group.replies_ahead", 0) == 0
