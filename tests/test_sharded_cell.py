"""The `sharded4` deployment on the CPU mesh the suite forces (four of its
devices, a small geometry): the cell's own seeded stream (the benchmark's
generator over `plain_sat16`, the configuration's 10,000 accounts with the
limit account among them) through `ShardedLedger` behind the wire state
machine, against the benchmark's plain reference byte for byte; the
configuration's fifth guarantee (every row on exactly one shard, the one
its id hashes to, and the shards' rows together the reference's rows, each
once); and the launch bookkeeping and the drain `ShardedLedger` shares with
`DeviceLedger`. Every test runs over two drives of the stream: commits left
in flight behind one another (what the cell runs since PR 35), and one at a
time.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from benchmarks import run as bench_run
from benchmarks.harness import check, traffic
from benchmarks.reference.wire_types import ACCOUNT_DTYPE, TRANSFER_DTYPE
from tigerbeetle_tpu.constants import ConfigProcess
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARDS = 4
SEED = 2**31 + 34
CREATES = 9
# small: a batch of 1,024 and per-shard tables of 2^13 slots (the guard
# admits 4,096 rows a shard: 2,500 accounts, ~2,300 transfers)
SMALL = {"batch_events": 1024, "account_slots_log2": 13,
         "transfer_slots_log2": 13}


WINDOW = 4  # handles left in flight by the `in_flight` drive


@pytest.fixture(scope="module", params=["in_flight", "one_at_a_time"])
def driven(request):
    """One drive of the stream through both: the account load, CREATES
    create_transfers requests, then the cell's own read-back (every
    account, the ids of every create batch). `in_flight` drives it as the
    replica does since PR 35, `commit_async` with a window of handles and
    `commit_finish` oldest first (a lookup's handle is its reply: inline on
    this backend, behind every create dispatched before it);
    `one_at_a_time` through the synchronous `commit`."""
    import jax
    from jax.sharding import Mesh

    from tigerbeetle_tpu.parallel.mesh import ShardedLedger
    from tigerbeetle_tpu.state_machine import StateMachine

    with open(os.path.join(REPO, "benchmarks", "configs", "sharded4.json")) as f:
        config = dict(json.load(f), **SMALL)
    assert config["start_args"] == ["--shards", str(SHARDS)]
    mix = dict(traffic.load_traffic("plain_sat16"), readback_batches=CREATES)
    stream = traffic.Stream(mix, config, SEED)
    mesh = Mesh(np.array(jax.devices()[:SHARDS]), ("shard",))
    ledger = ShardedLedger(mesh, ConfigProcess(
        account_slots_log2=config["account_slots_log2"],
        transfer_slots_log2=config["transfer_slots_log2"]))
    metrics = Metrics()
    ledger.instrument(metrics, ledger.tracer)
    sm = StateMachine(ledger)
    requests, got, handles = [], [], []
    ts = 1_000_000_000

    def finish(keep: int) -> None:
        while len(handles) > keep:
            got.append(sm.commit_finish(handles.pop(0)))

    def send(operation, body: bytes, events: int) -> None:
        nonlocal ts
        ts += events
        requests.append(SimpleNamespace(
            operation=int(operation), body=body, ts=ts, op=len(requests) + 1))
        if request.param == "one_at_a_time":
            got.append(sm.commit(Operation(int(operation)), ts, body))
            return
        handles.append(sm.commit_async(Operation(int(operation)), ts, body))
        if events:  # a create: left in flight
            assert isinstance(handles[-1], tuple)
        finish(WINDOW)

    for arr in stream.account_batches():
        send(Operation.create_accounts, arr.tobytes(), len(arr))
    for _ in range(CREATES):
        _cls, arr = stream.next_create()
        send(Operation.create_transfers, arr.tobytes(), len(arr))
    for op, ids in bench_run.readback_requests(stream, mix, config, SEED):
        send(op, ids.tobytes(), 0)
    finish(0)
    want, _fp, ref = check.replay(requests)
    return SimpleNamespace(drive=request.param,
                           config=config, ledger=ledger, metrics=metrics,
                           requests=requests, got=got, want=want, ref=ref)


@pytest.mark.parametrize("operation", [
    Operation.create_accounts, Operation.create_transfers,
    Operation.lookup_accounts, Operation.lookup_transfers])
def test_the_cells_stream_through_four_shards_equals_the_reference(
        driven, operation):
    picked = [(r, g, w) for r, g, w in zip(driven.requests, driven.got,
                                           driven.want)
              if r.operation == int(operation)]
    n_accounts, batch = driven.config["accounts"], SMALL["batch_events"]
    assert len(picked) == {
        Operation.create_accounts: -(-n_accounts // batch),
        Operation.create_transfers: CREATES,
        Operation.lookup_accounts: -(-n_accounts // batch),
        Operation.lookup_transfers: CREATES}[operation]
    for r, g, w in picked:
        assert g == w, (operation.name, r.op, len(g), len(w))
    body = b"".join(g for _r, g, _w in picked)
    if operation == Operation.lookup_accounts:
        rows = np.frombuffer(body, dtype=ACCOUNT_DTYPE)
        assert len(rows) == n_accounts
        # the limit account is among them, and the traffic left it alone
        limit = rows[rows["id_lo"] == n_accounts]
        assert len(limit) == 1 and int(limit["flags"][0]) & 0b10
        assert int(limit["debits_posted_lo"][0]) == 0
        # the transfers moved money, and it is conserved
        assert rows["debits_posted_lo"].sum() == rows["credits_posted_lo"].sum() > 0
    elif operation == Operation.lookup_transfers:
        assert len(body) == 128 * CREATES * batch
    else:
        assert body == b""  # plain traffic: every event succeeds


@pytest.mark.parametrize("table", ["acct_rows", "xfer_rows"])
def test_every_row_is_on_its_owner_shard_and_the_shards_add_up(driven, table):
    from tigerbeetle_tpu.models.ledger import _occupied_rows
    from tigerbeetle_tpu.parallel.mesh import owner_of_ids_np

    ref = driven.ref
    if table == "acct_rows":
        dtype = ACCOUNT_DTYPE
        want = np.frombuffer(ref.lookup_account_rows(sorted(ref.accounts)),
                             dtype=dtype)
    else:
        dtype = TRANSFER_DTYPE
        want = ref.transfers.all_rows()
    state = np.asarray(driven.ledger.state[table])
    assert state.shape[0] == SHARDS
    parts = []
    for shard in range(SHARDS):
        rows = state[shard][:-1]  # the last row is the dump slot
        held = np.frombuffer(rows[_occupied_rows(rows)].tobytes(), dtype=dtype)
        assert len(held) > 0
        owners = owner_of_ids_np(held["id_lo"], held["id_hi"], SHARDS)
        assert (owners == shard).all(), (table, shard)
        parts.append(held)
    got = np.concatenate(parts)
    assert len(got) == len(want) == len(np.unique(got["id_lo"]))  # each once
    assert (np.sort(got, order="id_lo").tobytes()
            == np.sort(want, order="id_lo").tobytes())
    if table == "xfer_rows":
        # the host's per-shard charge (what the gauges read) is the truth
        assert [len(p) for p in parts] == driven.ledger._xfer_used.tolist()


def test_sharded_launches_are_booked_where_the_device_ledgers_are(driven):
    from tigerbeetle_tpu.models.ledger import DeviceLedger, HostLedgerBase

    snap = driven.metrics.snapshot()
    c, g = snap["counters"], snap["gauges"]
    loads = -(-driven.config["accounts"] // SMALL["batch_events"])
    assert c["device.commit_launches"] == c["device.commit_batches"] \
        == c["device.commit_slots"] == loads + CREATES
    assert c["ledger.tier.fast"] == CREATES and not c["ledger.tier.serial"]
    assert c["loop.fetch_s"] > 0
    # plain traffic: every batch was drained from its two-word summary
    assert c["ledger.drain_all_ok"] == loads + CREATES
    assert not c.get("ledger.drain_dense")
    used = driven.ledger._xfer_used
    assert g["sharded.xfer_rows_max"] == used.max()
    assert g["sharded.xfer_rows_mean"] == CREATES * SMALL["batch_events"] / SHARDS
    assert 1.0 <= g["sharded.xfer_rows_max"] / g["sharded.xfer_rows_mean"] < 1.1
    # no completion thread outside the serving process: nothing booked
    assert "device.commit_busy_s" not in c and driven.ledger.launch_clock is None
    # one bookkeeping path, not a copy
    for name in ("_note_launch", "_fetch", "drain", "drain_reply"):
        assert name not in vars(type(driven.ledger)) and name not in vars(DeviceLedger)
        assert name in vars(HostLedgerBase)
