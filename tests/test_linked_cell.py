"""Split payments (PR 37): the stream the cell
`linked_onpath.linked3_sat16` sends (every transfer in a linked chain of
three from one payer, one chain in 16 rolled back) and what the program and
the benchmark add for it.

Contracts under test:

- the modifier `chain_payer` is a pure function of the batch: every whole
  chain has one payer, no payee equals it, and the flags and the broken
  chains stay `linked_chains`'s;
- `HazardTracker.plan` answers `serial` for a batch of the cell's traffic
  and books its lanes and chains into `ledger.linked_events` /
  `ledger.linked_chains` (a full batch: 8190 / 2,730; the rehearsal's 64:
  63 / 21);
- the DeviceLedger, launched the way the replica launches a full queue,
  equals the benchmark's plain reference on the cell's own stream: result
  codes (the failing event its own code, its chain's other two
  `linked_event_failed`), every balance, every row of a committed chain,
  and none of a broken chain's ids; and the solo launch books its jit call
  into `ledger.solo_dispatch_us` / `ledger.solo_dispatches`;
- the algorithm's bytes of an all-committed and of an all-rolled-back batch,
  by hand, and the traced span's batches counted as a fraction.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from benchmarks.harness import roofline, roofline_linked, traffic
from benchmarks.harness.named import named
from benchmarks.reference.ledger_ref import ReferenceLedger
from benchmarks.reference.wire_types import ACCOUNT_DTYPE, TRANSFER_DTYPE
from benchmarks.reference.wire_types import TransferFlags as TF
from tigerbeetle_tpu.metrics import CATALOG, Metrics
from tigerbeetle_tpu.tracer import JsonTracer
from tigerbeetle_tpu.types import CreateTransferResult as R
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 37
LINKED = int(TF.linked)
SMALL = {"batch_events": 64, "accounts": 300, "id_order": "reversed"}


def cell_config(**over) -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "linked_onpath.json")) as f:
        return dict(json.load(f), **over)


def cell_stream(**over):
    return traffic.Stream(traffic.load_traffic("linked3_sat16"),
                          cell_config(**over), SEED)


def chains_of(arr: np.ndarray) -> np.ndarray:
    """(chains, 3) view of a batch's whole chains."""
    whole = len(arr) // 3 * 3
    return arr[:whole].reshape(-1, 3)


# -- the traffic -----------------------------------------------------------


def test_the_configuration_is_default_onpath_but_for_what_the_issue_names():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "default_onpath.json")) as f:
        base = json.load(f)
    cfg = cell_config()
    rewritten = {"name", "source", "deployment", "guarantees", "assumed"}
    assert {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)
            } == rewritten
    assert cfg["guarantees"][:4] == base["guarantees"]
    assert "whole or not at all" in cfg["guarantees"][4]
    assert set(base["assumed"]) < set(cfg["assumed"])
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == ["transfers"]
    mix = traffic.load_traffic("linked3_sat16")
    plain = traffic.load_traffic("plain_sat16")
    changed = {"about", "cycle", "classes", "warm_cycles",
               "warm_until_lag_plateau", "trace_seconds"}
    assert {k for k in set(mix) | set(plain) if mix.get(k) != plain.get(k)
            } == changed
    # between the issue's steps: 2.0 ends a traced run at 278-298 s of the
    # driver's 360, 1.0 leaves the profiler's start latency at the
    # harness's 5 % limit (PERF.md section 4)
    assert mix["trace_seconds"] == 1.5


@pytest.mark.parametrize("batch", [8190, 64, 7])
def test_chain_payer_gives_every_whole_chain_one_payer(batch):
    stream = cell_stream(batch_events=batch)
    plain = stream._plain(batch, 10**9)
    chained = named("modifiers", "linked_chains").apply(
        stream, {"chains": "all", "length": 3, "break_every": 16},
        plain.copy(), 10**9)
    out = named("modifiers", "chain_payer").apply(
        stream, {"length": 3}, chained.copy(), 10**9)
    c = chains_of(out)
    # one payer a chain, the first lane's as drawn; no payee is the payer
    assert (c["debit_account_id_lo"] == c["debit_account_id_lo"][:, :1]).all()
    assert (c["debit_account_id_lo"][:, 0]
            == chains_of(plain)["debit_account_id_lo"][:, 0]).all()
    assert (c["credit_account_id_lo"] != c["debit_account_id_lo"]).all()
    assert 1 <= out["credit_account_id_lo"].min()
    assert out["credit_account_id_lo"].max() <= stream.plain_accounts
    # a payee moves only where it met the payer; nothing else is touched
    moved = out["credit_account_id_lo"] != chained["credit_account_id_lo"]
    assert (chained["credit_account_id_lo"][moved]
            == out["debit_account_id_lo"][moved]).all()
    for field in TRANSFER_DTYPE.names:
        if field not in ("debit_account_id_lo", "credit_account_id_lo"):
            assert (out[field] == chained[field]).all(), field
    tail = len(out) // 3 * 3  # lanes past the last whole chain: as drawn
    assert (out[tail:] == chained[tail:]).all()
    # a pure function of the batch: no rng, no state
    again = named("modifiers", "chain_payer").apply(
        stream, {"length": 3}, chained.copy(), 10**9)
    assert again.tobytes() == out.tobytes()


def test_the_cells_batch_is_2730_chains_of_three_and_171_roll_back():
    _cls, arr = cell_stream().next_create()
    assert _cls == "split3" and len(arr) == 8190
    c = chains_of(arr)
    assert len(c) == 2730
    assert (c["flags"] == [LINKED, LINKED, 0]).all()
    broken = np.nonzero((c["amount_lo"] == 0).any(axis=1))[0]
    assert len(broken) == 171 and (broken % 16 == 1).all()
    assert (c["amount_lo"][broken, 1] == 0).all()
    assert (c["amount_lo"][broken][:, [0, 2]] > 0).all()
    assert roofline_linked.batch_outcome(arr.tobytes()) == (8190 - 513, 513)


# -- the planner ------------------------------------------------------------


@pytest.mark.parametrize("batch,events,chains", [(8190, 8190, 2730),
                                                 (64, 63, 21)])
def test_plan_answers_serial_and_counts_lanes_and_chains(batch, events, chains):
    from tigerbeetle_tpu.models.ledger import HazardTracker

    tracker = HazardTracker()
    m = Metrics()
    tracker.bind_counters(m)
    stream = cell_stream(batch_events=batch)
    for k in (1, 2):
        _cls, arr = stream.next_create()
        assert tracker.plan(arr) == ("serial", None)
        counters = m.snapshot()["counters"]
        assert counters["ledger.linked_events"] == k * events
        assert counters["ledger.linked_chains"] == k * chains
    assert tracker.plan_stats["serial"] == 2
    # a plain batch adds nothing to either
    plain = stream._plain(batch, 5 * 10**9)
    assert tracker.plan(plain)[0] == "fast"
    assert m.snapshot()["counters"]["ledger.linked_events"] == 2 * events
    for name in ("ledger.linked_events", "ledger.linked_chains",
                 "ledger.solo_dispatch_us", "ledger.solo_dispatches"):
        assert name in CATALOG


# -- the program against the reference, on the cell's stream ----------------

CREATES = 6


@pytest.fixture(scope="module")
def driven():
    """The account load and CREATES batches of the cell's stream through a
    DeviceLedger the way the replica commits a full queue (a fuse probe
    over the run at the head, turned down; the head batch alone), and
    through the reference."""
    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger

    led = DeviceLedger(process=ConfigProcess(account_slots_log2=10,
                                             transfer_slots_log2=12))
    m, tracer = Metrics(), JsonTracer()
    led.instrument(m, tracer)
    ref = ReferenceLedger()
    stream = cell_stream(**SMALL)
    ts = 10**15
    for acc in stream.account_batches():
        ts += len(acc) + 3
        assert led.execute(Operation.create_accounts, ts, acc) == \
            ref.execute(Operation.create_accounts, ts, acc) == []
    batches = []
    for _ in range(CREATES):
        _cls, arr = stream.next_create()
        ts += len(arr) + 3
        batches.append((ts, arr))
    got = []
    for i, (ts, arr) in enumerate(batches):
        assert led.try_execute_group_async(batches[i:i + 4]) is None or i == CREATES - 1
        pending = led.execute_async(Operation.create_transfers, ts, arr)
        got.append(([(k, c) for k, c in enumerate(led.drain(pending)) if c],
                    led.drain_reply(pending, Operation.create_transfers)))
    led.check_fault()
    want = [ref.execute(Operation.create_transfers, ts, arr)
            for ts, arr in batches]
    return SimpleNamespace(led=led, ref=ref, metrics=m, tracer=tracer,
                           batches=batches, got=got, want=want,
                           accounts=SMALL["accounts"])


def test_result_codes_equal_the_reference_and_a_broken_chain_fails_whole(driven):
    for (sparse, reply), want in zip(driven.got, driven.want):
        assert sparse == want
        # chains 1 and 17 of the 21: lanes 3-5 and 51-53, the zero amount
        # on the second lane
        assert sparse == [
            (3, R.linked_event_failed), (4, R.amount_must_not_be_zero),
            (5, R.linked_event_failed), (51, R.linked_event_failed),
            (52, R.amount_must_not_be_zero), (53, R.linked_event_failed)]
        assert len(reply) == 8 * 6  # (index, result) u32 pairs on the wire


def test_balances_equal_the_reference_and_hold_committed_chains_only(driven):
    ids = list(range(1, driven.accounts + 1))
    rows = driven.led.lookup_rows(Operation.lookup_accounts, ids)
    assert rows == driven.ref.lookup_account_rows(ids)
    acc = np.frombuffer(rows, dtype=ACCOUNT_DTYPE)
    committed = sum(
        int(chains_of(arr)["amount_lo"][
            (chains_of(arr)["amount_lo"] > 0).all(axis=1)].sum())
        + int(arr["amount_lo"][63]) for _ts, arr in driven.batches)
    assert int(acc["debits_posted_lo"].sum()) == committed
    assert int(acc["credits_posted_lo"].sum()) == committed
    assert not acc["debits_pending_lo"].any()


def test_a_broken_chains_ids_are_absent_from_a_lookup(driven):
    for _ts, arr in driven.batches:
        ids = [int(i) for i in arr["id_lo"]]
        rows = driven.led.lookup_rows(Operation.lookup_transfers, ids)
        assert rows == driven.ref.lookup_transfer_rows(ids)
        found = np.frombuffer(rows, dtype=TRANSFER_DTYPE)["id_lo"]
        broken = arr["id_lo"][[3, 4, 5, 51, 52, 53]]
        assert len(found) == 64 - 6 and not np.isin(broken, found).any()
        assert driven.led.lookup_rows(
            Operation.lookup_transfers, [int(i) for i in broken]) == b""
    assert driven.led.fingerprint()["transfers"] == CREATES * (64 - 6) == \
        driven.ref.fingerprint()["transfers"]


def test_every_launch_is_serial_and_its_jit_call_is_timed(driven):
    counters = driven.metrics.snapshot()["counters"]
    assert counters["ledger.tier.serial"] == CREATES
    assert counters.get("ledger.tier.fast", 0) == 0
    assert counters["ledger.group_probe_rejected"] == CREATES - 1
    # plan ran for every probe's batches and once for each launch
    assert counters["ledger.linked_events"] == 63 * counters["ledger.plan_calls"]
    assert counters["ledger.linked_chains"] == 21 * counters["ledger.plan_calls"]
    # the account loads and the creates: one timed jit call each
    loads = -(-driven.accounts // 64)
    assert counters["ledger.solo_dispatches"] == CREATES + loads
    assert counters["ledger.solo_dispatch_us"] > 0
    assert counters["ledger.drain_dense"] == CREATES  # 6 failures a batch
    spans = [e for e in driven.tracer.events_ordered()
             if e["name"] == "ledger.solo_dispatch"]
    assert len(spans) == CREATES + loads
    assert [e["args"]["tier"] for e in spans] == \
        ["accounts"] * loads + ["serial"] * CREATES
    # the span lies inside the launch's span and holds the jit call alone:
    # the planner's span ends before it starts
    plans = [e for e in driven.tracer.events_ordered()
             if e["name"] == "ledger.plan" and e["args"].get("tier") == "serial"]
    assert len(plans) == CREATES
    for plan, span in zip(plans, spans[loads:]):
        assert plan["ts"] + plan["dur"] <= span["ts"]


# -- the roofline -------------------------------------------------------------


def batch_of(n: int, zero_every: int | None) -> bytes:
    arr = np.zeros(n, dtype=TRANSFER_DTYPE)
    arr["amount_lo"] = 5
    arr["flags"].reshape(-1, 3)[:, :2] = LINKED
    if zero_every:
        arr["amount_lo"][1::zero_every] = 0
    return arr.tobytes()


def test_the_bytes_of_a_committed_and_of_a_rolled_back_batch_by_hand():
    assert roofline_linked.batch_outcome(batch_of(30, None)) == (30, 0)
    assert roofline_linked.batch_outcome(batch_of(30, 3)) == (0, 30)
    # a plain lane is a chain of one: only the zero amounts fail
    plain = np.zeros(4, dtype=TRANSFER_DTYPE)
    plain["amount_lo"] = [1, 0, 2, 0]
    assert roofline_linked.batch_outcome(plain.tobytes()) == (2, 2)
    # an open chain at the batch's end still rolls back with its zero
    arr = np.frombuffer(batch_of(6, None), dtype=TRANSFER_DTYPE).copy()
    arr["flags"][5] = LINKED
    arr["amount_lo"][4] = 0
    assert roofline_linked.batch_outcome(arr.tobytes()) == (3, 3)
    # on empty tables a probe sequence is one key long: a committed
    # transfer moves 128 + 16 + 2 x (256 + 16) = 688 B, as a plain one; a
    # rolled-back one 16 + 2 x (128 + 16) = 304 B and writes nothing
    assert roofline.commit_bytes(1, 0.0, 0.0) == 688
    assert roofline_linked.rolled_back_bytes(1, 0.0, 0.0) == 304
    assert roofline_linked.rolled_back_bytes(513, 0.0, 0.0) == 513 * 304
    half = roofline_linked.rolled_back_bytes(1, 0.5, 0.5)
    assert half == 2.5 * 16 + 2 * (128 + 1.5 * 16)


def synthetic_ctx(module_s: float, collected_s: float, done: int,
                  bodies: list, window_s: float = 40.0):
    rec = [SimpleNamespace(operation=int(Operation.create_transfers), done=1.0,
                           body=b, events=len(b) // 128) for b in bodies]
    stats = lambda t, busy, n: {"t": t, "metrics": {"counters": {  # noqa: E731
        "device.tier_busy_s.serial": busy,
        "device.tier_batches_done.serial": n}}}
    return {"records": rec, "device": {"kind": "TPU v5 lite"},
            "config": {"accounts": 0, "account_slots_log2": 20,
                       "transfer_slots_log2": 40},
            "stats0": stats(7.0, 1.0, 1),
            "stats1": stats(7.0 + window_s, 1.0 + 2.0 * done, 1 + done),
            "trace": {"collected_s": collected_s,
                      "modules": [["jit__commit_transfers", module_s, 1],
                                  ["jit__lookup_accounts", 9.0, 3]]}}


def test_the_spans_batches_are_a_fraction_and_the_share_follows_the_trace():
    # 20 batches in a window of 40 s: a span of 0.5 s holds a quarter of a
    # batch, and its commit program ran all of it
    bodies = [batch_of(30, None), batch_of(30, 3)]
    ctx = synthetic_ctx(0.5, 0.5, 20, bodies)
    assert roofline_linked.span_batches_fraction(ctx) == (0.25, 0.5)
    per_batch = (30 * 688 + 30 * 304) / 2
    assert roofline_linked.linked_batch_bytes(ctx) == pytest.approx(per_batch)
    share = roofline_linked.serial_kernels_roofline(ctx)
    assert share == pytest.approx(100 * 0.25 * per_batch / 819e9 / 0.5)
    # the same share whatever the length of the span, while the commit
    # program fills it
    assert roofline_linked.serial_kernels_roofline(
        synthetic_ctx(0.9, 0.9, 20, bodies)) == pytest.approx(share)
    # the time is the trace's: a commit program that ran half the span did
    # the same work in half the device seconds, and the share doubles
    assert roofline_linked.serial_kernels_roofline(
        synthetic_ctx(0.25, 0.5, 20, bodies)) == pytest.approx(2 * share)
    # the work is the count of batches: twice as many in the window and
    # the span holds twice the bytes
    assert roofline_linked.serial_kernels_roofline(
        synthetic_ctx(0.5, 0.5, 40, bodies)) == pytest.approx(2 * share)
    # no clock of the program enters: the seconds the launch clock booked
    # to those batches may read anything
    skewed = synthetic_ctx(0.5, 0.5, 20, bodies)
    skewed["stats1"]["metrics"]["counters"]["device.tier_busy_s.serial"] = 3.0
    assert roofline_linked.serial_kernels_roofline(skewed) == share
    # nothing to read: no device plane, or a parent without the tier clock
    no_trace = dict(ctx, trace={"error": "no /device:TPU plane"})
    assert roofline_linked.serial_kernels_roofline(no_trace) is None
    no_clock = dict(ctx, stats0={"t": 0.0, "metrics": {"counters": {}}})
    assert roofline_linked.serial_kernels_roofline(no_clock) is None
    for name in ("kernel_ms_serial_window", "linked_share",
                 "solo_dispatch_ms_per_batch"):
        assert named("layer_metrics", name).read(no_clock) is None
    assert named("layer_metrics", "kernel_ms_serial_window").read(ctx) == 2000.0
