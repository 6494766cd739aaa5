"""Customer accounts that cannot be overdrawn: the stream the cell
`limits_onpath.limited_sat16` sends (every account
debits_must_not_exceed_credits, 9,900 of them funded, 99 empty) and what
the program and the benchmark add for it.

Contracts under test:

- the configuration is `default_onpath`'s but for the accounts' flags and
  balances, and its stream flags every account, funds 1..9,900 from the
  funding account 10,001 and leaves 9,901..9,999 empty;
- `HazardTracker.plan` answers `waves` with no residue for every full
  batch of the cell's traffic, and its layout is the sequential
  longest-path layout; a chain that needs more sweeps than the planner
  once allowed (8) still plans `waves`, and lanes at or past WAVE_CAP fall
  to the residue exactly;
- `_touches_limit` equals the set lookup over u128 ids, hi limbs and ids
  that share a lo limb included;
- the DeviceLedger, launched the way the replica launches a full queue,
  equals the benchmark's plain reference on the cell's own stream (result
  codes with `exceeds_credits`, every balance, every row), while the
  `ignore_limits` reference does not; it books `waves.scan_steps` /
  `waves.live_steps` / `waves.scan_lanes` / `waves.live_lanes`, a
  `ledger.waves` span a wave batch and the planner's sweeps, and compiles
  its one wave program at its first wave batch;
- the algorithm's bytes of an all-committed and of an all-declined batch,
  by hand, and the readers of the cell's own metrics;
- the cell rehearsed end to end on the CPU through benchmarks/run.py:
  `correct`, both controls not, no compile in the window.
"""

import argparse
import collections
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from benchmarks.harness import roofline, roofline_limits, traffic
from benchmarks.harness.named import named
from benchmarks.reference.ledger_ref import ReferenceLedger
from benchmarks.reference.wire_types import (
    ACCOUNT_DTYPE,
    CREATE_TRANSFERS_RESULT_DTYPE,
    TRANSFER_DTYPE,
    AccountFlags,
)
from tigerbeetle_tpu.metrics import CATALOG, Metrics
from tigerbeetle_tpu.models.ledger import WAVE_CAP, HazardTracker
from tigerbeetle_tpu.tracer import JsonTracer
from tigerbeetle_tpu.types import CreateTransferResult as R
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 42
LIMIT = int(AccountFlags.debits_must_not_exceed_credits)
CELL = "limits_onpath.limited_sat16"
# the rehearsal's size: 39 drawn accounts, 3 of them empty, batches of 64
# (10-15 waves deep, as the cell's 8190 over 9,999 are)
SMALL = {"accounts": 40, "batch_events": 64,
         "opening_balances": {"amount": 1000000, "accounts": {"from": 1, "to": 37}}}


def cell_config(**over) -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "limits_onpath.json")) as f:
        return dict(json.load(f), **over)


def cell_stream(seed=SEED, **over):
    return traffic.Stream(traffic.load_traffic("limited_sat16"),
                          cell_config(**over), seed)


def limited_tracker(stream) -> HazardTracker:
    tracker = HazardTracker()
    for arr in stream.account_batches():
        tracker.note_limit_accounts(arr)
    return tracker


def sequential_layout(arr, cap=None) -> np.ndarray:
    """Each lane's wave when every touch of an account is ordered: one
    more than the latest wave of either of its accounts; -1 from `cap` on."""
    last = collections.defaultdict(lambda: -1)
    out = np.zeros(len(arr), dtype=np.int64)
    for i, (dr, cr) in enumerate(zip(arr["debit_account_id_lo"].tolist(),
                                     arr["credit_account_id_lo"].tolist())):
        out[i] = last[dr] = last[cr] = max(last[dr], last[cr]) + 1
    if cap is not None:
        out[out >= cap] = -1
    return out


# -- the configuration and its stream ----------------------------------------


def test_the_configuration_is_default_onpath_but_for_the_accounts():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "default_onpath.json")) as f:
        base = json.load(f)
    cfg = cell_config()
    rewritten = {"name", "source", "deployment", "guarantees", "assumed",
                 "account_flags", "opening_balances"}
    assert {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)
            } == rewritten
    assert cfg["guarantees"][:4] == base["guarantees"]
    assert "exceeds_credits" in cfg["guarantees"][4]
    assert set(base["assumed"]) < set(cfg["assumed"])
    assert "empty_accounts" in cfg["assumed"]
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == ["transfers"]
    assert cfg["controls"] == ["lost_ack", "ignore_limits"]
    mix = traffic.load_traffic("limited_sat16")
    plain = traffic.load_traffic("plain_sat16")
    changed = {"about", "warm_cycles", "warm_until_lag_plateau", "trace_seconds"}
    assert {k for k in set(mix) | set(plain) if mix.get(k) != plain.get(k)
            } == changed
    assert mix["classes"] == {"plain": []} and mix["lookups"] is False
    assert (mix["warm_cycles"], mix["trace_seconds"]) == (32, 1.5)


def test_every_account_is_limited_and_9900_are_funded_from_10001():
    stream = cell_stream()
    accounts = np.concatenate(stream.account_batches())
    assert len(accounts) == 10001
    assert (accounts["id_lo"] == np.arange(1, 10002)).all()
    assert (accounts["flags"][:10000] == LIMIT).all()
    assert accounts["flags"][10000] == 0  # the funding account, 10,001
    opening = np.concatenate(stream.opening_batches())
    assert [len(a) for a in stream.opening_batches()] == [8190, 1710]
    assert (opening["debit_account_id_lo"] == 10001).all()
    assert (opening["credit_account_id_lo"] == np.arange(1, 9901)).all()
    assert (opening["amount_lo"] == 1000000).all()
    # the draw takes 1..9,999: the 99 accounts 9,901..9,999 start empty
    _cls, arr = stream.next_create()
    assert _cls == "plain" and len(arr) == 8190 and not arr["flags"].any()
    touched = np.concatenate([arr["debit_account_id_lo"], arr["credit_account_id_lo"]])
    assert touched.min() >= 1 and touched.max() <= 9999


# -- the planner ------------------------------------------------------------


def test_every_full_batch_plans_waves_in_the_sequential_layout():
    stream = cell_stream()
    tracker = limited_tracker(stream)
    for arr in stream.opening_batches():
        assert tracker.plan(arr) == ("fast", None)  # distinct payees: depth 1
    depths = collections.Counter()
    for _ in range(64):
        _cls, arr = stream.next_create()
        decision, plan = tracker.plan(arr)
        assert decision == "waves" and plan.residue_n == 0
        assert (plan.wave_of == sequential_layout(arr)).all()
        assert plan.n_waves == int(plan.wave_of.max()) + 1
        depths[plan.n_waves] += 1
        assert 1 <= tracker.last_sweeps <= WAVE_CAP
    assert tracker.plan_stats["serial"] == 0
    assert tracker.plan_stats["waves"] == 64
    assert min(depths) >= 8 and max(depths) < WAVE_CAP


def zigzag(n: int, width: int = 64) -> np.ndarray:
    """`n` lanes in one chain, lane i paying account i + 1 from account
    i + 2, so each lane shares one account with the lane before it: a sweep
    carries the chain one lane further. The rest of the batch pays between
    accounts of their own."""
    arr = np.zeros(width, dtype=TRANSFER_DTYPE)
    arr["id_lo"] = np.arange(1, width + 1)
    arr["debit_account_id_lo"][:n] = np.arange(2, n + 2)
    arr["credit_account_id_lo"][:n] = np.arange(1, n + 1)
    rest = width - n
    arr["debit_account_id_lo"][n:] = 1000 + 2 * np.arange(rest)
    arr["credit_account_id_lo"][n:] = 1001 + 2 * np.arange(rest)
    arr["amount_lo"] = 1
    arr["ledger"] = arr["code"] = 1
    return arr


def limits_on(ids) -> HazardTracker:
    tracker = HazardTracker()
    acc = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
    acc["id_lo"] = ids
    acc["flags"] = LIMIT
    tracker.note_limit_accounts(acc)
    return tracker


def test_a_chain_that_needs_more_than_eight_sweeps_plans_waves():
    arr = zigzag(12)
    tracker = limits_on(np.arange(1, 2000))
    decision, plan = tracker.plan(arr)
    # 11 sweeps carry the chain to its last lane, a 12th finds nothing to
    # move: the parent stopped at 8 and sent the whole batch to the serial
    # scan
    assert decision == "waves" and tracker.last_sweeps == 12
    assert plan.n_waves == 12 and plan.residue_n == 0
    assert (plan.wave_of == sequential_layout(arr)).all()


def test_lanes_at_or_past_the_cap_fall_to_the_residue_exactly():
    arr = zigzag(WAVE_CAP + 6)
    tracker = limits_on(np.arange(1, 2000))
    decision, plan = tracker.plan(arr)
    assert decision == "waves" and tracker.last_sweeps == WAVE_CAP
    assert plan.n_waves == WAVE_CAP and plan.residue_n == 6
    assert (plan.wave_of == sequential_layout(arr, cap=WAVE_CAP)).all()


@pytest.mark.parametrize("hi_limbs", [False, True])
def test_touches_limit_equals_the_set_lookup(hi_limbs):
    rng = np.random.default_rng(5)
    ids_lo = rng.integers(0, 64, 40, dtype=np.uint64)
    ids_hi = (rng.integers(0, 3, 40, dtype=np.uint64) if hi_limbs
              else np.zeros(40, dtype=np.uint64))
    acc = np.zeros(40, dtype=ACCOUNT_DTYPE)
    acc["id_lo"], acc["id_hi"], acc["flags"] = ids_lo, ids_hi, LIMIT
    tracker = HazardTracker()
    tracker.note_limit_accounts(acc)
    arr = np.zeros(4096, dtype=TRANSFER_DTYPE)
    for side in ("debit", "credit"):
        arr[f"{side}_account_id_lo"] = rng.integers(0, 80, 4096, dtype=np.uint64)
        if hi_limbs:
            arr[f"{side}_account_id_hi"] = rng.integers(0, 4, 4096, dtype=np.uint64)
    limited = tracker.limit_account_ids
    want = np.array([
        (int(a) | (int(b) << 64)) in limited or (int(c) | (int(d) << 64)) in limited
        for a, b, c, d in zip(arr["debit_account_id_lo"], arr["debit_account_id_hi"],
                              arr["credit_account_id_lo"], arr["credit_account_id_hi"])])
    assert want.any() and not want.all()
    assert (tracker._touches_limit(arr) == want).all()
    if hi_limbs:  # ids that share a lo limb take the 16-byte search
        assert len(np.unique(ids_lo)) < len(limited)
    # a tracker whose id set and lo limbs were written directly (a restore,
    # a test) answers the same
    other = HazardTracker()
    other.limit_account_ids = set(limited)
    other._limit_lo = np.sort(np.array([x & (2**64 - 1) for x in limited],
                                       dtype=np.uint64))
    assert (other._touches_limit(arr) == want).all()
    assert not HazardTracker()._touches_limit(arr).any()


# -- the program against the reference, on the cell's stream ----------------

CREATES = 10


@pytest.fixture(scope="module")
def driven():
    """The accounts, opening balances and CREATES batches of the cell's
    stream at the rehearsal's size through a served DeviceLedger the way
    the replica commits a full queue (a fuse probe over the run at the
    head, turned down; the head batch alone), and through the reference
    and its ignore_limits control."""
    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models import ledger as L

    # a geometry of its own: its kernels (and their wave programs) are
    # shared with no other test's ledger
    led = L.DeviceLedger(process=ConfigProcess(account_slots_log2=10,
                                               transfer_slots_log2=13))
    m, tracer = Metrics(), JsonTracer()
    led.instrument(m, tracer)
    ref, control = ReferenceLedger(), ReferenceLedger(fault="ignore_limits")
    stream = cell_stream(**SMALL)
    ts = 10**15
    for acc in stream.account_batches():
        ts += len(acc) + 3
        assert led.execute(Operation.create_accounts, ts, acc) == \
            ref.execute(Operation.create_accounts, ts, acc) == \
            control.execute(Operation.create_accounts, ts, acc) == []
    for arr in stream.opening_batches():
        ts += len(arr) + 3
        assert led.execute(Operation.create_transfers, ts, arr) == \
            ref.execute(Operation.create_transfers, ts, arr) == []
        control.execute(Operation.create_transfers, ts, arr)
    warm = led.kernels.commit_wave_chunks.fn._cache_size()
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
                    pending.plan))
    led.check_fault()
    want = [ref.execute(Operation.create_transfers, ts, arr) for ts, arr in batches]
    wrong = [control.execute(Operation.create_transfers, ts, arr)
             for ts, arr in batches]
    return SimpleNamespace(led=led, ref=ref, control=control, metrics=m,
                           tracer=tracer, batches=batches, got=got, want=want,
                           wrong=wrong, warm=warm,
                           programs=led.kernels.commit_wave_chunks.fn._cache_size())


def test_result_codes_equal_the_reference_and_the_empty_accounts_decline(driven):
    declined = 0
    for (sparse, (tier, _waves)), want in zip(driven.got, driven.want):
        assert tier == "waves"
        assert sparse == want
        assert {c for _i, c in sparse} <= {R.exceeds_credits}
        declined += len(sparse)
    assert declined > 0
    # the control enforces no limit: it answers nothing but ok
    assert not any(driven.wrong) and driven.wrong != driven.want


def test_every_balance_and_row_equals_the_reference(driven):
    ids = list(range(1, SMALL["accounts"] + 2))
    rows = driven.led.lookup_rows(Operation.lookup_accounts, ids)
    assert rows == driven.ref.lookup_account_rows(ids)
    assert rows != driven.control.lookup_account_rows(ids)
    acc = np.frombuffer(rows, dtype=ACCOUNT_DTYPE)
    limited = acc[:SMALL["accounts"]]
    assert (limited["debits_posted_lo"] <= limited["credits_posted_lo"]).all()
    for _ts, arr in driven.batches:
        ids = [int(i) for i in arr["id_lo"]]
        assert driven.led.lookup_rows(Operation.lookup_transfers, ids) == \
            driven.ref.lookup_transfer_rows(ids)
    assert driven.led.fingerprint() == driven.ref.fingerprint()


def test_the_wave_counters_spans_and_warm_programs(driven):
    counters = driven.metrics.snapshot()["counters"]
    waves = [w for (_s, (_t, w)) in driven.got]
    # a batch of 64 pads to 64 lanes, one pass wide: a pass a wave
    assert counters["ledger.tier.waves"] == CREATES
    assert counters["waves.scan_steps"] == counters["waves.live_steps"] == sum(waves)
    assert counters["waves.scan_lanes"] == 64 * sum(waves)
    assert counters["waves.live_lanes"] == 64 * CREATES
    for name in ("waves.scan_steps", "waves.live_steps", "waves.scan_lanes",
                 "waves.live_lanes"):
        assert name in CATALOG
    spans = [e for e in driven.tracer.events_ordered() if e["name"] == "ledger.waves"]
    assert [(e["args"]["waves"], e["args"]["steps"], e["args"]["residue"])
            for e in spans] == [(w, w, 0) for w in waves]
    plans = [e for e in driven.tracer.events_ordered()
             if e["name"] == "ledger.plan" and e["args"].get("tier") == "waves"]
    assert len(plans) == CREATES
    assert all(1 <= e["args"]["sweeps"] <= WAVE_CAP for e in plans)
    # nothing compiled a wave program before the first wave batch; it
    # compiled ONE, which every later wave batch ran, whatever its waves
    assert driven.warm == 0
    assert driven.programs == 1
    assert len(set(waves)) > 1


# -- the roofline and the readers -----------------------------------------


def record(events: int, failures: list, phase="window"):
    reply = np.zeros(len(failures), dtype=CREATE_TRANSFERS_RESULT_DTYPE)
    reply["index"] = np.arange(len(failures))
    reply["result"] = failures
    return SimpleNamespace(operation=int(Operation.create_transfers), done=1.0,
                           events=events, reply=reply.tobytes(), phase=phase)


def synthetic_ctx(records, module_s=0.5, collected_s=0.5, done=20, window_s=40.0):
    stats = lambda t, n: {"t": t, "metrics": {"counters": {  # noqa: E731
        "device.commit_batches_done": n}}}
    return {"records": records, "device": {"kind": "TPU v5 lite"},
            "config": {"accounts": 0, "account_slots_log2": 20,
                       "transfer_slots_log2": 40},
            "stats0": stats(7.0, 1), "stats1": stats(7.0 + window_s, 1 + done),
            "trace": {"collected_s": collected_s,
                      "modules": [["jit_commit_wave_chunks", module_s, 1],
                                  ["jit__lookup_accounts", 9.0, 3]]}}


def test_the_bytes_of_a_committed_and_of_a_declined_batch_by_hand():
    # on empty tables a probe sequence is one key long: a committed transfer
    # moves 128 + 16 + 2 x (256 + 16) = 688 B; a declined one is looked for
    # (16) and its two accounts found and read (2 x (128 + 16)): 304 B
    committed = record(30, [])
    declined = record(30, [int(R.exceeds_credits)] * 30)
    assert roofline_limits.declined(committed) == 0
    assert roofline_limits.declined(declined) == 30
    ctx = synthetic_ctx([committed])
    assert roofline.commit_bytes(30, 0.0, 0.0) == 30 * 688
    assert roofline_limits.batch_bytes(ctx) == pytest.approx(30 * 688)
    ctx = synthetic_ctx([declined])
    assert roofline_limits.batch_bytes(ctx) == pytest.approx(30 * 304)
    ctx = synthetic_ctx([committed, declined, record(30, [], phase="warm")])
    per_batch = (30 * 688 + 30 * 304) / 2  # the window's batches alone
    assert roofline_limits.batch_bytes(ctx) == pytest.approx(per_batch)
    # 20 batches in a window of 40 s: a span of 0.5 s holds a quarter
    assert roofline_limits.span_batches_fraction(ctx) == (0.25, 0.5)
    share = roofline_limits.waves_kernels_roofline(ctx)
    assert share == pytest.approx(100 * 0.25 * per_batch / 819e9 / 0.5)
    assert named("layer_metrics", "waves_kernels_roofline").read(ctx) == share
    # nothing to read: no device plane, or no launch clock
    assert roofline_limits.waves_kernels_roofline(
        dict(ctx, trace={"error": "no /device:TPU plane"})) is None
    assert roofline_limits.waves_kernels_roofline(
        dict(ctx, stats0={"t": 0.0, "metrics": {"counters": {}}})) is None


def test_the_readers_of_the_new_metrics():
    codes = [int(R.exceeds_credits), int(R.exceeds_debits), int(R.exists)]
    recs = [record(100, codes), record(100, []), record(100, codes, phase="warm")]
    ctx = synthetic_ctx(recs)
    assert named("layer_metrics", "limit_fail_share").read(ctx) == 100 * 2 / 200

    def counters(**c):
        return {"t": 0.0, "metrics": {"counters": c}}

    ctx = {"stats0": counters(**{"ledger.tier.fast": 2, "ledger.tier.fast_pv": 0,
                                 "ledger.tier.waves": 1, "ledger.tier.serial": 5,
                                 "waves.scan_steps": 12, "waves.live_steps": 11,
                                 "waves.scan_lanes": 6144, "waves.live_lanes": 4096,
                                 "device.tier_busy_s.waves": 1.0,
                                 "device.tier_batches_done.waves": 1}),
           "stats1": counters(**{"ledger.tier.fast": 2, "ledger.tier.fast_pv": 0,
                                 "ledger.tier.waves": 31, "ledger.tier.serial": 15,
                                 "waves.scan_steps": 412, "waves.live_steps": 375,
                                 "waves.scan_lanes": 216064, "waves.live_lanes": 180136,
                                 "device.tier_busy_s.waves": 25.0,
                                 "device.tier_batches_done.waves": 31})}
    ctx["stats1"]["t"] = 40.0
    assert named("layer_metrics", "waves_share").read(ctx) == 100 * 30 / 40
    assert named("layer_metrics", "wave_pad_share").read(ctx) == 100 * 36 / 400
    assert named("layer_metrics", "kernel_ms_waves_window").read(ctx) == 800.0
    assert named("layer_metrics", "wave_lane_fill").read(ctx) == \
        100 * (180136 - 4096) / (216064 - 6144)
    # a program without the new counters (the parent) reads nothing there
    bare = {"stats0": counters(), "stats1": dict(counters(), t=40.0)}
    for name in ("waves_share", "wave_pad_share", "kernel_ms_waves_window",
                 "wave_lane_fill"):
        assert named("layer_metrics", name).read(bare) is None


# -- the cell rehearsed end to end -------------------------------------------


def test_the_cell_rehearsed_is_correct_and_its_controls_are_not(capfd):
    from benchmarks import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"waves_share.sat", "wave_pad_share.sat", "kernel_ms_waves_window.sat",
           "waves_kernels_roofline.sat", "limit_fail_share.sat",
           "wave_lane_fill.sat"}
    listed = {m["name"] for m in run.cell_metrics(bench, "per_layer", CELL)}
    assert new < listed
    assert {m["name"] for m in run.cell_metrics(bench, "end_to_end", CELL)} == \
        {"committed_tps", "setup_s"}
    args = argparse.Namespace(workload=CELL, seed=2**31 + 420, seconds=3.0,
                              trace=1, control=["lost_ack", "ignore_limits"])
    reh = {"config": dict(SMALL, account_slots_log2=10, transfer_slots_log2=16),
           "mix": {"trace_seconds": 1.0}}
    result, code = run.run_cell(args, rehearse=reh)
    assert code == 3  # a rehearsal can never pass for a chip run
    assert result["correct"] is True and result["failed"] == 0
    log = capfd.readouterr().err
    assert "control lost_ack: correct=False" in log
    assert "control ignore_limits: correct=False" in log
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # the CPU has no device plane: the trace's own metrics stay out
    assert set(metrics) == listed - {"device_idle_share.sat",
                                     "idle_unnamed_share.sat",
                                     "waves_kernels_roofline.sat"}
    assert metrics["waves_share.sat"] == 100.0
    assert metrics["window_compiles"] == 0.0
    assert metrics["limit_fail_share.sat"] > 0.0
    assert metrics["wave_pad_share.sat"] == 0.0  # no pass runs without a lane
    assert 0.0 < metrics["wave_lane_fill.sat"] <= 100.0
    assert metrics["kernel_ms_waves_window.sat"] == pytest.approx(
        metrics["kernel_ms_window.sat"])
