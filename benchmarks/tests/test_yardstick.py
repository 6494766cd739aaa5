"""Tests of the yardstick itself (run: `python -m pytest benchmarks/tests -q`,
on the CPU; the served-path tests start real servers at a tiny geometry and
take about half a minute each).
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import check, readers, roofline, trace, traffic  # noqa: E402
from benchmarks.reference.ledger_ref import ReferenceLedger  # noqa: E402
from benchmarks.reference.scalar import OracleStateMachine  # noqa: E402
from benchmarks.reference.wire_types import Operation  # noqa: E402

TINY = {
    "config": {"account_slots_log2": 10, "transfer_slots_log2": 20,
               "accounts": 300, "batch_events": 64},
    "rate": 20,
    # on the CPU, with 64-event batches, the follower keeps up: its lag
    # never fills the window, so the rehearsal does not wait for a plateau
    "mix": {"trace_seconds": 1.0, "warm_until_lag_plateau": False},
}


def tiny_config(name="default_onpath"):
    with open(os.path.join(REPO, "benchmarks", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY["config"])
    return cfg


# -- trace -> {idle share, kernel time, roofline} ---------------------------

def recorded_planes():
    """A small trace: one chip, two programs, three ops, a 4 ms gap the
    host spent in XlaLinearize."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit__commit_transfers_1", 0.000, 0.010),
                ("jit__commit_transfers_1", 0.014, 0.010),
                ("jit_f_9", 0.0241, 0.00002),  # a reply fold: commits no batch
                ("jit__lookup_accounts_2", 0.024, 0.002)]},
            {"name": "XLA Ops", "events": [
                ("while.6", 0.000, 0.006), ("fusion.1", 0.006, 0.004),
                ("while.6", 0.014, 0.006), ("fusion.1", 0.020, 0.004),
                ("gather.3", 0.024, 0.002)]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "pjrt-tpu-tasks/123", "events": [
                ("XlaLinearize", 0.0095, 0.0040)]},
        ]},
    ]


def test_trace_reduction_on_recorded_trace():
    red = trace.reduce_planes(recorded_planes())
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.022)
    assert red["collected_s"] == pytest.approx(0.026)
    assert red["launches"] == 4
    assert red["device_ops"][0] == ["while.6", pytest.approx(0.012)]
    assert red["idle_gaps"][0][0] == "pjrt-tpu-tasks:XlaLinearize"
    assert red["idle_gaps"][0][1] == pytest.approx(0.004)
    mods = {m[0]: m[1] for m in red["modules"]}
    assert mods["jit__commit_transfers_1"] == pytest.approx(0.020)

    # the readers over it. The span's batches come from the trace and the
    # program's counters alone: 2 commit launches (the fold holds under 1 %
    # of the commit time and the lookup is a read) times the batches a
    # launch carried over the window; no acknowledgement enters
    rec = argparse.Namespace
    cfg = tiny_config()
    cfg.update(batch_events=8190, accounts=10_000, account_slots_log2=20,
               transfer_slots_log2=24)
    records = [rec(operation=readers.CREATE, done=0.01 * (i + 1), events=8190,
                   phase="window", error=None) for i in range(2)]

    def stats(**counters):
        return {"metrics": {"counters": counters}}

    ctx = {"trace": red, "records": records, "config": cfg, "kind": "rate",
           "device": {"kind": "TPU v5 lite"}, "trace_span": {"t_a": 0.0, "t_b": 0.027},
           # the follower's applier: 7 batches in 7 solo launches
           "stats0": stats(**{"shadow.batches": 10, "shadow.groups": 1, "shadow.solo": 2}),
           "stats1": stats(**{"shadow.batches": 17, "shadow.groups": 1, "shadow.solo": 9})}
    assert readers.span_batches(ctx) == pytest.approx(2.0)
    assert readers.kernel_ms_per_batch(ctx) == pytest.approx(10.01)  # fold in, lookup out
    # the divisor is the reduction's own collected span, not the stamps'
    assert trace.traced_window_s(red, 0.027) == red["collected_s"]
    assert readers.device_idle_share(ctx) == pytest.approx(100 * (1 - 0.022 / 0.026))
    assert readers.launches_per_batch(ctx) == pytest.approx(1.5)  # 2 commits + 1 fold
    assert readers.fused_share(ctx) == pytest.approx(0.0)
    bytes_ = roofline.commit_bytes(2 * 8190, 10_000 / 2**20, 2 * 8190 / 2**24)
    assert readers.commit_kernels_roofline(ctx) == pytest.approx(
        100 * bytes_ / 819e9 / 0.02002)
    # a saturated follower: 48 batches in 4 group launches = 12 a launch
    sat = {**ctx, "kind": "sat",
           "stats0": stats(**{"shadow.batches": 0, "shadow.groups": 0, "shadow.solo": 0}),
           "stats1": stats(**{"shadow.batches": 48, "shadow.groups": 4, "shadow.solo": 0})}
    assert readers.span_batches(sat) == pytest.approx(24.0)
    assert readers.kernel_ms_per_batch(sat) == pytest.approx(20.02 / 24)
    assert readers.fused_share(sat) == pytest.approx(100.0)
    # the chip on the reply path: the replica's grouping, 30 fused ops in 6
    # groups and 2 solo = 32 batches in 8 launches
    onpath = {**ctx, "kind": "sat", "stats0": stats(**{
        "commit.group.fused_ops": 0, "commit.group.solo_ops": 0,
        "commit.group.fused_groups": 0}), "stats1": stats(**{
            "commit.group.fused_ops": 30, "commit.group.solo_ops": 2,
            "commit.group.fused_groups": 6})}
    assert readers.span_batches(onpath) == pytest.approx(8.0)
    assert readers.fused_share(onpath) == pytest.approx(100 * 30 / 32)
    # no counters, nothing to count by: the metrics are left out
    assert readers.kernel_ms_per_batch({**ctx, "stats0": {}, "stats1": {}}) is None
    # no trace, nothing to read: the metric is left out, never 0
    assert readers.commit_kernels_roofline({**ctx, "trace": None}) is None
    with pytest.raises(KeyError):
        roofline.peak_hbm_bytes_per_s("TPU v9")


def test_trace_without_a_device_plane_is_an_error():
    assert "error" in trace.reduce_planes(recorded_planes()[1:])


def poll_line(first: float, last: float, every: float = 0.018) -> dict:
    """The event loop's thread: a `tb.loop.poll` span every 18 ms."""
    starts = [first + i * every for i in range(math.ceil((last - first) / every))]
    return {"name": "python3", "events": [
        ("tb.loop.poll", s, min(every, last - s)) for s in starts]}


def test_a_chip_that_never_idles_is_busy_for_at_most_its_window():
    """PR 27's case: ops back to back up to the last collected instant,
    the host tracer and the stop stamp both short of it. The stamps' span
    (here 2 ms shorter than what was collected) is not the divisor."""
    ops = [("fusion.1", 0.045 + 0.001 * i, 0.001) for i in range(4055)]  # .. 4.100
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0.045, 4.055)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [poll_line(0.046, 4.052)]},
    ]
    red = trace.reduce_planes(planes)
    stamps_s = red["collected_s"] - 0.002
    assert red["busy_s"] > stamps_s  # what the driver refused
    window_s = trace.traced_window_s(red, stamps_s)
    assert 0 < red["busy_s"] <= window_s
    assert red["collected_first_s"] == pytest.approx(0.045)
    assert red["collected_last_s"] == pytest.approx(4.100)
    idle = readers.device_idle_share({"trace": red})
    assert 0.0 <= idle < 1e-6


def test_a_chip_idle_at_the_edges_of_the_span_is_idle_inside_it():
    """The rate cell: the first kernel starts 0.1 s into the session and
    the last ends 0.12 s before its end; the program's spans on the host
    planes cover both edges, so the edge gaps count as idle."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ("while.6", 0.150 + i / 6, 0.0628) for i in range(23)]}]},
        {"name": "/host:CPU", "lines": [poll_line(0.050, 4.050)]},
    ]
    red = trace.reduce_planes(planes)
    assert red["collected_s"] == pytest.approx(4.0)
    assert red["last_s"] - red["first_s"] == pytest.approx(22 / 6 + 0.0628)
    assert red["busy_s"] == pytest.approx(23 * 0.0628)
    assert readers.device_idle_share({"trace": red}) == pytest.approx(
        100 * (1 - 23 * 0.0628 / 4.0))
    # the gaps between kernels are named as before; the edges are not gaps
    assert red["idle_gap_total_s"] == pytest.approx(22 * (1 / 6 - 0.0628))


def test_a_collected_span_far_from_the_stamps_is_a_failed_trace():
    red = trace.reduce_planes(recorded_planes())  # collected 0.026 s
    with pytest.raises(RuntimeError, match="collected"):
        trace.traced_window_s(red, 0.026 / 0.9)  # 10 % short of the stamps
    with pytest.raises(RuntimeError, match="collected"):
        trace.traced_window_s(red, 0.026 / 1.1)  # or long
    assert trace.traced_window_s(red, 0.026 / 0.97) == pytest.approx(0.026)


def test_commit_bytes_against_a_hand_count():
    # empty tables: one probe each; per transfer 128 B written + one 16 B
    # key probed, and two accounts each probed (16 B), read and written (256 B)
    assert roofline.commit_bytes(1, 0.0, 0.0) == 128 + 16 + 2 * (256 + 16)
    assert roofline.commit_bytes(8190, 0.0, 0.0) == 8190 * 688
    # half-full transfer table: 2.5 probes to insert (Knuth, linear probing)
    assert roofline.commit_bytes(1, 0.0, 0.5) == 128 + 2.5 * 16 + 2 * (256 + 16)
    assert roofline.probes_hit(0.5) == 1.5


# -- traffic: pure functions of (file, seed); the cycle is seed-free --------

ALL_CLASSES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "all_classes.json")


@pytest.mark.parametrize("mix_name", ["plain_sat16", "plain_rate", ALL_CLASSES])
def test_traffic_is_a_pure_function_of_file_and_seed(mix_name):
    mix = traffic.load_traffic(mix_name)
    cfg = tiny_config()

    def take(seed, n=90):
        st = traffic.Stream(mix, cfg, seed)
        out = [a.tobytes() for a in st.account_batches()]
        classes = []
        for _ in range(n):
            cls, arr = st.next_create()
            classes.append((cls, len(arr)))
            out.append(arr.tobytes())
            op, ids = st.next_lookup()
            out.append(bytes([op]) + ids.tobytes())
        return classes, out

    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    c1, o1 = take(big)
    c2, o2 = take(big)
    c3, o3 = take(7)
    assert o1 == o2
    assert o1 != o3
    assert c1 == c3  # same classes, same sizes, same order for every seed
    assert [c for c, _ in c1] == [mix["cycle"][k % len(mix["cycle"])]
                                  for k in range(len(c1))]


def test_all_classes_cycle_exercises_every_class_and_modifier():
    mix = traffic.load_traffic(ALL_CLASSES)
    mods = {m["do"] for steps in mix["classes"].values() for m in steps}
    here = os.path.join(REPO, "benchmarks", "modifiers")
    assert mods == {f[:-3] for f in os.listdir(here) if f.endswith(".py")}
    assert set(mix["cycle"]) == set(mix["classes"])
    # a resolve needs a pending batch before it, in every cycle
    pending = 0
    behind = mix["classes"]["post_behind"][0]["behind"]
    for cls in mix["cycle"]:
        pending += cls == "pending"
        if cls == "post_void":
            pending -= 1
            assert pending >= 0
        if cls == "post_behind":  # resolves the oldest, or is pending itself
            pending += -1 if pending >= behind else 1


def test_a_limit_account_class_needs_the_last_id_limited():
    """limit_account sends its crossing lanes to the last id: rules that
    leave it unlimited are refused, not run as a limit check of nothing."""
    mix = traffic.load_traffic(ALL_CLASSES)
    cfg = tiny_config()
    traffic.Stream(mix, {**cfg, "account_flags": [
        {"flag": "debits_must_not_exceed_credits", "accounts": {"every": 3}}]}, 1)
    with pytest.raises(ValueError, match="limit_account"):
        traffic.Stream(mix, {**cfg, "account_flags": [
            {"flag": "debits_must_not_exceed_credits", "accounts": {"from": 1, "to": 10}}]}, 1)
    with pytest.raises(ValueError, match="limit_account"):
        traffic.Stream(mix, {**cfg, "account_flags": [
            {"flag": "credits_must_not_exceed_debits", "accounts": "last"}]}, 1)


# -- the reference: numpy path == scalar loop --------------------------------

def test_reference_fast_path_equals_the_scalar_loop():
    from test_reference_paths import assert_same_state

    mix = traffic.load_traffic(ALL_CLASSES)
    cfg = tiny_config()
    st = traffic.Stream(mix, cfg, 2**31 + 5)
    fast, slow = ReferenceLedger(), OracleStateMachine()
    ts = 10**15
    for a in st.account_batches():
        ts += len(a) + 3
        assert fast.execute(Operation.create_accounts, ts, a) == \
            slow.execute(Operation.create_accounts, ts, a)
    classes, failed, failed_batches = [], 0, 0
    for _ in range(85):
        cls, arr = st.next_create()
        classes.append(cls)
        ts += len(arr) + 3
        got = fast.execute(Operation.create_transfers, ts, arr)
        assert got == slow.execute(Operation.create_transfers, ts, arr)
        failed += len(got)
        failed_batches += bool(got)
    # the numpy paths apply only events that succeed, so every failure is
    # the scalar loop's; each linked20 batch breaks 10 chains of 3
    assert failed >= 30 * classes.count("linked20") > 0
    assert fast.scalar_events >= failed
    assert fast.scalar_batches >= failed_batches
    assert fast.fast_batches > 40
    assert all(n > 0 for n in fast.path_events.values()), fast.path_events
    assert_same_state(fast, slow)


# -- run.py end to end on the CPU, at a tiny geometry ------------------------

def rehearse(workload, seconds=3.0, trace_flag=0, controls=(), fault=None,
             warm_cycles=None, conflicted=False, config=None):
    """One whole run of run.py on the CPU at the tiny geometry;
    `conflicted` swaps the cell's one plain class for the cycle of every
    batch class (tests/all_classes.json); `config` adds keys to the cell's
    configuration."""
    from benchmarks import run

    args = argparse.Namespace(workload=workload, seed=2**31 + 77,
                              seconds=seconds, trace=trace_flag,
                              control=list(controls))
    reh = {"config": {**(config or {}), **TINY["config"]}, "mix": dict(TINY["mix"]),
           "rate": TINY["rate"]}
    if conflicted:
        every = traffic.load_traffic(ALL_CLASSES)
        reh["mix"].update(cycle=every["cycle"], classes=every["classes"],
                          warm_cycles=1)
    if warm_cycles is not None:
        reh["mix"]["warm_cycles"] = warm_cycles
    return run.run_cell(args, rehearse=reh, fault=fault)


def test_rehearsal_runs_every_step_and_never_exits_zero():
    result, code = rehearse("default_follower.plain_rate",
                            controls=("lost_ack", "ignore_limits"))
    assert code == 3  # a rehearsal can never pass for a chip run
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 2 * 20 * 3  # a create and a lookup a tick
    assert set(result["metrics"]) == {"batch_p50_ms", "batch_p90_ms",
                                      "lookup_p50_ms", "setup_s"}
    assert list(result)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in result["compared"].values())


def test_the_controls_come_out_not_correct():
    """The reference with one guarantee broken, put in the program's
    place, must fail the comparison (follower: the chip's digest too)."""
    from benchmarks.harness import load

    seen = {}

    def grab(records, shadow):
        seen["records"], seen["shadow"] = list(records), dict(shadow)

    result, _code = rehearse("default_follower.plain_rate", fault=grab,
                             conflicted=True)
    assert result["correct"] is True
    out = check.compare(
        seen["records"], True, seen["shadow"].get("fingerprint_device"),
        {"exit_code": 0, "verified": True, "hash_log_ok": True, "error": None},
        controls=("lost_ack", "ignore_limits"))
    assert check.is_correct(out["numbers"])
    lost = out["controls"]["lost_ack"]
    assert lost["correct"] is False
    assert lost["numbers"]["chip_digest_fields_off"] > 0
    assert lost["numbers"]["lookup_mismatches"] > 0
    limits = out["controls"]["ignore_limits"]
    assert limits["correct"] is False and limits["numbers"]["reply_mismatches"] > 0
    assert load.CREATE == int(Operation.create_transfers)


def test_limited_funded_accounts_fail_the_ignore_limits_control():
    """limits_funded.json: every customer account carries
    debits_must_not_exceed_credits and is funded from the funding account
    before warm-up; uniform traffic then crosses limits. The sound
    reference comes out correct, the one that ignores limits does not."""
    with open(os.path.join(os.path.dirname(ALL_CLASSES), "limits_funded.json")) as f:
        cfg = json.load(f)
    seen = {}

    def grab(records, shadow):
        seen["records"], seen["shadow"] = list(records), dict(shadow)

    result, _code = rehearse(
        "default_follower.plain_rate", fault=grab,
        config={k: cfg[k] for k in ("account_flags", "opening_balances")})
    assert result["correct"] is True
    records = seen["records"]
    setup = [r for r in records if r.phase == "load"]
    accounts = TINY["config"]["accounts"]
    batches = -(-accounts // TINY["config"]["batch_events"])
    assert [r.operation for r in setup] == (
        [int(Operation.create_accounts)] * batches + [int(Operation.create_transfers)] * batches)
    lookups = [r for r in records if r.phase == "after"
               and r.operation == int(Operation.lookup_accounts)]
    assert sum(len(r.reply) for r in lookups) == 128 * (accounts + 1)  # the funding account too
    out = check.compare(
        records, True, seen["shadow"].get("fingerprint_device"),
        {"exit_code": 0, "verified": True, "hash_log_ok": True, "error": None},
        controls=("ignore_limits", "lost_ack"))
    assert check.is_correct(out["numbers"])
    assert out["detail"]["events_failed_by_design"] > 0  # limits were crossed
    assert out["reference"]["path_events"]["limited"] > 0
    for name in ("ignore_limits", "lost_ack"):
        assert out["controls"][name]["correct"] is False, name
    assert out["controls"]["ignore_limits"]["numbers"]["reply_mismatches"] > 0


def _alter_reply(records, shadow):
    """An answer altered where it is produced: one event of one window
    batch reports a failure it did not have."""
    rec = next(r for r in records if r.phase == "window"
               and r.operation == int(Operation.create_transfers)
               and r.reply == b"")
    rec.reply = np.array([(3, 46)], dtype=[("index", "<u4"), ("result", "<u4")]).tobytes()


def _alter_row(records, shadow):
    rec = next(r for r in records if r.phase == "after"
               and r.operation == int(Operation.lookup_accounts))
    rec.reply = rec.reply[:40] + bytes([rec.reply[40] ^ 1]) + rec.reply[41:]


def _drop_reply(records, shadow):
    rec = next(r for r in records if r.phase == "window")
    rec.reply, rec.done = None, 0.0


def _chip_digest_off(records, shadow):
    """The chip computed wrongly behind a correct C++ engine."""
    shadow["fingerprint_device"] = dict(
        shadow["fingerprint_device"],
        transfers_fp=shadow["fingerprint_device"]["transfers_fp"] ^ 1)


@pytest.mark.parametrize("workload,fault,number", [
    ("default_onpath.plain_sat16", _alter_reply, "reply_mismatches"),
    ("default_follower.plain_rate", _alter_row, "lookup_mismatches"),
    ("default_follower.plain_rate", _drop_reply, "unanswered"),
    ("default_follower.plain_sat16", _chip_digest_off, "chip_digest_fields_off"),
])
def test_a_broken_timed_path_comes_out_not_correct(workload, fault, number):
    result, _code = rehearse(workload, fault=fault,
                             warm_cycles=24 if "sat" in workload else None)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > result["compared"][number]["limit"]


def test_a_directory_with_nothing_to_measure_gives_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    run = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "default_follower.plain_sat16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
