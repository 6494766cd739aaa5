"""The readers of PR 26's per-layer metrics (benchmarks/harness/window.py)
on a recorded ctx: two [stats] snapshots with a flight-recorder history,
and a reduced trace whose idle gaps mix the program's `tb.` spans with
runtime names. Each value is checked against a count by hand, and each
reader returns None where its counter or span is absent (an older server,
the other backend).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import window  # noqa: E402
from benchmarks.harness.named import named  # noqa: E402


def snapshot(t, busy_s, done, batches, slots, fetch_s, frames=None):
    snap = {"t": t, "metrics": {"counters": {
        "device.commit_busy_s": busy_s, "device.commit_batches_done": done,
        "device.commit_batches": batches, "device.commit_slots": slots,
        "loop.fetch_s": fetch_s}, "gauges": {}, "histograms": {}}}
    if frames is not None:
        count, mean_us = frames
        snap["metrics"]["histograms"]["bus.frame_recv_us"] = {
            "count": count, "mean": mean_us}
    return snap


def recorded_ctx():
    """A 40 s window (t 100 -> 140): 32 s of device time for 480 batches
    done, launched as 30 groups of 16 slots and 6 of 4 carrying 500
    batches, 6 s of the loop inside the reply fetch, 200 large frames.
    The history has one entry every 2 s; a launch of 16 batches completes
    in each, costing 0.8 s in the first half and 1.2 s in the second."""
    history = [{"t": 98.0, "dt": None, "counters": {}}]
    for i in range(21):  # entries ending at 100, 102, ..., 140
        t = 100.0 + 2 * i
        history.append({"t": t, "dt": 2.0, "counters": {
            "device.commit_busy_s": 0.8 if t <= 120.0 else 1.2,
            "device.commit_batches_done": 16}})
    history.append({"t": 141.0, "dt": 1.0, "counters": {"loop.turns": 5}})
    stats1 = snapshot(140.0, 42.0, 680, 700, 784, 7.5, frames=(300, 2600.0))
    stats1["history"] = history
    return {
        "stats0": snapshot(100.0, 10.0, 200, 200, 280, 1.5, frames=(100, 3000.0)),
        "stats1": stats1,
        "trace": {
            "idle_gap_total_s": 2.5,
            "idle_gaps": [
                ["python3:tb.applier.wait_work", 1.9],
                ["futex-default-SDomainT:ReadSyncFlag", 0.3],
                ["python3:tb.ledger.group_launch", 0.1],
                ["python3:PjitFunction(convert_element_type)", 0.05],
                ["unattributed", 0.05],
                # a runtime thread whose NAME merely holds the letters
                ["tb.worker:Wait", 0.05]],
        },
    }


@pytest.mark.parametrize("name,want", [
    ("kernel_ms_window", 1e3 * 32.0 / 480),
    ("device_idle_window", 100.0 * (1 - 32.0 / 40.0)),
    ("group_fill", 100.0 * 500 / 504),
    ("loop_fetch_share", 100.0 * 6.0 / 40.0),
    # (300 x 2600 - 100 x 3000) us over 200 frames
    ("frame_recv_ms", 2.4),
    # last quarter (130, 140]: 5 entries of 1.2 s; first (100, 110]: 5 of 0.8
    ("kernel_ms_late_over_early", 1.5),
    # 2.5 s idle, 2.0 of them under a tb. span
    ("idle_unnamed_share", 100.0 * 0.5 / 2.5),
])
def test_reader_by_hand(name, want):
    ctx = recorded_ctx()
    assert getattr(window, name)(ctx) == pytest.approx(want, rel=1e-9)
    # the file BENCHMARK.json's entry names finds the same reader
    assert named("layer_metrics", name).read(ctx) == pytest.approx(want)


def without(ctx, *counters):
    for which in ("stats0", "stats1"):
        for c in counters:
            ctx[which]["metrics"]["counters"].pop(c, None)
    return ctx


@pytest.mark.parametrize("name,strip", [
    ("kernel_ms_window", lambda c: without(c, "device.commit_busy_s")),
    ("kernel_ms_window", lambda c: without(c, "device.commit_batches_done")),
    ("device_idle_window", lambda c: without(c, "device.commit_busy_s")),
    ("group_fill", lambda c: without(c, "device.commit_slots")),
    ("loop_fetch_share", lambda c: without(c, "loop.fetch_s")),
    ("frame_recv_ms",
     lambda c: c["stats1"]["metrics"]["histograms"].clear() or c),
    ("kernel_ms_late_over_early", lambda c: c["stats1"].pop("history") and c),
    ("idle_unnamed_share", lambda c: c.pop("trace") and c),
    ("idle_unnamed_share", lambda c: c["trace"].pop("idle_gaps") and c),
])
def test_reader_finds_nothing_to_read(name, strip):
    """The parent commit's server (no such counter, no `tb.` span) and
    the other backend: None, never an exception."""
    assert getattr(window, name)(strip(recorded_ctx())) is None


def test_a_launchless_window_reads_none_not_zero_division():
    ctx = recorded_ctx()
    ctx["stats1"]["metrics"]["counters"].update({
        "device.commit_batches_done": 200, "device.commit_slots": 280})
    assert window.kernel_ms_window(ctx) is None
    assert window.group_fill(ctx) is None


def test_late_over_early_needs_a_history_that_reaches_the_windows_start():
    ctx = recorded_ctx()
    # the wire snapshot shed the history to its newest entries
    ctx["stats1"]["history"] = ctx["stats1"]["history"][-8:]
    assert window.kernel_ms_late_over_early(ctx) is None
    # a quarter in which no launch completed has no time a batch
    ctx = recorded_ctx()
    for entry in ctx["stats1"]["history"]:
        if 130.0 < entry["t"] <= 140.0:
            entry["counters"] = {}
    assert window.kernel_ms_late_over_early(ctx) is None


def test_a_trace_without_gaps_has_no_unnamed_share():
    ctx = recorded_ctx()
    ctx["trace"].update(idle_gap_total_s=0.0, idle_gaps=[])
    assert window.idle_unnamed_share(ctx) is None
    # with every gap under runtime names (the parent commit): 100 %
    ctx = recorded_ctx()
    ctx["trace"]["idle_gaps"] = [["futex-default-SDomainT:ReadSyncFlag", 2.5]]
    assert window.idle_unnamed_share(ctx) == 100.0


def test_every_new_metric_of_benchmark_json_has_its_file_and_its_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sat = ["default_follower.plain_sat16", "default_onpath.plain_sat16"]
    rate = ["default_follower.plain_rate"]
    want = {
        "kernel_ms_window.sat": sat, "kernel_ms_window.rate": rate,
        "kernel_ms_late_over_early.sat": sat,
        "device_idle_window.sat": sat, "device_idle_window.rate": rate,
        "group_fill.sat": sat, "loop_fetch_share.sat": sat,
        "frame_recv_ms.rate": rate,
        "idle_unnamed_share.sat": sat, "idle_unnamed_share.rate": rate,
    }
    entries = {m["name"]: m for m in bench["per_layer"]}
    known = {w["name"] for w in bench["workloads"]}
    for name, cells in want.items():
        # later cells may join a metric; the first ones stay, and every
        # cell it names is one of BENCHMARK.json's
        listed = entries[name]["workloads"]
        assert set(cells) <= set(listed) <= known, name
        assert callable(named("layer_metrics", name.rsplit(".", 1)[0]).read)
