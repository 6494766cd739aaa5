"""The readers of the request anatomy's legs by class
(benchmarks/layer_metrics/lookup_wait_ms.py, reply_hold_ms.py) on a recorded pair of [stats] snapshots: each value is
the window mean of its histogram in ms, checked by hand, and each reader
returns None against a server whose registry lacks the histogram (the
program before the per-class anatomy) or saw no sample in the window.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness.named import named  # noqa: E402


def snapshot(t: float, hists: dict) -> dict:
    return {"t": t, "metrics": {"counters": {}, "gauges": {}, "histograms": {
        name: {"count": count, "mean": mean_us}
        for name, (count, mean_us) in hists.items()}}}


def recorded_ctx() -> dict:
    """A 40 s window (t 100 -> 140) of a rate cell: 240 creates and 240
    lookups sampled inside it, on top of 50 of each before it."""
    return {
        "stats0": snapshot(100.0, {
            "latency.lookup.commit_wait_us": (50, 60_000.0),
            "latency.create.reply_egress_us": (50, 400.0),
        }),
        "stats1": snapshot(140.0, {
            "latency.lookup.commit_wait_us": (290, 55_000.0),
            "latency.create.reply_egress_us": (290, 3_000.0),
        }),
    }


@pytest.mark.parametrize("name,want", [
    # (290 x 55000 - 50 x 60000) us over 240 lookups
    ("lookup_wait_ms", (290 * 55_000.0 - 50 * 60_000.0) / 240 / 1e3),
    # (290 x 3000 - 50 x 400) us over 240 creates
    ("reply_hold_ms", (290 * 3_000.0 - 50 * 400.0) / 240 / 1e3),
])
def test_reader_by_hand(name, want):
    assert named("layer_metrics", name).read(recorded_ctx()) == pytest.approx(
        want, rel=1e-12)


@pytest.mark.parametrize("name", ["lookup_wait_ms", "reply_hold_ms"])
def test_reader_finds_nothing_without_the_histogram(name):
    bare = {"stats0": snapshot(100.0, {"latency.e2e_us": (10, 5.0)}),
            "stats1": snapshot(140.0, {"latency.e2e_us": (20, 5.0)})}
    assert named("layer_metrics", name).read(bare) is None
    # nor in a window that sampled nothing of its class
    ctx = recorded_ctx()
    ctx["stats1"] = ctx["stats0"]
    assert named("layer_metrics", name).read(ctx) is None
