"""The two cells PR 30 adds, the one PR 34 adds and the one PR 37 adds,
rehearsed end to end on the CPU at a tiny geometry through benchmarks/run.py
(a real `start --backend device` or `--backend sharded --shards 4` server,
the cell's own traffic file, the reference's replay): `correct` true, the
cell's metrics, and the control `lost_ack` not correct. The rehearsal hook is the one
benchmarks/tests/test_yardstick.py uses (that directory is no package, so
its few lines are repeated here). The sharded server finds its four CPU
devices through the XLA_FLAGS tests/conftest.py puts into the environment,
which the served child inherits; TINY's geometry is then per shard.
"""

import argparse
import json
import os

import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {
    "config": {"account_slots_log2": 10, "transfer_slots_log2": 20,
               "accounts": 300, "batch_events": 64},
    "rate": 20,
    "mix": {"trace_seconds": 1.0, "warm_until_lag_plateau": False},
}
# the serial scan carries the whole transfer table through every step, and
# on the CPU copies it: a table of 2^16 slots keeps a batch of 64 at ~10 ms
TINY_BY_CELL = {
    "linked_onpath.linked3_sat16": {"transfer_slots_log2": 16},
}
# what a traced run reads from the program's counters and the generator's
# clock (the CPU has no device plane: the trace's own metrics stay out)
COUNTED = {
    "twophase_onpath.twophase_sat16": {
        "client_retries.sat", "create_p90_ms.sat", "loop_busy_share.sat",
        "fused_share.sat", "loop_fetch_share.sat", "kernel_ms_window.sat",
        "device_idle_window.sat", "plans_per_batch.sat",
        "plan_ms_per_batch.sat", "pending_registry_rows.sat",
        "kernel_ms_pv_window.sat"},
    "default_onpath.plain_rate": {
        "gen_late_ms.rate", "wire_ms.rate", "commit_wait_ms.rate",
        "kernel_ms_window.rate", "device_idle_window.rate"},
    "sharded4.plain_sat16": {
        "client_retries.sat", "create_p90_ms.sat", "loop_busy_share.sat",
        "loop_fetch_share.sat", "kernel_ms_window.sat",
        "device_idle_window.sat", "window_compiles", "shard_rows_skew.sat"},
    "linked_onpath.linked3_sat16": {
        "client_retries.sat", "create_p90_ms.sat", "loop_busy_share.sat",
        "fused_share.sat", "loop_fetch_share.sat", "kernel_ms_window.sat",
        "device_idle_window.sat",
        "window_compiles", "plans_per_batch.sat", "plan_ms_per_batch.sat",
        "kernel_ms_serial_window.sat", "linked_share.sat",
        "solo_dispatch_ms_per_batch.sat"},
}
TRACED = {
    "twophase_onpath.twophase_sat16": {
        "kernel_ms_per_batch.sat", "kernel_ms_late_over_early.sat",
        "device_idle_share.sat", "idle_unnamed_share.sat",
        "twophase_kernels_roofline.sat"},
    "default_onpath.plain_rate": {
        "frame_recv_ms.rate", "launches_per_batch.rate",
        "kernel_ms_per_batch.rate", "commit_kernels_roofline.rate",
        "device_idle_share.rate", "idle_unnamed_share.rate"},
    "sharded4.plain_sat16": {
        "kernel_ms_per_batch.sat", "kernel_ms_late_over_early.sat",
        "device_idle_share.sat", "idle_unnamed_share.sat",
        "sharded_kernels_roofline.sat"},
    "linked_onpath.linked3_sat16": {
        "device_idle_share.sat", "idle_unnamed_share.sat",
        "serial_kernels_roofline.sat"},
}
END_TO_END = {
    "twophase_onpath.twophase_sat16": {"committed_tps", "setup_s"},
    "default_onpath.plain_rate": {"batch_p50_ms", "batch_p90_ms",
                                  "lookup_p50_ms", "setup_s"},
    "sharded4.plain_sat16": {"committed_tps", "setup_s"},
    "linked_onpath.linked3_sat16": {"committed_tps", "setup_s"},
}


def rehearse(workload, trace_flag, seconds=3.0, controls=()):
    from benchmarks import run

    args = argparse.Namespace(workload=workload, seed=2**31 + 30,
                              seconds=seconds, trace=trace_flag,
                              control=list(controls))
    reh = {"config": dict(TINY["config"], **TINY_BY_CELL.get(workload, {})),
           "mix": dict(TINY["mix"]), "rate": TINY["rate"]}
    return run.run_cell(args, rehearse=reh)


# the linked case runs after the accepted three, which keep their order: the
# sharded case ends at its op-960 checkpoint, whose stall the harness's 10 s
# [stats] wait just outlasts, and with the linked case before it it did not
# (PERF.md section 7 row 29)
@pytest.mark.parametrize(
    "workload", sorted(COUNTED, key=lambda w: (w.startswith("linked"), w)))
def test_new_cell_rehearsed_is_correct_and_reports_its_metrics(workload, capfd):
    from benchmarks import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert {m["name"] for m in run.cell_metrics(bench, "end_to_end", workload)
            } == END_TO_END[workload]
    listed = {m["name"] for m in run.cell_metrics(bench, "per_layer", workload)}
    assert listed == COUNTED[workload] | TRACED[workload]

    result, code = rehearse(workload, trace_flag=1, controls=("lost_ack",))
    assert code == 3  # a rehearsal can never pass for a chip run
    assert result["correct"] is True and result["failed"] == 0
    assert all(v["value"] <= v["limit"] for v in result["compared"].values())
    assert COUNTED[workload] <= set(result["metrics"]) <= listed
    log = capfd.readouterr().err
    assert "control lost_ack: correct=False" in log
    failed_by_design = int(
        log.split('"events_failed_by_design": ')[1].split(",")[0])
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    if workload.startswith("linked"):
        # every batch is 21 chains of 3 and one plain lane (64 = 21 x 3 + 1:
        # 63 of 64 lanes in a chain), chains 1 and 17 of each roll back
        # whole; every launch is the serial tier's, solo
        assert failed_by_design > 0 and failed_by_design % 6 == 0
        assert metrics["linked_share.sat"] == pytest.approx(100 * 63 / 64)
        assert metrics["fused_share.sat"] == 0.0
        assert metrics["kernel_ms_serial_window.sat"] > 0.0
        assert metrics["kernel_ms_serial_window.sat"] == pytest.approx(
            metrics["kernel_ms_window.sat"])
        assert metrics["solo_dispatch_ms_per_batch.sat"] > 0.0
        assert metrics["plans_per_batch.sat"] >= 1.0
        assert metrics["window_compiles"] == 0.0
        return
    assert failed_by_design == 0
    if workload.startswith("twophase"):
        # past the ramp every launch is a solo launch, planned more than
        # once, and the registry holds the 32-33 batches no post resolved
        assert metrics["fused_share.sat"] < 50.0
        assert metrics["plans_per_batch.sat"] > 1.0
        assert metrics["plan_ms_per_batch.sat"] > 0.0
        assert metrics["kernel_ms_pv_window.sat"] > 0.0
        assert 32 * 64 <= metrics["pending_registry_rows.sat"] <= (33 + 16) * 64
    elif workload.startswith("sharded4"):
        # four devices hold the state; every commit is one launch left in
        # flight (PR 35: the loop is busy with something queued, and reads
        # a reply's two words off a landed buffer instead of waiting out
        # the kernel); the owner hash spreads the rows evenly
        assert result["device"]["count"] == 4
        assert metrics["loop_busy_share.sat"] > 0.0
        assert 0.0 < metrics["loop_fetch_share.sat"] < 50.0
        assert metrics["kernel_ms_window.sat"] > 0.0
        assert 0.0 <= metrics["device_idle_window.sat"] < 100.0
        assert metrics["window_compiles"] == 0.0
        assert 1.0 <= metrics["shard_rows_skew.sat"] < 1.2
    else:
        assert result["attempted"] == 2 * 20 * 3  # a create and a lookup a tick
