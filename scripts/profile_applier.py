#!/usr/bin/env python
"""One-shot applier profiler: drive a follower DualLedger with synthetic
batches, capture a bounded XLA trace window on the apply thread, and
report the commit_wait decomposition.

This is the incident tool the README cookbook ends on: when `inspect
live` / the frontier ladder says commit_wait dominates and the device
sub-leg columns name a sub-leg, this script reproduces the applier in
isolation and hands you (a) the per-sub-leg totals and slowest-apply
breakdown, and (b) a stitched Perfetto file where the jax.profiler
device timeline sits clock-aligned under the applier's spans — so the
sub-leg's interior (which XLA op, h2d vs kernel vs gap) is one click
deep.

Usage:
    python scripts/profile_applier.py --out /tmp/applier_profile
    python scripts/profile_applier.py --out /tmp/p --batches 64 \
        --batch 256 --window-s 2.0 --jax-platform cpu

Writes under --out:
    devtrace/...            the jax.profiler capture + clock-anchor meta
    applier.trace.json      the applier-side span dump (JsonTracer)
    stitched.json           spans + device timeline, one Perfetto file
    report.json             sub-leg totals, dominant, slowest applies,
                            compile-sentinel snapshot

Host+device in ONE process (no server, no sockets): the native engine
computes the reply codes exactly like the dual backend's reply path,
apply_commit feeds the follower queue, and finalize() proves parity
before the report is trusted.
"""

import argparse
import json
import os
import sys
from time import perf_counter_ns

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(
        description="capture an XLA trace window on the dual-backend "
        "applier thread and report the commit_wait sub-leg decomposition"
    )
    ap.add_argument("--out", required=True,
                    help="output directory (created)")
    ap.add_argument("--batches", type=int, default=32,
                    help="transfer batches to apply (default 32)")
    ap.add_argument("--batch", type=int, default=256,
                    help="events per batch (default 256)")
    ap.add_argument("--window-s", type=float, default=3.0,
                    help="device-trace window length (default 3.0)")
    ap.add_argument("--stall-s", type=float, default=0.0,
                    help="throttle the apply loop per run (forces queue "
                    "buildup + fused runs, like a real backlog)")
    ap.add_argument("--jax-platform", default=None,
                    help="JAX_PLATFORMS override (e.g. cpu)")
    args = ap.parse_args()

    if args.jax_platform:
        os.environ["JAX_PLATFORMS"] = args.jax_platform
    os.makedirs(args.out, exist_ok=True)

    import numpy as np

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.latency import device_leg_totals, dominant_leg
    from tigerbeetle_tpu.metrics import Metrics
    from tigerbeetle_tpu.models.dual_ledger import DualLedger
    from tigerbeetle_tpu.models.ledger import COMPILE_SENTINEL
    from tigerbeetle_tpu.tracer import JsonTracer
    from tigerbeetle_tpu.types import Operation

    from tigerbeetle_tpu.cli import announce_device

    device = announce_device()  # names the device; refuses an unasked CPU
    metrics = Metrics()
    tracer = JsonTracer(metrics=metrics)
    led = DualLedger(12, 14, follower=True, warm_kernels=True)
    led.instrument(metrics, tracer)
    devtrace = os.path.join(args.out, "devtrace")
    led.start_device_trace(devtrace, args.window_s)
    if args.stall_s:
        led._test_apply_delay_s = args.stall_s

    n_accounts = 64
    acc = np.zeros(n_accounts, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, n_accounts + 1, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1

    op_no = 0

    def drive(op, arr):
        # the replica's commit-finalize seam: native reply codes first,
        # then the follower enqueue — every op SAMPLED (lat_ns stamped)
        # so the report sees the full population, not 1-in-16
        nonlocal op_no
        op_no += 1
        led.prepare(op, len(arr))
        ts = led.prepare_timestamp
        p = led.execute_async(op, ts, arr)
        led.drain(p)
        with tracer.span("profile.commit", trace=op_no):
            led.apply_commit(op_no, op, ts, arr, p.codes,
                             prepare_checksum=0xABCD_0000 + op_no,
                             trace=op_no, lat_ns=perf_counter_ns())

    drive(Operation.create_accounts, acc)
    rng = np.random.default_rng(7)
    for b in range(args.batches):
        x = np.zeros(args.batch, dtype=types.TRANSFER_DTYPE)
        x["id_lo"] = np.arange(1000 + b * args.batch,
                               1000 + (b + 1) * args.batch,
                               dtype=np.uint64)
        deb = rng.integers(1, n_accounts + 1, args.batch, dtype=np.uint64)
        cred = deb % n_accounts + 1
        x["debit_account_id_lo"] = deb
        x["credit_account_id_lo"] = cred
        x["amount_lo"] = 1
        x["ledger"] = 1
        x["code"] = 1
        drive(Operation.create_transfers, x)

    led._test_apply_delay_s = 0.0
    snap_before = {}
    report_ok = led.finalize(timeout=600)
    snap = metrics.snapshot()
    totals = device_leg_totals(snap)
    leg, share = dominant_leg(snap_before, totals)
    report = {
        "device": device,
        "verified": report_ok.get("verified"),
        "device_subleg_totals_us": {k: round(v["total_us"], 1)
                                    for k, v in totals.items()},
        "dominant_subleg": leg,
        "dominant_share": share,
        "device_slowest": led.device_anatomy.slowest(limit=8),
        "compile_sentinel": COMPILE_SENTINEL.snapshot(),
        "trace_window_dir": devtrace,
    }
    with open(os.path.join(args.out, "report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    span_path = os.path.join(args.out, "applier.trace.json")
    tracer.dump(span_path)

    # stitch spans + device timeline into one Perfetto file
    from scripts.stitch_trace import load_device_trace
    from tigerbeetle_tpu.tracer import stitch

    merged = stitch([tracer.events_ordered()], labels=["applier"])
    dev = load_device_trace(devtrace, pid_base=1)
    merged.extend(dev)
    stitched = os.path.join(args.out, "stitched.json")
    with open(stitched, "w") as f:
        json.dump({"traceEvents": merged}, f, sort_keys=True,
                  separators=(",", ":"))

    print(f"verified={report['verified']} "
          f"dominant={leg} ({share:.0%})", file=sys.stderr)
    for k, v in sorted(totals.items(),
                       key=lambda kv: -kv[1]["total_us"]):
        print(f"  {k:<18} {v['total_us'] / 1000.0:9.2f} ms",
              file=sys.stderr)
    sent = report["compile_sentinel"]
    print(f"compiles total={sent['total']} "
          f"post_warmup={sent['post_warmup']}", file=sys.stderr)
    print(f"device events stitched: {len(dev)} -> {stitched}",
          file=sys.stderr)
    return 0 if report["verified"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
