"""The wave program of the limits cell on the live device: what one pass of
the masked fast kernel costs at each pass width, and what a batch costs.

Builds a DeviceLedger at the CLI's default geometry, loads the accounts
and opening balances of `benchmarks/configs/limits_onpath.json` (every
account debits_must_not_exceed_credits, 9,900 funded), then for each pass
width in turn (the lanes the wave executor packs a wave's live lanes into,
`models/ledger.py` WAVE_CHUNK, set here for the sweep and put back at the
end) commits batches of `benchmarks/traffic/limited_sat16.json` one at a
time, each waited for; the first batch of a width compiles its program and
is not timed. Fits ms a batch = passes x fixed + lanes x per-lane over
every timed batch. One JSON line per width (passes and lanes run a batch,
ms a batch and a pass, the compile) and a summary line (the fit, the width
whose batches ran fastest); the same
lines go to chiprun_out/profile_waves.json.

Run from the repo root: python scripts/profile_waves.py [--batches 12] [--seed 7]
Rehearse on the CPU at a tiny size (40 accounts, batches of 64):
JAX_PLATFORMS=cpu python scripts/profile_waves.py --rehearse
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import traffic  # noqa: E402
from tigerbeetle_tpu.constants import DEFAULT_PROCESS, ConfigProcess  # noqa: E402
from tigerbeetle_tpu.models import ledger as L  # noqa: E402
from tigerbeetle_tpu.types import Operation  # noqa: E402

WIDTHS = (256, 512, 1024, 2048, 8192)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batches", type=int, default=12,
                    help="timed batches a width (one more compiles)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from tigerbeetle_tpu.cli import announce_device

    device = announce_device()  # names the device; refuses an unasked CPU
    with open(os.path.join(REPO, "benchmarks", "configs", "limits_onpath.json")) as f:
        config = json.load(f)
    process, widths = DEFAULT_PROCESS, WIDTHS
    if args.rehearse:
        config.update(accounts=40, batch_events=64, opening_balances={
            "amount": 1000000, "accounts": {"from": 1, "to": 37}})
        process = ConfigProcess(account_slots_log2=10, transfer_slots_log2=16)
        widths = (8, 16, 32, 64)
    stream = traffic.Stream(traffic.load_traffic("limited_sat16"), config, args.seed)
    led = L.DeviceLedger(process=process)
    ts = 1 << 40
    for arr in stream.account_batches():
        ts += len(arr)
        assert led.execute(Operation.create_accounts, ts, arr) == []
    for arr in stream.opening_batches():
        ts += len(arr)
        assert led.execute(Operation.create_transfers, ts, arr) == []

    plan = led.hazards.plan
    plans: list = []

    def timed_plan(arr):
        t = time.perf_counter()
        out = plan(arr)
        plans.append((1e3 * (time.perf_counter() - t), out))
        return out

    led.hazards.plan = timed_plan
    chunk = L.WAVE_CHUNK
    out, rows = [], []
    try:
        for width in widths:
            L.WAVE_CHUNK = width
            seen = []
            for b in range(args.batches + 1):
                _cls, arr = stream.next_create()
                ts += len(arr)
                n = len(arr)
                t = time.perf_counter()
                pending = led.execute_async(Operation.create_transfers, ts, arr)
                jax.block_until_ready(pending.results)
                ms = 1e3 * (time.perf_counter() - t)
                plan_ms, (tier, wave_plan) = plans[-1]
                failed = sum(1 for c in led.drain(pending) if c)
                if tier != "waves":
                    continue
                idx, k = L.wave_chunks(wave_plan.wave_of, n, led._pad_for(n))
                row = {"width": idx.shape[1], "waves": wave_plan.n_waves,
                       "passes": k, "lanes": k * idx.shape[1],
                       "live": n - wave_plan.residue_n, "ms": ms - plan_ms,
                       "plan_ms": plan_ms, "failed": failed}
                if b:  # the first batch of a width compiles its program
                    seen.append(row)
            compiles = [e["ms"] for e in L.COMPILE_SENTINEL.snapshot()["events"]
                        if e["fn"] == "commit_wave_chunks"]
            rows += seen
            med = lambda key: float(np.median([r[key] for r in seen]))  # noqa: E731
            out.append({
                "width": width, "batches": len(seen),
                "passes_per_batch": med("passes"), "lanes_per_batch": med("lanes"),
                "live_per_batch": med("live"),
                "lane_fill": float(sum(r["live"] for r in seen)
                                   / sum(r["lanes"] for r in seen)),
                "ms_per_batch": med("ms"),
                "ms_per_pass": float(np.median([r["ms"] / r["passes"] for r in seen])),
                "compile_ms": compiles[-1] if compiles else None,
            })
        led.check_fault()
    finally:
        L.WAVE_CHUNK = chunk
    a = np.array([[r["passes"], r["lanes"]] for r in rows], dtype=float)
    (fixed_ms, lane_ms), *_ = np.linalg.lstsq(a, np.array([r["ms"] for r in rows]),
                                              rcond=None)
    out.append({
        "device": device, "seed": args.seed, "batches": len(rows),
        "fit_fixed_ms_per_pass": float(fixed_ms),
        "fit_us_per_lane": float(1e3 * lane_ms),
        "fastest_width": min(out, key=lambda w: w["ms_per_batch"])["width"],
        "waves": {str(w): sum(1 for r in rows if r["waves"] == w)
                  for w in sorted({r["waves"] for r in rows})},
        "plan_ms_median": float(np.median([r["plan_ms"] for r in rows])),
        "failed_per_batch": float(np.mean([r["failed"] for r in rows])),
        "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
    })
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "profile_waves.json"), "w") as f:
        for line in out:
            f.write(json.dumps(line) + "\n")
    for line in out:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
