"""The wave programs of the limits cell on the live device: what each
compiled bucket costs to compile, what it needs beside the tables, and
what one of its steps costs.

Builds a DeviceLedger at the CLI's default geometry, loads the accounts
and opening balances of `benchmarks/configs/limits_onpath.json` (every
account debits_must_not_exceed_credits, 9,900 funded), compiles the wave
steppers the served path warms (DeviceLedger._warm_wave_steppers), then
commits batches of `benchmarks/traffic/limited_sat16.json` one at a time,
each waited for. One JSON line per bucket and a summary line; the same
lines go to chiprun_out/profile_waves.json.

Run from the repo root: python scripts/profile_waves.py [--batches 48] [--seed 7]
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batches", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from tigerbeetle_tpu.cli import announce_device

    device = announce_device()  # names the device; refuses an unasked CPU
    with open(os.path.join(REPO, "benchmarks", "configs", "limits_onpath.json")) as f:
        config = json.load(f)
    process = DEFAULT_PROCESS
    if args.rehearse:
        config.update(accounts=40, batch_events=64, opening_balances={
            "amount": 1000000, "accounts": {"from": 1, "to": 37}})
        process = ConfigProcess(account_slots_log2=10, transfer_slots_log2=16)
    stream = traffic.Stream(traffic.load_traffic("limited_sat16"), config, args.seed)
    led = L.DeviceLedger(process=process)
    ts = 1 << 40
    for arr in stream.account_batches():
        ts += len(arr)
        assert led.execute(Operation.create_accounts, ts, arr) == []
    for arr in stream.opening_batches():
        ts += len(arr)
        assert led.execute(Operation.create_transfers, ts, arr) == []

    n_pad = led._pad_for(int(config["batch_events"]))
    t0 = time.perf_counter()
    led._warm_wave_steppers(n_pad)
    cache = L._wave_cache(led.kernels)
    programs = {}
    for W in L._WAVE_WARM:
        compiled = cache[(W, n_pad, "fast")].result()
        m = compiled.memory_analysis()
        programs[W] = {"temp_bytes": m.temp_size_in_bytes,
                       "argument_bytes": m.argument_size_in_bytes,
                       "alias_bytes": m.alias_size_in_bytes}
    warm_s = time.perf_counter() - t0
    for e in L.COMPILE_SENTINEL.snapshot()["events"]:
        for W in programs:
            if e["fn"] == f"wave_stepper_{W}x{n_pad}_fast":
                programs[W]["compile_ms"] = e["ms"]

    plan = led.hazards.plan
    plan_ms: list[float] = []

    def timed_plan(arr):
        t = time.perf_counter()
        out = plan(arr)
        plan_ms.append(1e3 * (time.perf_counter() - t))
        return out

    led.hazards.plan = timed_plan
    rows = []
    for _ in range(args.batches):
        _cls, arr = stream.next_create()
        ts += len(arr)
        t = time.perf_counter()
        pending = led.execute_async(Operation.create_transfers, ts, arr)
        jax.block_until_ready(pending.results)
        ms = 1e3 * (time.perf_counter() - t)
        tier, waves = pending.plan
        steps = L.wave_steps(waves)
        failed = sum(1 for c in led.drain(pending) if c)
        rows.append({"tier": tier, "waves": waves, "steps": steps,
                     "ms": ms, "plan_ms": plan_ms[-1], "failed": failed})
    led.check_fault()

    out = []
    # the first batch of each bucket pays the launch's first run: skip it
    for W in sorted(programs):
        seen = [r for r in rows if r["steps"] == W][1:]
        exec_ms = [r["ms"] - r["plan_ms"] for r in seen]
        out.append({"bucket": W, **programs[W], "batches": len(seen),
                    "ms_per_batch": float(np.median(exec_ms)) if seen else None,
                    "ms_per_step": float(np.median(exec_ms)) / W if seen else None})
    waves = [r["waves"] for r in rows]
    steps = [r["steps"] for r in rows]
    out.append({
        "device": device, "batches": len(rows), "seed": args.seed,
        "tiers": {t: sum(1 for r in rows if r["tier"] == t)
                  for t in sorted({r["tier"] for r in rows})},
        "waves": {str(w): waves.count(w) for w in sorted(set(waves))},
        "pad_share": 100.0 * (sum(steps) - sum(waves)) / sum(steps),
        "plan_ms_median": float(np.median(plan_ms)),
        "failed_per_batch": float(np.mean([r["failed"] for r in rows])),
        "warm_s": warm_s,
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
