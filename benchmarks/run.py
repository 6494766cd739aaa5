#!/usr/bin/env python3
"""One cell, once:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Formats a data file, starts `python -m tigerbeetle_tpu start --backend
dual|device` as a child that holds the chip, loads the accounts, warms every
tier and jit shape the cell's traffic uses (set-up), measures for --seconds,
reads back, stops the server, and compares everything the timed path
produced with the plain reference (benchmarks/reference). This process is
the load generator and never touches JAX. Everything that belongs to one
configuration, traffic mix or metric is a file found by its name in
BENCHMARK.json: configs/<config>.json, traffic/<traffic>.json (which names
its modifiers/<do>.py and draws/<name>.py), rates/<cell>.json (an open-loop
cell's fixed rate), end_to_end/<metric>.py, layer_metrics/<metric>.py.

Every line before the last names the device the SERVER reported; the last
line of stdout is the result object. No TPU, or fewer chips than the cell
asks for: exit 3 and no result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import warnings  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks.harness import check, load, server as srv, trace, traffic  # noqa: E402
from benchmarks.harness.named import named  # noqa: E402
from benchmarks.harness.readers import percentile  # noqa: E402
from benchmarks.reference.wire_types import Operation  # noqa: E402

# importing the program's client imports jax, whose TPU start-up hint is a
# line without the device's name: this process never starts a backend
warnings.filterwarnings("ignore", message="Transparent hugepages")

CACHE_DIR = os.path.join(REPO, ".jax_cache")  # fixed: the path is in the key


class Log:
    """Lines wait until the server has named its device, then every line
    carries it."""

    def __init__(self):
        self.device = None
        self._held: list[str] = []

    def __call__(self, *parts) -> None:
        text = " ".join(str(p) for p in parts)
        if self.device is None:
            self._held.append(text)
        else:
            self._emit(text)

    def _emit(self, text: str) -> None:
        d = self.device or {}
        print(f"[bench {d.get('platform')}/{d.get('kind')}/x{d.get('count')}] "
              f"{text}", file=sys.stderr, flush=True)

    def set_device(self, device: dict | None) -> None:
        self.device = device or {"platform": "unknown"}
        for text in self._held:
            self._emit(text)
        self._held = []


def load_named(folder: str, name: str):
    """The reader of a metric: benchmarks/<folder>/<name>.py, or for a
    quantity split by kind of cell (`<quantity>.sat`, `<quantity>.rate`)
    the one file benchmarks/<folder>/<quantity>.py that serves both."""
    try:
        return named(folder, name).read
    except FileNotFoundError:
        if "." not in name:
            raise
        return named(folder, name.rsplit(".", 1)[0]).read


def cell_metrics(bench: dict, key: str, workload: str) -> list[dict]:
    """The metrics of `end_to_end` or `per_layer` this cell reports."""
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


def class_table(records: list) -> dict:
    """Per batch class: count and due -> reply p50/p90/max in ms."""
    by: dict[str, list] = {}
    for r in records:
        if r.phase == "window" and r.done > 0:
            by.setdefault(r.cls, []).append(1e3 * (r.done - (r.due or r.sent)))
    return {c: {"n": len(v), "p50": round(percentile(v, 0.5), 2),
                "p90": round(percentile(v, 0.9), 2), "max": round(max(v), 2)}
            for c, v in sorted(by.items())}


def histogram(values: list, edges=(5, 10, 15, 20, 30, 50, 75, 100, 150, 200,
                                   300, 500, 750, 1000, 1500, 2000, 3000,
                                   5000)) -> dict:
    out = {f"<={e}": 0 for e in edges}
    out[f">{edges[-1]}"] = 0
    for v in values:
        for e in edges:
            if v <= e:
                out[f"<={e}"] += 1
                break
        else:
            out[f">{edges[-1]}"] += 1
    return {k: n for k, n in out.items() if n}


def run_cell(args, rehearse: dict | None = None, fault=None):
    """(result object or None, exit code). The two hooks are what the
    tests of benchmarks/tests need to drive a whole run with no chip and
    with the timed path broken underneath; the command line reaches
    neither. `rehearse` overrides sizes and pins the server to the CPU;
    such a run exercises every step and can never return exit code 0.
    `fault` alters the records where they are gathered."""
    log = Log()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return None, 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_traffic(cell["traffic"])
    if rehearse:
        config.update(rehearse.get("config", {}))
        mix.update(rehearse.get("mix", {}))
    if not os.path.isdir(os.path.join(REPO, "tigerbeetle_tpu")):
        print("benchmarks/run.py: no tigerbeetle_tpu package beside benchmarks/ "
              "- nothing to measure", file=sys.stderr)
        return None, 2
    kind = "sat" if mix["loop"] == "closed" else "rate"
    follower = config["backend"] == "dual"
    traced = bool(args.trace)
    env = srv.child_env(CACHE_DIR)
    if rehearse:
        env["TB_JAX_PLATFORM"] = "cpu"
    srv.build_native(env)
    workdir = tempfile.mkdtemp(prefix="tb_bench_")
    server = None
    loadgen = None
    try:
        path = os.path.join(workdir, "bench.tigerbeetle")
        srv.format_file(path, env, config)
        server = srv.Server(config, path, env, log, traced=traced)
        log.set_device(server.device)
        dev = server.device or {}
        if not rehearse and (dev.get("platform") != "tpu"
                             or dev.get("count") != cell["chips"]):
            log(f"the server reports {dev}: the cell needs {cell['chips']} TPU "
                "chip(s); no result")
            return None, 3
        log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} trace "
            f"{args.trace}; boot {server.boot_s:.1f}s; class cycle "
            f"{json.dumps(traffic.describe_cycle(mix))} x{len(mix['cycle'])}")

        stream = traffic.Stream(mix, config, args.seed)
        loadgen = load.Load(server.port, int(mix["sessions"]), mix["client"],
                            args.seed)
        for arr in stream.account_batches():
            rec = loadgen.call(load.Record(int(Operation.create_accounts),
                                           arr.tobytes(), "setup", "load",
                                           events=len(arr)))
            if rec.reply != b"":
                raise RuntimeError(f"account load failed: {rec.error or rec.reply[:64]}")

        ctx: dict = {"cell": cell, "config": config, "mix": mix, "kind": kind,
                     "seconds": args.seconds, "device": dev}
        lag_window = 0
        if follower and "--device-lag-window" in config.get("start_args", []):
            a = config["start_args"]
            lag_window = int(a[a.index("--device-lag-window") + 1])

        def snapshot() -> dict:
            snap = server.live_stats()
            snap["t"] = time.monotonic()
            return snap

        def lag_of(snap: dict):
            return snap.get("metrics", {}).get("gauges", {}).get(
                "shadow.device_lag_ops")

        def plateau() -> bool:
            """The follower's lag has filled its window: admission, not
            the clock's start, now sets what is acknowledged."""
            if not (follower and mix.get("warm_until_lag_plateau")):
                return True
            lag = lag_of(snapshot())
            # ... and the replica has begun to drop what it cannot admit
            # (a re-send is the client's side of a drop)
            resent = sum(s.counters.value("client.resends")
                         for s in loadgen.sessions)
            return lag is not None and lag >= lag_window and resent > 0

        def settle() -> None:
            """Rate cells: the follower has worked off the warm-up's lag."""
            if not follower:
                return
            deadline = time.monotonic() + 300.0
            while time.monotonic() < deadline:
                lag = lag_of(snapshot())
                if lag is None or lag <= 1:
                    return
                time.sleep(0.25)
            raise TimeoutError("the follower never worked off the warm-up's lag")

        tracer: dict = {}

        def trace_span() -> None:
            """Open and close the profiler window from a thread of its
            own, so that the generator never waits for the profiler."""
            span_s = float(mix.get("trace_seconds", 4.0))
            time.sleep(min(5.0, max(0.0, args.seconds - span_s) / 2))
            tdir = os.path.join(workdir, "trace")
            a = server.trace(f"start {tdir}")
            if not a or not a.get("ok"):
                tracer["error"] = f"trace start: {a}"
                return
            # the stamps: the moment the server was told to start and the
            # moment it was told to stop (its monotonic clock, which is
            # this machine's). They only cross-check the traced window,
            # which is the span the profiler collected: it goes on
            # collecting a few ms after it is told to stop
            span = {"t_a": a["asked_monotonic"]}
            time.sleep(span_s)
            b = server.trace("stop", timeout=240.0)
            if not b or not b.get("ok"):
                tracer["error"] = f"trace stop: {b}"
                return
            span["t_b"] = b["asked_monotonic"]
            tracer.update(span=span, dir=tdir)

        trace_thread = threading.Thread(target=trace_span, daemon=True)

        def on_window_start() -> None:
            ctx["stats0"] = snapshot()
            ctx["setup_s"] = time.monotonic() - T_PROCESS_START
            if traced:
                trace_thread.start()

        def on_window_end() -> None:
            ctx["stats1"] = snapshot()

        cycle = len(mix["cycle"])
        if kind == "sat":
            window = load.drive_closed(
                loadgen, stream, int(mix["warm_cycles"]) * cycle, args.seconds,
                plateau, on_window_start, on_window_end)
        else:
            # the rate belongs to the cell: benchmarks/rates/<cell>.json
            with open(os.path.join(HERE, "rates", f"{cell['name']}.json")) as f:
                rate = float((rehearse or {}).get("rate") or json.load(f)["per_second"])
            window = load.drive_open(
                loadgen, stream, rate, int(mix["warm_cycles"]) * cycle,
                args.seconds, settle, on_window_start, on_window_end)
        ctx["window"] = window
        if traced:
            trace_thread.join(timeout=300.0)

        # -- after the window: read back, stop the server ------------------
        t_after = time.monotonic()
        for op, ids in readback_requests(stream, mix, config, args.seed):
            loadgen.call(load.Record(op, ids.tobytes(), "readback", "after",
                                     events=len(ids) // 2))
        records = loadgen.records
        loadgen_sessions = loadgen.sessions
        loadgen.close()
        loadgen = None
        rc = server.terminate()
        final = server.stats or {}
        ctx["final"] = final
        shadow = final.get("device_shadow") or {}
        verdict = {
            "exit_code": rc, "verified": shadow.get("verified"),
            "error": shadow.get("error"),
            "hash_log_ok": (shadow.get("hash_log") or {}).get("ok"),
        }
        if fault is not None:
            fault(records, shadow)
        ctx["records"] = records
        log(f"window closed; read-back and shutdown {time.monotonic() - t_after:.1f}s; "
            f"server exit {rc}")

        # -- the trace (the server has exited: the chip is free) -----------
        device_out = {"platform": dev.get("platform"), "kind": dev.get("kind"),
                      "count": dev.get("count"),
                      "memory_peak_bytes": peak_bytes(final)}
        breakdown = None
        if traced:
            if "span" not in tracer:
                raise RuntimeError(f"no trace: {tracer.get('error')}")
            t_r = time.monotonic()
            red = subprocess.run(
                [sys.executable, os.path.join(HERE, "harness", "trace.py"),
                 tracer["dir"]],
                capture_output=True, text=True, timeout=300,
                env=dict(env, JAX_PLATFORMS="cpu"))
            reduced = json.loads(red.stdout.strip().splitlines()[-1])
            if "error" in reduced and not rehearse:
                raise RuntimeError(f"trace reduction: {reduced}")
            span = tracer["span"]
            ctx["trace"], ctx["trace_span"] = reduced, span
            if "error" not in reduced:
                stamps_s = span["t_b"] - span["t_a"]
                device_out["busy_s"] = reduced["busy_s"]
                device_out["window_s"] = trace.traced_window_s(reduced, stamps_s)
                log(f"traced window: the profiler collected {reduced['collected_s']:.6f}s "
                    f"({reduced['collected_first_s']:.6f} .. {reduced['collected_last_s']:.6f}), "
                    f"the stamps span {stamps_s:.6f}s, collected - stamps "
                    f"{1e3 * (reduced['collected_s'] - stamps_s):+.3f} ms; busy "
                    f"{reduced['busy_s']:.6f}s; planes (name, first, last event) "
                    f"{json.dumps(reduced['plane_ends'])}")
            breakdown = {"device_ops": reduced.get("device_ops"),
                         "idle_gaps": reduced.get("idle_gaps")}
            log(f"trace: {reduced.get('xplane_bytes')} B reduced in "
                f"{time.monotonic() - t_r:.1f}s; programs (name, device s, "
                f"launches): {json.dumps((reduced.get('modules') or [])[:12])}")

        # -- correct ---------------------------------------------------------
        t_c = time.monotonic()
        cmp = check.compare(
            records, follower, shadow.get("fingerprint_device"), verdict,
            controls=tuple(args.control or ()))
        numbers = cmp["numbers"]
        correct = check.is_correct(numbers)
        log(f"reference replay {time.monotonic() - t_c:.1f}s: {json.dumps(cmp['detail'])} "
            f"{json.dumps(cmp['reference'])}")
        for name, c in (cmp.get("controls") or {}).items():
            log(f"control {name}: correct={c['correct']} {json.dumps(c['numbers'])}")

        # -- metrics -----------------------------------------------------------
        key = "per_layer" if traced else "end_to_end"
        folder = "layer_metrics" if traced else "end_to_end"
        metrics = {}
        for m in cell_metrics(bench, key, cell["name"]):
            value = load_named(folder, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        win = [r for r in records if r.phase == "window"]
        attempted = len(win)
        failed = sum(1 for r in win if r.reply is None)
        log(f"classes (n, due->reply ms): {json.dumps(class_table(records))}")
        lat = [1e3 * (r.done - (r.due or r.sent)) for r in win
               if r.done > 0 and r.operation == load.CREATE]
        log("create due->reply ms histogram: " + json.dumps(histogram(lat)))
        if len(lat) >= 4:
            q = len(lat) // 4
            log("create due->reply ms, mean by quarter of the window (a backlog "
                "that grows shows here): "
                + json.dumps([round(sum(lat[i * q:(i + 1) * q]) / q, 1)
                              for i in range(4)]))
        log(f"lag at the window's ends: {lag_of(ctx['stats0'])} -> "
            f"{lag_of(ctx['stats1'])}; resends in window "
            f"{sum(r.resends for r in win)}; compiles in window "
            f"{load_named('layer_metrics', 'window_compiles')(ctx)}; "
            f"drain {window['drain_s']:.1f}s; sent - due p90 (open loop) "
            f"{percentile([1e3 * (r.sent - r.due) for r in win if r.due > 0], 0.9)} ms; "
            f"most sessions in flight "
            f"{window.get('most_in_flight', len(loadgen_sessions))} of {len(loadgen_sessions)}")
        compared = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
        result = {
            "correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device_out,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = compared
        for k, v in compared.items():
            log(f"compared {k}: {v['value']} (limit {v['limit']})")
        if rehearse:
            return result, 3
        return result, 0
    except BaseException:
        if server is not None:
            log("the server's last output:\n" + "".join(list(server.tail)[-15:]))
        raise
    finally:
        if loadgen is not None:
            loadgen.close()
        if server is not None:
            server.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def peak_bytes(final: dict):
    """The allocator's peak on the fullest chip, from the server's last
    [stats] line (one entry a device)."""
    peaks = (final.get("device") or {}).get("peak_bytes_in_use")
    if isinstance(peaks, list):
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None
    return peaks


def readback_requests(stream, mix: dict, config: dict, seed: int) -> list:
    """After the window: every account, and the ids of a sample of create
    batches drawn from the seed, with the last one sent in it."""
    out = []
    n, batch = int(config["accounts"]), int(config["batch_events"])
    for lo in range(1, n + 1, batch):
        ids = np.zeros(2 * min(batch, n + 1 - lo), dtype=np.uint64)
        ids[0::2] = np.arange(lo, lo + len(ids) // 2, dtype=np.uint64)
        out.append((int(Operation.lookup_accounts), ids))
    rng = traffic.rng_for(seed, 2)
    k = min(int(mix["readback_batches"]), len(stream.sent))
    picks = set(rng.choice(len(stream.sent), size=k, replace=False).tolist())
    picks.add(len(stream.sent) - 1)
    for i in sorted(picks):
        lo = stream.sent[i]["id_lo"]
        ids = np.zeros(2 * len(lo), dtype=np.uint64)
        ids[0::2] = lo
        out.append((int(Operation.lookup_transfers), ids))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's proof that the comparison can fail: also judge the
    # reference with one guarantee broken (never part of a driver's run)
    ap.add_argument("--control", action="append",
                    choices=sorted(check.CONTROLS))
    args = ap.parse_args(argv)
    try:
        result, code = run_cell(args)
    except Exception as e:
        print(f"benchmarks/run.py failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    if result is not None and code == 0:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
