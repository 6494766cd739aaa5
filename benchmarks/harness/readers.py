"""What the per-layer metric files (benchmarks/layer_metrics/<name>.py)
call. Each file defines `read(ctx)` and returns a number or None (nothing
to read in this cell or this run: the harness leaves the metric out).

ctx keys: cell, config, mix, kind ("sat" | "rate"), seconds, records (all),
window (t0, t_end), stats0 / stats1 (the server's live [stats] snapshot at
the window's ends, each with "t"), final (the [stats] line at SIGTERM),
trace (the reduced profiler trace, see trace.py) and trace_span (the stamps
around the traced span on the server's clock), device (the server's [device] line).
A quantity split by kind of cell (`<name>.sat`, `<name>.rate`) has one file
and one reader here.
"""

from __future__ import annotations

import math
import statistics

from benchmarks.harness import roofline
from benchmarks.reference.wire_types import Operation

CREATE = int(Operation.create_transfers)
# device programs that are not commit work (everything else a window runs is)
NOT_COMMIT = ("lookup", "fingerprint", "query")


def window_records(ctx, creates: bool | None = None) -> list:
    out = [r for r in ctx["records"] if r.phase == "window" and r.done > 0]
    if creates is None:
        return out
    return [r for r in out if (r.operation == CREATE) == creates]


def percentile(values: list, q: float) -> float | None:
    """Nearest-rank percentile (the value with at most (1-q) of the
    samples beyond it)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered) - 1e-9))
    return ordered[rank - 1]


def _counter(snap: dict, name: str):
    return (snap or {}).get("metrics", {}).get("counters", {}).get(name)


def counter_delta(ctx, name: str, a: str = "stats0", b: str = "stats1"):
    v0, v1 = _counter(ctx.get(a), name), _counter(ctx.get(b), name)
    if v0 is None or v1 is None:
        return None
    return v1 - v0


def gauge(ctx, name: str, which: str):
    return (ctx.get(which) or {}).get("metrics", {}).get("gauges", {}).get(name)


def hist_window_mean(ctx, name: str):
    """Mean of a cumulative histogram's samples that fell in the window."""
    h0 = (ctx.get("stats0") or {}).get("metrics", {}).get("histograms", {}).get(name)
    h1 = (ctx.get("stats1") or {}).get("metrics", {}).get("histograms", {}).get(name)
    if not h1:
        return None
    c0, m0 = (h0["count"], h0["mean"]) if h0 else (0, 0.0)
    n = h1["count"] - c0
    if n <= 0:
        return None
    return (h1["count"] * h1["mean"] - c0 * m0) / n


def window_seconds(ctx) -> float:
    return ctx["stats1"]["t"] - ctx["stats0"]["t"]


# -- the traced span ---------------------------------------------------------

def commit_modules(ctx) -> list:
    """[name, device seconds, launches] of the span's commit programs."""
    trace = ctx.get("trace")
    if not trace or "modules" not in trace:
        return []
    return [m for m in trace["modules"]
            if not any(x in m[0].lower() for x in NOT_COMMIT)]


def commit_kernel_s(ctx):
    total = sum(t for _name, t, _n in commit_modules(ctx))
    return total if total > 0 else None


def batches_per_launch(ctx):
    """create_transfers batches the commit backend handed to the chip over
    the launches it made for them, by the program's own counters over the
    whole window: the follower's applier (`shadow.*`) where the chip
    follows, the replica's commit grouping (`commit.group.*`) where the
    chip replies."""
    b = counter_delta(ctx, "shadow.batches")
    if b:
        launches = counter_delta(ctx, "shadow.groups") + counter_delta(ctx, "shadow.solo")
    else:
        fused = counter_delta(ctx, "commit.group.fused_ops")
        solo = counter_delta(ctx, "commit.group.solo_ops")
        groups = counter_delta(ctx, "commit.group.fused_groups")
        if fused is None or solo is None or groups is None:
            return None
        b, launches = fused + solo, groups + solo
    return b / launches if b > 0 and launches > 0 else None


def span_batches(ctx):
    """create_transfers batches the chip committed inside the traced span,
    from the trace itself: the span's commit launches times the batches a
    launch carries (above). A commit launch is a launch of a program that
    holds 1 % or more of the span's commit device time; the helpers beside
    it (reply folds, type converts, microseconds each) commit no batch.
    No acknowledgement and no host clock enters."""
    mods, per = commit_modules(ctx), batches_per_launch(ctx)
    total = commit_kernel_s(ctx)
    if not mods or per is None or total is None:
        return None
    launches = sum(n for _name, t, n in mods if t >= 0.01 * total)
    return launches * per if launches > 0 else None


def kernel_ms_per_batch(ctx):
    k, b = commit_kernel_s(ctx), span_batches(ctx)
    return None if k is None or b is None else 1e3 * k / b


def commit_kernels_roofline(ctx):
    """The algorithm's bytes for the span's batches over the HBM peak,
    as a share of the commit programs' device time (percent)."""
    k, b = commit_kernel_s(ctx), span_batches(ctx)
    if k is None or b is None:
        return None
    cfg = ctx["config"]
    rows = sum(r.events for r in ctx["records"]
               if r.operation == CREATE and r.done > 0)
    least_s = roofline.commit_bytes(
        b * cfg["batch_events"],
        cfg["accounts"] / (1 << cfg["account_slots_log2"]),
        min(0.99, rows / (1 << cfg["transfer_slots_log2"])),
    ) / roofline.peak_hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / k


def device_idle_share(ctx):
    """1 - busy over the span the profiler collected: both from the trace."""
    trace = ctx.get("trace")
    if not trace or not trace.get("collected_s") or "busy_s" not in trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["collected_s"])


def launches_per_batch(ctx):
    """Every launch of the span that is not a read, over its batches."""
    launches, b = sum(n for _name, _t, n in commit_modules(ctx)), span_batches(ctx)
    return launches / b if launches and b else None


# -- the generator's own clock ----------------------------------------------

def gen_late_ms(ctx):
    late = [1e3 * (r.sent - r.due) for r in window_records(ctx)]
    return percentile(late, 0.90)


def create_p90_ms(ctx):
    return percentile([1e3 * (r.done - r.sent)
                       for r in window_records(ctx, creates=True)], 0.90)


def client_retries(ctx):
    return float(sum(r.resends for r in ctx["records"]
                     if r.phase == "window" and r.operation == CREATE))


def wire_ms(ctx):
    """Client send -> reply, less the server's own arrival -> reply write
    (latency.e2e_us), both as means over the window: the wire, the two
    kernels' socket buffers and the client's framing."""
    recs = window_records(ctx)
    server_us = hist_window_mean(ctx, "latency.e2e_us")
    if not recs or server_us is None:
        return None
    client_ms = statistics.fmean(1e3 * (r.done - r.sent) for r in recs)
    return client_ms - server_us / 1e3


def loop_busy_share(ctx):
    d = counter_delta(ctx, "loop.busy_s")
    return None if d is None else 100.0 * d / window_seconds(ctx)


def fused_share(ctx):
    """Share of the window's create batches that reached the chip in a
    fused group launch: the applier's where the chip follows (there the
    replica's own grouping counts the C++ engine's commits), else the
    replica's."""
    b, solo = counter_delta(ctx, "shadow.batches"), counter_delta(ctx, "shadow.solo")
    if b:
        return 100.0 * (b - solo) / b
    f = counter_delta(ctx, "commit.group.fused_ops")
    s = counter_delta(ctx, "commit.group.solo_ops")
    if f is None or s is None or f + s <= 0:
        return None
    return 100.0 * f / (f + s)


def lag_delta_ops(ctx):
    a = gauge(ctx, "shadow.device_lag_ops", "stats0")
    b = gauge(ctx, "shadow.device_lag_ops", "stats1")
    return None if a is None or b is None else float(b - a)


def hist_ms(name: str):
    def read(ctx):
        us = hist_window_mean(ctx, name)
        return None if us is None else us / 1e3
    return read


def window_compiles(ctx):
    d = counter_delta(ctx, "device.compiles")
    return None if d is None else float(d)


# -- end to end (benchmarks/end_to_end/<name>.py) ----------------------------

NEVER_MS = 600_000.0  # a request that was never answered is over any limit


def committed_tps(ctx):
    w = ctx["window"]
    events = sum(r.events for r in ctx["records"]
                 if r.operation == CREATE and r.error is None
                 and w["t0"] <= r.done <= w["t_end"])
    return events / (w["t_end"] - w["t0"])


def due_to_reply_ms(ctx, creates: bool) -> list:
    return [1e3 * (r.done - r.due) if r.done > 0 else NEVER_MS
            for r in ctx["records"]
            if r.phase == "window" and (r.operation == CREATE) == creates]


def batch_ms(q: float):
    return lambda ctx: percentile(due_to_reply_ms(ctx, True), q)


def lookup_ms(q: float):
    return lambda ctx: percentile(due_to_reply_ms(ctx, False), q)


def setup_s(ctx):
    return ctx["setup_s"]
