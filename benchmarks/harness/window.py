"""Readers of what the program itself counts at the launch site and stamps
into the profiler's trace (PR 26): device time a batch over the WHOLE
window from `device.commit_busy_s` (the serving process's completion
thread, metrics.LaunchClock), how full a launch was, the event loop's
blocking reply fetch, the time a large frame takes to arrive, and how much
of the chip's idle time carries one of the program's own span names.

Same contract as readers.py: `read(ctx)` returns a number, or None where
the program has no such counter or span (an older server, the other
backend): the harness then leaves the metric out.
"""

from __future__ import annotations

from benchmarks.harness import readers

SPAN_PREFIX = "tb."  # tracer.ProfilerTracer: the program's spans


def _per(ctx, numerator: str, denominator: str, scale: float):
    n = readers.counter_delta(ctx, numerator)
    d = readers.counter_delta(ctx, denominator)
    return None if n is None or not d else scale * n / d


def kernel_ms_window(ctx):
    """Device seconds booked to the window's commit launches over the
    batches those launches carried (both booked at a launch's completion,
    by one thread)."""
    return _per(ctx, "device.commit_busy_s", "device.commit_batches_done", 1e3)


def device_idle_window(ctx):
    busy = readers.counter_delta(ctx, "device.commit_busy_s")
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / readers.window_seconds(ctx))


def group_fill(ctx):
    """Batches carried over batch slots run, at the launch site: a 16-slot
    group that carries 5 batches runs all 16."""
    return _per(ctx, "device.commit_batches", "device.commit_slots", 100.0)


def loop_fetch_share(ctx):
    d = readers.counter_delta(ctx, "loop.fetch_s")
    return None if d is None else 100.0 * d / readers.window_seconds(ctx)


def frame_recv_ms(ctx):
    us = readers.hist_window_mean(ctx, "bus.frame_recv_us")
    return None if us is None else us / 1e3


def kernel_ms_late_over_early(ctx):
    """kernel_ms_window over the last quarter of the window divided by
    the same over the first quarter, from the flight recorder's
    per-interval counter deltas (`history` of the window-end snapshot;
    an entry belongs to the quarter its interval ends in). None where the
    history does not reach back to the window's start, or a quarter
    completed no launch."""
    history = (ctx.get("stats1") or {}).get("history")
    if not history or "stats0" not in ctx:
        return None
    t0, t1 = ctx["stats0"]["t"], ctx["stats1"]["t"]
    first = history[0]
    if first["t"] - (first.get("dt") or 0.0) > t0:
        return None
    quarter = (t1 - t0) / 4.0

    def ms_a_batch(lo: float, hi: float):
        busy = done = 0.0
        for entry in history:
            if lo < entry["t"] <= hi:
                c = entry.get("counters", {})
                busy += c.get("device.commit_busy_s", 0.0)
                done += c.get("device.commit_batches_done", 0)
        return 1e3 * busy / done if done else None

    early, late = ms_a_batch(t0, t0 + quarter), ms_a_batch(t1 - quarter, t1)
    return None if not early or late is None else late / early


def idle_unnamed_share(ctx):
    """Of the chip's idle seconds in the traced span, the share whose gap
    was NOT named by one of the program's own spans: gaps the reducer
    attributed to a runtime event or to nothing, and what lies beyond its
    named list. None when the trace saw no gap at all."""
    trace = ctx.get("trace")
    if not trace or "idle_gaps" not in trace:
        return None
    total = trace.get("idle_gap_total_s") or 0.0
    if total <= 0:
        return None
    named = sum(s for name, s in trace["idle_gaps"]
                if name.split(":", 1)[-1].startswith(SPAN_PREFIX))
    return 100.0 * max(0.0, total - named) / total
