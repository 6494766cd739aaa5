"""The sharded commit's share of its roofline, beside roofline.py (whose
table of peaks and byte count of a plain transfer it imports) and
roofline_twophase.py (whose count of the span's batches it repeats: every
launch of the sharded backend carries one batch).

The numerator is the ALGORITHM's bytes, the same work whatever implements
it: sharding moves no byte more than one chip's commit has to (a row lives
on one shard). The tables' load is taken over all shards' slots, and the
peak is the HBM bandwidth of every chip the state lives on; the
denominator is the commit program's device time A CHIP (trace.py averages
modules and ops over the device planes).
"""

from __future__ import annotations

from benchmarks.harness import readers
from benchmarks.harness.roofline import commit_bytes, peak_hbm_bytes_per_s


def span_launches(ctx):
    """(commit launches of the traced span = its batches, the commit
    programs' device seconds a chip), or None. A commit launch is a launch
    of a program that holds 1 % or more of the span's commit device time."""
    mods, k = readers.commit_modules(ctx), readers.commit_kernel_s(ctx)
    if not mods or k is None:
        return None
    launches = int(sum(n for _name, t, n in mods if t >= 0.01 * k))
    return (launches, k) if launches > 0 else None


def sharded_kernels_roofline(ctx):
    """The algorithm's bytes for the span's batches over the HBM peak of
    all the chips, as a share of the commit programs' device time a chip
    (percent)."""
    span = span_launches(ctx)
    chips = (ctx.get("device") or {}).get("count")
    if span is None or not chips:
        return None
    launches, k = span
    cfg = ctx["config"]
    rows = sum(r.events for r in ctx["records"]
               if r.operation == readers.CREATE and r.done > 0)
    least_s = commit_bytes(
        launches * cfg["batch_events"],
        cfg["accounts"] / (chips << cfg["account_slots_log2"]),
        min(0.99, rows / (chips << cfg["transfer_slots_log2"])),
    ) / (chips * peak_hbm_bytes_per_s(ctx["device"]["kind"]))
    return 100.0 * least_s / k

