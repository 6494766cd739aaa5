"""The algorithm's bytes of a batch of linked chains, beside roofline.py
(whose table of peaks, row widths and probe counts it imports, and which
counts the plain transfer), and the share of the roofline a traced span of
the linked cell reaches.

From the batch's own flags and amounts and the row widths alone, as
roofline.py's: a transfer of a chain that COMMITS moves what a plain one
moves (`commit_bytes`: its row inserted after the probes of an insertion,
two account rows found, read and written back). A transfer of a chain that
ROLLS BACK is looked for (the probes of an unsuccessful search) and its two
account rows are found and read; the algorithm writes no row for it. Which
chains roll back is read off the batch: a lane whose amount is zero fails
(`amount_must_not_be_zero`), and with it every lane of its chain.

A serial batch outlasts the traced span (two seconds against two or less),
so the span's batches are counted as a FRACTION, and not by the seconds of
any clock of the program: the serial batches the launch clock saw complete
over the whole window, a second of the window (a count over the window's
length; the cell is closed-loop and saturated, so the rate is steady), times
the span the profiler collected. The time they are set against is the
trace's: the device seconds of the span's commit programs. A span whose
commit programs ran half of it reads twice the share of one they filled.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import readers, window
from benchmarks.harness.roofline import (
    KEY_BYTES,
    ROW_BYTES,
    commit_bytes,
    peak_hbm_bytes_per_s,
    probes_hit,
    probes_insert,
)
from benchmarks.reference.wire_types import TRANSFER_DTYPE, TransferFlags

_LINKED = int(TransferFlags.linked)


def rolled_back_bytes(transfers: int, account_load: float,
                      transfer_load: float) -> float:
    per_transfer = (
        probes_insert(transfer_load) * KEY_BYTES  # its id, not found
        + 2 * (ROW_BYTES + probes_hit(account_load) * KEY_BYTES)
    )
    return transfers * per_transfer


def batch_outcome(body: bytes) -> tuple[int, int]:
    """(transfers of chains that commit, transfers that fail or roll back)
    of one create_transfers body. A lane outside any chain is a chain of
    one."""
    arr = np.frombuffer(body, dtype=TRANSFER_DTYPE)
    if not len(arr):
        return 0, 0
    linked = (arr["flags"] & np.uint16(_LINKED)) != 0
    starts = np.ones(len(arr), dtype=bool)
    starts[1:] = ~linked[:-1]  # a chain opens after every lane not linked
    chain = np.cumsum(starts) - 1
    bad = (arr["amount_lo"] == 0) & (arr["amount_hi"] == 0)
    lost = np.bincount(chain, weights=bad)[chain] > 0
    return int((~lost).sum()), int(lost.sum())


def linked_batch_bytes(ctx):
    """Mean bytes of one of the run's acknowledged create batches."""
    bodies = [r.body for r in ctx["records"]
              if r.operation == readers.CREATE and r.done > 0]
    if not bodies:
        return None
    cfg = ctx["config"]
    account_load = cfg["accounts"] / (1 << cfg["account_slots_log2"])
    committed = rolled = 0
    for body in bodies:
        c, f = batch_outcome(body)
        committed, rolled = committed + c, rolled + f
    transfer_load = min(0.99, committed / (1 << cfg["transfer_slots_log2"]))
    return (commit_bytes(committed, account_load, transfer_load)
            + rolled_back_bytes(rolled, account_load, transfer_load)
            ) / len(bodies)


def serial_batch_ms(ctx):
    """Device ms the launch clock booked to a serial-tier batch, whole
    window (`kernel_ms_serial_window.sat`)."""
    return window._per(ctx, "device.tier_busy_s.serial",
                       "device.tier_batches_done.serial", 1e3)


def serial_batches_per_s(ctx):
    """Serial-tier batches completed in the window over the window's
    seconds, or None where the program has no such tier on its clock."""
    done = readers.counter_delta(ctx, "device.tier_batches_done.serial")
    return done / readers.window_seconds(ctx) if done else None


def span_batches_fraction(ctx):
    """(serial batches the traced span holds, as a fraction; the span's
    commit-program device seconds), or None where the trace has no device
    plane or the window completed no serial batch."""
    trace, k = ctx.get("trace") or {}, readers.commit_kernel_s(ctx)
    rate = serial_batches_per_s(ctx)
    if k is None or rate is None or not trace.get("collected_s"):
        return None
    return rate * trace["collected_s"], k


def serial_kernels_roofline(ctx):
    """The algorithm's bytes for the span's (fractional) batches over the
    HBM peak, as a share of the commit programs' device time in the span
    (percent)."""
    span, per_batch = span_batches_fraction(ctx), linked_batch_bytes(ctx)
    if span is None or per_batch is None:
        return None
    batches, k = span
    least_s = batches * per_batch / peak_hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / k
