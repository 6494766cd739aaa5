"""The algorithm's bytes of a batch of payments between balance-limited
accounts, beside roofline.py (whose table of peaks, row widths and probe
counts it imports, and which counts the plain transfer), and the share of
the roofline a traced span of the limits cell reaches.

From the replies and the row widths alone: a transfer the ledger COMMITS
moves what a plain one moves (`roofline.commit_bytes`: its row inserted
after the probes of an insertion, two account rows found, read and written
back). A transfer it DECLINES (`exceeds_credits`, or any other code) is
looked for and its two account rows are found and read; the algorithm
writes nothing for it (`roofline_linked.rolled_back_bytes`). Which
transfers were declined is read off each reply's (index, result) pairs, so
the bytes are the same whatever tier ran the batch: waves, the serial scan
or a fast pass.

A batch takes a second or so, against a traced span of 1.5 s, so the
span's batches are counted as a FRACTION, as roofline_linked.py counts
them: the create batches the launch clock saw complete over the whole
window, every tier (`device.commit_batches_done`), a second of the window,
times the span the profiler collected. The time they are set against is
the trace's: the device seconds of the span's commit programs.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import readers
from benchmarks.harness.roofline import commit_bytes, peak_hbm_bytes_per_s
from benchmarks.harness.roofline_linked import rolled_back_bytes
from benchmarks.reference.wire_types import (
    CREATE_TRANSFERS_RESULT_DTYPE,
    CreateTransferResult,
)

LIMIT_CODES = (int(CreateTransferResult.exceeds_credits),
               int(CreateTransferResult.exceeds_debits))
_PAIR = CREATE_TRANSFERS_RESULT_DTYPE.itemsize  # one (index, result) pair


def declined(record) -> int:
    """Transfers of one acknowledged create_transfers request that failed:
    the pairs of its sparse reply."""
    return len(record.reply or b"") // _PAIR


def limit_declined(record) -> int:
    """Of those, the ones that failed on a balance limit."""
    res = np.frombuffer(record.reply or b"", dtype=CREATE_TRANSFERS_RESULT_DTYPE)
    return int(np.isin(res["result"], LIMIT_CODES).sum())


def _acknowledged(ctx, window_only: bool) -> list:
    return [r for r in ctx["records"]
            if r.operation == readers.CREATE and r.done > 0
            and r.reply is not None and (r.phase == "window" or not window_only)]


def batch_bytes(ctx):
    """Mean bytes of one of the window's acknowledged create batches."""
    recs = _acknowledged(ctx, window_only=True)
    if not recs:
        return None
    cfg = ctx["config"]
    account_load = cfg["accounts"] / (1 << cfg["account_slots_log2"])
    # the transfer table's fill: every transfer the run committed
    rows = sum(r.events - declined(r) for r in _acknowledged(ctx, False))
    transfer_load = min(0.99, rows / (1 << cfg["transfer_slots_log2"]))
    committed = sum(r.events - declined(r) for r in recs)
    failed = sum(declined(r) for r in recs)
    return (commit_bytes(committed, account_load, transfer_load)
            + rolled_back_bytes(failed, account_load, transfer_load)
            ) / len(recs)


def span_batches_fraction(ctx):
    """(create batches the traced span holds, as a fraction; the span's
    commit-program device seconds), or None where the trace has no device
    plane or the program has no launch clock."""
    trace, k = ctx.get("trace") or {}, readers.commit_kernel_s(ctx)
    done = readers.counter_delta(ctx, "device.commit_batches_done")
    if k is None or not done or not trace.get("collected_s"):
        return None
    return done / readers.window_seconds(ctx) * trace["collected_s"], k


def waves_kernels_roofline(ctx):
    """The algorithm's bytes for the span's (fractional) batches over the
    HBM peak, as a share of the commit programs' device time in the span
    (percent)."""
    span, per_batch = span_batches_fraction(ctx), batch_bytes(ctx)
    if span is None or per_batch is None:
        return None
    batches, k = span
    least_s = batches * per_batch / peak_hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / k


def limit_fail_share(ctx):
    """Window transfers answered exceeds_credits / exceeds_debits over the
    window transfers acknowledged (percent)."""
    recs = _acknowledged(ctx, window_only=True)
    events = sum(r.events for r in recs)
    if not events:
        return None
    return 100.0 * sum(limit_declined(r) for r in recs) / events
