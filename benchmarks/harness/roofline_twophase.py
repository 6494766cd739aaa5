"""The algorithm's bytes of a two-phase commit, beside roofline.py (whose
table of peaks, row widths and probe counts it imports, and which counts
the plain transfer), and the share of the roofline a traced span of the
two-phase cell reaches.

From event counts and row widths alone, as roofline.py's: a PENDING create
moves what a plain one moves (one row inserted after its probes, two
account rows found, read and written: the amount goes to the *_pending
columns of the same rows). A POST finds its pending row in the transfer
table by `pending_id` (the probes of a hit, 128 B read: accounts and amount
come from that row), inserts a row of its own, and finds, reads and writes
the two account rows (pending -> posted).
"""

from __future__ import annotations

from benchmarks.harness import readers
from benchmarks.harness.roofline import (
    KEY_BYTES,
    ROW_BYTES,
    commit_bytes,
    peak_hbm_bytes_per_s,
    probes_hit,
    probes_insert,
)
from benchmarks.reference.wire_types import TRANSFER_DTYPE, TransferFlags

_RESOLVE = int(TransferFlags.post_pending_transfer
               | TransferFlags.void_pending_transfer)
_FLAGS_AT = TRANSFER_DTYPE.fields["flags"][1]  # byte offset in a wire row


pending_bytes = commit_bytes  # a pending create moves what a plain one moves


def post_bytes(transfers: int, account_load: float, transfer_load: float) -> float:
    per_transfer = (
        ROW_BYTES + probes_hit(transfer_load) * KEY_BYTES  # the pending row
        + ROW_BYTES + probes_insert(transfer_load) * KEY_BYTES  # its own row
        + 2 * (2 * ROW_BYTES + probes_hit(account_load) * KEY_BYTES)
    )
    return transfers * per_transfer


def span_classes(ctx, launches: int) -> tuple[int, int]:
    """(pending or plain events, post/void events) of the `launches`
    batches the traced span committed. Every launch of this cell is a solo
    launch of one batch, so the span's batches are its commit launches,
    and they are consecutive in commit order: the first is the batch that
    was acknowledged first after the span opened (the stamps are on this
    machine's monotonic clock, as the records are)."""
    creates = sorted((r for r in ctx["records"]
                      if r.operation == readers.CREATE and r.done > 0),
                     key=lambda r: r.op)
    t_a = (ctx.get("trace_span") or {}).get("t_a", 0.0)
    first = next((i for i, r in enumerate(creates) if r.done >= t_a), 0)
    first = max(0, min(first, len(creates) - launches))
    created = resolved = 0
    for r in creates[first:first + launches]:
        # a batch of this traffic is of one class: its first event's flags
        flags = int.from_bytes(r.body[_FLAGS_AT:_FLAGS_AT + 2], "little")
        if flags & _RESOLVE:
            resolved += r.events
        else:
            created += r.events
    return created, resolved


def twophase_kernels_roofline(ctx):
    """The algorithm's bytes for the span's batches over the HBM peak, as
    a share of the commit programs' device time in the span (percent)."""
    mods, k = readers.commit_modules(ctx), readers.commit_kernel_s(ctx)
    if not mods or k is None:
        return None
    launches = int(sum(n for _name, t, n in mods if t >= 0.01 * k))
    if launches <= 0:
        return None
    created, resolved = span_classes(ctx, launches)
    if created + resolved <= 0:
        return None
    cfg = ctx["config"]
    rows = sum(r.events for r in ctx["records"]
               if r.operation == readers.CREATE and r.done > 0)
    account_load = cfg["accounts"] / (1 << cfg["account_slots_log2"])
    transfer_load = min(0.99, rows / (1 << cfg["transfer_slots_log2"]))
    least_s = (
        pending_bytes(created, account_load, transfer_load)
        + post_bytes(resolved, account_load, transfer_load)
    ) / peak_hbm_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / k
