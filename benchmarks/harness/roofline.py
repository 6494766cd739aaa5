"""The table of peaks and the algorithm's bytes of a commit.

The roofline's numerator is what the ALGORITHM has to move for the
transfers committed, from event counts and row widths alone, so that it
reads the same work whatever implements the kernel. The commit is bound by
HBM bandwidth (it does no arithmetic to speak of): share = bytes / peak
bytes per second / kernel seconds.
"""

from __future__ import annotations

ROW_BYTES = 128  # one account or transfer row on the wire and in the table
KEY_BYTES = 16  # the u128 id a probe compares

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak known for device kind {device_kind!r}: add it "
                       "to benchmarks/harness/roofline.py PEAKS with its source")
    return PEAKS[device_kind]["hbm_bytes_per_s"]


def probes_hit(load: float) -> float:
    """Expected probes of a successful search, linear probing (Knuth)."""
    return 0.5 * (1.0 + 1.0 / (1.0 - load))


def probes_insert(load: float) -> float:
    """Expected probes of an insertion (unsuccessful search)."""
    return 0.5 * (1.0 + 1.0 / (1.0 - load) ** 2)


def commit_bytes(transfers: int, account_load: float, transfer_load: float) -> float:
    """Bytes the commit of `transfers` transfers has to move: per transfer
    one transfer row written after the probes of its insertion, and two
    account rows found (probes), read and written back."""
    per_transfer = (
        ROW_BYTES + probes_insert(transfer_load) * KEY_BYTES
        + 2 * (2 * ROW_BYTES + probes_hit(account_load) * KEY_BYTES)
    )
    return transfers * per_transfer
