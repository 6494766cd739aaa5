"""The first half of the batch is pending; the second half posts it, in the same batch."""
import numpy as np

from benchmarks.reference.wire_types import TRANSFER_DTYPE, TransferFlags as TF


def apply(stream, mod, arr, base):
    n = len(arr)
    half = n // 2
    arr["flags"][:half] = int(TF.pending)
    res = np.zeros(n - half, dtype=TRANSFER_DTYPE)
    res["id_lo"] = arr["id_lo"][half:]
    res["pending_id_lo"] = arr["id_lo"][: n - half]
    res["flags"] = int(TF.post_pending_transfer)
    arr[half:] = res
    return arr
