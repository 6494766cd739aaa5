"""A batch of nothing but post/void of the OLDEST unresolved pending batch,
once at least `behind` pending batches are unresolved (so that the batch it
resolves was acknowledged long before); until then the batch is itself a
pending one, remembered. Posts `post_share` of the batch, voids the rest."""
import numpy as np

from benchmarks.reference.wire_types import TRANSFER_DTYPE, TransferFlags as TF


def apply(stream, mod, arr, base):
    if len(stream.pending) < int(mod["behind"]):
        arr["flags"] = int(TF.pending)
        stream.pending.append(arr)
        return arr
    pend = stream.pending.pop(0)
    out = np.zeros(len(pend), dtype=TRANSFER_DTYPE)
    out["id_lo"] = arr["id_lo"][: len(pend)]
    out["pending_id_lo"] = pend["id_lo"]
    cut = int(len(pend) * float(mod["post_share"]))
    out["flags"][:cut] = int(TF.post_pending_transfer)
    out["flags"][cut:] = int(TF.void_pending_transfer)
    return out
