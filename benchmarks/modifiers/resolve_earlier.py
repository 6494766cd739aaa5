"""A batch of nothing but post/void: posts `post_share` of the oldest
unresolved pending batch and voids the rest."""
import numpy as np

from benchmarks.reference.wire_types import TRANSFER_DTYPE, TransferFlags as TF


def apply(stream, mod, arr, base):
    if not stream.pending:
        raise ValueError("resolve_earlier with no pending batch before it")
    pend = stream.pending.pop(0)
    out = np.zeros(len(pend), dtype=TRANSFER_DTYPE)
    out["id_lo"] = arr["id_lo"][: len(pend)]
    out["pending_id_lo"] = pend["id_lo"]
    cut = int(len(pend) * float(mod["post_share"]))
    out["flags"][:cut] = int(TF.post_pending_transfer)
    out["flags"][cut:] = int(TF.void_pending_transfer)
    return out
