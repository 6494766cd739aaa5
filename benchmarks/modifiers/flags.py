"""Every transfer of the batch carries one flag (`flags`: a TransferFlags name)."""
from benchmarks.reference.wire_types import TransferFlags


def apply(stream, mod, arr, base):
    arr["flags"] = int(TransferFlags[mod["flags"]])
    return arr
