"""`chains` chains of `length` at the head of the batch (`"all"`: the whole
batch); every `break_every`-th chain holds a zero amount and rolls back."""
from benchmarks.reference.wire_types import TransferFlags as TF


def apply(stream, mod, arr, base):
    n = len(arr)
    length = int(mod["length"])
    chains = n // length if mod["chains"] == "all" else int(mod["chains"])
    chains = min(chains, n // length)
    for c in range(chains):
        arr["flags"][length * c: length * (c + 1) - 1] = int(TF.linked)
        if mod.get("break_every") and c % int(mod["break_every"]) == 1:
            arr["amount_lo"][length * c + 1] = 0
    return arr
