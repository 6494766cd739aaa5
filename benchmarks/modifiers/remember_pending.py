"""The batch is pending: a later `resolve_earlier` batch posts or voids it."""


def apply(stream, mod, arr, base):
    stream.pending.append(arr)
    return arr
