"""Uniform accounts (upstream's benchmark driver): every transfer debits one
plain account drawn uniformly and credits another."""
import numpy as np


def draw(rng, n: int, accounts: int, params: dict):
    debit = rng.integers(1, accounts + 1, size=n, dtype=np.uint64)
    off = rng.integers(1, accounts, size=n, dtype=np.uint64)
    return debit, (debit - 1 + off) % accounts + 1
