"""The one general traffic generator: (traffic file, configuration, seed) ->
requests. A mix is DATA (benchmarks/traffic/<name>.json): the loop kind, the
sessions, the fixed cycle of batch classes, each class as a list of
modifiers over a plain batch (benchmarks/modifiers/<do>.py) and the draw of
accounts (benchmarks/draws/<name>.py), both found by the name the file
gives. `--seed` draws accounts, amounts and user data only; the schedule (which class when, how many requests, which
rows a lookup reads) is the same for every seed, so every seed does the
same work.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.harness.named import named
from benchmarks.reference.wire_types import (
    ACCOUNT_DTYPE,
    TRANSFER_DTYPE,
    AccountFlags,
    Operation,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ID_BASE = 1_000_000_000
ID_STRIDE = 10_000  # id space one create request owns (> a full batch)


def load_traffic(name: str) -> dict:
    """The mix `benchmarks/traffic/<name>.json` (tests pass a path)."""
    path = name if name.endswith(".json") else os.path.join(
        HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    for key in ("loop", "sessions", "cycle", "classes", "warm_cycles", "client"):
        if key not in mix:
            raise ValueError(f"traffic {name}: missing {key!r}")
    for cls in mix["cycle"]:
        if cls not in mix["classes"]:
            raise ValueError(f"traffic {name}: cycle names unknown class {cls!r}")
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop must be closed or open")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Seeds go a little past 2**31; SeedSequence takes any non-negative
    integer, and the stream index keeps the draws of accounts, transfers
    and client jitter apart."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class Stream:
    """All requests of one run, made on demand in schedule order."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.batch = int(config["batch_events"])
        self.n_accounts = int(config["accounts"])
        self.reversed_ids = config.get("id_order", "reversed") == "reversed"
        self.seed = int(seed)
        self.rng = rng_for(seed, 1)
        self.limit_id = self.n_accounts  # the one limit account, last id
        self.plain_accounts = self.n_accounts - 1
        self.k = 0  # create requests made so far (schedule position)
        self.lookups_made = 0
        self.draw = mix.get("account_draw") or {"name": "uniform"}
        self.pending: list[np.ndarray] = []  # unresolved pending batches
        self.sent: list[np.ndarray] = []  # every create batch, by position

    # -- accounts ---------------------------------------------------------

    def account_batches(self) -> list[np.ndarray]:
        rng = rng_for(self.seed, 0)
        out = []
        for lo in range(1, self.n_accounts + 1, self.batch):
            n = min(self.batch, self.n_accounts + 1 - lo)
            arr = np.zeros(n, dtype=ACCOUNT_DTYPE)
            arr["id_lo"] = np.arange(lo, lo + n, dtype=np.uint64)
            arr["ledger"] = 1
            arr["code"] = 1
            arr["user_data_64"] = rng.integers(0, 1 << 40, n, dtype=np.uint64)
            out.append(arr)
        out[-1]["flags"][-1] = int(AccountFlags.debits_must_not_exceed_credits)
        assert int(out[-1]["id_lo"][-1]) == self.limit_id
        return out

    # -- create_transfers -------------------------------------------------

    def class_at(self, k: int) -> str:
        cycle = self.mix["cycle"]
        return cycle[k % len(cycle)]

    def _plain(self, n: int, base: int) -> np.ndarray:
        rng, plain = self.rng, self.plain_accounts
        arr = np.zeros(n, dtype=TRANSFER_DTYPE)
        ids = np.arange(base, base + n, dtype=np.uint64)
        arr["id_lo"] = ids[::-1] if self.reversed_ids else ids
        arr["debit_account_id_lo"], arr["credit_account_id_lo"] = named(
            "draws", self.draw["name"]).draw(rng, n, plain, self.draw)
        arr["amount_lo"] = rng.integers(1, 1001, size=n, dtype=np.uint64)
        arr["user_data_64"] = rng.integers(0, 1 << 40, n, dtype=np.uint64)
        arr["ledger"] = 1
        arr["code"] = 1
        return arr

    def next_create(self) -> tuple[str, np.ndarray]:
        """The next create_transfers batch of the schedule."""
        k = self.k
        self.k += 1
        name = self.class_at(k)
        base = ID_BASE + k * ID_STRIDE
        n = self.batch
        arr = self._plain(n, base)
        for mod in self.mix["classes"][name]:
            arr = named("modifiers", mod["do"]).apply(self, mod, arr, base)
        self.sent.append(arr)
        return name, arr

    # -- lookups ----------------------------------------------------------

    def next_lookup(self) -> tuple[int, np.ndarray]:
        """Alternating lookup_accounts / lookup_transfers over rows the
        stream has written: a slice of the accounts, or the ids of the
        create batch sent `lookup_behind` requests ago."""
        j = self.lookups_made
        self.lookups_made += 1
        n = min(int(self.mix["lookup_ids"]), self.batch)
        if j % 2 == 0:
            start = (j // 2 * n) % self.n_accounts
            lo = (np.arange(start, start + n, dtype=np.uint64)
                  % np.uint64(self.n_accounts)) + np.uint64(1)
            op = Operation.lookup_accounts
        else:
            back = max(0, len(self.sent) - 1 - int(self.mix["lookup_behind"]))
            lo = self.sent[back]["id_lo"][:n]
            op = Operation.lookup_transfers
        ids = np.zeros(2 * len(lo), dtype=np.uint64)
        ids[0::2] = lo
        return int(op), ids


def describe_cycle(mix: dict) -> dict:
    """Class counts of one cycle (printed by run.py on an early line)."""
    counts: dict[str, int] = {}
    for name in mix["cycle"]:
        counts[name] = counts.get(name, 0) + 1
    return counts
