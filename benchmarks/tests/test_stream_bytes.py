"""The request streams of the configurations and traffic files, pinned to
their bytes (run: `python -m pytest benchmarks/tests -q`).

Each file of benchmarks/tests/stream_digests/ pins streams, one line a
(configuration file, traffic file, seed): the account batches, the
opening-balance batches, the first 40 creates with a lookup after each, and
the read-back requests, each hashed. The pins of the configurations and
traffic files that came before `account_flags` and `opening_balances` were
computed by the generator as it stood then, so a change of the generator
that moves one byte of an existing stream fails here. A new configuration
or traffic file pins its own streams in a file of its own there.
"""

import hashlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402
from benchmarks.harness import traffic  # noqa: E402

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "stream_digests")
CREATES = 40


def stream_digest(traffic_mod, run_mod, cfg: dict, mix: dict, seed: int) -> str:
    """One digest over the five parts of a run's requests (each part's
    own digest goes into it)."""
    st = traffic_mod.Stream(mix, cfg, seed)
    parts = {k: hashlib.sha256() for k in
             ("accounts", "opening", "creates", "lookups", "readback")}
    for arr in st.account_batches():
        parts["accounts"].update(arr.tobytes())
    for arr in getattr(st, "opening_batches", list)():
        parts["opening"].update(arr.tobytes())
    for _ in range(CREATES):
        cls, arr = st.next_create()
        parts["creates"].update(cls.encode() + b"\0" + arr.tobytes())
        op, ids = st.next_lookup()
        parts["lookups"].update(op.to_bytes(2, "little") + ids.tobytes())
    for op, ids in run_mod.readback_requests(st, mix, cfg, seed):
        parts["readback"].update(op.to_bytes(2, "little") + ids.tobytes())
    whole = hashlib.sha256()
    for k, h in parts.items():
        whole.update(k.encode() + h.digest())
    return whole.hexdigest()[:20]


def pin_files() -> list[str]:
    return sorted(f for f in os.listdir(PINS) if f.endswith(".json"))


@pytest.mark.parametrize("pin_file", pin_files())
def test_request_stream_bytes_are_pinned(pin_file):
    with open(os.path.join(PINS, pin_file)) as f:
        pins = json.load(f)["pins"]
    assert pins
    got, want = {}, {}
    for p in pins:
        key = (p["config"], p["traffic"], p["seed"])
        with open(os.path.join(REPO, p["config"])) as f:
            cfg = json.load(f)
        mix = traffic.load_traffic(os.path.join(REPO, p["traffic"]))
        got[key] = stream_digest(traffic, run, cfg, mix, p["seed"])
        want[key] = p["digest"]
    assert got == want
