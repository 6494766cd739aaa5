"""A lookup's ids reach every backend as one array.

A lookup body is n little-endian u128 ids; read as u32 words it IS the
[n, 4] `key4` the probe programs take, and as bytes it is what the C++
engine reads. `StateMachine` takes that view once (`decode_key4`) and every
backend consumes it: the native and dual engines hand its buffer to
`tb_ledger_lookup`, the device and sharded ledgers copy it into the padded
`key4` batch with one slice. Python ints are made only at the edges that
need them (the oracle, the spill merge, list-taking calls), through
`types.ids_to_key4` / `types.key4_to_ids`.

Here: every backend's reply on the array path equals, byte for byte, its
reply to the same ids given as ints and the oracle's; the array equals
what the per-id conversions built; and a full lookup on the served
backends makes no Python int at all."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import TEST_CLUSTER, TEST_PROCESS
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.models.dual_ledger import DualLedger
from tigerbeetle_tpu.models.ledger import DeviceLedger, ids_to_batch
from tigerbeetle_tpu.models.native_ledger import NativeLedger
from tigerbeetle_tpu.models.oracle import OracleStateMachine
from tigerbeetle_tpu.parallel.mesh import ShardedLedger
from tigerbeetle_tpu.state_machine import (
    StateMachine,
    decode_ids,
    decode_key4,
    encode_ids,
)
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import Operation

U64_MAX = (1 << 64) - 1
U128_MAX = (1 << 128) - 1
FULL = 8190  # a full lookup message's ids

# ids whose high word is non-zero among the plain ones, and the largest
# valid id (2^128 - 1 itself is refused by create)
ACCOUNT_IDS = (
    list(range(1, 9))
    + [(1 << 64) | k for k in range(1, 9)]
    + [(0xDEADBEEF << 64) | (U64_MAX - k) for k in range(6)]
    + [1 << 127, U128_MAX - 1]
)
TRANSFER_IDS = (
    list(range(1000, 1012))
    + [(7 << 64) | k for k in range(1000, 1012)]
    + [(U64_MAX << 64) | k for k in range(4)]
)
CASES = ("edges", "high_word", "duplicates", "missing", "n1", "n7", "n8190")
OPS = {
    "accounts": (Operation.lookup_accounts, ACCOUNT_IDS),
    "transfers": (Operation.lookup_transfers, TRANSFER_IDS),
}


def _case_ids(case: str, present: list[int]) -> list[int]:
    rng = np.random.default_rng(40)
    missing = [int(x) for x in rng.integers(1 << 40, 1 << 62, size=4)] + [
        (int(x) << 64) | 5 for x in rng.integers(1, 1 << 62, size=4)
    ]
    if case == "edges":
        return [0, U128_MAX, present[0], U128_MAX - 1, 1 << 64, U64_MAX,
                present[-1]]
    if case == "high_word":
        return [x for x in present if x >> 64][:7]
    if case == "duplicates":
        a, b = present[0], present[9]
        return [a, a, b, missing[0], b, a, a]
    if case == "missing":
        return missing[:7]
    if case == "n1":
        return [present[10]]
    if case == "n7":
        return present[2:6] + missing[5:8]
    assert case == "n8190"
    pool = np.array(present + missing, dtype=object)
    return [int(x) for x in rng.choice(pool, size=FULL)]


def _accounts(ids) -> np.ndarray:
    key4 = types.ids_to_key4(ids).view("<u8")
    acc = np.zeros(len(ids), dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"], acc["id_hi"] = key4[:, 0], key4[:, 1]
    acc["ledger"] = 1
    acc["code"] = 1
    return acc


def _transfers(ids, accounts) -> np.ndarray:
    arr = np.zeros(len(ids), dtype=types.TRANSFER_DTYPE)
    key4 = types.ids_to_key4(ids).view("<u8")
    arr["id_lo"], arr["id_hi"] = key4[:, 0], key4[:, 1]
    n = len(accounts)
    dr = types.ids_to_key4([accounts[i % n] for i in range(len(ids))]).view("<u8")
    cr = types.ids_to_key4(
        [accounts[(i + 5) % n] for i in range(len(ids))]).view("<u8")
    arr["debit_account_id_lo"], arr["debit_account_id_hi"] = dr[:, 0], dr[:, 1]
    arr["credit_account_id_lo"], arr["credit_account_id_hi"] = cr[:, 0], cr[:, 1]
    arr["amount_lo"] = 3 + np.arange(len(ids))
    arr["ledger"] = 1
    arr["code"] = 1
    return arr


def _load(sm: StateMachine) -> None:
    ts = 1_000_000_000
    for op, body in (
        (Operation.create_accounts, _accounts(ACCOUNT_IDS).tobytes()),
        (Operation.create_transfers,
         _transfers(TRANSFER_IDS, ACCOUNT_IDS).tobytes()),
    ):
        ts += 64
        assert sm.commit(op, ts, body) == b""


def _backend(name: str, metrics: Metrics):
    if name == "native":
        return NativeLedger(10, 12)
    if name == "dual":
        return DualLedger(10, 12)
    if name == "sharded":
        mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
        return ShardedLedger(mesh, TEST_PROCESS)
    ledger = DeviceLedger(TEST_CLUSTER, TEST_PROCESS)
    ledger.instrument(metrics, NULL_TRACER)
    return ledger


@pytest.fixture(scope="module")
def oracle():
    sm = StateMachine(OracleStateMachine(), TEST_CLUSTER)
    _load(sm)
    return sm


@pytest.fixture(scope="module", params=["native", "dual", "device", "sharded"])
def loaded(request):
    metrics = Metrics()
    backend = _backend(request.param, metrics)
    sm = StateMachine(backend, TEST_CLUSTER, metrics=metrics)
    _load(sm)
    return request.param, backend, sm, metrics


def _counters(metrics: Metrics) -> dict:
    return dict(metrics.snapshot()["counters"])


def _delta(after: dict, before: dict, name: str) -> int:
    return after.get(name, 0) - before.get(name, 0)


# -- the array is what the per-id conversions built ------------------------


def _per_id_key4(ids) -> np.ndarray:
    """The probe batch's words as the per-id loop built them."""
    k4 = np.zeros((len(ids), 4), dtype=np.uint32)
    for i, x in enumerate(ids):
        lo, hi = x & U64_MAX, x >> 64
        k4[i] = (lo & 0xFFFFFFFF, lo >> 32, hi & 0xFFFFFFFF, hi >> 32)
    return k4


def _per_id_engine_bytes(ids) -> bytes:
    """The C++ engine's input as the per-id loop built it."""
    raw = np.zeros(2 * len(ids), dtype=np.uint64)
    for i, x in enumerate(ids):
        raw[2 * i] = x & U64_MAX
        raw[2 * i + 1] = x >> 64
    return raw.tobytes()


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", sorted(OPS))
def test_the_body_view_is_what_the_per_id_loops_built(case, kind):
    ids = _case_ids(case, OPS[kind][1])
    body = encode_ids(ids)
    key4 = decode_key4(body)
    assert key4.shape == (len(ids), 4) and key4.dtype == np.uint32
    assert key4.tobytes() == body == _per_id_engine_bytes(ids)
    assert np.array_equal(key4, _per_id_key4(ids))
    pad = 1 << max(0, (len(ids) - 1).bit_length())
    want = np.zeros((pad, 4), dtype=np.uint32)
    want[: len(ids)] = _per_id_key4(ids)
    assert np.array_equal(np.asarray(ids_to_batch(key4, pad)["key4"]), want)
    assert decode_ids(body) == types.key4_to_ids(key4) == ids
    assert types.as_key4(key4) is key4
    assert np.array_equal(types.as_key4(ids), key4)


@pytest.mark.parametrize("bad", [-1, 1 << 128])
def test_an_id_outside_u128_is_refused(bad):
    with pytest.raises(OverflowError):
        types.ids_to_key4([1, bad])


def test_a_key4_array_of_another_width_is_refused():
    with pytest.raises(ValueError, match="4 words a row"):
        types.as_key4(np.zeros((3, 2), dtype=np.uint32))


# -- every backend answers the array as it answers the ints ----------------


@pytest.mark.parametrize("path", ["commit", "commit_async"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", sorted(OPS))
def test_every_backend_answers_the_wire_array_as_the_ints_and_the_oracle(
        loaded, oracle, kind, case, path):
    name, backend, sm, metrics = loaded
    operation, present = OPS[kind]
    ids = _case_ids(case, present)
    body = encode_ids(ids)
    want = oracle.commit(operation, 0, body)
    found = [x for x in ids if x in set(present)]
    assert len(want) == 128 * len(found)
    rows = np.frombuffer(want, dtype=types.ACCOUNT_DTYPE if kind == "accounts"
                         else types.TRANSFER_DTYPE)
    assert types.key4_to_ids(
        np.stack([rows["id_lo"], rows["id_hi"]], 1).view("<u4")) == found

    before = _counters(metrics)
    if path == "commit":
        got = sm.commit(operation, 0, body)
    else:
        handle = sm.commit_async(operation, 0, body)
        assert isinstance(handle, tuple) == (name == "device")
        got = sm.commit_finish(handle)
    assert got == want
    wire = _counters(metrics)
    assert _delta(wire, before, "lookup.keys_wire") == 1
    assert _delta(wire, before, "lookup.keys_scalar") == 0

    # the same ids as ints, the way a list-taking caller hands them over
    assert backend.lookup_rows(operation, ids) == want
    objs = (backend.lookup_accounts(ids) if kind == "accounts"
            else backend.lookup_transfers(ids))
    assert b"".join(o.to_np().tobytes() for o in objs) == want

    if name == "device":
        deferred = path == "commit_async"
        listed = _counters(metrics)
        assert _delta(wire, before, "ledger.lookup_deferred") == deferred
        assert _delta(wire, before, "ledger.lookup_inline") == (not deferred)
        assert _delta(listed, wire, "ledger.lookup_deferred") == 0
        assert _delta(listed, wire, "ledger.lookup_inline") == 1
        # the same keys probe the same chains, whichever way they came
        steps = _delta(wire, before, "ledger.lookup_probe_steps")
        assert steps >= 1
        # lookup_rows, then the convenience call
        assert _delta(listed, wire, "ledger.lookup_probe_steps") == 2 * steps


# -- the served backends make no Python int of a lookup's ids -------------


def _refuse(*_a, **_k):
    raise AssertionError("a lookup's ids were converted one by one")


@pytest.mark.parametrize("kind", sorted(OPS))
@pytest.mark.parametrize("name", ["native", "dual", "device"])
def test_a_full_lookup_makes_no_python_int(name, kind, oracle, monkeypatch):
    metrics = Metrics()
    backend = _backend(name, metrics)
    sm = StateMachine(backend, TEST_CLUSTER, metrics=metrics)
    _load(sm)
    operation, present = OPS[kind]
    body = encode_ids(_case_ids("n8190", present))
    want = oracle.commit(operation, 0, body)
    for fn in ("join_u128", "split_u128", "ids_to_key4", "key4_to_ids"):
        monkeypatch.setattr(types, fn, _refuse)
    assert sm.commit_finish(sm.commit_async(operation, 0, body)) == want
    assert sm.commit(operation, 0, body) == want
    c = _counters(metrics)
    assert (c["lookup.keys_wire"], c["lookup.keys_scalar"]) == (2, 0)


def test_the_oracle_books_its_ids_as_python_ints(oracle):
    metrics = Metrics()
    sm = StateMachine(oracle.backend, TEST_CLUSTER, metrics=metrics)
    body = encode_ids(ACCOUNT_IDS[:3] + [0])
    assert sm.commit_async(Operation.lookup_accounts, 0, body) == \
        oracle.commit(Operation.lookup_accounts, 0, body)
    c = _counters(metrics)
    assert (c["lookup.keys_wire"], c["lookup.keys_scalar"]) == (0, 1)
