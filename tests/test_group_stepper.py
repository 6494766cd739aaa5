"""The group stepper loops over the batches it carries, not over the slots
of its capacity (PR 29).

Contracts under test (CPU, tiny geometry):

- a group launch of m batches in a k-slot program equals m sequential
  execute_async calls, code for code and fingerprint for fingerprint, for
  every fill the served path produces (partial and full, both capacities);
- `flat` [k * n_pad + 1] and `summary` [k + 1] are bit for bit what the
  fixed-length scan over all k slots returned: slots >= m read all zero
  (results and failure counts) and the fault word is last;
- the trip count comes from the number of batches, never from `ns > 0`:
  an EMPTY batch in the middle of a group is a batch, and the ones after
  it run;
- the loop bound of the lowered program is an operand of the launch, not
  the constant k, and one compiled program serves every fill.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import ConfigProcess
from tigerbeetle_tpu.models import ledger
from tigerbeetle_tpu.models.ledger import DeviceLedger
from tigerbeetle_tpu.types import CreateTransferResult, Operation

PROCESS = ConfigProcess(account_slots_log2=12, transfer_slots_log2=14)
N = 32  # transfers a batch (= n_pad)
ACCOUNTS = 16
TS0 = 1 << 40


def _accounts() -> np.ndarray:
    acc = np.zeros(ACCOUNTS, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, ACCOUNTS + 1, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1
    return acc


def _transfers(start: int, n: int = N, bad: tuple = ()) -> np.ndarray:
    """n plain transfers with fresh ids; lanes in `bad` debit an account
    that does not exist (a failure code the fast tier returns)."""
    x = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    x["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    x["debit_account_id_lo"] = 1 + np.arange(n) % 9
    x["credit_account_id_lo"] = 1 + (np.arange(n) + 1) % 9
    x["amount_lo"] = 1 + np.arange(n)
    x["ledger"] = 1
    x["code"] = 1
    for lane in bad:
        x["debit_account_id_lo"][lane] = 9_999
    return x


def _ledger() -> DeviceLedger:
    led = DeviceLedger(process=PROCESS)
    led.drain(led.execute_async(Operation.create_accounts, TS0, _accounts()))
    return led


def _items(sizes: list, bad_in: dict, group: int = 0) -> list:
    """[(timestamp, batch)] with one batch of `sizes[i]` transfers each;
    `group` keeps the ids and timestamps of successive groups apart."""
    items, ts = [], TS0 + 64 + 4_096 * group
    for i, n in enumerate(sizes):
        ts += 64
        first_id = 100_000 * (group + 1) + 1_000 * i
        items.append((ts, _transfers(first_id, n, bad_in.get(i, ()))))
    return items


@functools.cache
def _scan_reference(k: int, n_pad: int):
    """The stepper as it was before PR 29: a lax.scan over all k slots,
    padding slots carrying n = 0. What `flat` and `summary` must equal."""
    kernels = ledger.get_kernels(PROCESS)

    def step(state, rows, ns, tss):
        def body(st, x):
            r, n, t = x
            st, res = kernels._commit_transfers(st, {"rows": r}, n, t, mode="fast")
            res = res.astype(jnp.uint32)
            lane = jnp.arange(res.shape[0], dtype=jnp.int32)
            return st, (res, jnp.sum(((res != 0) & (lane < n)).astype(jnp.uint32)))

        state, (results, cnts) = jax.lax.scan(body, state, (rows, ns, tss))
        fault = state["fault"].reshape(1).astype(jnp.uint32)
        return (state, jnp.concatenate([results.reshape(-1), fault]),
                jnp.concatenate([cnts, fault]))

    return jax.jit(step)


def _staged(items: list, k: int, n_pad: int):
    rows = np.zeros((k, n_pad, ledger.ROW_WORDS), dtype=np.uint32)
    ns = np.zeros(k, dtype=np.int32)
    tss = np.zeros(k, dtype=np.uint64)
    for i, (ts, arr) in enumerate(items):
        rows[i, : len(arr)] = arr.view(np.uint32).reshape(len(arr), ledger.ROW_WORDS)
        ns[i], tss[i] = len(arr), ts
    return rows, ns, tss


def _check_group(k: int, sizes: list, bad_in: dict) -> None:
    m = len(sizes)
    items = _items(sizes, bad_in)
    fused, serial, scanned = _ledger(), _ledger(), _ledger()

    pendings = fused.try_execute_group_async(items)
    assert pendings is not None and len(pendings) == m
    group = pendings[0].group
    assert group.k == k and group.n_pad == N
    want = [
        serial.drain(serial.execute_async(Operation.create_transfers, ts, arr))
        for ts, arr in items
    ]
    missing = int(CreateTransferResult.debit_account_not_found)
    for i, (p, codes) in enumerate(zip(pendings, want)):
        assert fused.drain(p) == codes, i
        assert [j for j, c in enumerate(codes) if c] == list(bad_in.get(i, ()))
        assert all(c in (0, missing) for c in codes)
    fused.check_fault()
    assert fused.fingerprint() == serial.fingerprint()

    flat = np.asarray(group.fetch())
    summary = np.asarray(group.fetch_summary())
    assert flat.shape == (k * N + 1,) and flat.dtype == np.uint32
    assert summary.shape == (k + 1,) and summary.dtype == np.uint64
    for i, codes in enumerate(want):
        assert flat[i * N : i * N + len(codes)].tolist() == codes
        assert summary[i] == sum(1 for c in codes if c)
    # every word of a slot the group does not carry is zero; fault is last
    assert not flat[m * N : -1].any()
    assert not summary[m:-1].any()
    assert flat[-1] == 0 and summary[-1] == 0

    # bit for bit what the scan over all k slots gave
    rows, ns, tss = _staged(items, k, N)
    state, ref_flat, ref_summary = _scan_reference(k, N)(
        scanned.state, jnp.asarray(rows), jnp.asarray(ns), jnp.asarray(tss)
    )
    scanned.state = state
    np.testing.assert_array_equal(flat, np.asarray(ref_flat))
    np.testing.assert_array_equal(summary, np.asarray(ref_summary))
    assert scanned.fingerprint() == fused.fingerprint()


@pytest.mark.parametrize(
    "k,m", [(4, 2), (4, 3), (4, 4), (16, 5), (16, 10), (16, 16)]
)
def test_group_launch_equals_sequential_commits(k, m):
    """m batches in a k-slot launch == m execute_async calls; the slots
    the group does not carry read zero; flat/summary equal the old scan's."""
    # a failure in the first, a middle and the last batch of the group
    bad_in = {0: (3,), m // 2: (0, N - 1), m - 1: (7, 8, 9)}
    if m // 2 in (0, m - 1):
        del bad_in[m // 2]
    _check_group(k, [N] * m, bad_in)


def test_an_empty_batch_inside_a_group_is_a_batch_not_padding():
    """[full, EMPTY, short, full]: the empty create_transfers is committed
    (its pending drains to no codes), and the two batches after it run with
    their own timestamps. A trip count taken from `ns > 0` would stop at 3
    of 4 or skip the wrong slot."""
    sizes = [N, 0, 5, N]
    _check_group(4, sizes, {2: (4,), 3: (1,)})

    led = _ledger()
    items = _items(sizes, {})
    pendings = led.try_execute_group_async(items)
    assert [p.n for p in pendings] == sizes
    assert led.drain(pendings[1]) == []
    # the last batch's rows carry ITS timestamps: ts - n + 1 + lane
    ts_last, last = items[-1]
    ids = [int(i) for i in last["id_lo"]]
    got = led.lookup_transfers(ids)
    assert [t.id for t in got] == ids
    assert [t.timestamp for t in got] == [ts_last - N + 1 + j for j in range(N)]
    assert led.commit_timestamp == ts_last


def _derived_from(jaxpr, source) -> set:
    """Variables of `jaxpr` computed from `source` (itself included)."""
    seen = {source}
    for eqn in jaxpr.eqns:
        if any(isinstance(v, Var) and v in seen for v in eqn.invars):
            seen.update(eqn.outvars)
    return seen


@pytest.mark.parametrize("k", DeviceLedger.GROUP_KS)
def test_the_loop_bound_is_an_operand_of_the_launch(k):
    """The stepper's one loop is a `while` whose bound is computed from the
    launch's last argument; no scan of length k is left in the program."""
    # the stepper reads only self.kernels (as tests/test_tpu_compile.py)
    stand_in = SimpleNamespace(kernels=ledger.get_kernels(PROCESS))
    stepper = DeviceLedger._group_stepper(stand_in, k, N)
    args = (
        jax.eval_shape(lambda: ledger.init_state(PROCESS)),
        jax.ShapeDtypeStruct((k, N, ledger.ROW_WORDS), jnp.uint32),
        jax.ShapeDtypeStruct((k,), jnp.int32),
        jax.ShapeDtypeStruct((k,), jnp.uint64),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    (call,) = jax.make_jaxpr(stepper.fn)(*args).jaxpr.eqns
    body = call.params["jaxpr"].jaxpr
    loops = [e for e in body.eqns if e.primitive.name in ("while", "scan")]
    assert [e.primitive.name for e in loops] == ["while"], loops
    from_m = _derived_from(body, body.invars[-1])
    # what the loop's condition reads: its constants and the carry (fori_loop
    # carries its upper bound), never the body's constants
    (loop,) = loops
    n_cond, n_body = loop.params["cond_nconsts"], loop.params["body_nconsts"]
    operands = loop.invars[:n_cond] + loop.invars[n_cond + n_body:]
    cond = loop.params["cond_jaxpr"].jaxpr
    read = {v for e in cond.eqns for v in e.invars if isinstance(v, Var)}
    bounds = [op for v, op in zip(cond.invars, operands) if v in read]
    assert any(isinstance(op, Var) and op in from_m for op in bounds), bounds
    assert not any(getattr(op, "val", None) == k for op in bounds), bounds


def test_one_compiled_program_serves_every_fill():
    """2, 3 and 4 batches in the 4-slot program: one compile."""
    led = _ledger()
    # a pad no other test of this process uses, so the count starts here
    led.pad_to = 2 * N
    name = f"group_stepper_4x{2 * N}"
    before = ledger.COMPILE_SENTINEL.per_name.get(name, 0)
    for m in (2, 3, 4):
        pendings = led.try_execute_group_async(_items([N] * m, {}, group=m))
        assert pendings is not None and pendings[0].group.k == 4
        for p in pendings:
            assert led.drain(p) == [0] * N
    assert ledger.COMPILE_SENTINEL.per_name.get(name, 0) - before == 1
