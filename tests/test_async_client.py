"""Async packet client ABI (native/tb_client.cc tb_client_async_*): the
reference's packet/completion model (src/clients/c/tb_client/packet.zig,
thread.zig) — N requests in flight from one process over a session pool,
same-op create packets coalesced into one message and their sparse results
demuxed per packet with rebased indices."""

import os
import subprocess
import sys

import pytest

from tigerbeetle_tpu.types import Account, Operation, Transfer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from tests.test_process import _free_port, _kill_group, _spawn_server

    tmp = tmp_path_factory.mktemp("async_client")
    path = str(tmp / "data.tigerbeetle")
    port = _free_port()
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format",
         "--cluster", "0", "--replica", "0", "--replica-count", "1",
         "--grid-mb", "8", path],
        cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr
    proc = _spawn_server(path, port)
    yield {"proc": proc, "port": port}
    _kill_group(proc)


def test_concurrent_packets_end_to_end(server):
    """Many packets in flight at once; every reply lands on the right
    packet (ids/results verified through the blocking control session)."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.client_ffi import AsyncNativeClient, NativeClient
    from tigerbeetle_tpu.state_machine import decode_results

    addr = f"127.0.0.1:{server['port']}"
    ctl = NativeClient(addr)
    assert ctl.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in (1, 2)]
    ) == []

    ac = AsyncNativeClient(addr, sessions=4)
    try:
        futs = []
        for g in range(16):
            transfers = [
                Transfer(id=1000 + g * 10 + i, debit_account_id=1,
                         credit_account_id=2, amount=1, ledger=1, code=1)
                for i in range(8)
            ]
            body = types.transfers_to_np(transfers).tobytes()
            futs.append(ac.submit(Operation.create_transfers, body))
        for f in futs:
            assert f.result(timeout=120) == b""  # all succeeded
    finally:
        ac.close()
    accounts = ctl.lookup_accounts([1, 2])
    assert accounts[0].debits_posted == 16 * 8
    assert accounts[1].credits_posted == 16 * 8

    # failures come back demuxed with correctly REBASED indices: submit
    # two single-event packets where only the second fails — its sparse
    # result must carry index 0 (not its index inside a coalesced message)
    ac2 = AsyncNativeClient(addr, sessions=1)
    try:
        ok_t = [Transfer(id=5000, debit_account_id=1, credit_account_id=2,
                         amount=1, ledger=1, code=1)]
        bad_t = [Transfer(id=5001, debit_account_id=1, credit_account_id=1,
                          amount=1, ledger=1, code=1)]  # same accounts
        f1 = ac2.submit(
            Operation.create_transfers, types.transfers_to_np(ok_t).tobytes()
        )
        f2 = ac2.submit(
            Operation.create_transfers, types.transfers_to_np(bad_t).tobytes()
        )
        assert f1.result(timeout=120) == b""
        res = decode_results(f2.result(timeout=120),
                             Operation.create_transfers)
        assert res == [(0, int(types.CreateTransferResult.accounts_must_be_different))]
    finally:
        ac2.close()
    ctl.close()


def test_async_lookup_packets(server):
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.client_ffi import AsyncNativeClient
    from tigerbeetle_tpu.state_machine import encode_ids

    import numpy as np

    addr = f"127.0.0.1:{server['port']}"
    ac = AsyncNativeClient(addr, sessions=2)
    try:
        f = ac.submit(Operation.lookup_accounts, encode_ids([1, 2, 404]))
        rows = np.frombuffer(f.result(timeout=120), dtype=types.ACCOUNT_DTYPE)
        assert len(rows) == 2  # 404 skipped
        assert sorted(int(r["id_lo"]) for r in rows) == [1, 2]
    finally:
        ac.close()


def test_async_driver_e2e_smoke():
    """run_e2e(driver="async"): the BASELINE protocol through the async
    ABI from one process, conservation verified over the wire."""
    from tigerbeetle_tpu.benchmark import run_e2e

    out = run_e2e(
        n_accounts=200, n_transfers=64 * 8, batch=64, clients=4,
        warmup_batches=1, jax_platform="cpu", backend="native",
        driver="async",
    )
    assert out["driver"] == "async_abi"
    assert out["durable_tps"] > 0


def test_async_driver_two_phase_smoke():
    from tigerbeetle_tpu.benchmark import run_e2e

    out = run_e2e(
        n_accounts=200, n_transfers=64 * 6, batch=64, clients=3,
        warmup_batches=1, jax_platform="cpu", backend="dual",
        driver="async", workload="two_phase",
    )
    assert out["durable_tps"] > 0
    assert out["device_shadow"]["verified"] is True
