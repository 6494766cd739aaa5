"""Real-process integration (reference: src/testing/tmp_tigerbeetle.zig +
client integration tests): spawn the server binary, drive it over real TCP
with the native C client and the REPL, kill it, restart it, verify
durability."""

import os
import signal
import socket
import subprocess
import sys

import pytest

from tigerbeetle_tpu.types import (
    Account,
    CreateTransferResult,
    Transfer,
    TransferFlags,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(path: str, port: int, aof: str | None = None):
    cmd = [
        sys.executable, "-m", "tigerbeetle_tpu", "start",
        "--addresses", f"127.0.0.1:{port}",
        "--grid-mb", "8",
        "--account-slots-log2", "10",
        "--transfer-slots-log2", "12",
    ]
    if aof:
        cmd += ["--aof", aof]
    cmd.append(path)
    env = dict(os.environ, TB_JAX_PLATFORM="cpu", PYTHONPATH=REPO,
               TB_PARENT_WATCHDOG="1")
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    line = proc.stdout.readline()  # blocks until "listening" (or crash)
    if "listening" not in line:
        rest = proc.stdout.read()
        _kill_group(proc)
        raise AssertionError(f"server failed to start: {line}{rest}")
    return proc


def _kill_group(proc) -> None:
    """Kill the server's whole process group (spawned with
    start_new_session=True, so pgid == pid) and reap it; leaked servers
    from partial teardowns used to survive the suite and burn CPU."""
    from tigerbeetle_tpu.benchmark import kill_process_group

    kill_process_group(proc)
    try:
        proc.wait(timeout=10)
    except Exception:
        pass


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("proc")
    path = str(tmp / "data.tigerbeetle")
    aof = str(tmp / "data.aof")
    port = _free_port()
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format",
         "--cluster", "0", "--replica", "0", "--replica-count", "1",
         "--grid-mb", "8", path],
        cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr
    proc = _spawn_server(path, port, aof=aof)
    state = {"proc": proc, "path": path, "port": port, "aof": aof}
    yield state
    _kill_group(state["proc"])  # the kill/restart test replaces "proc"


def test_native_client_end_to_end(server):
    from tigerbeetle_tpu.client_ffi import NativeClient

    client = NativeClient("127.0.0.1", server["port"])
    assert client.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in (1, 2, 3)]
    ) == []
    results = client.create_transfers([
        Transfer(id=10, debit_account_id=1, credit_account_id=2, amount=100,
                 ledger=1, code=1),
        Transfer(id=11, debit_account_id=1, credit_account_id=3, amount=50,
                 ledger=1, code=1, flags=int(TransferFlags.pending)),
        Transfer(id=12, pending_id=11,
                 flags=int(TransferFlags.post_pending_transfer)),
        Transfer(id=13, debit_account_id=1, credit_account_id=1, amount=5,
                 ledger=1, code=1),
    ])
    assert results == [(3, int(CreateTransferResult.accounts_must_be_different))]
    accounts = client.lookup_accounts([1, 2, 3, 404])
    assert len(accounts) == 3
    assert accounts[0].debits_posted == 150 and accounts[0].debits_pending == 0
    transfers = client.lookup_transfers([12])
    assert transfers[0].amount == 50 and transfers[0].pending_id == 11
    client.close()


@pytest.mark.parametrize("backend", ["native+device", "bogus"])
def test_start_refuses_a_backend_it_does_not_have(backend, tmp_path):
    """An unknown --backend is refused where the arguments are parsed:
    one line naming the backends there are, before the data file is
    opened (there is none here) or the socket bound."""
    out = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{_free_port()}",
         "--backend", backend, str(tmp_path / "no_such.tigerbeetle")],
        cwd=REPO, env=dict(os.environ, TB_JAX_PLATFORM="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert out.stderr.strip().splitlines()[-1] == (
        f"error: unknown --backend {backend!r} (native|dual|device|sharded)"
    ), out.stderr


@pytest.mark.parametrize("argv,lists", [
    (["-m", "tigerbeetle_tpu", "start", "--help"], "--backend <str>"),
    (["chip_smoke.py", "--help"], "--chips"),
])
def test_help_is_printed_without_a_data_file_or_a_device(argv, lists):
    out = subprocess.run(
        [sys.executable, *argv], cwd=REPO,
        env=dict(os.environ, TB_JAX_PLATFORM="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert lists in out.stdout


def test_repl_against_live_server(server):

    from tigerbeetle_tpu.repl import Repl, parse_statement
    from tigerbeetle_tpu.types import Operation

    op, events = parse_statement(
        "create_transfers id=77 debit_account_id=2 credit_account_id=3 "
        "amount=7 ledger=1 code=1;"
    )
    assert op == Operation.create_transfers and events[0].amount == 7

    repl = Repl([("127.0.0.1", server["port"])])
    repl.connect()
    out = repl.execute(*parse_statement(
        "create_accounts id=500 ledger=1 code=9;"
    ))
    assert out == "ok"
    out = repl.execute(*parse_statement("lookup_accounts id=500;"))
    assert "id=500" in out and "code=9" in out
    out = repl.execute(*parse_statement("create_accounts id=500 ledger=1 code=8;"))
    assert "exists_with_different_code" in out


def test_three_replica_tcp_cluster(tmp_path):
    """Three real server processes over real sockets: consensus across
    OS process boundaries, driven by the native C client."""
    from tigerbeetle_tpu.client_ffi import NativeClient

    ports = [_free_port() for _ in range(3)]
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    procs = []
    try:
        for i in range(3):
            path = str(tmp_path / f"r{i}.tigerbeetle")
            fmt = subprocess.run(
                [sys.executable, "-m", "tigerbeetle_tpu", "format",
                 "--cluster", "0", "--replica", str(i),
                 "--replica-count", "3", "--grid-mb", "8", path],
                cwd=REPO, env=dict(os.environ, PYTHONPATH=REPO),
                capture_output=True, text=True, timeout=120,
            )
            assert fmt.returncode == 0, fmt.stderr
        for i in range(3):
            cmd = [
                sys.executable, "-m", "tigerbeetle_tpu", "start",
                "--addresses", addresses, "--replica", str(i),
                "--grid-mb", "8", "--account-slots-log2", "10",
                "--transfer-slots-log2", "12",
                str(tmp_path / f"r{i}.tigerbeetle"),
            ]
            env = dict(os.environ, TB_JAX_PLATFORM="cpu", PYTHONPATH=REPO,
               TB_PARENT_WATCHDOG="1")
            p = subprocess.Popen(cmd, cwd=REPO, env=env,
                                 start_new_session=True,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(p)
            line = p.stdout.readline()
            assert "listening" in line, line + (p.stdout.read() or "")

        client = NativeClient(addresses)  # rotates to find the primary
        assert client.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in (1, 2)]
        ) == []
        assert client.create_transfers([
            Transfer(id=10, debit_account_id=1, credit_account_id=2,
                     amount=42, ledger=1, code=1)
        ]) == []
        accounts = client.lookup_accounts([1, 2])
        assert accounts[0].debits_posted == 42
        assert accounts[1].credits_posted == 42
        client.close()
    finally:
        for p in procs:
            _kill_group(p)


def test_statsd_and_tracer_units(tmp_path):
    import json as _json
    import socket as _socket

    from tigerbeetle_tpu.statsd import StatsD
    from tigerbeetle_tpu.tracer import JsonTracer, Tracer

    # statsd: packets really hit the wire in the documented format
    sink = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    sink.bind(("127.0.0.1", 0))
    sink.settimeout(2)
    port = sink.getsockname()[1]
    s = StatsD("127.0.0.1", port, prefix="tb")
    s.count("ops", 3)
    s.gauge("commit", 17)
    s.timing("batch", 1.5)
    got = {sink.recv(256).decode() for _ in range(3)}
    assert got == {"tb.ops:3|c", "tb.commit:17|g", "tb.batch:1.5|ms"}
    s.close()
    sink.close()

    # tracer: spans nest and dump as Chrome trace events
    tr = JsonTracer()
    with tr.span("commit", op=7):
        with tr.span("prefetch"):
            pass
    path = str(tmp_path / "trace.json")
    tr.dump(path)
    events = _json.load(open(path))["traceEvents"]
    assert {e["name"] for e in events} == {"commit", "prefetch"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    # the none backend is a no-op
    with Tracer().span("x"):
        pass


def test_c_example_client_against_live_server(server):
    """Compile and run the pure-C example program (no Python anywhere in the
    client path): the C ABI + wire protocol end to end."""
    native_dir = os.path.join(REPO, "native")
    exe = os.path.join(native_dir, "example_client")
    cc = subprocess.run(
        ["gcc", "-O2", "-o", exe, "example_client.c",
         # -lpthread explicitly: libtb_native.so uses pthreads and some
         # toolchains do not resolve transitive shared-lib deps
         "-L.", "-ltb_native", "-lpthread", "-Wl,-rpath," + native_dir],
        cwd=native_dir, capture_output=True, text=True,
    )
    assert cc.returncode == 0, cc.stderr
    run = subprocess.run(
        [exe, f"127.0.0.1:{server['port']}"],
        capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    # ids 1,2 already exist from earlier tests in this module: the creates
    # report result codes; the transfer and lookups still round-trip
    assert "transfer: ok" in run.stdout
    assert "account 901:" in run.stdout and "account 902:" in run.stdout


def test_kill_restart_durability_and_aof(server):
    from tigerbeetle_tpu import aof as aof_mod
    from tigerbeetle_tpu.client_ffi import NativeClient
    from tigerbeetle_tpu.types import Operation

    proc = server["proc"]
    proc.send_signal(signal.SIGKILL)  # hard kill, no cleanup
    proc.wait(timeout=30)

    # AOF alone can reconstruct the committed history
    ops = list(aof_mod.replay(server["aof"]))
    assert len(ops) >= 3
    assert {Operation(h.operation) for h, _ in ops} >= {
        Operation.create_accounts, Operation.create_transfers
    }

    proc2 = _spawn_server(server["path"], server["port"], aof=server["aof"] + "2")
    server["proc"] = proc2
    client = NativeClient("127.0.0.1", server["port"])
    accounts = client.lookup_accounts([1, 500])
    assert accounts[0].debits_posted == 150  # survived the kill
    assert accounts[1].code == 9
    # and the restarted server still serves writes
    assert client.create_accounts([Account(id=600, ledger=1, code=1)]) == []
    client.close()
