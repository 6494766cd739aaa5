"""The rules that keep the program honest about the device it runs on, tested
on the CPU: chip_smoke.py fails without a chip; `start` names its device and
refuses a CPU nobody asked for; a dual server whose applier died exits
non-zero; the compile cache is placed from outside; several device-backed
servers at once fail early; a server that dies before `listening` explains
itself.

Where a rule is a function, it is tested as one over a faked device list —
a child that probes for the chip loads the TPU library. The few children
started here with no platform pinned find no chip and fall back to the CPU,
which is exactly the situation under test.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
from types import SimpleNamespace

import pytest

from tigerbeetle_tpu import benchmark
from tigerbeetle_tpu.cli import asked_platforms, serving_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = ("--account-slots-log2", "10", "--transfer-slots-log2", "12",
         "--grid-mb", "8")


def _env(**over) -> dict:
    """The test's environment with NO platform pinned (the driver's command
    sets JAX_PLATFORMS=cpu, which children would inherit), plus `over`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "TB_JAX_PLATFORM",
                     "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(PYTHONPATH=REPO, TB_PARENT_WATCHDOG="1", **over)
    return env


def _dev(platform: str, kind: str = "fake"):
    return SimpleNamespace(platform=platform, device_kind=kind, id=0)


# -- (a) chip_smoke.py without a chip ----------------------------------

@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_chip(where, tmp_path):
    """From the checkout: the server it starts refuses the CPU it fell back
    to, the phase line says so, exit code non-zero, and no `ok: true` line.
    Alone in a directory (the script and nothing else of the repo): exits
    non-zero and prints no result at all."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = str(tmp_path)
    done = subprocess.run(
        [sys.executable, script], cwd=cwd, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0, done.stdout[-2000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    if where == "alone":
        assert lines == []
        return
    last = json.loads(lines[-1])
    assert last.get("ok") is not True
    assert last["phase"] == "dual" and "no TPU" in last["error"], last


# -- (b) the server names its device and refuses the wrong one ----------

@pytest.mark.parametrize("devices,asked,want", [
    ([_dev("tpu", "TPU v5 lite")], (), ("tpu", "TPU v5 lite", 1)),
    ([_dev("tpu", "TPU v5 lite")] * 4, (), ("tpu", "TPU v5 lite", 4)),
    ([_dev("cpu", "cpu")], ("cpu",), ("cpu", "cpu", 1)),
    ([_dev("cpu", "cpu")], (), None),          # fell back unasked: refuse
    ([_dev("cpu", "cpu")], ("tpu",), None),    # asked for tpu, got cpu
    ([_dev("gpu", "X")], ("cpu",), None),      # asked for another one
    ([_dev("cpu", "cpu")], ("tpu", "cpu"), None),  # `tpu,cpu` asks for tpu
])
def test_serving_device_over_a_faked_device_list(devices, asked, want, capsys):
    if want is None:
        with pytest.raises(SystemExit) as e:
            serving_device(devices, asked)
        assert e.value.code != 0
        assert "no TPU" in capsys.readouterr().err
    else:
        info = serving_device(devices, asked)
        assert (info["platform"], info["kind"], info["count"]) == want


@pytest.mark.parametrize("environ,want", [
    ({}, ()),
    ({"JAX_PLATFORMS": "cpu"}, ("cpu",)),
    ({"TB_JAX_PLATFORM": "cpu", "JAX_PLATFORMS": "tpu"}, ("cpu",)),
    ({"JAX_PLATFORMS": "tpu, cpu"}, ("tpu", "cpu")),
    ({"JAX_PLATFORMS": ""}, ()),
])
def test_asked_platforms(environ, want):
    assert asked_platforms(environ) == want


@pytest.mark.parametrize("backend", ["device", "dual", "sharded"])
def test_start_refuses_a_cpu_it_was_not_asked_for(backend, tmp_path):
    """No platform pinned on a chipless box: JAX falls back to the CPU and
    the server must exit non-zero with the refusal, before it opens or
    allocates anything (the data file does not even exist)."""
    done = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{benchmark.free_port()}",
         "--backend", backend, *SMALL, str(tmp_path / "none.tigerbeetle")],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert done.returncode != 0
    assert "no TPU" in done.stderr and "TB_JAX_PLATFORM=cpu" in done.stderr
    assert "listening" not in done.stdout


def _format(path: str) -> None:
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster", "0",
         "--replica", "0", "--replica-count", "1", "--grid-mb", "8", path],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr


def _start(argv_prefix, path, backend, **env):
    port = benchmark.free_port()
    proc = subprocess.Popen(
        [sys.executable, *argv_prefix, "start",
         "--addresses", f"127.0.0.1:{port}", "--backend", backend,
         *SMALL, path],
        cwd=REPO, env=_env(**env), start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    return proc, port


def test_start_prints_the_device_line_before_listening(tmp_path):
    path = str(tmp_path / "d.tigerbeetle")
    _format(path)
    proc, _port = _start(("-m", "tigerbeetle_tpu"), path, "device",
                         TB_JAX_PLATFORM="cpu")
    try:
        head = benchmark.wait_listening(proc, "device", deadline_s=240)
    finally:
        benchmark.kill_process_group(proc)
        proc.wait(timeout=30)
    marks = [i for i, ln in enumerate(head) if ln.startswith("[device] ")]
    assert len(marks) == 1 and "listening" in head[-1]
    assert marks[0] < len(head) - 1
    device = json.loads(head[marks[0]][len("[device] "):])
    assert (device["platform"], device["count"]) == ("cpu", 1)
    assert "bytes_in_use" in device and "kind" in device


# -- (c) a dual server whose applier died exits non-zero ----------------

_DEAD_APPLIER = """
import sys
from tigerbeetle_tpu.models import dual_ledger, ledger

def boom(self, *a, **k):
    raise RuntimeError("injected applier fault")

dual_ledger.DualLedger._warm_device_kernels = lambda self, process: None
ledger.DeviceLedger.execute_async = boom
from tigerbeetle_tpu.cli import main
sys.exit(main(sys.argv[1:]))
"""


def test_dual_server_with_a_dead_applier_exits_nonzero(tmp_path):
    """The applier thread parks its exception for finalize; the SIGTERM
    handler used to print it as a [stats] field and leave with 0."""
    path = str(tmp_path / "dual.tigerbeetle")
    _format(path)
    proc, port = _start(("-c", _DEAD_APPLIER), path, "dual",
                        TB_JAX_PLATFORM="cpu")
    try:
        benchmark.wait_listening(proc, "dual", deadline_s=240)
        session = benchmark._BenchClient(0xE0001, port)
        session.register()
        session.client.request(
            benchmark.Operation.create_accounts,
            benchmark._accounts_body(1, 8),
        )
        _header, reply = session.wait_reply()
        assert reply == b""  # the native engine served it: all ok
        session.bus.drop_connections()
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        benchmark.kill_process_group(proc)
    stats = [ln for ln in out.splitlines() if ln.startswith("[stats] ")]
    assert len(stats) == 1, out[-2000:]  # the line landed first
    shadow = json.loads(stats[0][len("[stats] "):])["device_shadow"]
    assert shadow["verified"] is False
    assert "injected applier fault" in shadow["error"]
    assert proc.returncode == 1


# -- (d) the compile cache is placed from outside ------------------------

@pytest.mark.parametrize("given", ["/x/given-from-outside", None])
def test_compile_cache_directory_rule(given):
    env = _env(JAX_PLATFORMS="cpu")
    if given:
        env["JAX_COMPILATION_CACHE_DIR"] = given
    env["TB_JAX_CACHE"] = "/ignored"  # the old knob has no reader
    done = subprocess.run(
        [sys.executable, "-c",
         "import tigerbeetle_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == (given or os.path.join(REPO, ".jax_cache"))


# -- one process per chip -------------------------------------------------

@pytest.mark.parametrize("backend,n,platform,environ,refused", [
    ("native", 3, None, {}, False),            # never touches a device
    ("dual", 1, None, {}, False),              # one server, one chip
    ("dual", 3, "cpu", {}, False),             # pinned off the chip
    ("device", 2, None, {"JAX_PLATFORMS": "cpu"}, False),
    ("dual", 3, None, {}, True),               # three would claim one chip
    ("device", 2, "tpu", {}, True),
    ("sharded", 2, None, {"TB_JAX_PLATFORM": "tpu"}, True),
])
def test_several_device_servers_at_once_fail_early(
        backend, n, platform, environ, refused, monkeypatch):
    for k in ("JAX_PLATFORMS", "TB_JAX_PLATFORM"):
        monkeypatch.delenv(k, raising=False)
    for k, v in environ.items():
        monkeypatch.setenv(k, v)
    if refused:
        with pytest.raises(RuntimeError, match="one process"):
            benchmark.require_one_process_per_chip("t", backend, n, platform)
    else:
        benchmark.require_one_process_per_chip("t", backend, n, platform)


# -- the wait for `listening` has a deadline and keeps the child's words ---

@pytest.mark.parametrize("child,deadline_s,why", [
    ("import sys; print('[boot] x'); print('error: disk on fire'); "
     "sys.exit(3)", 60, "died before"),
    ("import time; print('[boot] compiling', flush=True); time.sleep(600)",
     2, "did not reach"),
])
def test_wait_listening_explains_a_server_that_never_listens(
        child, deadline_s, why):
    proc = subprocess.Popen(
        [sys.executable, "-c", child], start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        with pytest.raises(RuntimeError) as e:
            benchmark.wait_listening(proc, "test", deadline_s=deadline_s)
    finally:
        benchmark.kill_process_group(proc)
        proc.wait(timeout=30)
    msg = str(e.value)
    assert why in msg and "[boot]" in msg
    if why == "died before":
        assert "exit code 3" in msg and "disk on fire" in msg
