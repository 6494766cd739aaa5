"""The system under test as a child process: `python -m tigerbeetle_tpu
format`, then `start --backend <dual|device>`, which holds the chip. This
process never touches JAX. Copies (PR 25) of chip_smoke.py's Server and of
benchmark.py's free_port / wait_listening / kill_process_group and
inspect.py's inspect_live, kept here so that the yardstick does not move
when the program's own harnesses do.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BOOT_DEADLINE_S = 900.0


def child_env(cache_dir: str) -> dict:
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    # the program sets no cache directory in code when this is set, so the
    # fixed path inside the checkout is the one it uses
    env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
    env.pop("BENCH_RUN", None)
    return env


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def kill_process_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass


def build_native(env: dict) -> None:
    """The one build: native/libtb_native.so (git does not carry it)."""
    make = subprocess.run(["make", "-s", "-C", os.path.join(REPO, "native")],
                          capture_output=True, text=True, env=env)
    if make.returncode != 0:
        raise RuntimeError(f"make -C native failed: {make.stdout}{make.stderr}")


def format_file(path: str, env: dict, config: dict) -> None:
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster", "0",
         "--replica", "0", "--replica-count", "1",
         "--clients-max", str(config["clients_max"]),
         "--grid-mb", str(config["grid_mb"]), path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    if fmt.returncode != 0:
        raise RuntimeError(f"format failed: {fmt.stdout}{fmt.stderr}")


def wait_listening(proc, what: str, log, deadline_s: float = BOOT_DEADLINE_S):
    """Lines up to the server's `listening` line ([device] among them); a
    server that dies or is still booting at the deadline raises with its
    last output."""
    tail: collections.deque = collections.deque(maxlen=40)
    expired = threading.Event()

    def _expire() -> None:
        expired.set()
        kill_process_group(proc)

    timer = threading.Timer(deadline_s, _expire)
    timer.daemon = True
    timer.start()
    try:
        while True:
            line = proc.stdout.readline()
            if not line:
                try:
                    rc = proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    rc = None
                why = ("did not reach `listening` in time" if expired.is_set()
                       else "died before `listening`")
                raise RuntimeError(f"{what} server {why} (exit code {rc}); "
                                   "its last output:\n" + "".join(tail))
            tail.append(line)
            if "listening" in line:
                return list(tail)
            log(line.rstrip())
    finally:
        timer.cancel()


class Server:
    """One serving child and what it printed. With `traced`, the child is
    benchmarks/harness/traced_start.py: the same `start` entry, plus a
    profiler window that this process opens and closes over the child's
    stdin (only the process that holds the chip can trace it)."""

    def __init__(self, config: dict, path: str, env: dict, log,
                 traced: bool = False, extra: tuple = ()):
        self.port = free_port()
        self.backend = config["backend"]
        entry = ([os.path.join(REPO, "benchmarks", "harness", "traced_start.py")]
                 if traced else ["-m", "tigerbeetle_tpu"])
        argv = [sys.executable, *entry, "start",
                "--addresses", f"127.0.0.1:{self.port}",
                "--backend", self.backend,
                "--clients-max", str(config["clients_max"]),
                "--grid-mb", str(config["grid_mb"]),
                "--account-slots-log2", str(config["account_slots_log2"]),
                "--transfer-slots-log2", str(config["transfer_slots_log2"]),
                *config.get("start_args", ()), *extra, path]
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, start_new_session=True,
            stdin=subprocess.PIPE if traced else subprocess.DEVNULL,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        t0 = time.monotonic()
        head = wait_listening(self.proc, self.backend, log)
        self.boot_s = time.monotonic() - t0
        self.device = None
        for line in head:
            if line.startswith("[device] "):
                self.device = json.loads(line[len("[device] "):])
        self.stats: dict | None = None
        self.trace_marks: list[dict] = []
        self._trace_event = threading.Event()
        self.tail: collections.deque = collections.deque(maxlen=60)
        self._drain = threading.Thread(target=self._drain_stdout, daemon=True)
        self._drain.start()

    def _drain_stdout(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("[stats] "):
                try:
                    self.stats = json.loads(line[len("[stats] "):])
                except ValueError:
                    self.tail.append(line)
            elif line.startswith("[trace] "):
                try:
                    self.trace_marks.append(json.loads(line[len("[trace] "):]))
                except ValueError:
                    self.tail.append(line)
                self._trace_event.set()
            else:
                self.tail.append(line)

    def trace(self, command: str, timeout: float = 120.0) -> dict | None:
        """`start <dir>` / `stop` to the traced child; its answer line."""
        self._trace_event.clear()
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        if not self._trace_event.wait(timeout):
            return None
        return self.trace_marks[-1]

    def live_stats(self, timeout: float = 10.0) -> dict:
        return live_stats("127.0.0.1", self.port, timeout)

    def terminate(self, timeout: float = 300.0) -> int | None:
        """SIGTERM: the server prints [stats] (dual: after draining the
        applier and reading the chip's fingerprint) and exits."""
        self.proc.terminate()
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        self.kill()
        self._drain.join(timeout=10)
        return rc

    def kill(self) -> None:
        kill_process_group(self.proc)
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def live_stats(host: str, port: int, timeout: float = 10.0) -> dict:
    """The running replica's [stats] snapshot over the wire (a one-shot
    request_stats frame; answered from the event loop in any status)."""
    from tigerbeetle_tpu.vsr.header import HEADER_SIZE, Command, Header

    req = Header(command=int(Command.request_stats), client=0xBE7C4)
    req.set_checksum_body(b"")
    req.set_checksum()
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(req.to_bytes())
        buf = b""
        while True:
            if len(buf) >= HEADER_SIZE:
                header = Header.from_bytes(buf[:HEADER_SIZE])
                if not HEADER_SIZE <= header.size <= (1 << 21):
                    raise RuntimeError(f"bad stats frame size {header.size}")
                if len(buf) >= header.size:
                    frame, buf = buf[: header.size], buf[header.size:]
                    if header.command == int(Command.stats):
                        return json.loads(frame[HEADER_SIZE:].decode())
                    continue
            chunk = s.recv(1 << 16)
            if not chunk:
                raise RuntimeError("server closed without a stats reply")
            buf += chunk
