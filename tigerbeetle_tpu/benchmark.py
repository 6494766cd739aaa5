"""End-to-end test drivers: the BASELINE protocol through the FULL
system.

The reference measures its headline number by formatting a data file,
starting a real replica process, and driving create_transfers through a
client over the wire at batch=8190 (reference: scripts/benchmark.sh:34-78,
src/benchmark.zig:23-73: 10k accounts, 10M transfers, batch latency
percentiles printed at the end). This module is that harness for the TPU
build: a real `tigerbeetle_tpu start` server process (WAL on, consensus
path, TCP), driven by native session clients.

Unlike the reference's single sequential client, several clients each keep
one request in flight (the replica's commit window overlaps their journal
writes and device commits — reference: src/vsr/replica.zig:52-70); pass
clients=1 for the strictly sequential protocol.

A test harness, not the yardstick: the tests drive it at tiny sizes on
the CPU backend (`run_e2e`, `run_ingress_sessions`, `run_frontier`), and
chaos, prodday, federation and chip_smoke.py borrow its spawn helpers.
What the chip is judged by is `benchmarks/run.py`.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from tigerbeetle_tpu.types import ACCOUNT_DTYPE, TRANSFER_DTYPE, Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 8190  # (1 MiB - 128 B) / 128 B


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def kill_process_group(proc) -> None:
    """Last-resort sweep of a server's WHOLE process group (the server is
    spawned with start_new_session=True so pgid == its pid). Idempotent;
    safe after a normal wait()."""
    import signal

    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass


# A cold `start --backend dual` at the CLI's default geometry compiles about
# ten 8192-batch programs before it prints `listening` (one to two minutes
# on an empty compile cache); the deadline covers that several times over.
BOOT_DEADLINE_S = 900.0


def wait_listening(proc, what: str, log=None,
                   deadline_s: float = BOOT_DEADLINE_S) -> list[str]:
    """Read a spawned server's output up to its `listening` line and
    return the lines read (the `[device]` line is among them). A server
    that dies first, or is still booting at the deadline (then killed),
    raises with its exit code and its last output in the message — the
    child's own words are the diagnosis."""
    from collections import deque

    tail: deque = deque(maxlen=40)
    expired = threading.Event()

    def _expire() -> None:
        expired.set()
        kill_process_group(proc)  # unblocks the readline below with EOF

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
                why = (
                    f"did not reach `listening` within {deadline_s:.0f}s"
                    if expired.is_set() else "died before `listening`"
                )
                raise RuntimeError(
                    f"{what} server {why} (exit code {rc}); its last "
                    "output:\n" + "".join(tail)
                )
            tail.append(line)
            if "listening" in line:
                return list(tail)
            if log is not None:
                log(line.rstrip())
    finally:
        timer.cancel()


def require_one_process_per_chip(what: str, backend: str, n_servers: int,
                                 jax_platform: str | None) -> None:
    """A chip belongs to ONE process at a time. A launcher that starts
    several device-backed servers at once (a 3-replica cluster, two
    federation regions) cannot give each of them the chip: the second
    would fail, or hang waiting for a chip its sibling holds. Such a
    launcher must pin the servers to another platform by name, or fail
    here, early, instead."""
    if backend == "native" or n_servers <= 1:
        return
    # the servers' platform, by cli.asked_platforms' rule (not imported:
    # this module stays out of the CLI's import closure): the launcher's
    # pin, else what they inherit; the first name is the one that serves
    asked = (
        jax_platform
        or os.environ.get("TB_JAX_PLATFORM")
        or os.environ.get("JAX_PLATFORMS")
        or ""
    ).lower().split(",")[0].strip()
    if asked in ("", "tpu"):
        raise RuntimeError(
            f"{what}: {n_servers} `--backend {backend}` servers at once "
            "would all claim the chip, and a chip belongs to one process. "
            "Run them with jax_platform='cpu' (TB_JAX_PLATFORM=cpu) or "
            "with --backend native."
        )


def _accounts_body(start_id: int, count: int) -> bytes:
    arr = np.zeros(count, dtype=ACCOUNT_DTYPE)
    arr["id_lo"] = np.arange(start_id, start_id + count, dtype=np.uint64)
    arr["ledger"] = 1
    arr["code"] = 1
    return arr.tobytes()


def _transfers_body(rng, start_id: int, count: int, n_accounts: int,
                    flags: int = 0) -> bytes:
    arr = np.zeros(count, dtype=TRANSFER_DTYPE)
    # id_order=reversed (reference: src/benchmark.zig:66-73 default)
    arr["id_lo"] = np.arange(
        start_id + count - 1, start_id - 1, -1, dtype=np.uint64
    )
    dr = rng.integers(1, n_accounts + 1, size=count, dtype=np.uint64)
    off = rng.integers(1, n_accounts, size=count, dtype=np.uint64)
    arr["debit_account_id_lo"] = dr
    arr["credit_account_id_lo"] = (dr - 1 + off) % n_accounts + 1
    arr["amount_lo"] = 1
    arr["ledger"] = 1
    arr["code"] = 1
    arr["flags"] = flags
    return arr.tobytes()


def _post_body(pend_body: bytes, start_id: int) -> bytes:
    """Full-amount posts of every pending transfer in `pend_body`
    (two-phase second leg; reference: src/state_machine.zig:907-1014)."""
    pend = np.frombuffer(pend_body, dtype=TRANSFER_DTYPE)
    arr = np.zeros(len(pend), dtype=TRANSFER_DTYPE)
    arr["id_lo"] = np.arange(start_id, start_id + len(pend), dtype=np.uint64)
    arr["pending_id_lo"] = pend["id_lo"]
    arr["pending_id_hi"] = pend["id_hi"]
    arr["flags"] = 4  # post_pending_transfer
    return arr.tobytes()


class _BenchClient:
    """One session: its own TCP connection + vsr Client, one request in
    flight, per-batch latency recorded. Retries belong to the client
    RUNTIME (timeout/backoff state machine, vsr/client.py): the driver
    maps wall time onto its ticks and otherwise only pumps."""

    def __init__(self, client_id: int, port: int):
        from tigerbeetle_tpu.io.message_bus import TCPMessageBus
        from tigerbeetle_tpu.vsr.client import Client, WallTicker

        self.bus = TCPMessageBus([("127.0.0.1", port)], client_id)
        self.client = Client(client_id, self.bus, replica_count=1)
        # 0.1s ticks x 30-tick base = first retry ~3s, exponential after
        self.ticker = WallTicker(self.client, tick_s=0.1)
        self.sent_at = 0.0
        self.latencies_ms: list[float] = []
        self.replies: list[bytes] = []

    def pump(self) -> None:
        self.bus.pump(timeout=0.0)

    def wait_reply(self, deadline_s: float = 120.0) -> tuple:
        t0 = time.monotonic()
        while not self.client.done:
            self.pump()
            now = time.monotonic()
            if now - t0 > deadline_s:
                raise TimeoutError("benchmark client: no reply")
            self.ticker.advance(now)  # the runtime owns retransmits
            if not self.client.done:
                time.sleep(0.0001)
        return self.client.take_reply()

    def register(self) -> None:
        self.client.register()
        self.wait_reply()


def run_e2e(
    n_accounts: int = 10_000,
    n_transfers: int = 1_000_000,
    batch: int = BATCH,
    clients: int = 16,
    warmup_batches: int = 2,
    jax_platform: str | None = None,
    tmpdir: str | None = None,
    backend: str = "native",
    workload: str = "simple",
    driver: str = "python",
    log=None,
) -> dict:
    """Format, start a real replica, drive the protocol, return metrics.

    The server process owns the accelerator; this process stays host-only
    (numpy + sockets) so both can run on a machine with one TPU chip."""
    log = log or (lambda *_: None)
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="tb_bench_")
        tmpdir = tmp.name
    path = os.path.join(tmpdir, "bench.tigerbeetle")
    port = free_port()

    slots_log2 = 14
    warm_est = warmup_batches + 16 + 4 + 2 + 1  # singles + group rounds
    while n_transfers + warm_est * batch > (1 << slots_log2) // 2:
        slots_log2 += 1
    acct_log2 = max(14, (n_accounts * 2 + 2).bit_length())

    # prepend (not replace) PYTHONPATH: the TPU runtime may be provided by
    # a site dir already on it
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    if jax_platform:
        env["TB_JAX_PLATFORM"] = jax_platform
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format",
         "--cluster", "0", "--replica", "0", "--replica-count", "1", path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr
    # Own process group (start_new_session): teardown kills the whole group
    # so a wedged server (or anything it forked) cannot outlive the bench
    # and skew later timings. The server also carries a parent-death
    # watchdog (cli._install_parent_death_watchdog) for the paths where
    # this harness itself is SIGKILLed.
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{port}",
         "--account-slots-log2", str(acct_log2),
         "--transfer-slots-log2", str(slots_log2),
         "--backend", backend, path],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        wait_listening(proc, "bench", log)
        log(f"server up on :{port} (slots 2^{slots_log2})")

        # Keep draining server output: an unread pipe fills and BLOCKS the
        # server's next print (debug mode would wedge the whole benchmark).
        server_stats: dict = {}

        def _drain_stdout():
            import json as _json

            for out in proc.stdout:
                line = out.rstrip()
                if line.startswith("[stats] "):
                    try:
                        server_stats.update(_json.loads(line[8:]))
                    except ValueError:
                        pass
                log("[server]", line)

        drain_thread = threading.Thread(target=_drain_stdout, daemon=True)
        drain_thread.start()
        if driver == "async":
            result = _drive_async(
                port, n_accounts, n_transfers, batch, clients,
                warmup_batches, log, workload=workload,
            )
        else:
            result = _drive(
                proc, port, n_accounts, n_transfers, batch, clients,
                warmup_batches, log, workload=workload,
            )
        # SIGTERM makes the server emit its [stats] line (group-commit hit
        # rate etc.); after exit the pipe hits EOF, so joining the drain
        # thread is deterministic (no sleep race). Dual mode drains the
        # device shadow and compiles+runs the fingerprint kernels at
        # shutdown — off the clock, but the wait must cover it.
        proc.terminate()
        try:
            # dual: must outlast DualLedger.finalize's own drain
            # timeout (600s) or a slow-but-legal verification is killed
            # mid-flight and the [stats] line is lost
            proc.wait(timeout=650 if backend == "dual" else 10)
        except subprocess.TimeoutExpired:
            pass
        drain_thread.join(timeout=5)
        if server_stats:
            result["server_stats"] = server_stats
            g = server_stats.get("group", {})
            total = g.get("fused_ops", 0) + g.get("solo_ops", 0)
            if total:
                result["group_commit_hit_rate"] = round(
                    g.get("fused_ops", 0) / total, 4
                )
                if g.get("fused_groups"):
                    result["group_fuse_width"] = round(
                        g["fused_ops"] / g["fused_groups"], 2
                    )
                # fuse-window diagnostics: holds that expired short vs
                # holds at all, and the window the run ended at (autotune
                # moves it) — a low hit rate is attributable, not a mystery
                result["group_fuse_holds"] = g.get("fuse_holds", 0)
                result["group_fuse_expired"] = g.get("fuse_expired", 0)
            fuse = server_stats.get("fuse", {})
            if fuse:
                result["fuse_window_us"] = fuse.get("window_us")
                result["fuse_autotune"] = fuse.get("autotune")
            loop = server_stats.get("loop", {})
            if loop:
                result["loop_us_per_batch"] = loop.get("us_per_batch")
            if "metrics" in server_stats:
                # the server's full registry snapshot (counters + timing
                # histogram percentiles) — sourced from the same store as
                # the loop/group numbers above
                result["server_metrics"] = server_stats["metrics"]
            if "device_shadow" in server_stats:
                result["device_shadow"] = server_stats["device_shadow"]
                sh = server_stats["device_shadow"].get("shadow") or {}
                if sh.get("upload_overlap") is not None:
                    result["shadow_upload_overlap"] = sh["upload_overlap"]
                # the end-of-run hash-log ring check + the applier's
                # lag/overlap gauges
                hl = server_stats["device_shadow"].get("hash_log")
                if hl is not None:
                    result["device_hash_log_ok"] = hl.get("ok")
                gauges = server_stats.get("metrics", {}).get("gauges", {})
                if "shadow.device_lag_ops" in gauges:
                    result["device_lag_ops"] = gauges[
                        "shadow.device_lag_ops"
                    ]
                if "shadow.device_apply_overlap" in gauges:
                    result["device_apply_overlap"] = gauges[
                        "shadow.device_apply_overlap"
                    ]
        return result
    finally:
        if proc.poll() is None:
            proc.terminate()  # SIGTERM first: lets a profiling run dump
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        kill_process_group(proc)
        if own_tmp:
            tmp.cleanup()


def _drive_async(port, n_accounts, n_transfers, batch, clients,
                 warmup_batches, log, workload: str = "simple") -> dict:
    """Drive the protocol through the ASYNC packet ABI (native/tb_client.cc
    tb_client_async_*): ONE client process, one AsyncNativeClient whose
    session pool keeps `clients` requests in flight — the reference's
    packet/completion model replacing the Python per-session loop
    (reference: src/clients/c/tb_client/packet.zig)."""
    import threading as _threading

    from tigerbeetle_tpu.client_ffi import AsyncNativeClient, NativeClient
    from tigerbeetle_tpu.state_machine import decode_results

    rng = np.random.default_rng(42)
    addresses = f"127.0.0.1:{port}"
    ctl = NativeClient(addresses)  # blocking control-plane session

    t0 = time.monotonic()
    next_id = 1
    while next_id <= n_accounts:
        n = min(batch, n_accounts - next_id + 1)
        assert ctl._request(
            Operation.create_accounts, _accounts_body(next_id, n)
        ) == b"", "account create failed"
        next_id += n
    log(f"{n_accounts} accounts in {time.monotonic() - t0:.1f}s")

    ac = AsyncNativeClient(addresses, sessions=clients)
    log(f"async client up: {clients} pooled sessions")
    try:
        # -- build bodies (workload gen off the clock) --
        n_batches = (n_transfers + batch - 1) // batch
        nid = 1_000_000
        if workload == "two_phase":
            pends, posts = [], []
            for _ in range((n_batches + 1) // 2):
                pend = _transfers_body(rng, nid, batch, n_accounts, flags=2)
                nid += batch
                pends.append(pend)
                posts.append(_post_body(pend, nid))
                nid += batch
            waves = [pends, posts]
            posted_batches = len(posts)
        else:
            bodies = []
            for _ in range(n_batches):
                bodies.append(_transfers_body(rng, nid, batch, n_accounts))
                nid += batch
            waves = [bodies]
            posted_batches = len(bodies)

        # -- warmup (kernel compiles / cache warm): singles, then a full
        # concurrent burst so fused group paths compile before the clock --
        op = Operation.create_transfers
        warm = 0
        for _ in range(warmup_batches):
            pend = _transfers_body(rng, nid, batch, n_accounts, flags=2)
            nid += batch
            assert ac.submit(op, pend).result(timeout=600) == b""
            post = _post_body(pend, nid)
            nid += batch
            assert ac.submit(op, post).result(timeout=600) == b""
            warm += 2
        burst = [
            _transfers_body(rng, nid + i * batch, batch, n_accounts)
            for i in range(clients)
        ]
        nid += clients * batch
        for f in [ac.submit(op, b) for b in burst]:
            assert f.result(timeout=600) == b""
        warm += clients
        # warmup posted amounts: each pend+post pair posts ONE batch's
        # amounts (the pend batch itself only moves pending), plus the
        # simple burst batches
        posted_batches += warmup_batches + clients
        log(f"warmup done ({warm} batches); timing "
            f"{sum(len(w) for w in waves)} batches")

        # -- timed: submit with a bounded window (the pool keeps `clients`
        # requests on the wire; the window keeps its queue fed without
        # turning latency into pure queue depth) --
        sem = _threading.Semaphore(clients * 2)
        lat_ms: list[float] = []
        lat_lock = _threading.Lock()
        failures = 0
        t_start = time.monotonic()
        for wave in waves:
            futs = []
            for body in wave:
                sem.acquire()
                t_sub = time.monotonic()

                def _done(_f, t=t_sub):
                    with lat_lock:
                        lat_ms.append((time.monotonic() - t) * 1e3)
                    sem.release()

                fut = ac.submit(op, body)
                fut.add_done_callback(_done)
                futs.append(fut)
            for f in futs:  # wave barrier (two_phase: posts follow pends)
                failures += len(decode_results(f.result(timeout=600), op))
        wall = time.monotonic() - t_start
        n_timed = sum(len(w) for w in waves) * batch
        assert failures == 0, f"{failures} transfers failed"
    finally:
        ac.close()

    # -- conservation over the wire (blocking control session) --
    total = posted_batches * batch
    dpo = cpo = found = 0
    ids = list(range(1, n_accounts + 1))
    for i in range(0, len(ids), 8000):
        accounts = ctl.lookup_accounts(ids[i : i + 8000])
        found += len(accounts)
        dpo += sum(a.debits_posted for a in accounts)
        cpo += sum(a.credits_posted for a in accounts)
    assert found == n_accounts, (found, n_accounts)
    assert dpo == cpo == total, (dpo, cpo, total)
    log(f"conservation verified: {total} transfers, dpo==cpo=={total}")
    ctl.close()

    lat = np.percentile(lat_ms if lat_ms else [float("nan")],
                        [0, 25, 50, 75, 100])
    return {
        "durable_tps": round(n_timed / wall, 1) if wall else 0.0,
        "n_transfers": n_timed,
        "wall_s": round(wall, 2),
        "clients": clients,
        "driver": "async_abi",
        "latency_ms_p00_p25_p50_p75_p100": [round(float(x), 2) for x in lat],
    }


def _drive(proc, port, n_accounts, n_transfers, batch, clients,
           warmup_batches, log, workload: str = "simple") -> dict:
    from tigerbeetle_tpu.state_machine import decode_results

    rng = np.random.default_rng(42)
    sessions = [_BenchClient(0xB0000 + i, port) for i in range(clients)]
    for s in sessions:
        s.register()
    log(f"{clients} session(s) registered")

    # -- accounts (absorbs the create_accounts compile) --
    t0 = time.monotonic()
    next_id = 1
    while next_id <= n_accounts:
        n = min(batch, n_accounts - next_id + 1)
        sessions[0].client.request(
            Operation.create_accounts, _accounts_body(next_id, n)
        )
        _h, body = sessions[0].wait_reply()
        assert body == b"", "account create failed"
        next_id += n
    log(f"{n_accounts} accounts in {time.monotonic() - t0:.1f}s")

    # -- warmup rounds: singles compile the per-batch kernel; k
    # simultaneous batches compile each fused group kernel (k=8/4/2) —
    # lazily compiling those mid-run would stall the timed phase for
    # tens of seconds each (device backend; the native engine just warms
    # its caches) --
    from tigerbeetle_tpu.models.ledger import DeviceLedger

    group_rounds = sorted(
        {min(g, clients) for g in DeviceLedger.GROUP_KS if clients >= 2},
        reverse=True,
    )
    group_rounds = [k for k in group_rounds if k >= 2]
    rounds = [1] * warmup_batches + group_rounds
    total_warm = sum(rounds)

    # -- build all bodies up front (workload gen off the clock), split
    # into PER-SESSION queues. two_phase: each session alternates a
    # pending batch with the full-amount posts of ITS OWN previous batch
    # (the session's one-in-flight protocol orders post after pend) --
    id_stride = (n_transfers // clients + 3 * batch) * 2
    per_session: list[list[bytes]] = [[] for _ in sessions]
    n_total_batches = (n_transfers + batch - 1) // batch + total_warm
    posted_batches = 0  # batches that land posted amounts (conservation)
    for i, _s in enumerate(sessions):
        nid = 1_000_000 + i * id_stride
        share = n_total_batches // clients + (
            1 if i < n_total_batches % clients else 0
        )
        q = per_session[i]
        if workload == "two_phase":
            while len(q) < share:
                pend = _transfers_body(rng, nid, batch, n_accounts, flags=2)
                nid += batch
                q.append(pend)
                if len(q) < share:
                    q.append(_post_body(pend, nid))
                    nid += batch
                    posted_batches += 1
        else:
            for _ in range(share):
                q.append(_transfers_body(rng, nid, batch, n_accounts))
                nid += batch
                posted_batches += 1

    # warmup: pull evenly from the per-session queues (two_phase pairs
    # stay in order within a session)
    warm_done = 0
    for k in rounds:
        active = [
            (s, q) for s, q in zip(sessions, per_session) if q
        ][: max(k, 1)]
        for s, q in active:
            s.client.request(Operation.create_transfers, q.pop(0))
        for s, _q in active:
            _h, body = s.wait_reply(deadline_s=600.0)  # compiles are slow
            assert body == b"", decode_results(
                body, Operation.create_transfers
            )[:3]
            warm_done += 1
    n_work = sum(len(q) for q in per_session)
    log(f"warmup done ({warm_done} batches, rounds {rounds}); "
        f"timing {n_work} batches")

    # -- timed phase: each session keeps one batch in flight --
    import selectors as _selectors

    # One wakeup selector over every session's socket: the idle path blocks
    # until ANY reply bytes arrive instead of sleep-polling (time.sleep's
    # ~0.5 ms real granularity dominated the driver and starved the server).
    wake = _selectors.DefaultSelector()
    for s in sessions:
        for conn in s.bus.conns.values():
            try:
                wake.register(conn.sock, _selectors.EVENT_READ)
            except (KeyError, ValueError):
                pass
    lat_ms: list[float] = []
    failures = 0
    inflight: dict[int, float] = {}
    t_start = time.monotonic()
    for s, q in zip(sessions, per_session):
        if q:
            s.client.request(Operation.create_transfers, q.pop(0))
            inflight[s.client.client_id] = time.monotonic()
    deadline = t_start + max(600.0, n_transfers / 1000)
    done_batches = 0
    while inflight:
        progressed = False
        for s, q in zip(sessions, per_session):
            cid = s.client.client_id
            if cid not in inflight:
                continue
            s.pump()
            if s.client.reply is None:
                # a loss under backpressure retransmits via the client
                # runtime's own timeout ladder
                s.ticker.advance(time.monotonic())
                continue
            _h, body = s.client.take_reply()
            lat_ms.append(
                (time.monotonic() - inflight.pop(cid)) * 1e3
            )
            failures += len(decode_results(body, Operation.create_transfers))
            done_batches += 1
            progressed = True
            if q:
                s.client.request(Operation.create_transfers, q.pop(0))
                inflight[cid] = time.monotonic()
        if not progressed:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"benchmark stalled at batch {done_batches}/{n_work}"
                )
            # reconcile registrations: a dropped+redialed connection has a
            # NEW socket that must wake the idle path too
            regged = {k.fileobj for k in wake.get_map().values()}
            current = {
                c.sock for s in sessions for c in s.bus.conns.values()
            }
            for sock in current - regged:
                try:
                    wake.register(sock, _selectors.EVENT_READ)
                except (KeyError, ValueError, OSError):
                    pass
            for sock in regged - current:
                try:
                    wake.unregister(sock)
                except (KeyError, ValueError, OSError):
                    pass
            wake.select(timeout=0.002)  # woken by the first reply bytes
    wake.close()
    wall = time.monotonic() - t_start
    n_timed = done_batches * batch
    assert failures == 0, f"{failures} transfers failed"
    # conservation total: every POSTED batch moves amount=1 per event
    # (simple batches post directly; two_phase pend batches only move
    # pending amounts, released when their post batch lands)
    total = posted_batches * batch
    return _verify_and_report(
        sessions[0], n_accounts, total, wall, n_timed, lat_ms, clients, log
    )


# ---------------------------------------------------------------------
# ingress: 10k multiplexed sessions through the gateway
# ---------------------------------------------------------------------


class _MuxSession:
    """One logical session multiplexed over a shared (demux) bus
    connection, driven by the client RUNTIME: busy sheds back off on the
    decorrelated ladder, losses retransmit on the timeout ladder — the
    driver only advances the ticker and harvests replies."""

    __slots__ = ("client", "ticker", "sent_at", "events")

    def __init__(self, client_id: int, bus):
        from tigerbeetle_tpu.vsr.client import Client, WallTicker

        # 5ms ticks: busy retries land at 10-320ms (decorrelated), the
        # loss ladder starts at 200ms (40 ticks) and caps at 4x
        self.client = Client(
            client_id, bus, replica_count=1,
            request_timeout_ticks=40, max_backoff_exponent=2,
            ping_ticks=0,  # 10k idle sessions must not ping-storm
        )
        self.ticker = WallTicker(self.client, tick_s=0.005)
        self.sent_at = 0.0
        self.events = 0  # events this session has in flight

    def poll(self, now: float) -> bool:
        """Drive one in-flight request: True once its reply landed.
        Retry cadence lives in the Client's runtime config now
        (request_timeout_ticks), not here."""
        c = self.client
        if c.done:
            return True
        if c.in_flight is None:
            return False
        self.ticker.advance(now)
        return c.done


def run_ingress_sessions(
    n_sessions: int = 10_000,
    conns: int = 16,
    n_accounts: int = 512,
    baseline_sessions: int = 10,
    driver_batches: int = 30,
    batch: int = 512,
    bg_window: int = 32,
    sat_window: int = 256,
    sat_batches: int = 120,
    reg_window: int = 512,
    reply_slots: int = 64,
    jax_platform: str | None = "cpu",
    tmpdir: str | None = None,
    log=None,
) -> dict:
    """The ingress_sessions bench segment: `n_sessions` LOGICAL sessions
    multiplexed over `conns` TCP connections against one gateway-fronted
    replica (native backend — ingress is a host-path measurement).

    Phases:
    A. baseline: `baseline_sessions` sessions drive `driver_batches`
       batches each; per-batch latency p99 is the 10-session reference.
    B. live: ALL `n_sessions` sessions register (the connect storm —
       every register is a consensus op through admission), then the
       same driver workload runs while a rotating background window
       keeps distant sessions active. p99 here vs A is the acceptance
       ratio (<= 2x with 10k live sessions).
    C. saturation: `sat_window` sessions keep full batches in flight
       concurrently — far past the pipeline cap, so the regulator sheds
       (typed busy replies, client backoff-retry). Event throughput here
       vs B shows shedding protects the pipeline instead of collapsing
       it.

    Conservation is verified over the wire at the end (every acked
    transfer moved amount=1)."""
    import json as _json
    from collections import deque

    from tigerbeetle_tpu.io.message_bus import TCPMessageBus
    from tigerbeetle_tpu.types import Operation

    log = log or (lambda *_: None)
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="tb_ingress_")
        tmpdir = tmp.name
    path = os.path.join(tmpdir, "ingress.tigerbeetle")
    port = free_port()
    clients_max = n_sessions + 64
    slots_log2 = 14
    total_est = (
        (baseline_sessions + bg_window) * driver_batches * batch * 4
        + sat_batches * batch + n_sessions
    )
    while total_est > (1 << slots_log2) // 2:
        slots_log2 += 1

    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    if jax_platform:
        env["TB_JAX_PLATFORM"] = jax_platform
    session_args = (
        "--clients-max", str(clients_max),
        "--client-reply-slots", str(reply_slots),
    )
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format",
         "--cluster", "0", "--replica", "0", "--replica-count", "1",
         *session_args, path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{port}",
         "--account-slots-log2", str(max(14, (n_accounts * 2 + 2).bit_length())),
         "--transfer-slots-log2", str(slots_log2),
         "--backend", "native", "--ingress", *session_args, path],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    buses: list[TCPMessageBus] = []
    try:
        wait_listening(proc, "ingress", log)
        log(f"server up on :{port} ({n_sessions} sessions over {conns} conns)")
        server_stats: dict = {}

        def _drain_stdout():
            for out in proc.stdout:
                line = out.rstrip()
                if line.startswith("[stats] "):
                    try:
                        server_stats.update(_json.loads(line[8:]))
                    except ValueError:
                        pass
                log("[server]", line)

        drain_thread = threading.Thread(target=_drain_stdout, daemon=True)
        drain_thread.start()

        # demux buses: one TCP connection each, N sessions' Clients per
        # bus dispatching by the reply frame's client id
        buses = [
            TCPMessageBus(
                [("127.0.0.1", port)], 0xC0DE0000 + b, demux=True
            )
            for b in range(conns)
        ]

        def pump_all() -> None:
            for b in buses:
                b.pump(timeout=0.0)

        rng = np.random.default_rng(7)
        next_id = [1_000_000]

        def transfer_body(count: int) -> bytes:
            body = _transfers_body(rng, next_id[0], count, n_accounts)
            next_id[0] += count
            return body

        def register_all(sessions, deadline_s: float) -> float:
            """Bounded-window registration storm; returns wall seconds."""
            t0 = time.monotonic()
            pending = deque(sessions)
            active: list[_MuxSession] = []
            while pending or active:
                now = time.monotonic()
                if now - t0 > deadline_s:
                    raise TimeoutError(
                        f"registration stalled: {len(pending)} pending "
                        f"{len(active)} active"
                    )
                while pending and len(active) < reg_window:
                    s = pending.popleft()
                    s.client.register()
                    s.sent_at = now
                    active.append(s)
                pump_all()
                still = []
                for s in active:
                    if s.poll(now):
                        s.client.take_reply()
                        assert s.client.session != 0
                    else:
                        still.append(s)
                active = still
            return time.monotonic() - t0

        def run_phase(drivers, bodies, deadline_s: float,
                      background=None, lat_ms=None) -> tuple[int, float]:
            """Each driver keeps one body from the shared deque in
            flight (busy -> backoff resend). `background` (sessions,
            window): a rotating window of single-transfer requests over
            the whole live set. Returns (events acked, wall seconds)."""
            t0 = time.monotonic()
            events = 0

            def take_ok_reply(s, prefix):
                _h, body = s.client.take_reply()
                if body != b"":
                    from tigerbeetle_tpu.state_machine import decode_results

                    raise AssertionError(
                        f"{prefix}: "
                        f"{decode_results(body, Operation.create_transfers)[:4]} "
                        f"(reply client={_h.client:#x} req={_h.request} "
                        f"operation={_h.operation} op={_h.op} "
                        f"events={s.events})"
                    )

            inflight: dict[int, _MuxSession] = {}
            bg_inflight: list[_MuxSession] = []
            bg_iter = None
            if background is not None:
                bg_sessions, bg_cap = background

                def bg_cycle():
                    while True:
                        yield from bg_sessions

                bg_iter = bg_cycle()
            idle = [s for s in drivers]
            while bodies or inflight or bg_inflight:
                now = time.monotonic()
                if now - t0 > deadline_s:
                    raise TimeoutError(
                        f"ingress phase stalled: {len(bodies)} bodies "
                        f"{len(inflight)} inflight"
                    )
                while bodies and idle:
                    s = idle.pop()
                    body = bodies.popleft()
                    s.events = len(body) // 128
                    s.client.request(Operation.create_transfers, body)
                    s.sent_at = now
                    inflight[s.client.client_id] = s
                if bg_iter is not None and bodies:
                    scanned = 0  # bounded: never spin hunting an idle session
                    while len(bg_inflight) < bg_cap and scanned < 4 * bg_cap:
                        s = next(bg_iter)
                        scanned += 1
                        if (
                            s.client.in_flight is not None
                            or s.client.session == 0
                        ):
                            continue
                        s.events = 1
                        s.client.request(
                            Operation.create_transfers, transfer_body(1)
                        )
                        s.sent_at = now
                        bg_inflight.append(s)
                pump_all()
                for cid in list(inflight):
                    s = inflight[cid]
                    if s.poll(now):
                        take_ok_reply(s, "transfer failed")
                        events += s.events
                        if lat_ms is not None:
                            lat_ms.append((time.monotonic() - s.sent_at) * 1e3)
                        del inflight[cid]
                        idle.append(s)
                still_bg = []
                for s in bg_inflight:
                    if s.poll(now):
                        take_ok_reply(s, "bg transfer failed")
                        events += s.events
                    else:
                        still_bg.append(s)
                bg_inflight = still_bg
            return events, time.monotonic() - t0

        # -- build sessions: drivers first, then the long tail --
        all_sessions = [
            _MuxSession(0xB0000000 + i, buses[i % conns])
            for i in range(n_sessions)
        ]
        drivers = all_sessions[:baseline_sessions]

        # -- phase A: 10-session baseline --
        reg_s0 = register_all(drivers, deadline_s=120.0)
        s0 = drivers[0]
        next_acct = 1
        while next_acct <= n_accounts:
            k = min(BATCH, n_accounts - next_acct + 1)
            s0.client.request(
                Operation.create_accounts, _accounts_body(next_acct, k)
            )
            s0.sent_at = time.monotonic()
            t_acct = time.monotonic()
            while not s0.poll(time.monotonic()):
                pump_all()
                if time.monotonic() - t_acct > 120:
                    raise TimeoutError("account create stalled")
            _h, body = s0.client.take_reply()
            assert body == b"", "account create failed"
            next_acct += k
        warm = deque(transfer_body(batch) for _ in range(4))
        run_phase(drivers, warm, deadline_s=300.0)  # warm engine caches
        lat_a: list[float] = []
        bodies = deque(
            transfer_body(batch)
            for _ in range(baseline_sessions * driver_batches)
        )
        ev_a, wall_a = run_phase(
            drivers, bodies, deadline_s=600.0, lat_ms=lat_a
        )
        p99_a = float(np.percentile(lat_a, 99))
        log(f"baseline: {ev_a} events in {wall_a:.2f}s p99={p99_a:.2f}ms")

        # -- phase B: the full session population goes live --
        reg_s = register_all(
            all_sessions[baseline_sessions:],
            deadline_s=max(300.0, n_sessions / 20),
        )
        log(f"{n_sessions} sessions registered in {reg_s0 + reg_s:.1f}s")
        lat_b: list[float] = []
        bodies = deque(
            transfer_body(batch)
            for _ in range(baseline_sessions * driver_batches)
        )
        ev_b, wall_b = run_phase(
            drivers, bodies, deadline_s=600.0,
            background=(all_sessions[baseline_sessions:], bg_window),
            lat_ms=lat_b,
        )
        p99_b = float(np.percentile(lat_b, 99))
        tps_b = ev_b / wall_b if wall_b else 0.0
        log(f"live: {ev_b} events in {wall_b:.2f}s p99={p99_b:.2f}ms")

        # -- phase C: deliberate saturation (shed expected) --
        busy_before = sum(s.client.busy_replies for s in all_sessions)
        sat = all_sessions[:sat_window]
        bodies = deque(transfer_body(batch) for _ in range(sat_batches))
        ev_c, wall_c = run_phase(sat, bodies, deadline_s=600.0)
        tps_c = ev_c / wall_c if wall_c else 0.0
        busy_replies = (
            sum(s.client.busy_replies for s in all_sessions) - busy_before
        )
        log(f"saturated: {ev_c} events in {wall_c:.2f}s "
            f"busy_replies={busy_replies}")

        # -- conservation over the wire --
        from tigerbeetle_tpu.state_machine import decode_accounts, encode_ids

        total = ev_a + ev_b + ev_c + batch * 4  # + warmup
        s0 = drivers[0]
        dpo = cpo = found = 0
        for i in range(0, n_accounts, 8000):
            ids = list(range(1 + i, 1 + min(i + 8000, n_accounts)))
            s0.client.request(Operation.lookup_accounts, encode_ids(ids))
            s0.sent_at = time.monotonic()
            t0 = time.monotonic()
            while not s0.poll(time.monotonic()):
                pump_all()
                if time.monotonic() - t0 > 120:
                    raise TimeoutError("conservation lookup stalled")
            _h, body = s0.client.take_reply()
            arr = decode_accounts(body)
            found += len(arr)
            dpo += int(arr["debits_posted_lo"].sum())
            cpo += int(arr["credits_posted_lo"].sum())
        assert found == n_accounts, (found, n_accounts)
        assert dpo == cpo == total, (dpo, cpo, total)
        log(f"conservation verified: {total} transfers")

        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        drain_thread.join(timeout=5)
        out = {
            "sessions": n_sessions,
            "conns": conns,
            "register_s": round(reg_s0 + reg_s, 2),
            "baseline_sessions": baseline_sessions,
            "p99_baseline_ms": round(p99_a, 2),
            "p99_live_ms": round(p99_b, 2),
            "p99_ratio": round(p99_b / p99_a, 3) if p99_a else None,
            "tps_live": round(tps_b, 1),
            "tps_saturated": round(tps_c, 1),
            "tps_saturated_ratio": (
                round(tps_c / tps_b, 3) if tps_b else None
            ),
            "busy_replies": busy_replies,
            "n_transfers": total,
        }
        m = server_stats.get("metrics", {})
        if m:
            c = m.get("counters", {})
            out["ingress_shed"] = c.get("ingress.shed", 0)
            out["ingress_admitted"] = c.get("ingress.admitted", 0)
            out["ingress_retransmits"] = c.get("ingress.retransmits", 0)
            out["ingress_sessions_gauge"] = m.get("gauges", {}).get(
                "ingress.sessions"
            )
        return out
    finally:
        for b in buses:
            try:
                b.sel.close()
            except Exception:
                pass
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        kill_process_group(proc)
        if own_tmp:
            tmp.cleanup()


# ---------------------------------------------------------------------
# frontier: offered-load ladder vs latency against a live server
# ---------------------------------------------------------------------


def run_frontier(
    steps=(20_000, 50_000, 100_000, 200_000),
    step_s: float = 6.0,
    batch: int = 2048,
    sessions: int = 32,
    conns: int = 4,
    n_accounts: int = 512,
    backend: str = "dual",
    sample_every: int = 1,
    warmup_batches: int = 4,
    drain_s: float = 60.0,
    jax_platform: str | None = None,
    tmpdir: str | None = None,
    log=None,
) -> dict:
    """The load/latency FRONTIER segment (ROADMAP item 4's artifact):
    step offered load across a ladder against one live gateway-fronted
    server and report, per step, offered vs achieved tps, client-side
    p50/p95/p99, the typed-shed rate, and the DOMINANT critical-path leg
    from the server's per-request latency anatomy (latency.py) — "where
    do the milliseconds go as load rises", the artifact that picks the
    first target of the latency attack.

    The driver is OPEN-LOOP: submissions are scheduled at the offered
    rate, queue when every session is busy, and each request's latency
    is measured from its SCHEDULED time — so saturation shows up as
    rising latency (no coordinated omission), and typed busy sheds ride
    the client runtime's backoff ladder like production traffic. Server-
    side numbers come from live [stats] wire snapshots taken between
    steps (inspect_live): counter deltas give the step's sheds, and
    latency.* histogram deltas give its dominant leg.

    The final snapshot's slowest-request breakdown proves the
    decomposition ACCOUNTS for the time: legs are consecutive stamp
    intervals, so sum(legs) must be within rounding of e2e
    (`breakdown_accounted_ratio`, asserted by the frontier smoke)."""
    import json as _json
    from collections import deque

    from tigerbeetle_tpu.inspect import inspect_live
    from tigerbeetle_tpu.io.message_bus import TCPMessageBus
    from tigerbeetle_tpu.latency import (
        device_leg_totals,
        dominant_leg,
        leg_totals,
    )

    log = log or (lambda *_: None)
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="tb_frontier_")
        tmpdir = tmp.name
    path = os.path.join(tmpdir, "frontier.tigerbeetle")
    port = free_port()
    total_est = int(
        sum(r * step_s for r in steps) * 1.5
        + (warmup_batches + 4) * batch + sessions * batch
    )
    slots_log2 = 15
    while total_est > (1 << slots_log2) // 2:
        slots_log2 += 1
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    if jax_platform:
        env["TB_JAX_PLATFORM"] = jax_platform
    session_args = ("--clients-max", str(sessions + 16))
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format",
         "--cluster", "0", "--replica", "0", "--replica-count", "1",
         *session_args, path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert fmt.returncode == 0, fmt.stderr
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{port}",
         "--account-slots-log2",
         str(max(14, (n_accounts * 2 + 2).bit_length())),
         "--transfer-slots-log2", str(slots_log2),
         "--backend", backend, "--ingress",
         "--latency-sample-every", str(sample_every),
         *session_args, path],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    buses: list[TCPMessageBus] = []
    try:
        wait_listening(proc, "frontier", log)
        log(f"server up on :{port} backend={backend} ladder={list(steps)}")
        server_stats: dict = {}

        def _drain_stdout():
            for out_line in proc.stdout:
                s = out_line.rstrip()
                if s.startswith("[stats] "):
                    try:
                        server_stats.update(_json.loads(s[8:]))
                    except ValueError:
                        pass
                log("[server]", s)

        drain_thread = threading.Thread(target=_drain_stdout, daemon=True)
        drain_thread.start()

        buses = [
            TCPMessageBus([("127.0.0.1", port)], 0xF0000000 + b, demux=True)
            for b in range(conns)
        ]

        def pump_all() -> None:
            for b in buses:
                b.pump(timeout=0.0)

        fleet = [
            _MuxSession(0xF1000000 + i, buses[i % conns])
            for i in range(sessions)
        ]
        # registration (bounded window, reusing the runtime's retries)
        t0 = time.monotonic()
        pending = deque(fleet)
        active: list[_MuxSession] = []
        while pending or active:
            now = time.monotonic()
            if now - t0 > 120:
                raise TimeoutError("frontier registration stalled")
            while pending and len(active) < 64:
                s = pending.popleft()
                s.client.register()
                active.append(s)
            pump_all()
            active = [s for s in active if not (
                s.poll(now) and (s.client.take_reply() or True)
            )]
        rng = np.random.default_rng(11)
        next_id = [1_000_000]

        def transfer_body(count: int) -> bytes:
            body = _transfers_body(rng, next_id[0], count, n_accounts)
            next_id[0] += count
            return body

        def drive_one(s: _MuxSession, op, body, deadline=120.0) -> bytes:
            s.client.request(op, body)
            t_req = time.monotonic()
            while not s.poll(time.monotonic()):
                pump_all()
                if time.monotonic() - t_req > deadline:
                    raise TimeoutError("frontier control request stalled")
            _h, rbody = s.client.take_reply()
            return rbody

        next_acct = 1
        while next_acct <= n_accounts:
            k = min(8190, n_accounts - next_acct + 1)
            assert drive_one(
                fleet[0], Operation.create_accounts,
                _accounts_body(next_acct, k),
            ) == b"", "account create failed"
            next_acct += k
        for _ in range(warmup_batches):  # engine/kernel warm, off the clock
            assert drive_one(
                fleet[0], Operation.create_transfers, transfer_body(batch)
            ) == b""
        log(f"{sessions} sessions + {n_accounts} accounts ready")

        def counters(snap: dict) -> dict:
            return snap.get("metrics", {}).get("counters", {})

        out_steps: list[dict] = []
        acked_total = 0
        by_id = {s.client.client_id: s for s in fleet}
        # in flight ACROSS steps: a drain-timeout leaves requests on the
        # wire, and the next step must neither double-submit on a busy
        # session (the client asserts one in-flight request) nor count
        # the stale replies into its own numbers (value None = stale).
        inflight: dict[int, float | None] = {}  # client_id -> due time
        for rate in steps:
            # stamp the ladder step as a flight-recorder phase: the
            # server's per-interval history slices by step exactly the
            # way a prodday timeline slices by phase (prodday.py
            # slice_history), so a frontier run's recorder entries
            # carry which offered rate produced them
            try:
                from tigerbeetle_tpu.inspect import send_mark

                send_mark("127.0.0.1", port, f"step:{rate}", timeout=2.0)
            except (OSError, RuntimeError, ValueError):
                pass  # observability only: a missed mark never fails a step
            snap0 = inspect_live("127.0.0.1", port)
            interval = batch / rate
            t_start = time.monotonic()
            t_end = t_start + step_s
            due = t_start
            backlog: deque[float] = deque()  # scheduled-but-unsubmitted
            idle = [s for s in fleet if s.client.client_id not in inflight]
            lat_ms: list[float] = []
            offered = acked_win = failures = 0
            while True:
                now = time.monotonic()
                if now >= t_end and not inflight and not backlog:
                    break
                if now - t_end > drain_s:
                    break  # overloaded step: stop draining, report as-is
                while due <= now and due < t_end:
                    backlog.append(due)
                    offered += batch
                    due += interval
                while backlog and idle and now < t_end + drain_s:
                    s = idle.pop()
                    due_t = backlog.popleft()
                    s.client.request(
                        Operation.create_transfers, transfer_body(batch)
                    )
                    inflight[s.client.client_id] = due_t
                if now >= t_end:
                    backlog.clear()  # never submitted: offered, not acked
                pump_all()
                for cid in list(inflight):
                    s = by_id[cid]
                    if s.poll(now):
                        _h, rbody = s.client.take_reply()
                        if rbody != b"":
                            failures += 1
                        due_t = inflight.pop(cid)
                        idle.append(s)
                        acked_total += batch
                        if due_t is None:
                            continue  # a prior step's straggler
                        # latency is recorded for EVERY request scheduled
                        # in the window, even those completing during the
                        # drain — dropping the late ones would understate
                        # p99 exactly at the knee (coordinated omission
                        # through the back door); only window THROUGHPUT
                        # is bounded to the step itself
                        lat_ms.append((now - due_t) * 1e3)
                        if now < t_end:
                            acked_win += batch
            # whatever is still on the wire belongs to no later step
            for cid in inflight:
                inflight[cid] = None
            wall = min(time.monotonic() - t_start, step_s)
            snap1 = inspect_live("127.0.0.1", port)
            c0, c1 = counters(snap0), counters(snap1)
            sheds = c1.get("ingress.shed", 0) - c0.get("ingress.shed", 0)
            admitted = (
                c1.get("ingress.admitted", 0)
                - c0.get("ingress.admitted", 0)
            )
            leg, share = dominant_leg(
                leg_totals(snap0.get("metrics", {})),
                leg_totals(snap1.get("metrics", {})),
            )
            # the commit_wait DECOMPOSITION (device anatomy): which
            # applier sub-leg dominated this step — the "why" behind a
            # commit_wait-dominated knee
            dleg, dshare = dominant_leg(
                device_leg_totals(snap0.get("metrics", {})),
                device_leg_totals(snap1.get("metrics", {})),
            )
            pct = (
                np.percentile(lat_ms, [50, 95, 99])
                if lat_ms else [float("nan")] * 3
            )
            step = {
                "offered_tps": rate,
                "achieved_tps": round(acked_win / wall, 1) if wall else 0.0,
                "offered_events": offered,
                "acked_events_in_window": acked_win,
                "p50_ms": round(float(pct[0]), 3),
                "p95_ms": round(float(pct[1]), 3),
                "p99_ms": round(float(pct[2]), 3),
                "sheds": sheds,
                "shed_rate": (
                    round(sheds / (sheds + admitted), 4)
                    if sheds + admitted else 0.0
                ),
                "dominant_leg": leg,
                "dominant_leg_share": share,
                "dominant_device_subleg": dleg,
                "dominant_device_subleg_share": dshare,
                "failures": failures,
            }
            out_steps.append(step)
            log(f"step {rate}/s: achieved {step['achieved_tps']}/s "
                f"p50={step['p50_ms']}ms p99={step['p99_ms']}ms "
                f"shed_rate={step['shed_rate']} dominant={leg}"
                + (f" device={dleg}" if dleg else ""))
            assert failures == 0, f"{failures} transfer batches failed"

        # decomposition accounting proof: the slowest sampled request's
        # legs are consecutive intervals and must sum to its e2e
        final = inspect_live("127.0.0.1", port)
        breakdown = None
        slowest = final.get("latency_slowest") or []
        if slowest:
            rec = slowest[0]
            legs_sum = sum(rec.get("legs", {}).values())
            breakdown = {
                "e2e_us": rec.get("e2e_us"),
                "legs": rec.get("legs"),
                "dominant": rec.get("dominant"),
                "sum_legs_us": round(legs_sum, 3),
                "accounted_ratio": (
                    round(legs_sum / rec["e2e_us"], 4)
                    if rec.get("e2e_us") else None
                ),
            }
        # device-granularity accounting proof: the slowest sampled APPLY
        # item's sub-legs are consecutive and must sum to its span
        # exactly (accounted_ratio 1.0 — the commit_wait decomposition)
        device_breakdown = None
        dev_slowest = final.get("device_slowest") or []
        if dev_slowest:
            drec = dev_slowest[0]
            dsum = sum(drec.get("legs", {}).values())
            device_breakdown = {
                "apply_e2e_us": drec.get("e2e_us"),
                "legs": drec.get("legs"),
                "dominant": drec.get("dominant"),
                "sum_legs_us": round(dsum, 3),
                "accounted_ratio": (
                    round(dsum / drec["e2e_us"], 4)
                    if drec.get("e2e_us") else None
                ),
            }
        achieved = [s["achieved_tps"] for s in out_steps]
        peak = max(achieved) if achieved else 0.0
        knee = None
        for s in out_steps:
            if s["achieved_tps"] < 0.9 * s["offered_tps"]:
                knee = s["offered_tps"]
                break
        proc.terminate()
        try:
            proc.wait(timeout=650 if backend == "dual" else 30)
        except subprocess.TimeoutExpired:
            pass
        drain_thread.join(timeout=5)
        out = {
            "backend": backend,
            "batch": batch,
            "step_s": step_s,
            "sessions": sessions,
            "sample_every": sample_every,
            "steps": out_steps,
            "peak_achieved_tps": peak,
            "saturation_offered_tps": knee,
            "breakdown": breakdown,
            "device_breakdown": device_breakdown,
            "acked_events": acked_total,
        }
        if backend == "dual" and server_stats:
            shadow = server_stats.get("device_shadow") or {}
            out["device_shadow_verified"] = shadow.get("verified")
        return out
    finally:
        for b in buses:
            try:
                b.sel.close()
            except Exception:
                pass
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        kill_process_group(proc)
        if own_tmp:
            tmp.cleanup()


def _verify_and_report(session, n_accounts, total, wall, n_timed, lat_ms,
                       clients, log) -> dict:
    from tigerbeetle_tpu.state_machine import decode_accounts, encode_ids

    dpo = cpo = found = 0
    ids = list(range(1, n_accounts + 1))
    for i in range(0, len(ids), 8000):
        chunk = ids[i : i + 8000]
        session.client.request(Operation.lookup_accounts, encode_ids(chunk))
        _h, body = session.wait_reply()
        arr = decode_accounts(body)
        found += len(arr)
        dpo += int(arr["debits_posted_lo"].sum())
        cpo += int(arr["credits_posted_lo"].sum())
    assert found == n_accounts, (found, n_accounts)
    assert dpo == cpo == total, (dpo, cpo, total)
    log(f"conservation verified: {total} transfers, dpo==cpo=={total}")

    lat = np.percentile(lat_ms if lat_ms else [float("nan")],
                        [0, 25, 50, 75, 100])
    return {
        "durable_tps": round(n_timed / wall, 1) if wall else 0.0,
        "n_transfers": n_timed,
        "wall_s": round(wall, 2),
        "clients": clients,
        "latency_ms_p00_p25_p50_p75_p100": [round(float(x), 2) for x in lat],
    }
