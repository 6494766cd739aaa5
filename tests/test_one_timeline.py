"""One timeline (PR 26): the program's spans inside the profiler's trace,
and launch / busy counters stamped where the chip is launched.

Contracts under test:

- tracer.ProfilerTracer writes `tb.`-prefixed events, with their args,
  into an xplane captured with jax.profiler.start_trace and read back with
  jax.profiler.ProfileData; nested spans nest; start/stop/annotate work
  across the token interface; with no session open a span costs less than
  the budget test_metrics.py pins for the no-op backend (measured here:
  ~0.5 us against 1.5 us);
- device.commit_launches / _batches / _slots equal the launches made,
  batches carried and capacities used, exactly, fused and solo, for the
  `device` ledger driven directly and for the `dual` follower's applier;
- metrics.LaunchClock: the sum of device.launch_busy_us equals
  device.commit_busy_s, never exceeds wall time, each launch is booked
  once, and close() drains and joins with launches still in flight;
- bus.frame_recv_us observes a multi-read 1 MiB frame once and a small
  frame never;
- a served `start --backend dual|device --device-trace <dir>` leaves an
  xplane that holds `tb.` events and a [stats] line whose launch counters
  agree with each other;
- every new name is in the CATALOG.
"""

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu import benchmark, types
from tigerbeetle_tpu.metrics import CATALOG, LaunchClock, Metrics
from tigerbeetle_tpu.tracer import NULL_TRACER, PROFILER_PREFIX, ProfilerTracer
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the profiler backend ------------------------------------------


def _tb_events(trace_dir: str) -> list:
    """(name, start_ns, duration_ns, stats) of every `tb.` event in the
    newest xplane under `trace_dir`."""
    import jax

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert found, f"no xplane under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(found[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROFILER_PREFIX):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return out


def _profiler_options():
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's traced start
    return options


def test_profiler_tracer_writes_tb_events_with_args_into_the_xplane(tmp_path):
    import jax

    tr = ProfilerTracer()
    assert tr.enabled is False  # no session: call sites skip trace ids
    with tr.span("before.session", op=1):
        pass
    jax.profiler.start_trace(str(tmp_path), profiler_options=_profiler_options())
    try:
        assert tr.enabled is True
        with tr.span("replica.commit_dispatch", op=7, trace=0xABCDEF):
            time.sleep(0.002)
            with tr.span("ledger.group_launch", slots=16, batches=5,
                         xfer_used=81900):
                time.sleep(0.002)
            time.sleep(0.001)
        tok = tr.start("bus.frame_parse")
        tr.annotate(tok, traces=[11, 12])
        time.sleep(0.001)
        tr.stop(tok)
        tr.stop(0)  # a token handed out while no session was open

        def worker():
            with tr.span("applier.wait_work"):
                time.sleep(0.002)

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    events = {name: (start, dur, stats)
              for name, start, dur, stats in _tb_events(str(tmp_path))}
    assert set(events) == {
        "tb.replica.commit_dispatch", "tb.ledger.group_launch",
        "tb.bus.frame_parse", "tb.applier.wait_work",
    }, sorted(events)
    o_start, o_dur, o_stats = events["tb.replica.commit_dispatch"]
    i_start, i_dur, i_stats = events["tb.ledger.group_launch"]
    assert (o_stats["op"], o_stats["trace"]) == (7, 0xABCDEF)
    assert (i_stats["slots"], i_stats["batches"], i_stats["xfer_used"]) == (
        16, 5, 81900)
    # nested spans nest, on one clock
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    assert i_dur >= 2e6 and o_dur >= i_dur + 3e6
    # annotate() reaches a span opened with start()
    assert "11" in str(events["tb.bus.frame_parse"][2]["traces"])
    assert events["tb.applier.wait_work"][1] >= 2e6


def test_profiler_tracer_with_no_session_costs_less_than_the_noop_budget():
    """The chip backends keep this tracer all the time: with no profiler
    session open a span must stay under the 1.5 us the no-op backend is
    held to (tests/test_metrics.py). Measured ~0.5 us here (min of 5)."""
    tr = ProfilerTracer()
    n = 50_000
    per_run = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("replica.commit_dispatch", op=5, trace=0):
                pass
        per_run.append((time.perf_counter() - t0) / n * 1e6)
    assert min(per_run) < 1.5, f"profiler span with no session: {per_run}"
    t0 = time.perf_counter()
    for _ in range(n):
        tr.stop(tr.start("replica.quorum_wait", op=5))
    assert (time.perf_counter() - t0) / n * 1e6 < 1.5


# -- (b) launch-site counters ------------------------------------------


def _accounts(start: int, n: int = 16) -> np.ndarray:
    acc = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1
    return acc


def _transfers(start: int, n: int = 32) -> np.ndarray:
    x = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    x["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    x["debit_account_id_lo"] = 1 + np.arange(n) % 9
    x["credit_account_id_lo"] = 1 + (np.arange(n) + 1) % 9
    x["amount_lo"] = 1
    x["ledger"] = 1
    x["code"] = 1
    return x


class _LaunchSpy:
    """Wraps a DeviceLedger's two launch entry points and records what
    each launch that was MADE carried: (batches, slots)."""

    def __init__(self, device):
        self.made: list[tuple[int, int]] = []
        group, solo = device.try_execute_group_async, device.execute_async

        def spy_group(items):
            pendings = group(items)
            if pendings is not None:
                self.made.append((len(items), pendings[0].group.k))
            return pendings

        def spy_solo(operation, timestamp, events):
            pending = solo(operation, timestamp, events)
            self.made.append((1, 1))
            return pending

        device.try_execute_group_async = spy_group
        device.execute_async = spy_solo


def _drive_device(metrics) -> _LaunchSpy:
    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger

    led = DeviceLedger(process=ConfigProcess(account_slots_log2=12,
                                             transfer_slots_log2=14))
    led.instrument(metrics, NULL_TRACER)
    spy = _LaunchSpy(led)
    ts = 1 << 40

    def group(first_id: int, k: int) -> list:
        nonlocal ts
        items = []
        for j in range(k):
            ts += 32
            items.append((ts, _transfers(first_id + 32 * j)))
        return items

    ts += 16
    led.drain(led.execute_async(Operation.create_accounts, ts, _accounts(1)))
    for p in led.try_execute_group_async(group(1000, 5)):  # the 16-slot program
        assert led.drain(p) == [0] * 32
    for p in led.try_execute_group_async(group(2000, 3)):  # the 4-slot program
        led.drain(p)
    ts += 32
    led.drain(led.execute_async(Operation.create_transfers, ts, _transfers(3000)))
    assert led.try_execute_group_async(group(4000, 1)) is None  # too short
    led.check_fault()
    # scripted: accounts solo, 5 in 16 slots, 3 in 4 slots, one solo
    assert spy.made == [(1, 1), (5, 16), (3, 4), (1, 1)]
    return spy


def _drive_dual(metrics) -> _LaunchSpy:
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    led = DualLedger(12, 14)
    led.instrument(metrics, NULL_TRACER)
    spy = _LaunchSpy(led.device)
    op = [0]

    def commit(operation, arr) -> None:
        op[0] += 1
        led.prepare(operation, len(arr))
        ts = led.prepare_timestamp
        p = led.execute_async(operation, ts, arr)
        led.drain(p)
        led.apply_commit(op[0], operation, ts, arr, p.codes)

    commit(Operation.create_accounts, _accounts(1))
    assert led.drain_applier(500)
    # stall the applier on its first one-item run so that the batches
    # behind it are all queued when it comes back: one fused group
    led._test_apply_delay_s = 0.5
    for g in range(6):
        commit(Operation.create_transfers, _transfers(1000 + 32 * g))
    led._test_apply_delay_s = 0.0
    report = led.finalize(timeout=500)
    assert report["verified"] is True, report
    assert sum(b for b, _k in spy.made) == 7
    assert any(k > 1 for _b, k in spy.made), spy.made  # fused ...
    assert (1, 1) in spy.made  # ... and solo
    return spy


@pytest.mark.parametrize("backend", ["device", "dual"])
def test_launch_counters_equal_the_launches_made(backend):
    m = Metrics()
    spy = (_drive_device if backend == "device" else _drive_dual)(m)
    c = m.snapshot()["counters"]
    assert c["device.commit_launches"] == len(spy.made)
    assert c["device.commit_batches"] == sum(b for b, _k in spy.made)
    assert c["device.commit_slots"] == sum(k for _b, k in spy.made)
    if backend == "dual":
        # the applier's own grouping counters tell the same story
        assert c["shadow.groups"] + c["shadow.solo"] == len(spy.made)
        assert c["shadow.batches"] == c["device.commit_batches"]
    # no completion thread outside the serving process: nothing booked
    assert "device.commit_busy_s" not in c
    assert "launch-clock" not in {t.name for t in threading.enumerate()}


# -- (c) the completion thread -----------------------------------------


class _Handle:
    """A result handle that becomes ready `seconds` after the device
    starts on it; the 'device' runs handles one after another."""

    device_free_at = 0.0
    lock = threading.Lock()

    def __init__(self, seconds: float, fail: bool = False):
        with _Handle.lock:
            start = max(time.perf_counter(), _Handle.device_free_at)
            self.ready_at = _Handle.device_free_at = start + seconds
        self.fail = fail

    def block_until_ready(self):
        left = self.ready_at - time.perf_counter()
        if left > 0:
            time.sleep(left)
        if self.fail:
            raise RuntimeError("launch failed on the device")
        return self


def _booked(m: Metrics) -> tuple:
    snap = m.snapshot()
    h = snap["histograms"]["device.launch_busy_us"]
    return (snap["counters"]["device.commit_busy_s"],
            h["count"] * h["mean"] / 1e6, h["count"],
            snap["counters"]["device.commit_batches_done"])


def test_launch_clock_books_each_launch_once_and_never_more_than_wall_time():
    m = Metrics()
    clock = LaunchClock(m)
    t_wall = time.perf_counter()
    # three launches dispatched back to back (the second and third queue
    # behind the first on the device), an idle stretch, then a fourth
    for seconds, batches in ((0.05, 16), (0.05, 16), (0.03, 4)):
        clock.launched(_Handle(seconds), time.perf_counter_ns(), batches)
    time.sleep(0.25)
    clock.launched(_Handle(0.04), time.perf_counter_ns(), 1)
    assert clock.close(timeout=30) is True
    wall = time.perf_counter() - t_wall
    busy_s, hist_s, launches, batches = _booked(m)
    assert (launches, batches) == (4, 37)
    assert busy_s == pytest.approx(hist_s, rel=1e-4)
    assert busy_s <= wall
    # the idle stretch is not booked: 0.17 s of device time, not 0.42
    assert 0.17 <= busy_s < 0.23, busy_s
    assert "launch-clock" not in {t.name for t in threading.enumerate()}


def test_launch_clock_close_drains_launches_still_in_flight():
    m = Metrics()
    clock = LaunchClock(m)
    for _ in range(3):
        clock.launched(_Handle(0.1), time.perf_counter_ns(), 16)
    clock.launched(_Handle(0.01, fail=True), time.perf_counter_ns(), 16)
    clock.launched(_Handle(0.05), time.perf_counter_ns(), 2)
    t0 = time.perf_counter()
    assert clock.close(timeout=30) is True  # joins after the last one
    assert time.perf_counter() - t0 >= 0.3
    busy_s, hist_s, launches, batches = _booked(m)
    # the failed launch books nothing; the one after it is still booked
    assert (launches, batches) == (4, 50)
    assert busy_s == pytest.approx(hist_s, rel=1e-4)


def test_launch_clock_on_a_device_ledger_agrees_with_the_launch_counters():
    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger

    m = Metrics()
    led = DeviceLedger(process=ConfigProcess(account_slots_log2=12,
                                             transfer_slots_log2=14))
    led.instrument(m, NULL_TRACER)
    led.launch_clock = LaunchClock(m)
    t_wall = time.perf_counter()
    ts = (1 << 40) + 16
    led.execute_async(Operation.create_accounts, ts, _accounts(1))
    items = []
    for j in range(5):
        ts += 32
        items.append((ts, _transfers(1000 + 32 * j)))
    pendings = led.try_execute_group_async(items)
    ts += 32
    pendings.append(
        led.execute_async(Operation.create_transfers, ts, _transfers(3000)))
    # closed with launches still in flight: nothing was drained yet
    assert led.launch_clock.close(timeout=300) is True
    wall = time.perf_counter() - t_wall
    for p in pendings:
        led.drain(p)
    c = m.snapshot()["counters"]
    busy_s, hist_s, launches, batches = _booked(m)
    assert launches == c["device.commit_launches"] == 3
    assert batches == c["device.commit_batches"] == 7
    assert c["device.commit_slots"] == 18
    assert 0 < busy_s <= wall and busy_s == pytest.approx(hist_s, rel=1e-4, abs=1e-6)
    assert c["loop.fetch_s"] > 0  # the drains above went through _fetch


# -- (d) bus.frame_recv_us ----------------------------------------------


def _frame(client: int, body: bytes, request: int) -> bytes:
    from tigerbeetle_tpu.vsr.header import HEADER_SIZE, Command, Header

    h = Header(command=int(Command.request), client=client, request=request,
               operation=int(Operation.create_transfers),
               size=HEADER_SIZE + len(body))
    h.set_checksum_body(body)
    h.set_checksum()
    return h.to_bytes() + body


def test_frame_recv_observes_a_multi_read_frame_once_and_a_small_frame_never():
    from tigerbeetle_tpu.io.message_bus import FRAME_RECV_MIN, TCPMessageBus

    port = benchmark.free_port()
    bus = TCPMessageBus([("127.0.0.1", port)], 0, listen=True)
    m = Metrics()
    bus.metrics = m
    got = []
    bus.attach(0, lambda peer, frame: got.append(len(frame)))
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    try:
        hist = m.histogram("bus.frame_recv_us")

        def pump_until(n_frames: int) -> None:
            deadline = time.monotonic() + 20
            while len(got) < n_frames and time.monotonic() < deadline:
                bus.pump(timeout=0.01)
            assert len(got) == n_frames

        small = _frame(0xC11E, b"x" * 128, 1)
        sock.sendall(small)
        pump_until(1)
        assert hist.count == 0
        big = _frame(0xC11E, bytes((1 << 20) - 256), 2)
        assert len(big) > FRAME_RECV_MIN
        third = len(big) // 3
        t0 = time.perf_counter()
        for part in (big[:third], big[third:2 * third]):
            sock.sendall(part)
            for _ in range(5):
                bus.pump(timeout=0.01)  # reads: the frame is not whole yet
            time.sleep(0.02)
        assert got == [len(small)] and hist.count == 0
        # the rest of the frame, and a small one right behind it
        sock.sendall(big[2 * third:] + _frame(0xC11E, b"y" * 64, 3))
        pump_until(3)
        elapsed_us = (time.perf_counter() - t0) * 1e6
        assert got[1] == len(big)
        assert hist.count == 1  # the big frame once, the small ones never
        # first byte read -> handed on spans the three reads
        assert 40_000 <= hist.max <= elapsed_us
    finally:
        sock.close()
        bus.drop_connections()
        bus.listener.close()


# -- the served process: spans in the xplane, counters in [stats] -------

SMALL = ("--account-slots-log2", "10", "--transfer-slots-log2", "12",
         "--grid-mb", "8")


@pytest.mark.parametrize("backend", ["device", "dual", "sharded"])
def test_served_device_trace_holds_tb_spans_and_stats_hold_the_launches(
        backend, tmp_path):
    # `sharded`: four shards on the suite's CPU devices (conftest's
    # XLA_FLAGS reach the child through the environment)
    shards = ("--shards", "4") if backend == "sharded" else ()
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(PYTHONPATH=REPO, TB_PARENT_WATCHDOG="1", TB_JAX_PLATFORM="cpu")
    path = str(tmp_path / "d.tigerbeetle")
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster", "0",
         "--replica", "0", "--replica-count", "1", "--grid-mb", "8", path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert fmt.returncode == 0, fmt.stderr
    port = benchmark.free_port()
    trace_dir = str(tmp_path / "trace")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tigerbeetle_tpu", "start",
         "--addresses", f"127.0.0.1:{port}", "--backend", backend, *shards,
         *SMALL, "--device-trace", trace_dir, "--device-trace-s", "600", path],
        cwd=REPO, env=env, start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        benchmark.wait_listening(proc, backend, deadline_s=240)
        session = benchmark._BenchClient(0xE0001, port)
        session.register()
        session.client.request(Operation.create_accounts,
                               benchmark._accounts_body(1, 8))
        assert session.wait_reply()[1] == b""
        rng = np.random.default_rng(1)
        # the window the first request opened stays open until SIGTERM
        # closes it: a 2 s window could close behind the compiles the
        # `device` backend runs behind its first requests (on a loaded
        # machine they take longer than that), with no commit inside it
        t_end = time.monotonic() + 3.0
        sent = 0
        while time.monotonic() < t_end:
            session.client.request(
                Operation.create_transfers,
                benchmark._transfers_body(rng, 1000 + 16 * sent, 16, 8))
            assert session.wait_reply()[1] == b""
            sent += 1
            time.sleep(0.05)
        session.bus.drop_connections()
        # SIGTERM with the window still open and launches in flight:
        # the clock drains, the window closes, [stats] lands
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=300)
    finally:
        benchmark.kill_process_group(proc)
    assert proc.returncode == 0, out[-3000:]
    assert "[device-trace] window written" in out, out[-3000:]
    stats = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("[stats] "))[8:])
    c = stats["metrics"]["counters"]
    h = stats["metrics"]["histograms"]["device.launch_busy_us"]
    assert c["device.commit_batches"] == sent + 1  # the accounts too
    assert c["device.commit_batches_done"] == c["device.commit_batches"]
    assert h["count"] == c["device.commit_launches"] >= 1
    assert c["device.commit_slots"] >= c["device.commit_batches"]
    assert c["device.commit_busy_s"] == pytest.approx(
        h["count"] * h["mean"] / 1e6, rel=1e-3)
    emitted = set(c) | set(stats["metrics"]["gauges"])
    assert not emitted - set(CATALOG), emitted - set(CATALOG)
    names = {name for name, *_ in _tb_events(trace_dir)}
    assert "tb.replica.commit_dispatch" in names, names
    if backend == "sharded":
        # one launch a batch, left in flight by the replica's window
        # (solo_ops) and drained from its two-word summary, every one in
        # the fast tier, and the owner hash's skew beside them
        assert "tb.ledger.sharded_launch" in names, names
        assert c["device.commit_launches"] == c["device.commit_batches"]
        assert c["ledger.tier.fast"] == sent
        assert c["commit.group.solo_ops"] >= sent
        assert c["ledger.drain_all_ok"] == sent + 1
        assert not c.get("ledger.drain_dense")
        g = stats["metrics"]["gauges"]
        assert g["sharded.xfer_rows_max"] >= g["sharded.xfer_rows_mean"] > 0
        assert 4 * g["sharded.xfer_rows_mean"] == 16 * sent  # every row, once
    else:
        assert ("tb.ledger.solo_launch" in names
                or "tb.ledger.group_launch" in names)
        assert "tb.ledger.plan" in names  # the planner, inside the launch span
    if backend == "dual":
        assert "tb.applier.wait_work" in names and "tb.shadow.upload" in names
    else:
        assert "tb.ledger.fetch_replies" in names
        assert c["loop.fetch_s"] > 0


# -- (e) the catalog ----------------------------------------------------


@pytest.mark.parametrize("name,kind,unit", [
    ("device.commit_launches", "counter", ""),
    ("device.commit_batches", "counter", ""),
    ("device.commit_slots", "counter", ""),
    ("device.commit_busy_s", "counter", "s"),
    ("device.commit_batches_done", "counter", ""),
    ("device.launch_busy_us", "histogram", "us"),
    ("loop.fetch_s", "counter", "s"),
    ("bus.frame_recv_us", "histogram", "us"),
    ("ledger.lookup_deferred", "counter", ""),
    ("ledger.lookup_inline", "counter", ""),
    ("commit.group.replies_ahead", "counter", "ops"),
    ("ledger.drain_all_ok", "counter", ""),
    ("ledger.drain_dense", "counter", ""),
])
def test_new_metric_names_are_cataloged(name, kind, unit):
    assert name in CATALOG, name
    got_kind, got_unit, help_ = CATALOG[name]
    assert (got_kind, got_unit) == (kind, unit) and help_


def test_the_bridge_counter_left_the_catalog():
    assert "device.trace_windows" not in CATALOG


# -- (f) the yardstick's divisor over such a trace ----------------------


def test_a_chip_that_never_idles_is_busy_for_at_most_its_window():
    """The case of benchmarks/tests/test_yardstick.py (not tier-1), held
    here too: ops back to back up to the last collected instant, the host
    tracer and the stop stamp both short of it. The divisor is the span
    the profiler collected, not the stamps' (here 2 ms shorter): busy_s
    over the stamps' span read more than 100 %, which the driver refuses."""
    import math

    from benchmarks.harness import readers, trace

    ops = [("fusion.1", 0.045 + 0.001 * i, 0.001) for i in range(4055)]
    every = 0.018  # the event loop's thread: a `tb.loop.poll` span each
    polls = [0.046 + i * every
             for i in range(math.ceil((4.052 - 0.046) / every))]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0.045, 4.055)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("tb.loop.poll", s, min(every, 4.052 - s)) for s in polls]}]},
    ]
    red = trace.reduce_planes(planes)
    stamps_s = red["collected_s"] - 0.002
    assert red["busy_s"] > stamps_s
    window_s = trace.traced_window_s(red, stamps_s)
    assert 0 < red["busy_s"] <= window_s
    assert red["collected_first_s"] == pytest.approx(0.045)
    assert red["collected_last_s"] == pytest.approx(4.100)
    idle = readers.device_idle_share({"trace": red})
    assert 0.0 <= idle < 1e-6
