"""The request anatomy by operation class, from the kernel's receive
stamp (tigerbeetle_tpu/latency.py, io/message_bus.py, io/network.py).

Contracts under test:

- every class (create / lookup / other) samples on its own countdown
  at the stride, plus a floor: a record that opens 10 ms or more after
  its class's last one has the class sample its next request too. So an
  alternating create / lookup stream spaced over the floor is sampled
  whole, and one spaced under it 1 in 16 a class; the stride's picks
  are the same either way;
- a record's legs still sum to its e2e exactly, per class as overall,
  and the overall histograms are the sums of the class ones;
- every transport hands each request frame over just before its
  handler runs: on a loopback TCP bus the record opens at the kernel's
  receive of the request's first byte (a request left unread 50 ms
  books >= 50 ms of `socket_wait`, inside its e2e) where the kernel
  stamps; a transport without the control message folds `socket_wait`
  as 0 and counts the record unstamped; the in-process transports hand
  over the handler entry alone;
- every new name is cataloged and in the replica's [stats] registry;
- the dual applier is handed an enqueue stamp (and so fences) only for
  the records the stride picked, never for the floor's.
"""

import socket
import time

import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu.latency import (
    CLASSES,
    LEG_DISPATCH,
    LEG_FINALIZE,
    LEG_FUSE,
    LEG_QUORUM,
    LEG_WAIT,
    LEG_WAL,
    LEGS,
    SAMPLE_FLOOR_NS,
    LatencyAnatomy,
)
from tigerbeetle_tpu.metrics import CATALOG, Metrics
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.header import HEADER_SIZE, Command, Header

CREATE = int(Operation.create_transfers)
LOOKUP = int(Operation.lookup_transfers)
_LEGS_AFTER_ADMISSION = (LEG_WAL, LEG_QUORUM, LEG_FUSE, LEG_DISPATCH,
                         LEG_WAIT, LEG_FINALIZE)


class _Clock:
    """A clock the test sets by hand (every read returns `t`)."""

    def __init__(self):
        self.t = 1_000_000_000

    def __call__(self):
        return self.t


def _serve(a: LatencyAnatomy, tid: int, op: int, step_ns: int = 0,
           clock: _Clock | None = None, stamped: bool = True):
    """One request through the protocol, handed over as a transport
    hands it (`received`), with a kernel stamp taken just now unless
    `stamped` is False; returns (token, strided)."""
    a.received(op, time.time_ns() if stamped else 0)
    tok = a.open(tid, op) if a.want(op) else 0
    strided = a.strided(tok)
    if tok:
        for leg in _LEGS_AFTER_ADMISSION:
            if clock is not None:
                clock.t += step_ns
            a.stamp(tok, leg)
        a.finish(tok)
    return tok, strided


@pytest.mark.parametrize("spacing_ns,stamped,per_class", [
    (2 * SAMPLE_FLOOR_NS, True, 32),  # over the floor: every request
    # under it: the stride's 1 in 16, and the request after the class's
    # first record (which had no record before it)
    (SAMPLE_FLOOR_NS // 200, True, 3),
    # no kernel stamp (in process): the same sampling
    (2 * SAMPLE_FLOOR_NS, False, 32),
])
def test_alternating_stream_samples_both_classes(spacing_ns, stamped,
                                                 per_class):
    m = Metrics()
    clk = _Clock()
    a = LatencyAnatomy(metrics=m, clock=clk, sample_every=16)
    strided = {CREATE: [], LOOKUP: []}
    index = {CREATE: 0, LOOKUP: 0}
    for i in range(64):
        clk.t += spacing_ns
        op = CREATE if i % 2 == 0 else LOOKUP
        tok, s = _serve(a, 1 + i, op, stamped=stamped)
        if s:
            strided[op].append(index[op])
        index[op] += 1
    c = m.snapshot()["counters"]
    assert c["latency.create.samples"] == per_class
    assert c["latency.lookup.samples"] == per_class
    assert c["latency.samples"] == 2 * per_class
    assert c.get("latency.other.samples", 0) == 0
    assert c.get("latency.unstamped", 0) == (0 if stamped else 2 * per_class)
    # the stride's own picks do not depend on the floor: the 1st and
    # 17th request of each class, whatever the spacing
    assert strided == {CREATE: [0, 16], LOOKUP: [0, 16]}


def test_legs_sum_to_e2e_exactly_per_class():
    m = Metrics()
    clk = _Clock()
    a = LatencyAnatomy(metrics=m, clock=clk, sample_every=1)
    ops = (CREATE, LOOKUP, int(Operation.register),
           int(Operation.create_accounts), int(Operation.lookup_accounts))
    for i in range(25):
        clk.t += 1_000_000
        if i % 3:
            # a bus handoff: the kernel received it (i * 7) us ago
            a.received(ops[i % len(ops)], time.time_ns() - i * 7_000)
            tok = a.open(100 + i, ops[i % len(ops)])
            for leg in _LEGS_AFTER_ADMISSION:
                clk.t += 1_000 + 37 * i
                a.stamp(tok, leg)
            a.finish(tok)
        else:
            _serve(a, 100 + i, ops[i % len(ops)], step_ns=1_000 + 37 * i,
                   clock=clk)
    hists = m._histograms
    for cls in CLASSES:
        e2e = hists[f"latency.{cls}.e2e_us"]
        assert e2e.count == m.counter(f"latency.{cls}.samples").value > 0
        legs = sum(hists[f"latency.{cls}.{leg}_us"].total for leg in LEGS)
        assert legs == pytest.approx(e2e.total, rel=1e-12, abs=1e-6), cls
    # the overall histograms see every record: they are the class ones'
    # sums, bucket for bucket
    for name in (*LEGS, "e2e"):
        overall = hists[f"latency.{name}_us"]
        parts = [hists[f"latency.{c}.{name}_us"] for c in CLASSES]
        assert overall.count == sum(p.count for p in parts) == 25
        assert overall.total == pytest.approx(sum(p.total for p in parts))
        assert overall.counts == [sum(b) for b in zip(*(p.counts
                                                         for p in parts))]
        assert overall.snapshot()["count"] == 25
    assert hists["latency.socket_wait_us"].total > 0
    for rec in a.slowest():
        assert rec["class"] in CLASSES
        assert rec["operation"] in {op.name for op in Operation}
        assert sum(rec["legs"].values()) == pytest.approx(
            rec["e2e_us"], abs=0.01), rec


# -- the TCP bus: the kernel's receive stamp opens the record -----------


def _request_frame(client: int, op: int, request: int = 1) -> bytes:
    body = b"\x00" * 256
    h = Header(command=int(Command.request), client=client, request=request,
               operation=op)
    h.set_checksum_body(body)
    h.set_checksum()
    return h.to_bytes() + body


def _serve_over_tcp(hold_s: float):
    """A listening bus whose handler drives the anatomy as the replica
    does; one raw client sends one create and leaves it unread for
    `hold_s`. Returns (anatomy's slowest record, registry snapshot)."""
    from tigerbeetle_tpu.benchmark import free_port
    from tigerbeetle_tpu.io.message_bus import TCPMessageBus

    port = free_port()
    bus = TCPMessageBus([("127.0.0.1", port)], 0, listen=True)
    m = Metrics()
    bus.metrics = m
    lat = LatencyAnatomy(metrics=m, clock=time.monotonic_ns, sample_every=1)
    served = []

    def handler(_src, frame):
        header = Header.from_bytes(frame[:HEADER_SIZE])
        if header.command != Command.request:
            return
        op = header.operation
        tok = lat.open(header.trace(), op) if lat.want(op) else 0
        for leg in _LEGS_AFTER_ADMISSION:
            lat.stamp(tok, leg)
        lat.finish(tok)
        served.append(tok)

    bus.attach(0, handler, latency=lat)
    s = socket.create_connection(("127.0.0.1", port))
    try:
        s.sendall(_request_frame(0xC1, CREATE))
        time.sleep(hold_s)
        deadline = time.monotonic() + 5
        while not served and time.monotonic() < deadline:
            bus.pump(timeout=0.01)
        assert served and served[0], "the request was never served"
        return lat.slowest()[0], m.snapshot()
    finally:
        s.close()
        for conn in list(bus._links):
            bus._close(conn)
        bus.listener.close()


def _kernel_stamps_receives() -> bool:
    from tigerbeetle_tpu.io.message_bus import _enable_rx_stamps

    with socket.socket() as probe:
        return _enable_rx_stamps(probe)


def test_socket_wait_books_a_request_left_unread():
    if not _kernel_stamps_receives():
        pytest.skip("this kernel refuses SO_TIMESTAMPNS (gVisor does)")
    rec, snap = _serve_over_tcp(0.05)
    assert rec["class"] == "create"
    assert rec["legs"]["socket_wait"] >= 50_000.0, rec
    assert rec["e2e_us"] >= rec["legs"]["socket_wait"]
    assert sum(rec["legs"].values()) == pytest.approx(rec["e2e_us"],
                                                      abs=0.01)
    assert snap["counters"]["latency.unstamped"] == 0
    assert snap["histograms"]["latency.create.socket_wait_us"]["count"] == 1


def test_a_transport_without_the_stamp_folds_socket_wait_zero(monkeypatch):
    from tigerbeetle_tpu.io import message_bus

    monkeypatch.setattr(message_bus, "_SO_TIMESTAMPNS", None)
    rec, snap = _serve_over_tcp(0.02)
    assert "socket_wait" not in rec["legs"], rec  # 0 us: dropped
    h = snap["histograms"]["latency.socket_wait_us"]
    assert h["count"] == 1 and h["mean"] == 0.0
    assert snap["counters"]["latency.unstamped"] == 1


def test_in_process_transport_folds_socket_wait_zero():
    from tigerbeetle_tpu.testing.simulator import Simulator

    sim = Simulator(3, ticks=300, crash_probability=0.0,
                    latency_sample_every=1)
    sim.run()
    primary = next(r for r in sim.replicas if r.is_primary)
    snap = primary.metrics.snapshot()
    samples = snap["counters"]["latency.samples"]
    assert samples > 0
    h = snap["histograms"]["latency.socket_wait_us"]
    assert h["count"] == samples and h["max"] == 0.0
    assert snap["counters"]["latency.unstamped"] == samples


@pytest.mark.parametrize("transport", ["in_process", "packet_simulator"])
def test_in_process_transports_hand_each_request_over(transport):
    """The in-process transports hand a request frame over to its
    destination's anatomy just before its handler runs, as the TCP bus
    does (without a kernel stamp); other frames pass untouched."""
    from tigerbeetle_tpu.io.network import InProcessNetwork
    from tigerbeetle_tpu.testing.packet_simulator import PacketSimulator

    clk = _Clock()
    lat = LatencyAnatomy(metrics=Metrics(), clock=clk, sample_every=1)
    if transport == "in_process":
        net = InProcessNetwork()
        deliver = net.run
    else:
        net = PacketSimulator(seed=1, replica_count=1)

        def deliver():
            for _ in range(64):
                net.tick()

    seen = []
    net.attach(0, lambda _src, frame: seen.append(lat._rx_entry),
               latency=lat)
    ping = Header(command=int(Command.ping_client), client=0xC1)
    ping.set_checksum_body(b"")
    ping.set_checksum()
    net.send(0xC1, 0, ping.to_bytes())
    deliver()
    net.send(0xC1, 0, _request_frame(0xC1, LOOKUP))
    deliver()
    assert seen == [0, clk.t]
    tok = lat.open(0xAB, LOOKUP) if lat.want(LOOKUP) else 0
    assert lat._recs[tok][1] == lat._recs[tok][3] == clk.t


def test_spaced_in_process_requests_are_sampled_whole():
    """One sampling policy on every transport: a cluster on the
    in-process network, its clock stepped 20 ms between requests, has
    every request recorded at the default stride, as a TCP server at
    that rate does; requests back to back fall to the stride."""
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(replica_count=1)
    r = cluster.replicas[0]
    assert r.latency.sample_every == 16
    c = cluster.add_client()
    for _ in range(20):
        cluster.run_ticks(2)
        cluster.execute(c, Operation.lookup_accounts, b"\x00" * 16)
    assert r.metrics.counter("latency.lookup.samples").value == 20
    for _ in range(32):
        cluster.execute(c, Operation.lookup_accounts, b"\x00" * 16)
    # the request the last spaced record had the floor take, then the
    # stride's picks (the 33rd and 49th lookup)
    assert r.metrics.counter("latency.lookup.samples").value == 20 + 3


def test_every_new_name_is_cataloged_and_in_the_stats_registry():
    from tigerbeetle_tpu.testing.cluster import Cluster

    names = ["latency.socket_wait_us", "latency.unstamped"]
    for cls in CLASSES:
        names += [f"latency.{cls}.{leg}_us" for leg in LEGS]
        names += [f"latency.{cls}.e2e_us", f"latency.{cls}.samples"]
    for name in names:
        assert name in CATALOG, name
    assert "bus.pump_us" not in CATALOG
    cluster = Cluster(replica_count=1)
    c = cluster.add_client()
    cluster.execute(c, Operation.lookup_accounts, b"\x00" * 16)
    snap = cluster.replicas[0].metrics.snapshot()
    present = {**snap["counters"], **snap["histograms"]}
    for name in names:
        assert name in present, name
    assert snap["counters"]["latency.lookup.samples"] == 1


def test_dual_applier_fences_the_strides_records_only():
    """Every create is sampled (the clock steps past the floor on every
    read, so each record has the floor take the next request), but only
    the stride's picks hand the applier an enqueue stamp, so it opens as
    many device records as the stride alone would — the floor never
    fences it more often."""
    import numpy as np

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.models.dual_ledger import DualLedger
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(replica_count=1,
                      backend_factory=lambda: DualLedger(12, 14))
    r = cluster.replicas[0]
    assert r._dual_apply
    clk = _Clock()

    def stepping():
        clk.t += 2 * SAMPLE_FLOOR_NS
        return clk.t

    r.latency._clock = stepping
    r.latency.sample_every = 4
    stamps = []
    apply = r.ledger.apply_commit

    def spy(*args, **kw):
        stamps.append(kw.get("lat_ns", 0))
        return apply(*args, **kw)

    r.ledger.apply_commit = spy
    c = cluster.add_client()
    acc = np.zeros(10, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, 11, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1
    _h, body = cluster.execute(c, Operation.create_accounts, acc.tobytes())
    assert body == b""
    for g in range(8):
        x = np.zeros(16, dtype=types.TRANSFER_DTYPE)
        x["id_lo"] = np.arange(100 + 16 * g, 116 + 16 * g, dtype=np.uint64)
        x["debit_account_id_lo"] = 1 + np.arange(16) % 9
        x["credit_account_id_lo"] = 1 + (np.arange(16) + 1) % 9
        x["amount_lo"] = 1
        x["ledger"] = 1
        x["code"] = 1
        _h, body = cluster.execute(c, Operation.create_transfers,
                                   x.tobytes())
        assert body == b""
    assert r.ledger.drain_applier(500)
    # nine creates, every one sampled; the stride of 4 picks the 1st,
    # 5th and 9th, and only those carry the applier's enqueue stamp
    assert r.metrics.counter("latency.create.samples").value == 9
    assert len(stamps) == 9
    assert [i for i, s in enumerate(stamps) if s] == [0, 4, 8]
    assert r.metrics.counter("device.samples").value == 3
    report = r.ledger.finalize(timeout=500)
    assert report["verified"] is True, report


def test_watch_line_names_the_dominant_leg_over_every_class():
    """`inspect live --watch` picks the interval's dominant leg among the
    overall histograms: a class's copy of the same leg (equal when one
    class was sampled) never stands in for it."""
    from tigerbeetle_tpu.inspect import _watch_line

    w = {"count": 10, "mean": 5_000.0, "p50": 4096, "p95": 8192, "p99": 8192}
    small = dict(w, mean=10.0)
    # the flight recorder's order: sorted, so a class's copy comes first
    hists = {"latency.socket_wait_us": w, "latency.create.socket_wait_us": w,
             "latency.commit_wait_us": small,
             "latency.create.commit_wait_us": small}
    entry = {"t": 2.0, "dt": 1.0, "counters": {},
             "histograms": dict(sorted(hists.items()))}
    assert "dominant=socket_wait(50.0ms)" in _watch_line(entry)
