"""Per-request critical-path latency attribution (tigerbeetle_tpu/latency.py).

Contracts under test:

- legs are CONSECUTIVE stamp intervals, so a finished record's legs sum
  to its end-to-end latency exactly (the decomposition accounts for all
  of the time);
- sampling: one request in `sample_every` opens a record, the rest pay
  only the countdown — and the amortized no-op-backend cost of the full
  stamp sequence stays under the 1us/request budget at the default rate;
- the stamps ride the DETERMINISTIC time seam: a seeded simulator run
  with stamping forced on commits a byte-identical history AND folds
  identical latency histograms across runs;
- a real in-process pipeline (Simulator, crashes off) produces slowest-
  request breakdowns whose legs account for the measured e2e;
- eviction/discard never leak open records.
"""

import time

import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu.latency import (
    LEG_DISPATCH,
    LEG_FINALIZE,
    LEG_FUSE,
    LEG_QUORUM,
    LEG_WAIT,
    LEG_WAL,
    LEGS,
    LatencyAnatomy,
    dominant_leg,
    leg_totals,
)
from tigerbeetle_tpu.metrics import CATALOG, NULL_METRICS, Metrics

_ALL_LEGS = (LEG_WAL, LEG_QUORUM, LEG_FUSE, LEG_DISPATCH, LEG_WAIT,
             LEG_FINALIZE)


class _FakeClock:
    """Deterministic ns clock: each read advances by the next scripted
    delta (cycled)."""

    def __init__(self, deltas=(1000,)):
        self.t = 0
        self.deltas = list(deltas)
        self.i = 0

    def __call__(self):
        self.t += self.deltas[self.i % len(self.deltas)]
        self.i += 1
        return self.t


def _run_one(anatomy: LatencyAnatomy, tid: int) -> int:
    """Drive one request through the full stamp protocol; returns the
    token (0 if unsampled)."""
    anatomy.received(0)
    tok = anatomy.open(tid) if anatomy.want() else 0
    if tok:
        for leg in _ALL_LEGS:
            anatomy.stamp(tok, leg)
        anatomy.egress(tok, client=tid, context=tid * 7)
    return tok


def test_legs_partition_e2e_exactly():
    m = Metrics()
    a = LatencyAnatomy(metrics=m, clock=_FakeClock([1000, 3000, 500]),
                       sample_every=1)
    assert _run_one(a, 0xABC)
    rec = a.slowest()[0]
    assert rec["trace"] == f"{0xABC:016x}"
    assert abs(sum(rec["legs"].values()) - rec["e2e_us"]) < 1e-6, rec
    assert rec["dominant"] in rec["legs"]
    snap = m.snapshot()
    assert snap["counters"]["latency.samples"] == 1
    assert snap["histograms"]["latency.e2e_us"]["count"] == 1
    # per-leg histograms observed exactly once each
    for leg in LEGS:
        h = snap["histograms"][f"latency.{leg}_us"]
        assert h["count"] == 1, leg


def test_every_leg_and_lane_is_cataloged():
    for leg in LEGS:
        assert f"latency.{leg}_us" in CATALOG, leg
    for name in ("latency.e2e_us", "latency.samples", "latency.dropped",
                 "latency.device_apply_lag_us", "latency.wal_lane_us",
                 "flight.records"):
        assert name in CATALOG, name


def test_sampling_takes_one_in_n():
    a = LatencyAnatomy(metrics=NULL_METRICS, clock=_FakeClock(),
                       sample_every=4)
    sampled = sum(1 for i in range(100) if _run_one(a, 1000 + i))
    assert sampled == 25
    # 0 disables entirely
    off = LatencyAnatomy(metrics=NULL_METRICS, clock=_FakeClock(),
                         sample_every=0)
    assert sum(1 for i in range(50) if _run_one(off, i + 1)) == 0
    # ... including when turned off at RUNTIME with `_take` still armed
    # from construction (the --latency-sample-every 0 server path)
    late_off = LatencyAnatomy(metrics=NULL_METRICS, clock=_FakeClock())
    late_off.sample_every = 0
    assert sum(1 for i in range(50) if _run_one(late_off, i + 1)) == 0


def test_capacity_eviction_never_leaks_open_records():
    a = LatencyAnatomy(metrics=NULL_METRICS, clock=_FakeClock(),
                       sample_every=1, capacity=8)
    for i in range(100):  # open without ever finishing
        if a.want():
            a.open(1 + i)
    assert len(a._recs) <= 8
    # discard is a no-op for unknown/zero tokens
    a.discard(0)
    a.discard(None)
    a.discard(123456)


def test_deferred_egress_parks_and_finishes_by_reply_key():
    m = Metrics()
    a = LatencyAnatomy(metrics=m, clock=_FakeClock(), sample_every=1)
    a.defer_egress = True
    assert a.want()
    tok = a.open(77)
    a.stamp(tok, LEG_FINALIZE)
    a.egress(tok, client=0xC1, context=0xBEEF)
    assert a.pending_egress[(0xC1, 0xBEEF)] == tok
    assert m.snapshot()["counters"].get("latency.samples", 0) == 0
    # the bus pops the key and finishes at flush
    got = a.pending_egress.pop((0xC1, 0xBEEF))
    a.finish(got)
    assert m.snapshot()["counters"]["latency.samples"] == 1


def test_record_opens_at_the_handler_entry_handed_over():
    m = Metrics()
    clk = _FakeClock([0])  # manual control below
    a = LatencyAnatomy(metrics=m, clock=lambda: clk.t, sample_every=1)
    clk.t = 1_000
    a.received(0)  # the transport hands the frame over: handler entry
    clk.t += 50_000  # 50us of admission work
    assert a.want()
    tok = a.open(5)
    r = a._recs[tok]
    assert r[1] == r[3] == 1_000  # t0 = entry: no kernel stamp, wait 0
    assert r[5] == clk.t
    a.finish(tok)
    # the handoff is consumed: a request no transport handed over (a
    # direct call) opens at the clock's now, never at a stale entry
    clk.t += 1_000
    tok = a.open(6)
    assert a._recs[tok][1] == a._recs[tok][3] == clk.t
    a.finish(tok)
    assert m.counter("latency.unstamped").value == 2
    # a kernel stamp 2 ms behind the wall clock opens the record 2 ms
    # (and the time the test itself took since) before the entry
    clk.t += 1_000
    a.received(0, time.time_ns() - 2_000_000)
    tok = a.open(7)
    r = a._recs[tok]
    assert r[3] == clk.t and r[3] - r[1] >= 2_000_000
    assert m.counter("latency.unstamped").value == 2


def test_dominant_leg_delta_math():
    before = {"commit_finalize": {"count": 10, "total_us": 1000.0}}
    after = {
        "commit_finalize": {"count": 20, "total_us": 5000.0},
        "wal_write": {"count": 20, "total_us": 1000.0},
    }
    leg, share = dominant_leg(before, after)
    assert leg == "commit_finalize"
    assert share == 0.8
    assert dominant_leg({}, {}) == (None, 0.0)
    # leg_totals extracts count * mean from a registry snapshot shape
    snap = {"histograms": {
        "latency.wal_write_us": {"count": 4, "mean": 2.5},
        "latency.e2e_us": {"count": 4, "mean": 10.0},  # not a leg
    }}
    t = leg_totals(snap)
    assert t == {"wal_write": {"count": 4, "total_us": 10.0}}


@pytest.mark.parametrize("kernel_stamp", [False, True],
                         ids=["no_stamp", "kernel_stamp"])
def test_stamp_budget_under_1us_per_request_noop_backend(kernel_stamp):
    """The budget: amortized per-request stamping cost < 1us with the
    no-op metrics backend at the DEFAULT sampling rate, on the path a
    TCP server runs: the transport's handoff (`received`, with or
    without a kernel stamp), the class's countdown and, one request in
    16, the whole record, over an alternating create / lookup stream as
    fast as the loop runs (the floor never fires: a class's records are
    microseconds apart). The kernel stamp lies ahead of the wall clock
    (its wait clamps to 0), so every record's e2e is alike, as a live
    server's are: a stamp aging through the pass would make each record
    the slowest yet and time the top-K ring's insert instead. The cost
    is the thread's CPU time less that of the same loop calling an
    empty function, best of 20 passes: time the thread spent
    descheduled on a loaded CI machine is not stamping work, and a wall
    clock would charge it (the true cost is ~0.5us on this class of
    machine)."""
    from tigerbeetle_tpu.types import Operation
    from tigerbeetle_tpu.vsr.header import Command, Header

    req = Header(command=int(Command.request), client=0xABC, request=7)
    req.set_checksum_body(b"x" * 128)
    req.set_checksum()
    a = LatencyAnatomy(metrics=NULL_METRICS)  # default sample_every
    assert a.sample_every == 16
    stamp = time.time_ns() + 3_600_000_000_000 if kernel_stamp else 0
    ops = (int(Operation.create_transfers), int(Operation.lookup_accounts))

    def one_request(op):
        a.received(op, stamp)
        tok = a.open(req.trace(), op) if a.want(op) else 0
        if tok:
            for leg in _ALL_LEGS:
                a.stamp(tok, leg)
            a.egress(tok, 0xABC, 123)

    def nothing(op):
        return None

    def per_request(fn, n=5_000):
        t0 = time.thread_time_ns()
        for i in range(n):
            fn(ops[i & 1])
        return (time.thread_time_ns() - t0) / n

    best = min(per_request(one_request) - per_request(nothing)
               for _ in range(20))
    assert best < 1000.0, f"amortized stamp cost {best:.0f}ns >= 1us"


def test_simulator_determinism_with_stamping_enabled():
    """Same seed, stamping forced on EVERY request: byte-identical
    committed histories AND identical latency histogram folds across
    runs (the stamps ride the DeterministicTime seam, so they are part
    of the reproducible surface, not noise on top of it)."""
    from tigerbeetle_tpu.testing.simulator import Simulator

    def run():
        sim = Simulator(11, ticks=400, latency_sample_every=1)
        sim.run()
        hists = [sorted(h.items()) for h in sim.histories]
        lat = {
            k: v
            for k, v in sim.replicas[0].metrics.snapshot()[
                "histograms"
            ].items()
            if k.startswith("latency.")
        }
        return hists, lat

    h1, l1 = run()
    h2, l2 = run()
    assert h1 == h2, "stamping perturbed the committed history"
    assert l1 == l2, "latency folds diverged across identical runs"


def test_pipeline_breakdown_accounts_for_e2e():
    """A real in-process consensus pipeline (3 replicas, oracle
    backend, crashes off) folds sampled requests whose slowest-request
    breakdowns account for the measured end-to-end latency — the same
    invariant the live frontier asserts over TCP."""
    from tigerbeetle_tpu.testing.simulator import Simulator

    sim = Simulator(3, ticks=400, crash_probability=0.0,
                    latency_sample_every=1)
    sim.run()
    primary = next(r for r in sim.replicas if r.is_primary)
    snap = primary.metrics.snapshot()
    assert snap["counters"]["latency.samples"] > 0
    recs = primary.latency.slowest()
    assert recs, "no breakdown records on the primary"
    for rec in recs:
        total = sum(rec["legs"].values())
        assert abs(total - rec["e2e_us"]) <= max(0.02, 0.2 * rec["e2e_us"]), rec
        assert rec["dominant"] in rec["legs"]
    # quorum_wait must appear for a 3-replica quorum (acks cross ticks)
    assert snap["histograms"]["latency.quorum_wait_us"]["count"] > 0
