"""Device anatomy (tigerbeetle_tpu/latency.py DeviceAnatomy + the
models/ledger.py compile sentinel).

Contracts under test:

- device sub-legs are CONSECUTIVE stamp intervals: a finished apply
  record's sub-legs sum to its apply e2e exactly (accounted_ratio 1.0
  at device granularity) — with a fake clock AND through a live
  follower DualLedger;
- a forced applier stall (`_test_apply_delay_s`) makes queue_wait the
  dominant sub-leg, and the flight-recorder/`--watch` line grows the
  device columns (dev_q, dev_dominant naming queue_wait);
- every device.* metric name is CATALOG'd with kind + unit + help
  (drift guard, same contract as latency.*/cdc.*/ingress.*);
- the compile sentinel counts cold compiles, stays silent on cache
  hits, and flags a compile after mark_warm() as a post-warmup event;
- device stamping is observability only: two same-seed follower runs
  with every op sampled produce identical device code-stream digests.
"""

from time import perf_counter_ns

import numpy as np

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu import types
from tigerbeetle_tpu.latency import (
    DEVICE_LEGS,
    DLEG_BUSY,
    DLEG_COALESCE,
    DLEG_DISPATCH,
    DLEG_H2D,
    DeviceAnatomy,
    device_leg_totals,
    dominant_leg,
)
from tigerbeetle_tpu.metrics import CATALOG, Metrics
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import Operation


class _FakeClock:
    def __init__(self, deltas=(1000,)):
        self.t = 0
        self.deltas = list(deltas)
        self.i = 0

    def __call__(self):
        self.t += self.deltas[self.i % len(self.deltas)]
        self.i += 1
        return self.t


# -- pure DeviceAnatomy ------------------------------------------------


def test_device_sublegs_partition_apply_e2e_exactly():
    m = Metrics()
    a = DeviceAnatomy(metrics=m, clock=_FakeClock([700, 4000, 90, 12000]))
    tok = a.open(0xD1, t_enq=100)  # t_deq from clock: queue_wait = 700-100
    assert tok == 0xD1
    for leg in (DLEG_COALESCE, DLEG_H2D, DLEG_DISPATCH, DLEG_BUSY):
        a.stamp(tok, leg)
    a.finish(tok)
    rec = a.slowest()[0]
    assert rec["trace"] == f"{0xD1:016x}"
    assert abs(sum(rec["legs"].values()) - rec["e2e_us"]) < 1e-6, rec
    assert rec["dominant"] in rec["legs"]
    snap = m.snapshot()
    assert snap["counters"]["device.samples"] == 1
    assert snap["histograms"]["device.apply_e2e_us"]["count"] == 1
    # the folded per-sub-leg histogram totals partition e2e too
    totals = device_leg_totals(snap)
    total_us = sum(v["total_us"] for v in totals.values())
    e2e_us = snap["histograms"]["device.apply_e2e_us"]["mean"]
    assert abs(total_us - e2e_us) < 1e-3


def test_device_anatomy_explicit_stamps_and_dup_open():
    a = DeviceAnatomy(metrics=Metrics(), clock=_FakeClock())
    assert a.open(7, t_enq=1000, t_deq=3000) == 7
    assert a.open(7, t_enq=1000) == 0  # duplicate id
    assert a.open(0, t_enq=1000) == 0  # unsampled
    a.stamp(7, DLEG_DISPATCH, t=5000)
    a.finish(7, t=9000)
    rec = a.slowest()[0]
    assert rec["legs"]["queue_wait"] == 2.0  # (3000-1000) ns -> us
    assert rec["legs"]["dispatch"] == 2.0
    assert rec["legs"]["finalize_visible"] == 4.0
    assert rec["e2e_us"] == 8.0
    assert rec["dominant"] == "finalize_visible"


def test_device_anatomy_eviction_and_discard_leak_free():
    a = DeviceAnatomy(metrics=Metrics(), clock=_FakeClock(), capacity=4)
    for tid in range(1, 8):
        a.open(tid, t_enq=10)
    assert len(a._recs) == 4  # oldest evicted, never grows past capacity
    a.discard(7)
    a.discard(999)  # unknown: no-op
    assert 7 not in a._recs
    a.finish(6)
    assert a.slowest()  # the survivor folded


def test_device_metric_names_cataloged():
    for leg in DEVICE_LEGS:
        name = f"device.{leg}_us"
        assert name in CATALOG, name
        kind, unit, help_ = CATALOG[name]
        assert kind == "histogram" and unit == "us" and help_
    for name, want_kind in (
        ("device.apply_e2e_us", "histogram"),
        ("device.samples", "counter"),
        ("device.queue_depth", "gauge"),
        ("device.h2d_bytes", "counter"),
        ("device.dispatches", "counter"),
        ("device.compiles", "counter"),
        ("device.compiles_post_warmup", "counter"),
        ("device.compile_ms", "histogram"),
    ):
        assert name in CATALOG, name
        kind, unit, help_ = CATALOG[name]
        assert kind == want_kind and help_


# -- compile sentinel --------------------------------------------------


def test_compile_sentinel_counts_cold_cached_and_post_warmup():
    import jax.numpy as jnp

    from tigerbeetle_tpu.models.ledger import (
        COMPILE_SENTINEL,
        sentinel_jit,
    )

    was_warm = COMPILE_SENTINEL.warm
    try:
        COMPILE_SENTINEL.warm = False
        fn = sentinel_jit("test_sentinel_probe",
                          lambda x: x * 2 + jnp.sum(x))
        base = COMPILE_SENTINEL.per_name.get("test_sentinel_probe", 0)
        fn(jnp.arange(8))
        assert COMPILE_SENTINEL.per_name["test_sentinel_probe"] == base + 1
        fn(jnp.arange(8))  # cache hit: no growth, not a compile
        assert COMPILE_SENTINEL.per_name["test_sentinel_probe"] == base + 1
        post0 = COMPILE_SENTINEL.post_warmup
        COMPILE_SENTINEL.mark_warm()
        fn(jnp.arange(16))  # new shape AFTER warm: hot-path event
        assert COMPILE_SENTINEL.per_name["test_sentinel_probe"] == base + 2
        assert COMPILE_SENTINEL.post_warmup == post0 + 1
        snap = COMPILE_SENTINEL.snapshot()
        assert snap["total"] >= 2
        ev = [e for e in snap["events"]
              if e["fn"] == "test_sentinel_probe"]
        assert ev and ev[-1]["post_warmup"] is True
        assert ev[-1]["ms"] > 0
    finally:
        COMPILE_SENTINEL.warm = was_warm


def test_compile_sentinel_instrument_carries_totals():
    from tigerbeetle_tpu.models.ledger import COMPILE_SENTINEL

    m = Metrics()
    COMPILE_SENTINEL.instrument(m)
    snap = m.snapshot()
    # the fresh registry starts at zero; the process-wide totals carry in
    assert snap["counters"]["device.compiles"] == COMPILE_SENTINEL.total
    assert (snap["counters"]["device.compiles_post_warmup"]
            == COMPILE_SENTINEL.post_warmup)


def test_sentinel_jit_passes_through_non_jit_callables():
    from tigerbeetle_tpu.models.ledger import _SentinelJit

    calls = []
    wrapped = _SentinelJit(lambda x: calls.append(x) or x + 1,
                           "test_double")
    assert wrapped(41) == 42  # no _cache_size: plain passthrough
    assert calls == [41]


# -- live follower: stall -> queue_wait dominant; partition exactness --


def _valid_transfers(start: int, n: int) -> np.ndarray:
    x = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    x["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    x["debit_account_id_lo"] = 1 + np.arange(n) % 9
    x["credit_account_id_lo"] = 1 + (np.arange(n) + 1) % 9
    x["amount_lo"] = 1
    x["ledger"] = 1
    x["code"] = 1
    return x


def _drive_sampled(led, op, arr, op_no: int) -> None:
    """The replica's commit-finalize seam with the op SAMPLED (lat_ns
    stamped), so every item opens a device-anatomy record."""
    led.prepare(op, len(arr))
    ts = led.prepare_timestamp
    p = led.execute_async(op, ts, arr)
    led.drain(p)
    led.apply_commit(op_no, op, ts, arr, p.codes,
                     prepare_checksum=0xABCD_0000 + op_no,
                     trace=0xD000_0000 + op_no,
                     lat_ns=perf_counter_ns())


def _acc_batch(start: int, n: int = 16) -> np.ndarray:
    acc = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1
    return acc


def test_follower_stall_names_queue_wait_dominant_and_watch_columns():
    from tigerbeetle_tpu.inspect import _watch_line
    from tigerbeetle_tpu.metrics import FlightRecorder
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    led = DualLedger(12, 14)
    led.instrument(Metrics(), NULL_TRACER)
    # warm round on a throwaway registry: the solo-apply kernels compile
    # here, so the stall round below measures a WARM applier (a cold
    # compile inside dispatch would otherwise drown the stall signal —
    # which is exactly what the compile sentinel exists to flag)
    _drive_sampled(led, Operation.create_accounts, _acc_batch(1), 1)
    assert led.drain_applier(500)
    m = Metrics()
    led.instrument(m, NULL_TRACER)
    fr = FlightRecorder(m)
    fr.record(1.0)  # baseline entry (deltas need a predecessor)
    # stall the apply loop and queue NON-coalescable ops (accounts runs
    # never fuse): each op waits behind every earlier op's stalled run,
    # so queue_wait accumulates quadratically while coalesce_hold pays
    # only its own run's stall — queue_wait must dominate
    led._test_apply_delay_s = 0.2
    for g in range(6):
        _drive_sampled(led, Operation.create_accounts,
                       _acc_batch(100 + 16 * g), 2 + g)
    led._test_apply_delay_s = 0.0
    report = led.finalize(timeout=500)
    assert report["verified"] is True, report
    snap = m.snapshot()
    assert snap["counters"]["device.samples"] == 6
    leg, share = dominant_leg({}, device_leg_totals(snap))
    assert leg == "queue_wait", (leg, device_leg_totals(snap))
    assert share > 0.3
    # the slowest record agrees and accounts for its span exactly
    rec = led.device_anatomy.slowest()[0]
    assert rec["dominant"] == "queue_wait", rec
    assert abs(sum(rec["legs"].values()) - rec["e2e_us"]) <= 0.01, rec
    # flight entry -> --watch line: the device columns surfaced
    entry = fr.record(2.0)
    line = _watch_line(entry)
    assert "dev_dominant=queue_wait" in line, line
    assert "disp/s=" in line, line
    assert "h2d=" in line or "dev_busy_p99=" in line, line
    # counters that feed the columns really moved
    assert snap["counters"]["device.dispatches"] >= 1
    assert snap["counters"]["device.h2d_bytes"] > 0


def test_follower_partition_exactness_all_sampled_no_stall():
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    m = Metrics()
    led = DualLedger(12, 14)
    led.instrument(m, NULL_TRACER)
    acc = np.zeros(16, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"] = np.arange(1, 17, dtype=np.uint64)
    acc["ledger"] = 1
    acc["code"] = 1
    _drive_sampled(led, Operation.create_accounts, acc, 1)
    for g in range(3):
        _drive_sampled(led, Operation.create_transfers,
                       _valid_transfers(2000 + 32 * g, 32), 2 + g)
    report = led.finalize(timeout=500)
    assert report["verified"] is True, report
    snap = m.snapshot()
    assert snap["counters"]["device.samples"] == 4
    assert snap["histograms"]["device.apply_e2e_us"]["count"] == 4
    for rec in led.device_anatomy.slowest():
        assert abs(sum(rec["legs"].values()) - rec["e2e_us"]) <= 0.01, rec
        assert rec["dominant"] in rec["legs"]
    # histogram-level accounting: sum of sub-leg totals == e2e total
    totals = device_leg_totals(snap)
    h = snap["histograms"]["device.apply_e2e_us"]
    sub = sum(v["total_us"] for v in totals.values())
    e2e = h["count"] * h["mean"]
    assert abs(sub - e2e) / e2e < 1e-6, (sub, e2e)


def test_same_seed_follower_device_digests_identical_with_stamping():
    """Device stamping is observability, never state: two identical
    follower runs with EVERY op sampled produce identical device
    code-stream digests (and each verifies against native)."""
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    digests = []
    for _run in range(2):
        led = DualLedger(12, 14)
        led.instrument(Metrics(), NULL_TRACER)
        acc = np.zeros(16, dtype=types.ACCOUNT_DTYPE)
        acc["id_lo"] = np.arange(1, 17, dtype=np.uint64)
        acc["ledger"] = 1
        acc["code"] = 1
        _drive_sampled(led, Operation.create_accounts, acc, 1)
        for g in range(3):
            _drive_sampled(led, Operation.create_transfers,
                           _valid_transfers(3000 + 32 * g, 32), 2 + g)
        report = led.finalize(timeout=500)
        assert report["verified"] is True, report
        digests.append(report["code_stream_digest"]["device"])
    assert digests[0] == digests[1]
