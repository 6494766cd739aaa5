"""Cluster-causal op tracing: trace ids, Perfetto flow events, stitching.

The contract under test (tracer.py stitch/flow_events + the span tags
threaded through replica/journal/bus/cdc/dual_ledger):

- one client request's trace id (vsr/header.py trace_id, derived from
  client id + request checksum) tags every leg of the op — quorum wait,
  journal write, commit dispatch/finalize, CDC emit, device apply — on
  EVERY replica that executes it;
- stitching per-replica dumps yields ONE Perfetto file whose flow events
  (s/t/f) connect those legs across pids, with no dangling flow ids even
  when the span ring overwrote part of an op's history;
- the TCP bus tags its frame-parse (ingress) and flush (reply egress)
  spans with the same ids.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu import types
from tigerbeetle_tpu.tracer import JsonTracer, dump_stitched
from tigerbeetle_tpu.types import Operation
from tigerbeetle_tpu.vsr.header import Command, Header, trace_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _accounts(ids):
    acct = np.zeros(len(ids), dtype=types.ACCOUNT_DTYPE)
    acct["id_lo"] = ids
    acct["ledger"] = 1
    acct["code"] = 1
    return acct


def _transfer(tid, debit=1, credit=2):
    t = np.zeros(1, dtype=types.TRANSFER_DTYPE)
    t["id_lo"] = tid
    t["debit_account_id_lo"] = debit
    t["credit_account_id_lo"] = credit
    t["amount_lo"] = 1
    t["ledger"] = 1
    t["code"] = 1
    return t


def _flow_ids(events):
    return {e["id"] for e in events if e.get("ph") in ("s", "t", "f")}


def _assert_flows_well_formed(events):
    """Every flow id's legs are ordered s, t*, f — a lone start or a
    step without its start would render as a dangling arrow."""
    per_id: dict[str, list[str]] = {}
    for e in events:
        if e.get("ph") in ("s", "t", "f"):
            per_id.setdefault(e["id"], []).append(e["ph"])
    assert per_id, "no flow events generated"
    for fid, phs in per_id.items():
        assert phs[0] == "s" and phs[-1] == "f", (fid, phs)
        assert all(p == "t" for p in phs[1:-1]), (fid, phs)
        assert len(phs) >= 2, (fid, phs)


def test_trace_id_deterministic_and_derivable_from_every_leg():
    """The id assigned at ingress (request client+checksum) is exactly
    re-derivable from a prepare or reply header's (client, context) —
    the propagation contract that lets every process tag without
    coordination."""
    req = Header(command=int(Command.request), client=0xC11E27,
                 checksum=0xABCDEF)
    prepare = Header(command=int(Command.prepare), client=0xC11E27,
                     context=0xABCDEF)
    reply = Header(command=int(Command.reply), client=0xC11E27,
                   context=0xABCDEF)
    assert req.trace() == prepare.trace() == reply.trace()
    assert req.trace() == trace_id(0xC11E27, 0xABCDEF)
    assert trace_id(1, 2) != trace_id(2, 1)
    assert trace_id(0, 0) != 0  # 0 stays the untraced sentinel


def test_cluster_causal_flows_across_replicas(tmp_path):
    """One transfer through a 3-replica cluster, each replica tracing
    into its own ring: the stitched file links the op's quorum wait,
    journal writes, dispatch and finalize ACROSS replica pids as one
    flow."""
    from tigerbeetle_tpu.models.oracle import OracleStateMachine
    from tigerbeetle_tpu.testing.cluster import Cluster

    tracers = [JsonTracer(pid=i) for i in range(3)]
    cluster = Cluster(replica_count=3, backend_factory=OracleStateMachine,
                      tracer_factory=lambda i: tracers[i])
    client = cluster.add_client()
    cluster.execute(client, Operation.create_accounts,
                    _accounts([1, 2]).tobytes())
    hdr, _ = cluster.execute(client, Operation.create_transfers,
                             _transfer(100).tobytes())
    cluster.run_ticks(5)
    tid = trace_id(client.client_id, hdr.context)
    assert hdr.trace() == tid  # the reply carries the anchor back

    path = str(tmp_path / "cluster.json")
    dump_stitched(path, [tr.events_ordered() for tr in tracers],
                  labels=[f"replica {i}" for i in range(3)])
    events = json.load(open(path))["traceEvents"]
    tagged = [
        (e["pid"], e["name"]) for e in events
        if (e.get("args") or {}).get("trace") == tid
    ]
    # the op's legs span every replica...
    assert {p for p, _ in tagged} == {0, 1, 2}, tagged
    # ...and cover the whole commit path on the primary
    names = {n for _, n in tagged}
    assert {"replica.quorum_wait", "journal.write_prepare",
            "replica.commit_dispatch", "replica.commit_finalize"} <= names
    # connected flow events with this id, well-formed s..f
    assert f"{tid:x}" in _flow_ids(events)
    _assert_flows_well_formed(events)
    # process_name metadata names the pids
    meta = {e["pid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e["name"] == "process_name"}
    assert meta == {0: "replica 0", 1: "replica 1", 2: "replica 2"}


def test_dual_mode_transfer_full_causal_chain(tmp_path):
    """The acceptance chain: one transfer through a 3-replica cluster in
    DUAL mode (native serves, device follows) with a live CDC consumer —
    the stitched trace links quorum -> journal write -> commit dispatch
    -> finalize (reply) -> CDC emit -> device apply (shadow.upload, the
    dispatch the hash-log ring fold rides) under one trace id, across
    pids."""
    from tigerbeetle_tpu.cdc import CdcPump, MemoryCursor
    from tigerbeetle_tpu.cdc.sink import MemorySink
    from tigerbeetle_tpu.models.dual_ledger import DualLedger
    from tigerbeetle_tpu.testing.cluster import Cluster

    tracers = [JsonTracer(pid=i) for i in range(3)]
    cluster = Cluster(
        replica_count=3,
        backend_factory=lambda: DualLedger(12, 14),
        tracer_factory=lambda i: tracers[i],
    )
    r0 = cluster.replicas[0]
    assert r0._dual_apply
    r0.cdc_retain = True
    sink = MemorySink()
    pump = CdcPump(r0, sink, MemoryCursor(), window=32)
    pump.attach()

    client = cluster.add_client()
    cluster.execute(client, Operation.create_accounts,
                    _accounts([1, 2]).tobytes())
    hdr, body = cluster.execute(client, Operation.create_transfers,
                                _transfer(100).tobytes())
    assert body == b""  # committed clean
    cluster.run_ticks(5)
    pump.pump(budget_ops=16)
    for r in cluster.replicas:
        assert r.ledger.drain_applier(120)

    tid = trace_id(client.client_id, hdr.context)
    path = str(tmp_path / "dual.json")
    dump_stitched(path, [tr.events_ordered() for tr in tracers],
                  labels=[f"replica {i}" for i in range(3)])
    events = json.load(open(path))["traceEvents"]
    tagged = [
        (e["pid"], e["name"]) for e in events
        if (e.get("args") or {}).get("trace") == tid
    ]
    names0 = {n for p, n in tagged if p == 0}
    assert {"replica.quorum_wait", "journal.write_prepare",
            "replica.commit_dispatch", "replica.commit_finalize",
            "cdc.emit", "shadow.upload"} <= names0, sorted(names0)
    assert {p for p, _ in tagged} == {0, 1, 2}
    assert f"{tid:x}" in _flow_ids(events)
    _assert_flows_well_formed(events)


def test_ring_overflow_leaves_no_dangling_flows(tmp_path):
    """A ring smaller than the span load overwrites oldest-first; the
    stitched output still parses and every surviving flow id has a
    complete s..f leg sequence (flows are generated FROM surviving
    spans, so a dangling reference is impossible by construction)."""
    tr = JsonTracer(capacity=16)
    for i in range(200):
        t = trace_id(i % 40, i // 40)
        with tr.span("stage_a", op=i, trace=t):
            pass
        with tr.span("stage_b", op=i, trace=t):
            pass
    path = str(tmp_path / "ring.json")
    dump_stitched(path, [tr.events_ordered()], labels=["ring"])
    events = json.load(open(path))["traceEvents"]
    spans = [e for e in events if e["ph"] in ("X", "B")]
    assert len(spans) == 16  # the ring kept only the newest tail
    _assert_flows_well_formed(events)
    # no flow references a span that was overwritten out of the ring
    surviving = set()
    for e in spans:
        t = (e.get("args") or {}).get("trace")
        if t:
            surviving.add(f"{t:x}")
    assert _flow_ids(events) <= surviving


def test_stitch_is_deterministic(tmp_path):
    """Stitching the same inputs twice is byte-identical — the property
    the simulator's same-seed reproducibility rests on."""
    tr = JsonTracer(capacity=32, clock=iter(range(10_000)).__next__,
                    ts_div=1.0)
    for i in range(10):
        with tr.span("s", trace=trace_id(i % 3, 7)):
            pass
    ev = tr.events_ordered()
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    dump_stitched(p1, [ev, ev], labels=["x", "y"])
    dump_stitched(p2, [ev, ev], labels=["x", "y"])
    assert open(p1, "rb").read() == open(p2, "rb").read()


def test_bus_tags_ingress_parse_and_reply_flush(tmp_path):
    """The TCP bus's frame_parse span carries the trace ids of the
    request frames it dispatched (ingress), and the flush span carries
    the ids of the reply frames it sent (egress) — the wire hops of an
    op's causal tree."""
    from tigerbeetle_tpu.benchmark import free_port
    from tigerbeetle_tpu.io.message_bus import TCPMessageBus

    port = free_port()
    bus = TCPMessageBus([("127.0.0.1", port)], 0, listen=True)
    tracer = JsonTracer()
    bus.tracer = tracer
    bus.attach(0, lambda src, frame: None)
    cid = 0x5E551017
    req = Header(command=int(Command.request), client=cid, request=3,
                 operation=int(Operation.create_accounts))
    req.set_checksum_body(b"")
    req.set_checksum()
    s = socket.create_connection(("127.0.0.1", port))
    try:
        s.sendall(req.to_bytes())
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if bus.pump(timeout=0.05):
                break
        parses = [e for e in tracer.events_ordered()
                  if e["name"] == "bus.frame_parse"]
        want = trace_id(cid, req.checksum)
        assert any(
            want in (e.get("args") or {}).get("traces", ())
            for e in parses
        ), parses

        # now a reply back to that session: the flush span carries it
        reply = Header(command=int(Command.reply), client=cid,
                       context=req.checksum, request=3)
        reply.set_checksum_body(b"")
        reply.set_checksum()
        assert bus.send(0, cid, reply.to_bytes()) == "sent"
        bus.flush_pending()
        flushes = [e for e in tracer.events_ordered()
                   if e["name"] == "bus.flush"]
        assert any(
            want in (e.get("args") or {}).get("traces", ())
            for e in flushes
        ), flushes
    finally:
        s.close()
        bus.sel.close()


def test_bus_eager_flush_keeps_trace_ids_per_connection():
    """Reply trace ids are tracked PER CONNECTION: a large reply that
    triggers the eager in-send flush of ITS conn must not steal (or be
    mislabeled with) another connection's queued reply ids."""
    from tigerbeetle_tpu.benchmark import free_port
    from tigerbeetle_tpu.io.message_bus import TCPMessageBus

    port = free_port()
    bus = TCPMessageBus([("127.0.0.1", port)], 0, listen=True)
    tracer = JsonTracer()
    bus.tracer = tracer
    bus.attach(0, lambda src, frame: None)

    def connect(cid):
        req = Header(command=int(Command.request), client=cid, request=1,
                     operation=int(Operation.create_accounts))
        req.set_checksum_body(b"")
        req.set_checksum()
        s = socket.create_connection(("127.0.0.1", port))
        s.sendall(req.to_bytes())
        deadline = time.monotonic() + 5
        while cid not in bus.conns and time.monotonic() < deadline:
            bus.pump(timeout=0.05)
        assert cid in bus.conns
        return s

    cid_a, cid_b = 0xAAA0, 0xBBB0
    sa, sb = connect(cid_a), connect(cid_b)
    try:
        def reply_to(cid, body):
            r = Header(command=int(Command.reply), client=cid,
                       context=cid * 7 + 1, request=1)
            r.set_checksum_body(body)
            r.set_checksum()
            return r

        ra = reply_to(cid_a, b"")
        assert bus.send(0, cid_a, ra.to_bytes()) == "sent"  # small: queued
        big = reply_to(cid_b, b"\0" * bus.FLUSH_EAGER)  # eager: flushes B
        assert bus.send(0, cid_b, big.to_bytes() + b"\0" * bus.FLUSH_EAGER) \
            == "sent"
        tid_a = trace_id(cid_a, ra.context)
        tid_b = trace_id(cid_b, big.context)
        flushes = [
            (e.get("args") or {}).get("traces", [])
            for e in tracer.events_ordered() if e["name"] == "bus.flush"
        ]
        eager = [t for t in flushes if tid_b in t]
        assert eager and all(tid_a not in t for t in eager), flushes
        bus.flush_pending()  # A's queued reply flushes with A's id
        flushes = [
            (e.get("args") or {}).get("traces", [])
            for e in tracer.events_ordered() if e["name"] == "bus.flush"
        ]
        assert any(tid_a in t for t in flushes), flushes
    finally:
        sa.close()
        sb.close()
        bus.sel.close()


def test_stitch_trace_cli(tmp_path):
    """scripts/stitch_trace.py merges per-process dumps into one
    Perfetto file with cross-pid flows, deterministically."""
    tr0 = JsonTracer(clock=iter(range(10_000)).__next__, ts_div=1.0)
    tr1 = JsonTracer(clock=iter(range(10_000)).__next__, ts_div=1.0)
    t = trace_id(9, 9)
    with tr0.span("ingress", trace=t):
        pass
    with tr1.span("apply", trace=t):
        pass
    p0, p1 = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
    tr0.dump(p0)
    tr1.dump(p1)
    out1, out2 = str(tmp_path / "o1.json"), str(tmp_path / "o2.json")
    for out in (out1, out2):
        res = subprocess.run(
            [sys.executable, "scripts/stitch_trace.py",
             "--out", out, p0, p1],
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert res.returncode == 0, res.stderr
    assert open(out1, "rb").read() == open(out2, "rb").read()
    events = json.load(open(out1))["traceEvents"]
    pids = {e["pid"] for e in events if e["ph"] in ("X", "B")}
    assert pids == {0, 1}
    assert f"{t:x}" in _flow_ids(events)
    _assert_flows_well_formed(events)


@pytest.mark.slow
def test_sim_stitched_trace_multi_pid():
    """The simulator's per-replica tracers stitch into one multi-pid
    file (the fast byte-identity proof lives in test_metrics)."""
    import tempfile

    from tigerbeetle_tpu.testing.simulator import Simulator

    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/sim.json"
        Simulator(31337, ticks=300, trace_path=path).run()
        events = json.load(open(path))["traceEvents"]
        pids = {e["pid"] for e in events if e["ph"] in ("X", "B")}
        assert len(pids) >= 2
        _assert_flows_well_formed(events)
