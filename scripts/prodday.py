#!/usr/bin/env python
"""Run a scripted production day against a live cluster and score it.

Composes the existing drivers into one scenario run: a gateway-fronted
`--backend dual` cluster (testing/chaos.py's ChaosServer), an OPEN-LOOP
offered-load schedule derived from the timeline's phase curves (the
frontier driver's due-time discipline: a batch becomes due on the
schedule's clock whether or not the cluster kept up, so latency is
measured from DUE time and queueing delay is visible), the chaos fault
injectors fired at scripted offsets, and the CDC fan-out hub with one
count-throttled slow consumer. Phase boundaries are stamped into every
replica's flight recorder over the wire (`mark`, vsr/header.py), so the
phase-aligned SLO scorer (tigerbeetle_tpu/prodday.py) slices recorder
history per phase and names the dominant critical-path leg for any
violated budget.

Emits the scorecard report to --out and a PRODDAY artifact (the same
provenance discipline as BENCH artifacts: platform block, .jax_cache
sizes, compile-sentinel totals, segments_incomplete) to --artifact.

The same timeline replays seed-deterministically in the simulator:
  python -c "from tigerbeetle_tpu.prodday import *; \\
             print(run_sim_twin(production_day(), seed=1)['scorecard'])"

Example (sandbox-scaled rehearsal of the canonical day):
  python scripts/prodday.py --time-scale 0.25 --rate-scale 0.5 \\
      --artifact PRODDAY_r01.json
"""

import argparse
import json
import os
import random
import socket
import sys
import time
from collections import deque

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np

from tigerbeetle_tpu.artifact import jax_cache_bytes, wrap_artifact
from tigerbeetle_tpu.benchmark import (
    REPO,
    _accounts_body,
    _transfers_body,
    free_port,
    kill_process_group,
    require_one_process_per_chip,
)
from tigerbeetle_tpu.constants import ConfigCluster
from tigerbeetle_tpu.inspect import inspect_live, send_mark
from tigerbeetle_tpu.metrics import Metrics
from tigerbeetle_tpu.prodday import (
    offered_rate,
    production_day,
    scale_timeline,
    score,
    slice_history,
    smoke_timeline,
)
from tigerbeetle_tpu.testing.chaos import (
    ChaosFleet,
    ChaosServer,
    _parse_cdc_stream,
    inject_wal_fault,
)
from tigerbeetle_tpu.types import Operation


class ProddayFleet(ChaosFleet):
    """Open-loop fleet: batches become due on the timeline's clock and
    are issued on the first free session once due. Latency is ack time
    minus DUE time (not issue time), so a saturated cluster's queueing
    delay lands in the phase's p99 instead of silently stretching the
    schedule — the open-loop discipline run_frontier established."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.due: deque = deque()  # (due_t, phase, events, body)
        self.meta: dict = {}  # session id() -> (due_t, phase, events)
        self.latencies: dict = {}  # phase -> [ack - due, ...] seconds
        self.phase_counts: dict = {}  # phase -> {offered, acked, failed}

    def offer(self, due_t: float, phase: str, body: bytes) -> None:
        ev = len(body) // 128
        self.due.append((due_t, phase, ev, body))
        pc = self.phase_counts.setdefault(
            phase, {"offered": 0, "acked": 0, "failed": 0}
        )
        pc["offered"] += ev
        self.total_events += ev

    def step_open(self, now: float) -> int:
        dispatched = self.pump()
        harvested = 0
        for s in self.sessions:
            s.ticker.advance(now)
            c = s.client
            try:
                c.poll()
            except Exception as e:  # typed errors: count, never hang
                self.errors.append(f"{type(e).__name__}: {e}")
                m = self.meta.pop(id(s), None)
                if m is not None:
                    self.phase_counts[m[1]]["failed"] += m[2]
                s.events_inflight = 0
            if c.reply is not None:
                _h, body = c.take_reply()
                self.max_op = max(self.max_op, _h.op)
                if body != b"":
                    self.errors.append(
                        f"client {c.client_id:#x}: non-empty reply "
                        f"({len(body)} bytes of result structs)"
                    )
                t = time.monotonic()
                self.recovery.observe_reply(t, _h.view, s.issue_seq)
                m = self.meta.pop(id(s), None)
                if m is not None:
                    due_t, phase, ev = m
                    self.latencies.setdefault(phase, []).append(t - due_t)
                    self.phase_counts[phase]["acked"] += ev
                self.acked_events += s.events_inflight
                self.acked_timeline.append((t, s.events_inflight))
                s.acked += s.events_inflight
                s.events_inflight = 0
                harvested += 1
            if (
                c.in_flight is None and c.session != 0
                and id(s) not in self.meta
                and self.due and self.due[0][0] <= now
            ):
                due_t, phase, ev, body = self.due.popleft()
                s.events_inflight = ev
                self._issue_seq += 1
                s.issue_seq = self._issue_seq
                self.meta[id(s)] = (due_t, phase, ev)
                c.request(Operation.create_transfers, body)
        return harvested + dispatched


def build_schedule(timeline, events_per_batch: int, n_accounts: int,
                   seed: int):
    """Precompute the whole day's batches: (due_rel_s, phase_name,
    body). Deterministic in (timeline, seed); disjoint id namespaces
    keep the CDC duplicate audit meaningful. Flash-crowd phases with
    hot_accounts > 0 draw both sides of every transfer from the hot
    subset {1..hot} — the concentrated-contention shape."""
    nrng = np.random.default_rng(seed)
    sched = []
    t, dur, nid = 0.0, timeline.duration_s, 1_000_000
    while t < dur:
        phase, into = timeline.phase_at(t)
        rate = max(0.0, offered_rate(phase, into / phase.duration_s))
        if rate <= 0.0:
            t += 0.1
            continue
        acct = phase.hot_accounts or n_accounts
        sched.append(
            (t, phase.name,
             _transfers_body(nrng, nid, events_per_batch, acct))
        )
        nid += events_per_batch
        t += events_per_batch / rate
    return sched


def run_prodday(
    timeline,
    n_sessions: int = 32,
    conns: int = 4,
    n_accounts: int = 128,
    events_per_batch: int = 16,
    replica_count: int = 3,
    backend: str = "dual",
    restart_after_s: float = 2.0,
    seed: int = 1,
    jax_platform: str | None = "cpu",
    settle_s: float = 1.0,
    drain_grace_s: float = 120.0,
    harvest_every_s: float = 5.0,
    tmpdir: str | None = None,
    log=None,
) -> dict:
    """Drive `timeline` against a live cluster; return the report with
    the phase-aligned scorecard. Raises only on harness failures —
    SLO violations are scorecard rows, not exceptions."""
    import subprocess
    import tempfile

    log = log or (lambda *_: None)
    rng = random.Random(seed)
    timeline.validate()
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="tb_prodday_")
        tmpdir = tmp.name

    slow_events = [e for e in timeline.events if e.kind == "slow_consumer"]
    schedule = build_schedule(timeline, events_per_batch, n_accounts, seed)
    total_events = len(schedule) * events_per_batch + events_per_batch

    ports = [free_port() for _ in range(replica_count)]
    addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
    clients_max = n_sessions + 64
    reply_slots = 64
    session_args = (
        "--clients-max", str(clients_max),
        "--client-reply-slots", str(reply_slots),
    )
    cluster_cfg = ConfigCluster(
        replica_count=replica_count,
        clients_max=clients_max,
        client_reply_slots=reply_slots,
    )
    slots_log2 = 14
    while total_events * 2 + 4096 > (1 << slots_log2) // 2:
        slots_log2 += 1
    acct_log2 = max(14, (n_accounts * 2 + 2).bit_length())
    start_args = session_args + (
        "--account-slots-log2", str(acct_log2),
        "--transfer-slots-log2", str(slots_log2),
    )
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    if jax_platform:
        env["TB_JAX_PLATFORM"] = jax_platform
    require_one_process_per_chip(
        "prodday", backend, replica_count, jax_platform
    )

    paths = []
    for i in range(replica_count):
        path = os.path.join(tmpdir, f"prodday_{i}.tigerbeetle")
        paths.append(path)
        fmt = subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu", "format",
             "--cluster", "7", "--replica", str(i),
             "--replica-count", str(replica_count),
             *session_args, path],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert fmt.returncode == 0, fmt.stderr

    # The slow CDC consumer rides the fan-out hub so the audit stream
    # (jsonl) keeps full pace while the throttled laggard's position
    # falls behind — its lag is the `ingress.fanout_lag_ops` gauge the
    # cdc_lag SLO reads. The laggard is a UDP sink we also receive.
    udp_rx = None
    cdc_path = os.path.join(tmpdir, "prodday_cdc.jsonl")
    servers = []
    for i in range(replica_count):
        extra: tuple = ("--ingress",)
        if i == 0:
            extra = extra + (
                "--cdc-jsonl", cdc_path,
                "--cdc-cursor", cdc_path + ".cursor",
            )
            if slow_events:
                udp_rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                udp_rx.bind(("127.0.0.1", 0))
                udp_rx.setblocking(False)
                extra = extra + (
                    "--cdc-udp",
                    f"127.0.0.1:{udp_rx.getsockname()[1]}",
                    "--cdc-fanout",
                    "--cdc-slow-every", str(slow_events[0].arg or 4),
                )
        servers.append(ChaosServer(
            i, addresses, paths[i], env, backend, start_args, extra, log,
        ))

    metrics = Metrics()
    fleet = None
    report = {
        "timeline": timeline.name,
        "seed": seed,
        "backend": backend,
        "sessions": n_sessions,
        "conns": conns,
        "replicas": replica_count,
        "scheduled_batches": len(schedule),
        "events": {"kills": 0, "restarts": 0, "gray_stops": 0,
                   "conn_resets": 0, "disk_fault_slots": [],
                   "slow_consumer_every": (slow_events[0].arg or 4)
                   if slow_events else 0},
    }
    # merged flight history: (replica, entry_t) -> entry, harvested
    # periodically because a SIGKILL wipes the victim's in-memory ring
    flight: dict = {}
    slow_datagrams = 0

    def harvest() -> None:
        nonlocal slow_datagrams
        for s in servers:
            if not s.alive or s.stopped or not s.ready.is_set():
                continue
            try:
                live = inspect_live(
                    "127.0.0.1", ports[s.index], timeout=2.0
                )
            except (OSError, RuntimeError, ValueError):
                continue
            for e in live.get("history") or []:
                flight[(s.index, s.spawns, e["t"])] = e
        if udp_rx is not None:
            while True:
                try:
                    udp_rx.recv(65536)
                except (BlockingIOError, OSError):
                    break
                slow_datagrams += 1

    def mark_all(name: str) -> None:
        for s in servers:
            if s.alive and not s.stopped and s.ready.is_set():
                try:
                    send_mark("127.0.0.1", ports[s.index], name,
                              timeout=2.0)
                except (OSError, RuntimeError, ValueError):
                    pass  # a booting replica misses one boundary; the
                    # next mark (or its restart re-mark) catches it up

    try:
        t_boot = time.monotonic()
        for s in servers:
            s.spawn(wait=False)
        for s in servers:
            if not s.ready.wait(300.0):
                raise TimeoutError(f"replica {s.index} never listened")
        log(f"cluster up on {addresses} in "
            f"{time.monotonic() - t_boot:.1f}s")

        fleet = ProddayFleet(ports, n_sessions, conns, metrics)
        report["register_s"] = round(fleet.register_all(), 2)

        next_id = 1
        while next_id <= n_accounts:
            k = min(2048, n_accounts - next_id + 1)
            body = fleet.execute(
                fleet.sessions[0], Operation.create_accounts,
                _accounts_body(next_id, k),
            )
            assert body == b"", "account create failed"
            next_id += k
        warm = _transfers_body(
            np.random.default_rng(seed + 1), 500_000, events_per_batch,
            n_accounts,
        )
        assert fleet.execute(
            fleet.sessions[0], Operation.create_transfers, warm,
            deadline_s=600.0,
        ) == b""
        warm_events = events_per_batch

        # shed/timeout accounting per phase: counter totals sampled at
        # each boundary (one registry serves every session's client)
        def _ctr() -> tuple:
            snap = metrics.snapshot()["counters"]
            return (snap.get("client.busy_sheds", 0),
                    snap.get("client.timeouts", 0))

        starts = timeline.phase_starts_s()
        events_left = sorted(timeline.events, key=lambda e: e.at_s)
        pending_restarts: list = []  # [when, server, flip]
        pending_cont: list = []  # [when, server]
        owe_mark: list = []  # restarted servers owed the current phase
        disk_flip_armed = False
        faults_armed = 0
        boundary_ctr: dict = {}  # phase -> (sheds, timeouts) at entry
        phase_now = None
        sched_i = 0
        next_harvest = 0.0
        fault_log: list = []

        t0 = time.monotonic()
        duration = timeline.duration_s
        deadline = t0 + duration + drain_grace_s
        log(f"driving timeline '{timeline.name}': {duration:.0f}s, "
            f"{len(schedule)} batches, {len(events_left)} events")
        while True:
            now = time.monotonic()
            rel = now - t0
            done_load = sched_i >= len(schedule) and not fleet.due
            if rel >= duration and done_load and not fleet.meta:
                break
            if now > deadline:
                log(f"drain grace expired with "
                    f"{fleet.outstanding()} events outstanding")
                break

            # phase boundaries (stamped BEFORE the load that phase
            # offers: the driver waits for mark acks, so the recorder
            # slices can't smear across the boundary)
            while starts and rel >= starts[0][0]:
                _, p = starts.pop(0)
                phase_now = p.name
                boundary_ctr[p.name] = _ctr()
                mark_all(p.name)
                log(f"phase -> {p.name} at t+{rel:.1f}s")

            # offered load: enqueue every batch now due
            while sched_i < len(schedule) and schedule[sched_i][0] <= rel:
                due_rel, pname, body = schedule[sched_i]
                fleet.offer(t0 + due_rel, pname, body)
                sched_i += 1

            # scheduled faults
            while events_left and rel >= events_left[0].at_s:
                e = events_left.pop(0)
                if e.kind == "kill_primary":
                    victim = servers[fleet.view % replica_count]
                    if victim.alive:
                        victim.sigcont()
                        victim.kill()
                        report["events"]["kills"] += 1
                        fleet.mark_fault(time.monotonic())
                        faults_armed += 1
                        fault_log.append((round(rel, 1), e.kind))
                        log(f"event: SIGKILL replica {victim.index} "
                            f"(primary) at t+{rel:.1f}s")
                        pending_restarts.append([
                            time.monotonic() + restart_after_s, victim,
                        ])
                elif e.kind == "gray_primary":
                    victim = servers[fleet.view % replica_count]
                    if victim.alive and not victim.stopped:
                        victim.sigstop()
                        report["events"]["gray_stops"] += 1
                        fleet.mark_fault(time.monotonic())
                        faults_armed += 1
                        fault_log.append((round(rel, 1), e.kind))
                        log(f"event: SIGSTOP replica {victim.index} "
                            f"for {e.arg or 3}s at t+{rel:.1f}s")
                        pending_cont.append([
                            time.monotonic() + (e.arg or 3), victim,
                        ])
                elif e.kind == "reset_conns":
                    for b in fleet.buses:
                        b.drop_connections()
                    report["events"]["conn_resets"] += 1
                    fleet.mark_fault(time.monotonic())
                    faults_armed += 1
                    fault_log.append((round(rel, 1), e.kind))
                    log(f"event: reset every client connection "
                        f"at t+{rel:.1f}s")
                elif e.kind == "disk_fault_on_restart":
                    disk_flip_armed = True
                    fault_log.append((round(rel, 1), e.kind))
                    log(f"event: next restart boots from a faulted WAL")
                elif e.kind == "slow_consumer":
                    # armed at boot (sink wiring is a start-time flag);
                    # the event timestamp records the scenario beat
                    fault_log.append((round(rel, 1), e.kind))
                    log(f"event: slow CDC consumer in effect "
                        f"(accept every "
                        f"{report['events']['slow_consumer_every']}th)")

            for entry in list(pending_restarts):
                when, srv = entry
                if now >= when and not srv.alive:
                    pending_restarts.remove(entry)
                    if disk_flip_armed:
                        disk_flip_armed = False
                        slots = inject_wal_fault(
                            srv.path, cluster_cfg, rng
                        )
                        report["events"]["disk_fault_slots"] = slots
                        log(f"event: disk-fault flip on replica "
                            f"{srv.index}'s WAL (slots {slots})")
                    srv.spawn(wait=False)
                    report["events"]["restarts"] += 1
                    owe_mark.append(srv)
                    log(f"event: replica {srv.index} restarting")
            for entry in list(pending_cont):
                when, srv = entry
                if now >= when:
                    pending_cont.remove(entry)
                    srv.sigcont()
                    owe_mark.append(srv)  # it slept through boundaries
                    log(f"event: SIGCONT replica {srv.index}")
            for srv in list(owe_mark):
                if srv.alive and not srv.stopped and srv.ready.is_set():
                    owe_mark.remove(srv)
                    if phase_now:
                        try:
                            send_mark("127.0.0.1", ports[srv.index],
                                      phase_now, timeout=2.0)
                        except (OSError, RuntimeError, ValueError):
                            owe_mark.append(srv)

            if rel >= next_harvest:
                next_harvest = rel + harvest_every_s
                harvest()

            if fleet.step_open(now) == 0:
                time.sleep(0.0005)

        drive_wall = time.monotonic() - t0
        log(f"timeline complete: {fleet.acked_events}/"
            f"{fleet.total_events} events acked in {drive_wall:.1f}s; "
            f"recoveries_ms="
            f"{[round(r) for r in fleet.recoveries_ms]}")
        for _w, srv in pending_restarts:  # tail kill: still owed boot
            if not srv.alive:
                srv.spawn(wait=False)
                report["events"]["restarts"] += 1
        for _w, srv in pending_cont:
            srv.sigcont()
        for srv in servers:
            if srv.proc is not None and srv.alive:
                srv.ready.wait(300.0)

        time.sleep(settle_s)
        total = fleet.acked_events + warm_events
        from tigerbeetle_tpu.state_machine import (
            decode_accounts,
            encode_ids,
        )

        dpo = cpo = found = 0
        for i in range(0, n_accounts, 8000):
            ids = list(range(1 + i, 1 + min(i + 8000, n_accounts)))
            body = fleet.execute(
                fleet.sessions[0], Operation.lookup_accounts,
                encode_ids(ids),
            )
            arr = decode_accounts(body)
            found += len(arr)
            dpo += int(arr["debits_posted_lo"].sum())
            cpo += int(arr["credits_posted_lo"].sum())
        conservation_ok = (found == n_accounts and dpo == cpo == total)
        log(f"wire conservation: debits={dpo} credits={cpo} "
            f"acked+warm={total} -> {'OK' if conservation_ok else 'FAIL'}")

        # catch-up barrier before the CDC tail is read: the stream can
        # only carry what replica 0 committed
        target = fleet.max_op
        t_w = time.monotonic()
        for s in servers:
            while True:
                if time.monotonic() - t_w > 300.0:
                    raise TimeoutError(
                        f"replica {s.index} never caught up to {target}"
                    )
                try:
                    live = inspect_live(
                        "127.0.0.1", ports[s.index], timeout=2.0
                    )
                    if live["commit_min"] >= target:
                        break
                except (OSError, RuntimeError, ValueError):
                    pass
                time.sleep(0.25)
        harvest()  # final rings, post-barrier

        parity = {}
        sentinels = {}
        for s in servers:
            stats = s.terminate()
            shadow = stats.get("device_shadow") or {}
            parity[f"r{s.index}"] = {
                "verified": shadow.get("verified"),
                "hash_log_ok": (shadow.get("hash_log") or {}).get("ok"),
            }
            if stats.get("compile_sentinel") is not None:
                sentinels[f"r{s.index}"] = stats["compile_sentinel"]
            if stats.get("phases"):
                report.setdefault("replica_phase_logs", {})[
                    f"r{s.index}"
                ] = stats["phases"]

        cdc = _parse_cdc_stream(cdc_path)
        parity_ok = True
        if backend == "dual":
            parity_ok = all(
                v["verified"] and v["hash_log_ok"] is not False
                for v in parity.values()
            )
        checks = {
            "conservation_ok": conservation_ok,
            "parity_ok": parity_ok,
            "cdc_dup_free": cdc["dup_ids"] == 0
            and cdc["transfers_bad"] == 0,
            "cdc_complete": cdc["unique_ids"] == total,
        }

        # phase measurements from the driver's own bookkeeping
        measures = {}
        end_ctr = _ctr()
        names = [p.name for p in timeline.phases]
        for i, p in enumerate(timeline.phases):
            pc = fleet.phase_counts.get(p.name)
            if not pc or not pc["offered"]:
                continue
            lat = sorted(fleet.latencies.get(p.name, ()))
            c0 = boundary_ctr.get(p.name)
            c1 = (boundary_ctr.get(names[i + 1])
                  if i + 1 < len(names) else None) or end_ctr
            sheds = (c1[0] - c0[0]) if c0 else 0
            touts = (c1[1] - c0[1]) if c0 else 0
            batches = max(1, pc["offered"] // events_per_batch)
            # client-perceived attempt success rate: every shed, runtime
            # timeout (each retry counts) and failed batch is one failed
            # attempt; each acked batch is one successful attempt.
            # Dividing failures by BATCHES instead would clamp a phase
            # with heavy retries to 0.0 "total outage" even though every
            # event eventually acked.
            attempts = batches + sheds + touts + pc["failed"]
            m = {
                "offered": pc["offered"],
                "acked": pc["acked"],
                "failed": pc["failed"],
                "sheds": sheds,
                "timeouts": touts,
                "availability": round(batches / attempts, 5),
                "shed_rate": round(min(1.0, sheds / attempts), 5),
            }
            if lat:
                m["p99_ms"] = round(
                    lat[min(len(lat) - 1,
                            int(0.99 * len(lat)))] * 1e3, 3
                )
                m["p50_ms"] = round(lat[len(lat) // 2] * 1e3, 3)
            measures[p.name] = m

        entries = [flight[k] for k in sorted(flight)]
        slices = slice_history(entries)
        card = score(
            timeline, slices, measures=measures,
            recoveries_ms=list(fleet.recoveries_ms),
            faults_armed=faults_armed, checks=checks,
        )

        snap = metrics.snapshot()["counters"]
        report.update({
            "wall_s": round(drive_wall, 2),
            "acked_events": fleet.acked_events,
            "offered_events": fleet.total_events,
            "unacked_events": fleet.outstanding(),
            "tps": round(fleet.acked_events / max(drive_wall, 1e-9), 1),
            "recoveries_ms": [
                round(r, 1) for r in fleet.recoveries_ms
            ],
            "fault_log": fault_log,
            "conservation": {"debits": dpo, "credits": cpo,
                             "expected": total},
            "checks": checks,
            "cdc": cdc,
            "slow_consumer_datagrams": slow_datagrams,
            "parity": parity,
            "compile_sentinel": sentinels,
            "phase_measures": measures,
            "flight_entries": len(entries),
            "client_errors": fleet.errors[:8],
            "bus_reconnects": snap.get("bus.reconnects", 0),
            "scorecard": card,
        })
        return report
    finally:
        if fleet is not None:
            fleet.close()
        for s in servers:
            s.sigcont()
            if s.proc is not None:
                kill_process_group(s.proc)
        if udp_rx is not None:
            udp_rx.close()
        if own_tmp:
            tmp.cleanup()


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--timeline", default="production_day",
                    choices=("production_day", "smoke"))
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="shrink phase durations (0.25 = quarter-length"
                         " rehearsal; SLOs and event order unchanged)")
    ap.add_argument("--rate-scale", type=float, default=1.0,
                    help="scale offered rates to the box's frontier")
    ap.add_argument("--sessions", type=int, default=32)
    ap.add_argument("--conns", type=int, default=4)
    ap.add_argument("--accounts", type=int, default=128)
    ap.add_argument("--events-per-batch", type=int, default=16)
    ap.add_argument("--backend", default="dual")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None,
                    help="write the full report JSON here")
    ap.add_argument("--artifact", default=None,
                    help="write the PRODDAY artifact here "
                         "(e.g. PRODDAY_r01.json)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    tl = (production_day() if args.timeline == "production_day"
          else smoke_timeline())
    if args.time_scale != 1.0 or args.rate_scale != 1.0:
        tl = scale_timeline(tl, time=args.time_scale,
                            rate=args.rate_scale)

    log = (lambda *_: None) if args.quiet else (
        lambda *a: print(*a, file=sys.stderr, flush=True)
    )
    cache_start = jax_cache_bytes()
    t0 = time.monotonic()
    report = run_prodday(
        tl,
        n_sessions=args.sessions,
        conns=args.conns,
        n_accounts=args.accounts,
        events_per_batch=args.events_per_batch,
        replica_count=args.replicas,
        backend=args.backend,
        seed=args.seed,
        log=log,
    )
    report["harness_wall_s"] = round(time.monotonic() - t0, 1)
    report["jax_cache_bytes_start"] = cache_start
    report["jax_cache_bytes_end"] = jax_cache_bytes()

    card = report["scorecard"]
    for r in card["rows"]:
        state = {True: "PASS", False: "FAIL", None: "no-data"}[r["pass"]]
        extra = ""
        if r["pass"] is False and r.get("dominant_leg"):
            extra = (f"  dominant={r['dominant_leg']}"
                     f" ({r['dominant_leg_share']:.0%})")
            if r.get("dominant_device_subleg"):
                extra += f" device={r['dominant_device_subleg']}"
        m = r["measured"]
        if isinstance(m, dict):
            m = ",".join(k for k, v in sorted(m.items()) if not v) or "ok"
        print(f"{state:7} {r['phase']:>14} {r['slo']:<14} "
              f"measured={m} budget={r['budget']}{extra}")
    print(f"scorecard: {'PASS' if card['pass'] else 'FAIL'} "
          f"({card['violations']} violations, "
          f"{card['no_data']} no-data rows)")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=str)
            f.write("\n")
        print(f"wrote {args.out}")
    if args.artifact:
        segments_incomplete = []
        if report["unacked_events"]:
            segments_incomplete.append("drive_drain")
        if card["no_data"]:
            segments_incomplete.append("scorecard_no_data_rows")
        parsed = dict(report)
        parsed["compile_sentinel"] = report.get("compile_sentinel")
        artifact = wrap_artifact(
            cmd="python scripts/prodday.py "
                + " ".join(sys.argv[1:]),
            rc=0,
            env=f"TB_JAX_PLATFORM=cpu seed={args.seed}",
            tail="",
            parsed=parsed,
            segments_incomplete=segments_incomplete,
            # this harness pins its servers to the CPU (see run_prodday):
            # the platform asked for by name is the one observed
            backend="cpu",
        )
        with open(args.artifact, "w") as f:
            json.dump(artifact, f, indent=1, default=str)
            f.write("\n")
        print(f"wrote {args.artifact}")
    return 0 if card["pass"] else 2


if __name__ == "__main__":
    sys.exit(main())
