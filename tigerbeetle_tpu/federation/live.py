"""Live federation: real multi-region clusters, real settlement agent.

`run_federation_chaos` is the wall-clock twin of `federation/sim.py`'s
SimFederation, on the production stack: each region is a real N-replica
TCP cluster (`tigerbeetle_tpu start` processes with `--commitment-
interval`, `--federation-region`, and an AOF-backed `--cdc-jsonl` tail
on replica 0), the settlement agent is the SAME sans-IO `SettlementCore`
tailing the region's CDC JSONL file and posting mirror/resolve legs
through the fault-tolerant client runtime, and the region-level fault is
a real SIGKILL of EVERY replica process of one region mid-settlement
(`--kill-cluster` on the chaos CLI) followed by a whole-cluster restart
from disk.

Verification after the storm, all over the wire:

- cross-region conservation per ordered pair: escrow(a->b) posted
  credits on a == mirror posted debits on b == the amounts the harness
  issued toward valid beneficiaries; zero pending escrow residue (the
  void slice came back out);
- commitment-chain audit: each region's CDC JSONL replays through
  `inspect.verify_commitment_stream` (a fresh-oracle StreamVerifier) and
  the recomputed chain head must equal the head the region's replica 0
  published in its shutdown [stats] — the exact check a settlement
  counterparty runs before trusting a region's stream.

The stream tail here is deliberately paranoid about the JSONL file's
at-least-once framing: a SIGKILLed streamer leaves a torn tail line
that the next incarnation's append glues onto (skipped, counted), and
redelivery restarts below the high-water op (the possibly-torn trailing
group is discarded — the redelivery carries it complete). A group is
fed to the core only once a HIGHER op's line proves its emit completed.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
from collections import deque

import numpy as np

from tigerbeetle_tpu import types
from tigerbeetle_tpu.federation.agent import SettlementCore
from tigerbeetle_tpu.federation.topology import (
    FEDERATION_LEDGER,
    SETTLE_CODE,
    FederationTopology,
    escrow_account_id,
    home_account_id,
    mirror_account_id,
    origin_id,
)
from tigerbeetle_tpu.types import (
    CREATE_TRANSFERS_RESULT_DTYPE,
    Account,
    Operation,
    Transfer,
    TransferFlags,
)

HOME_ACCOUNTS = 4  # pinned user accounts per region (matches the sim)
HEARTBEAT_ID_TAG = 0xB0  # heartbeat account id: tag<<120 | region


def _dense_codes(reply_body: bytes, n: int) -> list:
    codes = [0] * n
    if reply_body:
        sparse = np.frombuffer(reply_body, dtype=CREATE_TRANSFERS_RESULT_DTYPE)
        for i, code in zip(sparse["index"], sparse["result"]):
            codes[int(i)] = int(code)
    return codes


class _StreamTail:
    """Incremental reader of a region's CDC JSONL with at-least-once
    framing (module docstring): yields per-op line groups that are
    PROVEN complete — a group is released only when a line of a higher
    op follows it (emission is per-op and file writes preserve order),
    and a redelivery restarting below the current group discards it
    (the redelivery re-carries it complete)."""

    def __init__(self, path: str):
        self.path = path
        self._pos = 0
        self._buf = ""
        self._group: tuple | None = None  # (op, [raw lines])
        self.ready: deque = deque()  # complete groups awaiting the core
        self.torn_lines = 0
        self.discarded_groups = 0

    def poll(self) -> int:
        """Read newly appended bytes; returns complete groups released."""
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                f.seek(self._pos)
                chunk = f.read()
                self._pos = f.tell()
        except FileNotFoundError:
            return 0
        if not chunk:
            return 0
        data = self._buf + chunk
        lines = data.split("\n")
        self._buf = lines.pop()  # trailing partial (or "")
        released = 0
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                # a SIGKILL tore the previous incarnation's tail line and
                # this incarnation's first append glued onto it; the
                # durable cursor redelivers the op intact
                self.torn_lines += 1
                continue
            kind = rec.get("kind")
            # gaps carry a range, not an op; order them at their start
            op = int(rec["from"]) if kind == "gap" else int(rec.get("op", 0))
            if self._group is None:
                self._group = (op, [line])
            elif op == self._group[0]:
                self._group[1].append(line)
            elif op > self._group[0]:
                # a higher op proves the held group's emit completed
                self.ready.append(self._group)
                released += 1
                self._group = (op, [line])
            else:
                # redelivery below the held group: it may be torn —
                # drop it, the redelivery carries it complete
                self.discarded_groups += 1
                self._group = (op, [line])
        return released

    @property
    def held_op(self) -> int:
        """Op of the group awaiting proof-of-completion (0 = none)."""
        return self._group[0] if self._group is not None else 0


class LiveSettlementAgent:
    """One region's settlement agent over the live stack: a
    `SettlementCore` fed from the region's CDC JSONL tail, legs posted
    synchronously through the regions' client fleets (the runtime owns
    retries/failover — a whole-region outage just makes the request
    wait out the restart)."""

    def __init__(self, region: int, topology: FederationTopology,
                 tail: _StreamTail, fleets: list, metrics=None,
                 window: int = 128, request_deadline_s: float = 180.0):
        self.region = region
        self.tail = tail
        self.fleets = fleets
        self.request_deadline_s = request_deadline_s
        self.core = SettlementCore(
            topology, region, window=window, metrics=metrics,
        )
        # settlement lag: committed ops the region's cluster is ahead of
        # the agent's watermark while legs are unfinished (ops, not ms —
        # comparable across rigs and with the sim's bound)
        self.max_lag_ops = 0

    def _create(self, target_region: int, transfers: list) -> list:
        fleet = self.fleets[target_region]
        body = fleet.execute(
            fleet.sessions[1], Operation.create_transfers,
            types.transfers_to_np(transfers).tobytes(),
            deadline_s=self.request_deadline_s,
        )
        return _dense_codes(body, len(transfers))

    def step(self) -> bool:
        """One drive turn: ingest stream groups, push staged legs.
        Returns True when anything moved."""
        progressed = self.tail.poll() > 0
        core = self.core
        while self.tail.ready:
            op, lines = self.tail.ready[0]
            if not core.emit_lines(lines):
                break  # window full: the deque still holds the op
            self.tail.ready.popleft()
            progressed = True
        if core.error is not None:
            raise AssertionError(f"agent r{self.region}: {core.error}")
        if core.pending_count():
            self.max_lag_ops = max(
                self.max_lag_ops,
                self.fleets[self.region].max_op - core.watermark(),
            )
        for dst in sorted(core.dsts_with_work()):
            legs = core.next_mirror_batch(dst, limit=16)
            if not legs:
                continue
            try:
                codes = self._create(dst, core.mirror_transfers(legs))
            except TimeoutError:
                core.on_request_failed(legs)
                raise
            core.on_mirror_replies(legs, codes)
            progressed = True
        legs = core.next_resolve_batch(limit=16)
        if legs:
            try:
                codes = self._create(self.region, core.resolve_transfers(legs))
            except TimeoutError:
                core.on_request_failed(legs)
                raise
            core.on_resolve_replies(legs, codes)
            progressed = True
        return progressed

    def idle(self) -> bool:
        return (
            self.core.idle()
            and not self.tail.ready
        )


def run_federation_chaos(
    regions: int = 2,
    replica_count: int = 3,
    payments: int = 24,
    batch: int = 4,
    commitment_interval: int = 8,
    void_fraction: float = 0.15,
    kill_cluster: bool = True,
    restart_after_s: float = 1.5,
    backend: str = "native",
    seed: int = 1,
    jax_platform: str | None = "cpu",
    deadline_s: float = 600.0,
    settle_deadline_s: float = 300.0,
    tmpdir: str | None = None,
    log=None,
) -> dict:
    """The `--kill-cluster` chaos mode (module docstring). `payments` is
    the number of cross-region origin pendings issued PER region, half
    before and half after the mid-run region kill."""
    import tempfile

    from tigerbeetle_tpu.benchmark import (
        REPO,
        free_port,
        kill_process_group,
        require_one_process_per_chip,
    )
    from tigerbeetle_tpu.inspect import inspect_live, verify_commitment_stream
    from tigerbeetle_tpu.metrics import Metrics
    from tigerbeetle_tpu.state_machine import decode_accounts, encode_ids
    from tigerbeetle_tpu.testing.chaos import ChaosFleet, ChaosServer

    assert regions >= 2, "federation needs at least two regions"
    log = log or (lambda *_: None)
    rng = random.Random(seed)
    own_tmp = tmpdir is None
    if own_tmp:
        tmp = tempfile.TemporaryDirectory(prefix="tb_fed_")
        tmpdir = tmp.name

    topology = FederationTopology.of(regions)
    clients_max = 8
    session_args = ("--clients-max", str(clients_max))
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, PYTHONPATH=f"{REPO}:{pp}" if pp else REPO,
               TB_PARENT_WATCHDOG="1")
    if jax_platform:
        env["TB_JAX_PLATFORM"] = jax_platform
    require_one_process_per_chip(
        "federation", backend, regions * replica_count, jax_platform
    )

    region_ports: list[list[int]] = []
    servers: list[list[ChaosServer]] = []
    cdc_paths: list[str] = []
    fmt_procs = []
    for r in range(regions):
        ports = [free_port() for _ in range(replica_count)]
        region_ports.append(ports)
        addresses = ",".join(f"127.0.0.1:{p}" for p in ports)
        cdc_path = os.path.join(tmpdir, f"region{r}_cdc.jsonl")
        cdc_paths.append(cdc_path)
        row = []
        for i in range(replica_count):
            path = os.path.join(tmpdir, f"region{r}_{i}.tigerbeetle")
            fmt_procs.append(subprocess.Popen(
                [sys.executable, "-m", "tigerbeetle_tpu", "format",
                 "--cluster", str(7000 + r), "--replica", str(i),
                 "--replica-count", str(replica_count),
                 *session_args, path],
                cwd=REPO, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
            extra: tuple = (
                "--account-slots-log2", "14",
                "--transfer-slots-log2", "14",
                "--commitment-interval", str(commitment_interval),
                "--federation-region", str(r),
                "--federation-regions", str(regions),
            )
            if i == 0:
                # the streamed replica: AOF so deep resume never gaps,
                # ack-interval 1 so the JSONL is flushed per op (the
                # live agent tails the file, not a socket)
                extra = extra + (
                    "--aof", os.path.join(tmpdir, f"region{r}.aof"),
                    "--cdc-jsonl", cdc_path,
                    "--cdc-cursor", cdc_path + ".cursor",
                    "--cdc-ack-interval", "1",
                )
            row.append(ChaosServer(
                i, addresses, path, env, backend, session_args, extra,
                lambda *a, _r=r: log(f"[region {_r}]", *a),
            ))
        servers.append(row)
    for p in fmt_procs:
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out

    metrics = Metrics()
    fleets: list[ChaosFleet] = []
    report: dict = {
        "regions": regions, "replicas": replica_count, "backend": backend,
        "payments_per_region": payments, "kills": 0, "restarts": 0,
    }
    t_run = time.monotonic()
    try:
        for row in servers:
            for s in row:
                s.spawn(wait=False)
        for row in servers:
            for s in row:
                if not s.ready.wait(300.0):
                    raise TimeoutError(
                        f"federation replica never listened ({s.path})"
                    )
        log(f"{regions} regions x {replica_count} replicas up in "
            f"{time.monotonic() - t_run:.1f}s")

        # two sessions per region: [0] workload/verification, [1] the
        # settlement write lane (both regions' agents share it — the
        # drive loop is single-threaded, requests are sequential)
        for r in range(regions):
            fleet = ChaosFleet(region_ports[r], 2, 1, metrics)
            fleet.register_all()
            fleets.append(fleet)

        # infrastructure + pinned home accounts, idempotent creates
        for r in range(regions):
            ids = topology.infra_account_ids(r) + [
                home_account_id(r, k, regions) for k in range(HOME_ACCOUNTS)
            ]
            accounts = [
                Account(id=i, ledger=FEDERATION_LEDGER, code=SETTLE_CODE)
                for i in ids
            ]
            body = fleets[r].execute(
                fleets[r].sessions[0], Operation.create_accounts,
                types.accounts_to_np(accounts).tobytes(),
            )
            assert body == b"", f"region {r} bootstrap failed"
        log("federation accounts bootstrapped")

        agents = [
            LiveSettlementAgent(
                r, topology, _StreamTail(cdc_paths[r]), fleets, metrics,
            )
            for r in range(regions)
        ]
        issued_seq = [0] * regions
        # expected POSTED amount per ordered pair (valid beneficiaries
        # only — the void slice must come back out of escrow)
        expected_posted: dict = {}
        issued_amount = 0
        void_targets = 0

        def issue(region: int, count: int) -> None:
            nonlocal issued_amount, void_targets
            fleet = fleets[region]
            left = count
            while left > 0:
                transfers = []
                for _ in range(min(batch, left)):
                    dst = rng.choice(
                        [d for d in range(regions) if d != region]
                    )
                    payer = home_account_id(
                        region, rng.randrange(HOME_ACCOUNTS), regions
                    )
                    void = rng.random() < void_fraction
                    k = (HOME_ACCOUNTS + rng.randrange(4)) if void \
                        else rng.randrange(HOME_ACCOUNTS)
                    beneficiary = home_account_id(dst, k, regions)
                    issued_seq[region] += 1
                    amount = rng.randint(1, 100)
                    issued_amount += amount
                    if void:
                        void_targets += 1
                    else:
                        key = (region, dst)
                        expected_posted[key] = (
                            expected_posted.get(key, 0) + amount
                        )
                    transfers.append(Transfer(
                        id=origin_id(region, issued_seq[region]),
                        debit_account_id=payer,
                        credit_account_id=escrow_account_id(region, dst),
                        amount=amount,
                        ledger=FEDERATION_LEDGER,
                        code=SETTLE_CODE,
                        flags=int(TransferFlags.pending),
                        user_data_128=beneficiary,
                    ))
                body = fleet.execute(
                    fleet.sessions[0], Operation.create_transfers,
                    types.transfers_to_np(transfers).tobytes(),
                )
                assert body == b"", (
                    f"origin pending rejected on region {region}"
                )
                left -= len(transfers)

        def heartbeat(region: int) -> None:
            """Commit a no-op op so the stream advances past the tail's
            held group (idempotent duplicate create; `exists` is fine)."""
            fleets[region].execute(
                fleets[region].sessions[0], Operation.create_accounts,
                types.accounts_to_np([Account(
                    id=(HEARTBEAT_ID_TAG << 120) | region,
                    ledger=FEDERATION_LEDGER, code=SETTLE_CODE,
                )]).tobytes(),
            )

        def outbound_total() -> int:
            return sum(a.core.stats["outbound_seen"] for a in agents)

        def drain(target_outbound: int, phase: str) -> None:
            t0 = time.monotonic()
            while True:
                if time.monotonic() - t0 > settle_deadline_s:
                    raise TimeoutError(
                        f"settlement stalled ({phase}): " + str([
                            (a.region, a.core.pending_count(),
                             a.tail.held_op) for a in agents
                        ])
                    )
                progressed = False
                for a in agents:
                    progressed |= a.step()
                if (outbound_total() >= target_outbound
                        and all(a.idle() for a in agents)):
                    return
                if not progressed:
                    # the tail may be holding the LAST committed op's
                    # group (released only by a higher op): push one
                    for a in agents:
                        if not a.idle() or a.tail.held_op:
                            heartbeat(a.region)
                    time.sleep(0.05)

        t_drive = time.monotonic()
        half = payments // 2
        for r in range(regions):
            issue(r, half)
        drain(half * regions, "pre-kill settle")
        log(f"phase 1 settled: {outbound_total()} outbound legs")

        # second wave lands, then the region-level fault mid-settlement
        for r in range(regions):
            issue(r, payments - half)
        for a in agents:  # partial progress: staged-but-unresolved legs
            a.step()

        victim = rng.randrange(regions) if kill_cluster else None
        if victim is not None:
            for s in servers[victim]:
                if s.alive:
                    s.sigcont()
                    s.kill()
                    report["kills"] += 1
            fleets[victim].mark_fault(time.monotonic())
            log(f"chaos: SIGKILL region {victim} (all {replica_count} "
                f"replicas) mid-settlement")
            time.sleep(restart_after_s)
            for s in servers[victim]:
                s.spawn(wait=False)
                report["restarts"] += 1
            for s in servers[victim]:
                if not s.ready.wait(300.0):
                    raise TimeoutError(
                        f"region {victim} replica {s.index} never "
                        "relistened"
                    )
            log(f"chaos: region {victim} restarted from disk")

        drain(payments * regions, "post-kill settle")
        drive_wall = time.monotonic() - t_drive
        log(f"all {payments * regions} origin pendings settled in "
            f"{drive_wall:.1f}s")

        # -- conservation, over the wire -------------------------------
        def account_row(region: int, account_id: int):
            body = fleets[region].execute(
                fleets[region].sessions[0], Operation.lookup_accounts,
                encode_ids([account_id]),
            )
            arr = decode_accounts(body)
            assert len(arr) == 1, f"missing account {account_id:#x}"
            return arr[0]

        pairs = {}
        for a in range(regions):
            for b in range(regions):
                if a == b:
                    continue
                esc = account_row(a, escrow_account_id(a, b))
                mir = account_row(b, mirror_account_id(b, a))
                posted = int(esc["credits_posted_lo"])
                assert posted == int(mir["debits_posted_lo"]), (
                    f"conservation broken {a}->{b}: escrow {posted} != "
                    f"mirror {int(mir['debits_posted_lo'])}"
                )
                assert int(esc["credits_pending_lo"]) == 0, (
                    f"unresolved escrow residue {a}->{b}"
                )
                assert posted == expected_posted.get((a, b), 0), (
                    f"settled amount drift {a}->{b}: {posted} != "
                    f"{expected_posted.get((a, b), 0)} issued"
                )
                pairs[f"{a}->{b}"] = posted
        log(f"cross-region conservation verified: {pairs}")

        # catch-up barrier before the SIGTERM drain (as run_chaos): the
        # final stream flush can only carry what each replica committed
        for r in range(regions):
            target = fleets[r].max_op
            for s in servers[r]:
                t_w = time.monotonic()
                while True:
                    if time.monotonic() - t_w > 300.0:
                        raise TimeoutError(
                            f"region {r} replica {s.index} never caught "
                            f"up to op {target}"
                        )
                    try:
                        live = inspect_live(
                            "127.0.0.1", region_ports[r][s.index],
                            timeout=2.0,
                        )
                        if live["commit_min"] >= target:
                            break
                    except (OSError, RuntimeError, ValueError):
                        pass
                    time.sleep(0.25)

        # graceful shutdown: replica 0's [stats] carries the published
        # commitment head + the federation identity stamp
        heads = {}
        for r in range(regions):
            for s in servers[r]:
                stats = s.terminate()
                if s.index == 0:
                    fed = stats.get("federation") or {}
                    assert fed.get("region") == r, (r, fed)
                    heads[r] = stats.get("commitments") or {}

        # -- the counterparty audit ------------------------------------
        stream_verify = {}
        for r in range(regions):
            rep = verify_commitment_stream(cdc_paths[r])
            assert rep["ok"], f"region {r} stream verify: {rep}"
            assert rep["checked"] > 0, f"region {r}: no checkpoints"
            assert rep["head_op"] == heads[r].get("head_op"), (
                f"region {r}: verifier head_op {rep['head_op']} != "
                f"published {heads[r].get('head_op')}"
            )
            assert rep["head"] == heads[r].get("head"), (
                f"region {r}: verifier head != published head"
            )
            stream_verify[str(r)] = {
                "checked": rep["checked"],
                "head_op": rep["head_op"],
                "ops_replayed": rep["ops_replayed"],
                "torn_lines": rep.get("torn_lines", 0),
                "redelivered_records": rep.get("redelivered_records", 0),
            }
        log("commitment streams verified against published heads")

        totals = [a.core.stats for a in agents]
        report.update({
            "issued": sum(issued_seq),
            "issued_amount": issued_amount,
            "settled": sum(t["legs_posted"] for t in totals),
            "voided": sum(t["legs_voided"] for t in totals),
            "void_targets": void_targets,
            "redeliveries": sum(t["redeliveries"] for t in totals),
            "settlement_lag_max_ops": max(
                a.max_lag_ops for a in agents
            ),
            "torn_lines": sum(a.tail.torn_lines for a in agents),
            "discarded_groups": sum(
                a.tail.discarded_groups for a in agents
            ),
            "region_killed": victim,
            "recovery_ms": (
                round(fleets[victim].recoveries_ms[0], 1)
                if victim is not None and fleets[victim].recoveries_ms
                else None
            ),
            "conservation": {"ok": True, "settled_amount": pairs},
            "commitment_heads": {
                str(r): [heads[r].get("head_op"), heads[r].get("head")]
                for r in range(regions)
            },
            "stream_verify": stream_verify,
            "wall_s": round(time.monotonic() - t_run, 2),
            "drive_wall_s": round(drive_wall, 2),
        })
        return report
    finally:
        for fleet in fleets:
            fleet.close()
        for row in servers:
            for s in row:
                s.sigcont()
                if s.proc is not None:
                    kill_process_group(s.proc)
        if own_tmp:
            tmp.cleanup()
