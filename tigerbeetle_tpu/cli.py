"""The process CLI: format | start | version | client | repl.

The reference's surface (reference: src/tigerbeetle/main.zig:26-33
composition root, src/tigerbeetle/cli.zig:54-116 flags):

  python -m tigerbeetle_tpu format --cluster=0 --replica=0 \
      --replica-count=1 data.tigerbeetle
  python -m tigerbeetle_tpu start --addresses=127.0.0.1:3001 [--aof=f] \
      data.tigerbeetle
  python -m tigerbeetle_tpu version
  python -m tigerbeetle_tpu repl --addresses=...

One dataclass per command is the whole CLI surface (the reference derives
its CLI from structs the same way, src/flags.zig); `flags.parse`
introspects it. `start` is the composition root: FileStorage +
TCPMessageBus + RealTime injected into the Replica, then the event loop
(bus pump + replica ticks at tick_ms; reference: main.zig start loop).
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time

from tigerbeetle_tpu import flags
from tigerbeetle_tpu.flags import positional

VERSION = "0.3.0"


@dataclasses.dataclass
class FormatArgs:
    file: str = positional("data file path")
    cluster: int = 0
    replica: int = 0
    replica_count: int = 1
    grid_mb: int = 64
    # Session capacity (consensus-affecting: part of the config
    # fingerprint, so format and start must agree). clients_max is the
    # replicated client-table cap; client_reply_slots caps the DURABLE
    # reply slots separately (each costs message_size_max on disk —
    # 10k+ multiplexed sessions cannot each own one; 0 = one per
    # client, the pre-ingress layout).
    clients_max: int = 32
    client_reply_slots: int = 0


BACKENDS = ("native", "dual", "device", "sharded")


@dataclasses.dataclass
class StartArgs:
    addresses: str  # comma-separated host:port per replica
    file: str = positional("data file path")
    replica: int = 0
    grid_mb: int = 64
    account_slots_log2: int = 20
    transfer_slots_log2: int = 24
    aof: str = ""  # append-only disaster-recovery log path
    statsd: str = ""  # statsd host | :port | host:port (batched emission)
    # Change-data-capture (tigerbeetle_tpu/cdc): attach a live CdcPump
    # tailing this replica's committed ops into a JSONL file and/or UDP
    # datagrams. The pump rides the event loop with a bounded per-turn
    # budget and pauses (never the replica) when the sink refuses.
    cdc_jsonl: str = ""  # change-stream JSONL path
    cdc_udp: str = ""  # change-stream UDP host | :port | host:port
    cdc_cursor: str = ""  # cursor file (default: <cdc-jsonl>.cursor)
    cdc_window: int = 256  # live in-flight window (ops)
    # Ops between durable-cursor acks. Every ack flushes the sink first,
    # so this is ALSO the staleness bound an external tailer of the JSONL
    # file sees; the live federation agent runs 1 (flush per op).
    cdc_ack_interval: int = 32
    # Deliberately slow consumer model (bench A/B): the sink accepts at
    # most one op's records per this many microseconds, REFUSING (not
    # sleeping) in between — backpressure without blocking the loop.
    cdc_slow_us: int = 0
    # Count-throttled slow consumer (the prodday timeline's laggard;
    # live analog of the simulator's _FanoutStore throttle_every): the
    # LAST named sink accepts only every K-th emission attempt. Under
    # --cdc-fanout only that consumer lags (its fanout position falls
    # behind; ingress.fanout_lag_ops names the gap). 0 disables.
    cdc_slow_every: int = 0
    # dump a Chrome trace-event JSON (Perfetto-loadable) of the commit
    # pipeline's spans to this path on shutdown (SIGTERM)
    trace: str = ""
    commit_window: int = 16  # async commits in flight (0 = sync); a full
    # GROUP_MAX fused group stays un-drained while the next one arrives
    # Group-commit fuse window in MICROSECONDS (0 disables): a short
    # quorum-ready run of create_transfers holds this long — only while
    # earlier commits are in flight — so near-simultaneous arrivals
    # coalesce into one fused dispatch (vsr/replica.py fuse_window_ns).
    # -1 (the default) AUTOTUNES: AIMD from observed hold outcomes —
    # expired-short holds widen the window, holds that fill to GROUP_MAX
    # shrink it (bounded 500us..8ms; starts at 2000us). The r05 driver's
    # 0.46 hit rate against the CPU A/B's 0.85 motivated making the
    # window track the workload instead of trusting one constant.
    fuse_window_us: int = -1
    # Commit backend: "native" = the C++ host engine (native/ledger.cc —
    # the durable hot path, replies at host speed; why the device is not
    # on the reply path by default: models/native_ledger.py),
    # "dual" = dual commit: native serves replies while the REPLICA
    # enqueues committed ops to the device applier at commit finalize
    # (h2d only) and shutdown verifies the device state bit-exact
    # (models/dual_ledger.py) — rolling per-op hash-log rings (first
    # divergent op named exactly), bounded-lag admission backpressure,
    # checkpoint/state-sync drains, and restart recovery via snapshot
    # row install,
    # "device" = the JAX DeviceLedger (the TPU compute path; supports
    # HBM->LSM spill), "sharded" = the multi-chip ShardedLedger over a
    # jax.sharding.Mesh (parallel/mesh.py; slots flags are PER SHARD).
    backend: str = "native"
    # Dual commit: device-applier lag (committed ops not yet
    # dispatched to the device) beyond this window throttles admission
    # (Replica.ingress_occupancy / the _on_request cap) instead of
    # growing without bound.
    device_lag_window: int = 128
    # hash_log surface (testing/hash_log.py; reference -Dhash-log-mode,
    # src/testing/hash_log.zig): "record:<path>" streams one prepare/reply
    # checksum pair per committed op to <path> at shutdown; "check:<path>"
    # replays against a recording and fails AT the first divergent op.
    # A bare "<path>" records.
    hash_log: str = ""
    shards: int = 0  # sharded backend: devices in the mesh (0 = all)
    # Session capacity — MUST match the values the data file was
    # formatted with (config fingerprint; see FormatArgs).
    clients_max: int = 32
    client_reply_slots: int = 0
    # Ingress gateway (tigerbeetle_tpu/ingress): session-multiplexed
    # admission front door. --ingress installs the gateway (credit-based
    # admission fed by pipeline occupancy + pool budget; saturated
    # requests get a typed busy reply instead of queueing or dropping).
    ingress: bool = False
    ingress_sessions_max: int = 0  # gateway session-table cap (0 = uncapped)
    ingress_backlog: int = 1024  # TCP listen backlog (accept-drain loop)
    ingress_accept_budget: int = 256  # accepts drained per readiness event
    ingress_dispatch_budget: int = 256  # frames per connection per pump turn
    # CDC fan-out: with BOTH --cdc-jsonl and --cdc-udp, give each sink
    # its own consumer (cursor + position) over one shared tail — a slow
    # sink pauses only itself (ingress/fanout.py). Default keeps the
    # PR-4 behavior: one pump, one cursor, all sinks move together.
    cdc_fanout: bool = False
    # Per-request critical-path attribution (tigerbeetle_tpu/latency.py):
    # one request in N is stamped at every pipeline leg and folded into
    # the latency.* histograms at reply egress; the slowest sampled
    # requests keep full breakdowns (SIGQUIT dump + `inspect live`).
    # 1 = every request (regression hunting), 0 = off.
    latency_sample_every: int = 16
    # Flight recorder (metrics.py FlightRecorder): seconds between
    # time-series snapshots of the registry (counter deltas + windowed
    # histogram percentiles), ring of ~180 entries served through the
    # [stats] wire command (`inspect live --watch`). 0 disables.
    flight_interval_s: float = 1.0
    # One bounded jax.profiler window (every backend that holds a chip)
    # into this directory, opened at the first request after serving
    # begins: the xplane holds the device's kernels AND the program's
    # own spans (`tb.*`, tracer.ProfilerTracer) on one clock.
    device_trace: str = ""
    device_trace_s: float = 3.0  # window length (seconds)
    # Checkpoint state commitments (federation/commitment.py): fold the
    # ledger's state fingerprint into a hash chain at every op multiple
    # of this interval. The chain rides checkpoints (restart-stable),
    # the [stats] snapshot, `inspect commitments`, and — when a CDC sink
    # is attached — the change stream itself as `commitment` records an
    # external consumer verifies with `inspect commitments --stream`.
    # 0 disables.
    commitment_interval: int = 0
    # Cross-ledger federation identity (federation/topology.py): which
    # region of an N-region federation this cluster is. Purely
    # declarative on the server (settlement runs in the agent process —
    # scripts/federate.py), but stamped into the [stats] snapshot so
    # operators and the live harness can tell regions apart.
    federation_region: int = -1
    federation_regions: int = 0

    def __post_init__(self):
        # refused where the arguments are parsed: before the data file
        # is opened, the socket bound or a device asked for
        if self.backend not in BACKENDS:
            flags.fatal(
                f"unknown --backend {self.backend!r} ({'|'.join(BACKENDS)})"
            )


@dataclasses.dataclass
class ReplArgs:
    addresses: str
    cluster: int = 0


@dataclasses.dataclass
class InspectArgs:
    """Offline data-file + live-state introspection (tigerbeetle_tpu/
    inspect.py; reference: src/tigerbeetle/inspect.zig). Topics:
    superblock | wal | replies | grid | lsm | client-table | all decode
    the data file; live reads the [stats] registry snapshot off a
    running server (--addresses)."""

    topic: str = positional(
        "superblock | wal | replies | grid | lsm | client-table | all | "
        "live | commitments"
    )
    file: str = dataclasses.field(
        default="", metadata={"positional": True,
                              "help": "data file path (offline topics)"}
    )
    op: int = -1  # wal: dump ONE prepare (inspect wal --op N)
    slot: int = -1  # wal: restrict the scan to one slot
    addresses: str = ""  # live: host:port of the running replica
    json: bool = False  # machine-readable report
    # live repeated-snapshot mode: re-poll every N seconds and print
    # per-interval deltas/rates from the server's flight-recorder
    # history (works against wedged replicas like single-shot live)
    watch: float = 0.0
    watch_count: int = 0  # stop after N polls (0 = until interrupted)
    # geometry the file was formatted with (same contract as `start`:
    # only non-defaults need repeating; the grid size is inferred from
    # the file size)
    clients_max: int = 32
    client_reply_slots: int = 0
    forest_blocks: int = 0  # LSM forest geometry (spill-enabled files)
    # `commitments` topic, verify mode: replay this CDC stream JSONL
    # through a fresh oracle and re-derive the commitment chain — a
    # tampered stream/state fails naming the exact checkpoint. With
    # --addresses instead, reads the live chain off the [stats] wire;
    # with a data file, decodes the checkpointed chain offline.
    stream: str = ""


@dataclasses.dataclass
class ChaosArgs:
    """Live-cluster chaos run (testing/chaos.py): spawn a real N-replica
    TCP cluster + a multiplexed client fleet on the fault-tolerant
    client runtime, inject live faults (SIGKILL/restart, SIGSTOP gray
    failure, connection resets, a WAL disk-fault flip on restart), and
    verify zero lost / zero duplicated transfers (client replies vs CDC
    stream vs wire conservation, dual-mode hash-log parity), reporting
    time-to-first-commit-after-kill."""

    sessions: int = 64
    conns: int = 4
    accounts: int = 128
    events_per_batch: int = 16
    batches_per_session: int = 6
    replicas: int = 3
    backend: str = "native"
    faults: str = "kill_primary"  # comma list, see CHAOS_ACTIONS
    restart_after_s: float = 2.0
    gray_s: float = 3.0
    disk_fault: bool = True  # flip WAL bytes on the first restart
    ingress: bool = False  # front every replica with the gateway
    seed: int = 1
    deadline_s: float = 600.0
    json: str = ""  # write the full report here too
    # Region-level federation mode (federation/live.py): spawn
    # --federation-regions whole clusters, run the live settlement agent
    # between them, SIGKILL EVERY replica of one region mid-settlement,
    # restart it from disk, and verify cross-region conservation plus
    # each region's commitment stream against its published head. The
    # per-session workload knobs above don't apply; `payments` origin
    # pendings are issued per region.
    kill_cluster: bool = False
    federation_regions: int = 2
    payments: int = 24
    commitment_interval: int = 8


@dataclasses.dataclass
class CdcArgs:
    """Offline change-stream tool: replay an AOF into a sink, resuming
    from (and advancing) a durable consumer cursor. The disaster-recovery
    log is the complete committed history from op 1; result codes are
    regenerated exactly by replaying each prepare through the scalar
    oracle (parity-locked with the device engines)."""

    file: str = positional("append-only file (AOF) path")
    consumer: str = "default"  # cursor namespace
    cursor: str = ""  # cursor file (default: <aof>.<consumer>.cursor)
    sink: str = "stdout"  # stdout | jsonl:<path> | udp:host[:port]
    limit: int = 0  # stop after N ops (0 = to end of log)


def _parse_addresses(s: str) -> list[tuple[str, int]]:
    out = []
    for part in s.split(","):
        host, _, port = part.strip().rpartition(":")
        out.append((host or "127.0.0.1", int(port)))
    return out


def _storage(path: str, cluster_cfg, create: bool, grid_mb: int):
    from tigerbeetle_tpu.io.storage import FileStorage, ZoneLayout

    layout = ZoneLayout(cluster_cfg, grid_size=grid_mb * 1024 * 1024)
    return FileStorage(path, layout, create=create)


def cmd_format(args) -> int:
    from tigerbeetle_tpu.constants import ConfigCluster
    from tigerbeetle_tpu.vsr.durable import format_data_file

    cluster_cfg = ConfigCluster(
        replica_count=args.replica_count,
        clients_max=args.clients_max,
        client_reply_slots=args.client_reply_slots,
    )
    storage = _storage(args.file, cluster_cfg, create=True, grid_mb=args.grid_mb)
    format_data_file(
        storage, cluster_cfg, cluster_id=args.cluster, replica=args.replica
    )
    storage.close()
    print(f"formatted {args.file}: cluster={args.cluster} "
          f"replica={args.replica}/{args.replica_count}")
    return 0


class _FanoutSink:
    """start --cdc-jsonl + --cdc-udp together: EVERY sink is offered each
    emission (no short-circuit), and the op counts as delivered only when
    all accepted. A refusal by one member means the pump retries the op,
    so sinks that already accepted see it again — at-least-once per sink,
    dedupable by op like any other redelivery. (Both current members
    always accept; this matters only for future refusing sinks.)"""

    def __init__(self, sinks):
        self.sinks = sinks

    def emit_lines(self, lines) -> bool:
        results = [s.emit_lines(lines) for s in self.sinks]
        return all(results)

    def flush(self) -> None:
        for s in self.sinks:
            s.flush()

    def close(self) -> None:
        for s in self.sinks:
            s.close()


def _install_parent_death_watchdog() -> None:
    """Die with the spawner — OPT-IN via TB_PARENT_WATCHDOG=1 (the bench and
    test harnesses set it when they spawn `start` as a subprocess). If the
    harness is SIGKILLed (or a teardown path is skipped) the server used to
    outlive it and burn CPU on the shared bench machine, skewing every
    later measurement. PR_SET_PDEATHSIG delivers SIGTERM the moment the
    parent thread exits; the ppid re-check closes the race where the parent
    died before the prctl landed. Opt-in because a production/daemonized
    start (systemd, `... start &` from a wrapper that exits) legitimately
    outlives its launcher."""
    import ctypes
    import os
    import signal

    if os.environ.get("TB_PARENT_WATCHDOG") != "1":
        return
    if not sys.platform.startswith("linux"):
        return
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, signal.SIGTERM, 0, 0, 0)
        if os.getppid() == 1:  # parent already gone: orphaned at birth
            raise SystemExit(0)
    except (OSError, AttributeError):
        pass  # non-glibc platform: watchdog unavailable, teardown still kills


def asked_platforms(environ=None) -> tuple[str, ...]:
    """The JAX platforms asked for BY NAME, in order: TB_JAX_PLATFORM (the
    knob spawned servers take) or JAX_PLATFORMS. The FIRST is the one asked
    to serve (JAX makes it the default backend; `tpu,cpu` asks for the
    TPU). Empty when nothing was pinned — then JAX picks, and on a machine
    whose chip is missing or held by another process it picks the CPU
    without a word."""
    import os

    environ = os.environ if environ is None else environ
    names = environ.get("TB_JAX_PLATFORM") or environ.get("JAX_PLATFORMS") or ""
    return tuple(n.strip().lower() for n in names.split(",") if n.strip())


def serving_device(devices, asked: tuple[str, ...]) -> dict:
    """The device a process serves (or measures) from, as JAX reports it:
    {platform, kind, count} over `devices`. Raises SystemExit with a plain
    message when the platform is not `tpu` and was not asked for by name:
    asked-for CPU is a test, CPU that JAX fell back to is the fault."""
    d = devices[0]
    info = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
    }
    wanted = asked[0] if asked else "tpu"
    if d.platform != "tpu" and d.platform != wanted:
        flags.fatal(
            f"no TPU: JAX placed this process on {d.platform!r} "
            f"({d.device_kind}, {len(devices)} device(s)) and the platform "
            f"asked for by name is {wanted!r}"
            f"{'' if asked else ' (nothing was pinned)'}. The chip is "
            "missing or held by another process (one process per chip). To "
            "run on the CPU on purpose set TB_JAX_PLATFORM=cpu (or "
            "JAX_PLATFORMS=cpu)."
        )
    return info


def announce_device() -> dict:
    """For tools that measure on a device IN-PROCESS
    (scripts/profile_kernel.py, scripts/probe_device.py): print the
    device this process got on stderr and refuse a CPU nobody asked for —
    the same rule `start` serves by."""
    import json

    import jax

    info = serving_device(jax.devices()[:1], asked_platforms())
    print(f"[device] {json.dumps(info)}", file=sys.stderr, flush=True)
    return info


def device_memory(devices) -> dict:
    """Per-device allocator readings where the backend reports them (the
    TPU does; the CPU returns None): bytes now and the process's peak."""
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "bytes_in_use": [m.get("bytes_in_use") for m in stats],
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in stats],
    }


class _ProfilerWindow:
    """`start --device-trace <dir>`: ONE bounded jax.profiler window,
    opened by the serve loop at the first request and closed from a
    thread of its own (stop_trace writes the xplane for seconds: the
    loop must not wait for it)."""

    def __init__(self, out_dir: str, seconds: float):
        self.out_dir = out_dir
        self.seconds = seconds
        self._thread = None
        self._stop = threading.Event()

    def open_once(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="profiler-window", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            options = jax.profiler.ProfileOptions()
            # no Python tracer: it logs every call of the event loop and
            # slows the server it is looking at
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            self._stop.wait(self.seconds)
            jax.profiler.stop_trace()
            print(f"[device-trace] window written under {self.out_dir}",
                  flush=True)
        except Exception as e:  # profiling must never take the server down
            print(f"[device-trace] failed: {type(e).__name__}: {e}",
                  flush=True)

    def close(self, timeout: float = 120.0) -> None:
        """Shutdown: end a window that is still open, and wait for its
        file."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)


def cmd_start(args) -> int:
    import faulthandler
    import os
    import signal

    faulthandler.register(signal.SIGUSR1)  # kill -USR1 <pid> dumps stacks
    _install_parent_death_watchdog()
    debug_boot = bool(os.environ.get("TB_DEBUG"))

    def boot(msg: str) -> None:
        if debug_boot:
            print(f"[boot] {msg}", file=sys.stderr, flush=True)

    plat = os.environ.get("TB_JAX_PLATFORM")
    if plat:  # tests pin the CPU backend for spawned servers
        import jax

        jax.config.update("jax_platforms", plat)
    if args.backend != "native":
        # refuse BEFORE any table is allocated or kernel compiled: a
        # device backend that fell back to the CPU unasked must not serve
        import jax

        serving_device(jax.devices(), asked_platforms())

    from tigerbeetle_tpu.aof import AOF
    from tigerbeetle_tpu.constants import ConfigCluster, ConfigProcess
    from tigerbeetle_tpu.io.message_bus import TCPMessageBus
    from tigerbeetle_tpu.io.time import RealTime
    from tigerbeetle_tpu.metrics import Metrics
    from tigerbeetle_tpu.statsd import StatsD, StatsDEmitter, parse_addr
    from tigerbeetle_tpu.tracer import JsonTracer, ProfilerTracer, Tracer
    from tigerbeetle_tpu.vsr.replica import Replica

    # ONE registry + tracer for the whole process: the replica, bus,
    # journal, ledger and spill pipeline all report here, and the [stats]
    # line / --statsd emission / --trace dump read from it (the reference
    # wires tracer.zig + statsd.zig through the same stages).
    metrics = Metrics()
    if args.trace:
        tracer = JsonTracer(metrics=metrics)
    elif args.backend != "native":
        # the process holds a chip: spans go to whatever profiler session
        # is open (none: a no-op in the runtime), beside the kernels
        tracer = ProfilerTracer()
    else:
        tracer = Tracer()

    addresses = _parse_addresses(args.addresses)
    cluster_cfg = ConfigCluster(
        replica_count=len(addresses),
        clients_max=args.clients_max,
        client_reply_slots=args.client_reply_slots,
    )
    process_cfg = ConfigProcess(
        account_slots_log2=args.account_slots_log2,
        transfer_slots_log2=args.transfer_slots_log2,
    )
    boot("imports done")
    storage = _storage(args.file, cluster_cfg, create=False, grid_mb=args.grid_mb)
    boot("storage open")
    bus = TCPMessageBus(
        addresses, args.replica, listen=True,
        listen_backlog=args.ingress_backlog,
        accept_budget=args.ingress_accept_budget,
        dispatch_budget=args.ingress_dispatch_budget,
    )
    bus.metrics = metrics
    bus.tracer = tracer
    boot("bus bound")  # must not contain "listening": spawners match on it
    backend_factory = None
    if args.backend == "native":
        from tigerbeetle_tpu.models.native_ledger import NativeLedger

        backend_factory = lambda: NativeLedger(  # noqa: E731
            args.account_slots_log2, args.transfer_slots_log2
        )
    elif args.backend == "dual":
        from tigerbeetle_tpu.models.dual_ledger import DualLedger

        backend_factory = lambda: DualLedger(  # noqa: E731
            args.account_slots_log2, args.transfer_slots_log2,
            # compiles happen at boot, before "listening" — an in-window
            # compile stalls the apply queue into the reply path
            warm_kernels=True,
            lag_window=args.device_lag_window,
        )
    elif args.backend == "sharded":
        import jax
        import numpy as _np
        from jax.sharding import Mesh

        from tigerbeetle_tpu.parallel.mesh import ShardedLedger

        devs = jax.devices()
        if args.shards:
            if args.shards > len(devs):
                flags.fatal(
                    f"--shards {args.shards} but only {len(devs)} device(s) "
                    "available — a silently smaller mesh would write "
                    "checkpoints with the wrong shard geometry"
                )
            devs = devs[: args.shards]
        mesh = Mesh(_np.array(devs), ("shard",))
        backend_factory = lambda: ShardedLedger(  # noqa: E731
            mesh, process_cfg
        )
    replica = Replica(
        args.replica, len(addresses), storage, bus, RealTime(),
        cluster_cfg, process_cfg, backend_factory=backend_factory,
        # production server, real time: spill/grid IO on a worker thread
        # (deterministic harnesses keep the default "deferred" executor)
        spill_io="threaded",
        metrics=metrics,
        tracer=tracer,
    )
    boot("replica constructed (device state allocated)")
    # latency anatomy: sampling knob + TCP egress (the bus finishes a
    # sampled record at the flush that writes its reply frame, so the
    # reply_egress leg measures finalize -> first socket write)
    replica.latency.sample_every = args.latency_sample_every
    replica.latency.defer_egress = True
    bus.latency = replica.latency
    flight = None
    if args.flight_interval_s > 0:
        from tigerbeetle_tpu.metrics import FlightRecorder

        flight = FlightRecorder(metrics)
        replica.flight_recorder = flight  # [stats] wire command history
    if args.aof:
        replica.aof = AOF(args.aof)
    replica.commit_window = args.commit_window
    if args.fuse_window_us < 0:
        # autotune (the default): start at the old 2ms constant, adapt
        # from hold outcomes (vsr/replica.py _fuse_hold AIMD)
        replica.fuse_autotune = True
        replica.fuse_window_ns = 2_000_000
    else:
        replica.fuse_window_ns = args.fuse_window_us * 1000
    if args.commitment_interval > 0:
        from tigerbeetle_tpu.federation.commitment import CommitmentLog

        # install BEFORE open(): the chain restores from the checkpoint
        # meta, then WAL replay re-records the tail idempotently
        replica.commitment_log = CommitmentLog(args.commitment_interval)
    hash_log = None
    if args.hash_log:
        from tigerbeetle_tpu.testing.hash_log import HashLog, parse_hash_log_spec

        mode, hl_path = parse_hash_log_spec(args.hash_log)
        hash_log = HashLog(mode, path=hl_path)
        # attach BEFORE open(): single-replica recovery re-commits the
        # journal tail — record mode re-records identical entries, check
        # mode re-verifies them (both idempotent by op)
        hash_log.attach(replica)
    cdc_pump = None
    if args.cdc_jsonl or args.cdc_udp:
        from tigerbeetle_tpu.cdc import (
            CdcPump,
            CountThrottleSink,
            FileCursor,
            JsonlFileSink,
            ThrottleSink,
            UdpSink,
        )

        named = []  # (consumer name, sink)
        if args.cdc_jsonl:
            named.append(("jsonl", JsonlFileSink(args.cdc_jsonl)))
        if args.cdc_udp:
            named.append(("udp", UdpSink(*parse_addr(args.cdc_udp))))
        if args.cdc_slow_us:
            named = [
                (n, ThrottleSink(s, args.cdc_slow_us)) for n, s in named
            ]
        if args.cdc_slow_every:
            # one count-throttled laggard: only the LAST named sink —
            # with --cdc-fanout the healthy consumers keep pace while
            # this one's position falls behind (the prodday timeline's
            # slow-consumer event)
            n_last, s_last = named[-1]
            named[-1] = (n_last, CountThrottleSink(s_last, args.cdc_slow_every))
        # an explicit --cdc-cursor names the cursor FILE and is used
        # verbatim (a restart must find the pre-existing cursor); the
        # fan-out path derives per-consumer files by suffixing it
        cursor_file = args.cdc_cursor or (
            (args.cdc_jsonl or args.file) + ".cursor"
        )
        if args.cdc_fanout and len(named) > 1:
            # one shared tail, one consumer (cursor + position) PER sink:
            # a slow sink pauses only itself (ingress/fanout.py)
            from tigerbeetle_tpu.ingress import CdcFanoutHub

            cdc_pump = CdcFanoutHub(
                replica, window=args.cdc_window,
                aof_path=args.aof or None,
            )
            for name, sink in named:
                cdc_pump.add_consumer(
                    name, sink, FileCursor(f"{cursor_file}.{name}"),
                    ack_interval=args.cdc_ack_interval,
                    commitments=args.commitment_interval > 0,
                )
        else:
            sink = (
                named[0][1] if len(named) == 1
                else _FanoutSink([s for _n, s in named])
            )
            cdc_pump = CdcPump(
                replica, sink, FileCursor(cursor_file),
                window=args.cdc_window,
                ack_interval=args.cdc_ack_interval,
                # the AOF (when on) is the deep-resume source: ops older
                # than the WAL ring replay through the oracle with exact
                # results
                aof_path=args.aof or None,
                commitments=args.commitment_interval > 0,
            )
        # attach BEFORE open(): single-replica recovery re-commits the
        # journal tail, and those redeliveries are exactly what the
        # cursor dedups — the pump must see them, not miss them
        cdc_pump.attach()
    statsd = emitter = None
    if args.statsd:
        # accepts `host`, `:port`, and `host:port` (a bare host used to
        # crash on int("") after rpartition)
        statsd = StatsD(*parse_addr(args.statsd))
        # batched emission: the WHOLE registry per flush, many metrics
        # per MTU-sized datagram, counters as deltas
        emitter = StatsDEmitter(statsd, metrics)
    boot("opening (superblock + snapshot + WAL recovery)")
    replica.open()
    boot("open done")
    if args.ingress:
        from tigerbeetle_tpu.ingress import IngressGateway

        gateway = IngressGateway(
            bus, replica, sessions_max=args.ingress_sessions_max
        )
        gateway.install()
        boot("ingress gateway installed")
    device_report = None  # () -> the [device]/[stats].device dict
    launch_clock = None
    if args.backend != "native":
        import json as _json

        # the devices the ledger state actually lives on (dual: the
        # follower's DeviceLedger), not merely what JAX can see
        dev_ledger = getattr(replica.ledger, "device", replica.ledger)
        if hasattr(dev_ledger, "launch_clock"):
            from tigerbeetle_tpu.metrics import LaunchClock

            # device time per commit launch over the whole run, booked
            # from a completion thread (this process only: harnesses that
            # construct a DeviceLedger never get one)
            launch_clock = dev_ledger.launch_clock = LaunchClock(metrics)
        state_devices = sorted(
            dev_ledger.state["acct_rows"].devices(), key=lambda d: d.id
        )
        device_info = serving_device(state_devices, asked_platforms())

        def device_report() -> dict:
            return {**device_info, **device_memory(state_devices)}

        print("[device] " + _json.dumps(device_report()), flush=True)
    print(
        f"replica {args.replica}/{len(addresses)} listening on "
        f"{addresses[args.replica][0]}:{addresses[args.replica][1]} "
        f"(op={replica.op}, commit={replica.commit_min})",
        flush=True,
    )
    if args.backend != "native":
        # compile sentinel: serving starts here — any XLA compile past
        # this point is a hot-path event (device.compiles_post_warmup +
        # the SIGQUIT dump's event log). The dual warm path already
        # marked warm; this covers device/sharded backends too.
        from tigerbeetle_tpu.models.ledger import COMPILE_SENTINEL

        COMPILE_SENTINEL.mark_warm()
    trace_window = None
    if args.device_trace:
        if args.backend == "native":
            print("--device-trace ignored: the native backend holds no chip",
                  flush=True)
        else:
            trace_window = _ProfilerWindow(
                args.device_trace, args.device_trace_s
            )
    profile_path = os.environ.get("TB_PROFILE")
    prof = None
    if profile_path:
        # Profile the event loop; dump pstats on SIGTERM (the bench harness
        # terminates the server when the drive completes).
        import cProfile

        prof = cProfile.Profile()

    # event-loop cost accounting: busy wall time (pump + commit dispatch +
    # flush, never blocking selects or idle sleeps) over ops committed BY
    # THIS PROCESS (commit_min starts at the recovered commit number on
    # restart) — the per-batch loop cost the bench reports as
    # loop_us_per_batch. Registry-backed: the [stats] line and --statsd
    # read the same counters.
    loop_stats = metrics.group("loop", ("busy_s", "turns"))
    boot_commit = replica.commit_min

    def _on_term(_sig, _frm):
        # Emit observability counters for the bench harness (group-commit
        # hit rate etc.), then exit. The harness parses the [stats] line.
        import json as _json

        device_shadow = None
        if hasattr(replica.ledger, "finalize"):
            # dual mode, FIRST: drain the device applier, then the
            # process's first d2h reads verify the device state bit-exact
            # (after the harness's clock has already stopped — the timed
            # phase never paid a device round trip). Everything below —
            # tier counters, compile sentinel, registry, device memory —
            # is read after the drain, so a lagging applier's work is in
            # it. Never let verification failure eat the [stats] line.
            try:
                replica.flush_commits()
                device_shadow = replica.ledger.finalize()
            except Exception as e:
                device_shadow = {
                    "verified": False,
                    "error": f"{type(e).__name__}: {e}",
                }
        if launch_clock is not None:
            # book the launches still in flight before the registry is
            # read (dual: the applier has drained; device: what the loop
            # had not fetched yet)
            launch_clock.close()
        if trace_window is not None:
            trace_window.close()
        hz = getattr(replica.ledger, "hazards", None)
        stats = {
            "group": dict(replica.group_stats),
            # the fuse window the run ENDED at (autotune moves it): the
            # bench records this per segment next to the hit rate, so a
            # bad hit rate is attributable to the window it ran with
            "fuse": {
                "window_us": replica.fuse_window_ns // 1000,
                "autotune": replica.fuse_autotune,
            },
            # the conflict-wave planner's decision counters (plan_stats);
            # the "split" key name is the DEPRECATED dashboard surface —
            # the dict carries both the wave keys (waves/wave_dispatches/
            # residue_events/chain_len_max) and the legacy split keys
            "split": dict(hz.split_stats) if hz is not None else {},
            "pool_dropped": bus.pool.dropped,
            "loop": {
                "busy_s": round(loop_stats["busy_s"], 3),
                "turns": loop_stats["turns"],
                "us_per_batch": round(
                    loop_stats["busy_s"] * 1e6
                    / max(1, replica.commit_min - boot_commit), 1
                ),
            },
            # the full registry (counters/gauges/histogram percentile
            # snapshots): the bench harness and --statsd read the SAME
            # store this line is printed from
            "metrics": metrics.snapshot(),
            # per-request breakdowns of the slowest sampled requests
            # (latency.py): where THOSE requests' milliseconds went
            "latency_slowest": replica.latency.slowest(limit=8),
        }
        if flight is not None and flight.phase_log:
            # the scenario-phase timeline (prodday `mark` markers): when
            # each phase of the scripted run began, by the recorder clock
            stats["phases"] = flight.phase_log
        if replica.commitment_log is not None:
            # checkpoint state-commitment chain head + recent entries —
            # the same surface `inspect commitments` reads live
            stats["commitments"] = replica.commitment_log.stats_snapshot()
        if args.federation_regions:
            stats["federation"] = {
                "region": args.federation_region,
                "regions": args.federation_regions,
            }
        _lmod = sys.modules.get("tigerbeetle_tpu.models.ledger")
        if _lmod is not None:
            # compile-sentinel totals + bounded event log (post-warmup
            # compiles are the .jax_cache pathology, named)
            stats["compile_sentinel"] = _lmod.COMPILE_SENTINEL.snapshot()
        _da = getattr(replica.ledger, "device_anatomy", None)
        if _da is not None and _da.slowest():
            # dual mode: slowest sampled apply items, sub-leg breakdowns
            stats["device_slowest"] = _da.slowest(limit=8)
        if getattr(replica.ledger, "spill", None) is not None:
            stats["spill"] = dict(replica.ledger.spill.stats)
        if hash_log is not None:
            # record mode persists the stream; both modes report coverage
            # (check mode would already have died AT a divergent op)
            try:
                if hash_log.mode == "record":
                    hash_log.save()
                stats["hash_log"] = {
                    "mode": hash_log.mode,
                    "path": hash_log.path,
                    # coverage THIS RUN (check mode preloads `entries`
                    # from the recording — its length is not coverage)
                    "ops": hash_log.ops_seen,
                }
            except Exception as e:
                stats["hash_log"] = {"error": f"{type(e).__name__}: {e}"}
        if device_shadow is not None:
            stats["device_shadow"] = device_shadow
        if device_report is not None:
            # the peak covers the verification epilogue (state_fingerprint
            # is the largest temp of a dual run)
            stats["device"] = device_report()
        print(f"[stats] {_json.dumps(stats)}", flush=True)
        # a server whose device state failed verification, or whose
        # applier died, must not leave with 0 (the [stats] line above has
        # landed first). verified None = the shadow stood down on a
        # snapshot restore: reported, not a failure.
        failed = (device_shadow or {}).get("verified") is False
        exit_code = 1 if failed else 0
        if cdc_pump is not None:
            # finalize any in-flight commits (their replies are what the
            # stream encodes), then a bounded final drain + durable
            # cursor/sink flush — a slow sink must not hold up shutdown
            try:
                replica.flush_commits()
            except Exception:
                pass  # stream what already finalized
            cdc_pump.pump(budget_ops=1024)
            cdc_pump.flush()
            if hasattr(cdc_pump, "close"):
                cdc_pump.close()  # fan-out hub: every consumer's sink
            else:
                cdc_pump.sink.close()
        if args.trace:
            tracer.dump(args.trace)
        if emitter is not None:
            emitter.flush()  # final batched emission before exit
        if prof is not None:
            prof.disable()
            prof.dump_stats(profile_path)
        os._exit(exit_code)

    signal.signal(signal.SIGTERM, _on_term)

    def _on_quit(_sig, _frm):
        # Hang diagnosis (kill -QUIT <pid>): a WEDGED server dumps its
        # evidence and KEEPS RUNNING (the operator decides what to do
        # next) — before this, SIGQUIT killed the process with nothing.
        # Dumped: every thread's stack (faulthandler), the consensus
        # state the [debug] line would show, and — when tracing is on —
        # the trace ring incl. still-open spans to <trace>.quit.json
        # (an open span IS the wedge's name).
        import json as _json

        metrics.counter("trace.sigquit_dumps").add()
        sys.stderr.write(
            f"[quit] status={replica.status} view={replica.view} "
            f"op={replica.op} commit={replica.commit_min} "
            f"pipeline={sorted(replica.pipeline)} "
            f"inflight={len(replica._inflight)} "
            f"wanted={sorted(replica._repair_wanted)}\n"
        )
        faulthandler.dump_traceback(file=sys.stderr)
        if args.trace:
            open_spans = [
                e for e in tracer.events_ordered() if e["ph"] == "B"
            ]
            sys.stderr.write(
                f"[quit] {len(open_spans)} open span(s): "
                + ", ".join(
                    f"{e['name']}{e.get('args') or ''}"
                    for e in open_spans[:16]
                )
                + "\n"
            )
            quit_path = f"{args.trace}.quit.json"
            try:
                tracer.dump(quit_path)
                sys.stderr.write(f"[quit] trace ring -> {quit_path}\n")
            except OSError as e:
                sys.stderr.write(f"[quit] trace dump failed: {e}\n")
        else:
            sys.stderr.write(
                "[quit] tracing off (start with --trace <path> for the "
                "span ring)\n"
            )
        snap = {
            "status": replica.status, "view": replica.view,
            "op": replica.op, "commit_min": replica.commit_min,
            "metrics": metrics.snapshot(),
            # the incident evidence the cumulative snapshot cannot give:
            # per-request breakdowns of the slowest sampled requests and
            # the flight recorder's last minute of per-interval history
            "latency_slowest": replica.latency.slowest(limit=8),
        }
        _lmod = sys.modules.get("tigerbeetle_tpu.models.ledger")
        if _lmod is not None:
            # a wedged applier's first suspect: a post-warmup compile
            # stalling the loop — the event log names the signature
            snap["compile_sentinel"] = _lmod.COMPILE_SENTINEL.snapshot()
        _da = getattr(replica.ledger, "device_anatomy", None)
        if _da is not None and _da.slowest():
            snap["device_slowest"] = _da.slowest(limit=8)
        _hz = getattr(replica.ledger, "hazards", None)
        if _hz is not None:
            snap["split"] = dict(_hz.split_stats)  # tier counters so far
        if device_report is not None:
            snap["device"] = device_report()
        if flight is not None:
            snap["history"] = flight.history(last=60)
            if flight.phase_log:
                # which scenario phase each slice of that history ran
                # under (prodday `mark` markers)
                snap["phases"] = flight.phase_log
        sys.stderr.write(f"[quit] stats {_json.dumps(snap)}\n")
        sys.stderr.flush()

    signal.signal(signal.SIGQUIT, _on_quit)
    if prof is not None:
        prof.enable()

    debug = bool(os.environ.get("TB_DEBUG"))
    tick_s = process_cfg.tick_ms / 1000.0
    last_tick = time.monotonic()
    last_debug = time.monotonic()
    last_statsd = time.monotonic()
    last_flight = time.monotonic()
    last_commit = replica.commit_min
    while True:
        # With async commits in flight — or a fuse window holding a short
        # run open for more arrivals — poll (timeout=0) so a quiet wire
        # flushes replies immediately and the window expiry is checked
        # every turn; otherwise block one tick.
        busy = bool(replica._inflight) or replica._fuse_started is not None
        t0 = time.monotonic()
        n = bus.pump(timeout=0.0 if busy else tick_s)
        if n and trace_window is not None:
            trace_window.open_once()
        # every turn (not only n > 0): same-turn arrivals fuse into a
        # group, and an expired fuse window must dispatch promptly
        replica.pump_commits()
        if cdc_pump is not None:
            # bounded change-stream progress OFF the commit path: one op
            # per turn while the wire is busy (an 8190-record encode is
            # real host time), a larger bite when idle. Not counted into
            # loop busy_s — that accounts the commit pipeline the bench's
            # loop_us_per_batch quotes.
            cdc_pump.pump(budget_ops=1 if busy else 8)
        if busy:
            loop_stats.add("busy_s", time.monotonic() - t0)
            loop_stats.add("turns")
        if n == 0 and busy:
            # Bus idle: send every reply whose OWN result is ready (WAL
            # durable, device handle computed), oldest first, and stop at
            # the first that is not — a create's reply does not wait for
            # the lookup dispatched behind it. Never blocks: the loop
            # keeps reading frames while the chip computes.
            t0 = time.monotonic()
            if replica.flush_commits(only_ready=True):
                loop_stats.add("busy_s", time.monotonic() - t0)
            elif replica._inflight:
                time.sleep(0.0002)
        now = time.monotonic()
        if now - last_tick >= tick_s:
            last_tick = now
            with tracer.span("loop.tick"):
                replica.tick()
            # registry updates are unconditional — the [stats] snapshot
            # and bench server_metrics carry them with or without statsd
            if replica.commit_min != last_commit:
                metrics.counter("server.ops_committed").add(
                    replica.commit_min - last_commit
                )
                metrics.gauge("server.commit_min").set(replica.commit_min)
                last_commit = replica.commit_min
            # batched flush on a ~1s cadence: the WHOLE registry rides a
            # handful of MTU-sized datagrams instead of one packet per
            # metric per tick
            if emitter is not None and now - last_statsd >= 1.0:
                last_statsd = now
                emitter.flush()
            # flight recorder: one time-series entry per interval —
            # counter deltas + windowed histogram percentiles, the
            # history `inspect live --watch` and the SIGQUIT dump read
            if flight is not None and now - last_flight >= args.flight_interval_s:
                last_flight = now
                flight.record(now)
        if debug and now - last_debug >= 1.0:
            last_debug = now
            print(
                f"[debug] status={replica.status} view={replica.view} "
                f"op={replica.op} commit={replica.commit_min} "
                f"pipeline={sorted(replica.pipeline)} "
                f"wanted={sorted(replica._repair_wanted)} "
                f"conns={sorted(str(k) if k < 1000 else 'client' for k in bus.conns)}",
                flush=True,
            )


def cmd_chaos(args) -> int:
    import json as _json

    from tigerbeetle_tpu.testing.chaos import CHAOS_ACTIONS, run_chaos

    if args.kill_cluster:
        from tigerbeetle_tpu.federation.live import run_federation_chaos

        def fed_log(*a):
            print("[chaos]", *a, file=sys.stderr, flush=True)

        report = run_federation_chaos(
            regions=args.federation_regions,
            replica_count=args.replicas,
            payments=args.payments,
            commitment_interval=args.commitment_interval,
            restart_after_s=args.restart_after_s,
            backend=args.backend, seed=args.seed,
            deadline_s=args.deadline_s,
            jax_platform=None,  # the CLI inherits the ambient platform
            log=fed_log,
        )
        if args.json:
            with open(args.json, "w") as f:
                _json.dump(report, f, indent=1, sort_keys=True)
        print(_json.dumps(report, indent=1, sort_keys=True))
        ok = (
            report["conservation"]["ok"]
            and all(
                v["checked"] > 0 for v in report["stream_verify"].values()
            )
        )
        return 0 if ok else 1

    faults = tuple(f for f in args.faults.split(",") if f)
    for f in faults:
        if f not in CHAOS_ACTIONS:
            flags.fatal(
                f"unknown fault {f!r} ({' | '.join(CHAOS_ACTIONS)})"
            )

    def log(*a):
        print("[chaos]", *a, file=sys.stderr, flush=True)

    report = run_chaos(
        n_sessions=args.sessions, conns=args.conns,
        n_accounts=args.accounts,
        events_per_batch=args.events_per_batch,
        batches_per_session=args.batches_per_session,
        replica_count=args.replicas, backend=args.backend,
        faults=faults, restart_after_s=args.restart_after_s,
        gray_s=args.gray_s, disk_fault_on_restart=args.disk_fault,
        ingress=args.ingress, seed=args.seed, deadline_s=args.deadline_s,
        jax_platform=None,  # the CLI inherits the ambient platform
        log=log,
    )
    if args.json:
        with open(args.json, "w") as f:
            _json.dump(report, f, indent=1, sort_keys=True)
    print(_json.dumps(report, indent=1, sort_keys=True))
    ok = report["lost_events"] == 0 and report["conservation_ok"]
    return 0 if ok else 1


def cmd_cdc(args) -> int:
    """Replay the AOF's change stream into a sink from the consumer's
    cursor. One shot: runs to the end of the log (or --limit), acks the
    cursor, exits — the operator bootstrap/backfill path; live tailing is
    `start --cdc-jsonl/...`."""
    from tigerbeetle_tpu.cdc import (
        AofReplaySource,
        FileCursor,
        JsonlFileSink,
        StdoutSink,
        UdpSink,
        encode_batch,
        gap_record,
        record_line,
    )
    from tigerbeetle_tpu.statsd import parse_addr

    if args.sink == "stdout":
        sink = StdoutSink()
    elif args.sink.startswith("jsonl:"):
        sink = JsonlFileSink(args.sink[len("jsonl:"):])
    elif args.sink.startswith("udp:"):
        sink = UdpSink(*parse_addr(args.sink[len("udp:"):]))
    else:
        flags.fatal(f"unknown --sink {args.sink!r} "
                    "(stdout | jsonl:<path> | udp:host[:port])")
    cursor = FileCursor(
        args.cursor or f"{args.file}.{args.consumer}.cursor"
    )
    acked_op, _ = cursor.load()
    source = AofReplaySource(args.file)
    ops = records = 0
    op = acked_op + 1
    last = None
    while not args.limit or ops < args.limit:
        got = source.read(op)
        if got is None:
            # an AOF hole (ops this replica never executed — a state-sync
            # jump): declare it and continue from where the log resumes
            resume = source.next_available()
            if resume is None:
                break  # end of log
            if not sink.emit_lines([record_line(gap_record(op, resume - 1))]):
                break
            op = resume
            continue
        header, body, reply = got
        recs = encode_batch(header, body, reply)
        if recs and not sink.emit_lines([record_line(r) for r in recs]):
            break  # a refusing sink ends the one-shot run; cursor holds
        records += len(recs)
        ops += 1
        last = header
        op += 1
    if last is not None:
        cursor.ack(last.op, last.checksum)
    sink.flush()
    sink.close()
    print(
        f"cdc: {records} records over {ops} ops "
        f"(consumer {args.consumer!r}, cursor at op {last.op if last else acked_op})",
        file=sys.stderr,
    )
    return 0


def cmd_inspect(args) -> int:
    import json as _json

    from tigerbeetle_tpu import inspect as _inspect
    from tigerbeetle_tpu.constants import ConfigCluster

    def emit(topic: str, report) -> None:
        if args.json:
            _json.dump(report, sys.stdout, indent=1, sort_keys=True,
                       default=str)
            sys.stdout.write("\n")
        else:
            _inspect.render(topic, report, sys.stdout)

    topics = ("superblock", "wal", "replies", "grid", "lsm",
              "client-table", "all", "live", "commitments")
    if args.topic not in topics:
        flags.fatal(
            f"unknown inspect topic {args.topic!r} ({' | '.join(topics)})"
        )
    if args.topic == "commitments":
        if args.stream:
            # external-consumer verify: replay the stream, re-derive the
            # chain, reject tampering at the exact checkpoint
            report = _inspect.verify_commitment_stream(args.stream)
            emit("commitments", report)
            return 0 if report["ok"] else 1
        if args.addresses:
            host, sep, port = args.addresses.strip().rpartition(":")
            if not sep or not port.isdigit():
                flags.fatal("inspect commitments needs --addresses host:port")
            live = _inspect.inspect_live(host or "127.0.0.1", int(port))
            report = _inspect.commitments_from_stats(live)
            emit("commitments", report)
            return 0 if report.get("enabled") else 1
        if not args.file:
            flags.fatal(
                "inspect commitments needs a data file, --addresses, or "
                "--stream"
            )
        cluster_cfg = ConfigCluster(
            clients_max=args.clients_max,
            client_reply_slots=args.client_reply_slots,
        )
        storage = _inspect.open_storage(
            args.file, cluster_cfg, forest_blocks=args.forest_blocks
        )
        try:
            report = _inspect.inspect_commitments_offline(storage)
        finally:
            storage.close()
        emit("commitments", report)
        return 0 if report.get("enabled") else 1
    if args.topic == "live":
        # a replica has no default port, so one is mandatory (`:3001`
        # and `host:3001` both work; statsd.parse_addr is wrong here —
        # its bare-host default is the statsd port)
        host, sep, port = args.addresses.strip().rpartition(":")
        if not sep or not port.isdigit():
            flags.fatal("inspect live needs --addresses host:port")
        if args.watch > 0:
            return _inspect.watch_live(
                host or "127.0.0.1", int(port), interval_s=args.watch,
                count=args.watch_count, out=sys.stdout,
                as_json=args.json,
            )
        report = _inspect.inspect_live(host or "127.0.0.1", int(port))
        emit("live", report)
        return 0

    if not args.file:
        flags.fatal(f"inspect {args.topic} needs a data file path")
    cluster_cfg = ConfigCluster(
        clients_max=args.clients_max,
        client_reply_slots=args.client_reply_slots,
    )
    storage = _inspect.open_storage(
        args.file, cluster_cfg, forest_blocks=args.forest_blocks
    )
    try:
        sb = _inspect.inspect_superblock(storage)
        state = sb["state"]
        if args.topic == "superblock":
            emit("superblock", sb)
        elif args.topic == "wal":
            if args.op >= 0:
                emit("wal-op", _inspect.inspect_wal_op(
                    storage, cluster_cfg, args.op
                ))
            else:
                report = _inspect.inspect_wal(storage, cluster_cfg, state)
                if args.slot >= 0:
                    report["slots"] = [
                        s for s in report["slots"]
                        if s["slot"] == args.slot
                    ]
                emit("wal", report)
        elif args.topic == "replies":
            emit("replies", _inspect.inspect_replies(storage, cluster_cfg))
        elif args.topic == "grid":
            emit("grid", _inspect.inspect_grid(storage, cluster_cfg, state))
        elif args.topic == "lsm":
            emit("lsm", _inspect.inspect_lsm(storage, cluster_cfg, state))
        elif args.topic == "client-table":
            emit("client-table",
                 _inspect.inspect_client_table(storage, state))
        else:  # "all" (the topic was validated above)
            for topic, report in (
                ("superblock", sb),
                ("wal", _inspect.inspect_wal(storage, cluster_cfg, state)),
                ("replies",
                 _inspect.inspect_replies(storage, cluster_cfg)),
                ("grid",
                 _inspect.inspect_grid(storage, cluster_cfg, state)),
                ("lsm", _inspect.inspect_lsm(storage, cluster_cfg, state)),
                ("client-table",
                 _inspect.inspect_client_table(storage, state)),
            ):
                if not args.json:
                    sys.stdout.write(f"== {topic} ==\n")
                emit(topic, report)
    finally:
        storage.close()
    return 0


def cmd_repl(args) -> int:
    from tigerbeetle_tpu.repl import Repl

    addresses = _parse_addresses(args.addresses)
    repl = Repl(addresses, cluster_id=args.cluster)
    return repl.run(sys.stdin, echo=not sys.stdin.isatty())


USAGE = """usage: tigerbeetle_tpu <command> [flags] [file]

commands:
  format   create a fresh data file
  start    run a replica
  version  print version
  repl     interactive client (alias: client)
  cdc      replay an AOF's change stream into a sink (cursor resume)
  inspect  decode a data file offline / read a live server's stats
  chaos    live-cluster chaos run (kill/gray/reset faults + verification)
"""

COMMANDS = {
    "format": (FormatArgs, cmd_format),
    "start": (StartArgs, cmd_start),
    "repl": (ReplArgs, cmd_repl),
    "client": (ReplArgs, cmd_repl),
    "cdc": (CdcArgs, cmd_cdc),
    "inspect": (InspectArgs, cmd_inspect),
    "chaos": (ChaosArgs, cmd_chaos),
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(USAGE, end="")
        return 0 if argv else 1
    command, rest = argv[0], argv[1:]
    if command == "version":
        print(f"tigerbeetle_tpu {VERSION}")
        return 0
    if command not in COMMANDS:
        flags.fatal(f"unknown command {command!r}\n{USAGE}")
    spec, fn = COMMANDS[command]
    return fn(flags.parse(spec, rest)) or 0


if __name__ == "__main__":
    raise SystemExit(main())
