"""Repo policy for the vet passes: scan sets, seams, allowlists.

Every allowlist entry carries its reason inline — an unexplained
exemption is as bad as an unexplained baseline entry.
"""

from __future__ import annotations

import dataclasses
import pathlib


@dataclasses.dataclass
class VetConfig:
    root: pathlib.Path

    # -- tidy ----------------------------------------------------------
    line_max: int = 100
    # golden-vector fixture tables transcribed verbatim from the
    # reference's test tables keep the reference's own formatting
    line_max_exempt: frozenset = frozenset({"tests/test_golden.py"})
    # user-facing surfaces: print IS their output channel
    print_ok: frozenset = frozenset({
        "tigerbeetle_tpu/cli.py",
        "tigerbeetle_tpu/repl.py",
        "tigerbeetle_tpu/__main__.py",
        "__graft_entry__.py",
    })

    # -- copyhound -----------------------------------------------------
    # the device compute path: everywhere a stray sync stalls dispatch
    copyhound_dirs: tuple = (
        "tigerbeetle_tpu/ops/",
        "tigerbeetle_tpu/models/",
        "tigerbeetle_tpu/parallel/",
        "tigerbeetle_tpu/vsr/",
        "tigerbeetle_tpu/lsm/",
        "tigerbeetle_tpu/cdc/",
        "tigerbeetle_tpu/ingress/",
        "tigerbeetle_tpu/io/",
    )
    # attribute holders whose method calls yield device arrays (jitted
    # kernel bundles) for the taint walk
    kernel_holders: tuple = ("self.kernels", "kernels", "self.k")

    # -- races ---------------------------------------------------------
    # the five thread seams (ISSUE 7): WAL writer pool, spill IO
    # executor, device-shadow loop, CDC pump, ingress/bus event loop —
    # plus the metric registry they all write into
    race_scan: frozenset = frozenset({
        "tigerbeetle_tpu/vsr/journal.py",
        "tigerbeetle_tpu/models/spill.py",
        "tigerbeetle_tpu/models/dual_ledger.py",
        "tigerbeetle_tpu/cdc/pump.py",
        "tigerbeetle_tpu/io/message_bus.py",
        "tigerbeetle_tpu/ingress/gateway.py",
        "tigerbeetle_tpu/ingress/fanout.py",
        "tigerbeetle_tpu/metrics.py",
    })
    # annotation names -> inferred thread names. "main" is whatever
    # thread drives the event loop (the server loop, the simulator, a
    # test) — the sequential context every un-spawned method runs on.
    thread_aliases: dict = dataclasses.field(default_factory=lambda: {
        "event-loop": "main",
        "commit": "main",
        "consumer": "main",
    })
    # repo-specific submit-forwarder method names (callables passed in
    # run on that seam's worker), beyond the generic submit/submit_io
    submit_forwarders: tuple = ()

    # -- determinism ---------------------------------------------------
    sim_roots: tuple = (
        "tigerbeetle_tpu/testing/simulator.py",
        "scripts/vopr.py",
        # the prodday harness: the timeline DSL/scorer must stay
        # clock-free (the sim twin replays timelines byte-identically),
        # and the live driver's clock reads must be baselined with whys
        "tigerbeetle_tpu/prodday.py",
        "scripts/prodday.py",
        # the federation composite: per-region Simulators + the sans-IO
        # settlement agent, all tick-driven — no wall clock anywhere
        "tigerbeetle_tpu/federation/sim.py",
    )
    clock_seam: frozenset = frozenset({
        # THE seam: RealTime wraps the OS clocks, DeterministicTime the
        # sim ticks — this is where wall clocks are supposed to live
        "tigerbeetle_tpu/io/time.py",
    })
    # modules inside the static import closure that only prod
    # composition roots construct (reason inline per entry)
    prod_only: dict = dataclasses.field(default_factory=lambda: {
        # observability backends: timing feeds histograms/trace spans,
        # never sim state; the sim asserts on op/state digests only
        "tigerbeetle_tpu/metrics.py":
            "metric timing is observability, not state",
        "tigerbeetle_tpu/tracer.py":
            "trace timestamps are observability, not state "
            "(SimTracer's deterministic dump carries no wall time)",
        "tigerbeetle_tpu/statsd.py":
            "StatsD emission is a prod sink",
        # prod transports/sinks reached via package __init__ imports
        "tigerbeetle_tpu/io/message_bus.py":
            "TCP bus: prod transport, sim uses PacketSimulator",
        "tigerbeetle_tpu/cdc/sink.py":
            "UDP/StatsD/throttle sinks are prod/bench surfaces; the "
            "sim uses in-memory sinks",
        # live-cluster drivers pulled in by scripts/prodday.py: they
        # drive real processes on wall clocks by design; the sim twin
        # reaches the simulator through tigerbeetle_tpu/prodday.py
        # without touching them
        "tigerbeetle_tpu/testing/chaos.py":
            "live chaos harness: subprocess clusters on wall time",
        "tigerbeetle_tpu/benchmark.py":
            "live bench driver: wall-clock load generation",
        "tigerbeetle_tpu/inspect.py":
            "wire inspection client for live servers",
        "tigerbeetle_tpu/artifact.py":
            "artifact provenance (filesystem walks), not sim state",
        "tigerbeetle_tpu/client_ffi.py":
            "FFI client binding (session nonces from OS entropy): prod "
            "client surface, the sim drives vsr/client.py directly",
        "tigerbeetle_tpu/federation/live.py":
            "live two-region driver: subprocess clusters, JSONL tailing "
            "and settlement on wall time; the sim twin is federation/"
            "sim.py on ticks",
    })
    # the executor seam itself + the WAL writer pool: the modules that
    # OWN thread construction behind deterministic alternatives
    executor_seam: dict = dataclasses.field(default_factory=lambda: {
        "tigerbeetle_tpu/models/spill.py":
            "ThreadedSpillIO/DeferredSpillIO IS the seam",
        "tigerbeetle_tpu/vsr/journal.py":
            "the WAL writer pool; deterministic runs use the sync path",
    })


def default_config(root: pathlib.Path) -> VetConfig:
    return VetConfig(root=root)
