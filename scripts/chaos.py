#!/usr/bin/env python
"""chaos: the live-cluster chaos runner (testing/chaos.py as a CLI).

Spawns a real N-replica TCP cluster plus a multiplexed client fleet on
the fault-tolerant client runtime, injects live faults (SIGKILL/restart,
SIGSTOP gray failures, connection resets, a disk-fault flip on restart),
and verifies zero lost / zero duplicated transfers three ways (client
replies vs CDC stream vs wire conservation, plus dual-mode hash-log
parity), reporting time-to-first-commit-after-kill.

  python scripts/chaos.py                      # default: 1 primary kill
  python scripts/chaos.py --sessions 1000 --conns 16 --backend dual \
      --faults kill_primary,gray_primary,kill_backup,reset_conns
  python scripts/chaos.py --json report.json
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    from tigerbeetle_tpu.testing.chaos import CHAOS_ACTIONS, run_chaos

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--conns", type=int, default=4)
    ap.add_argument("--accounts", type=int, default=128)
    ap.add_argument("--events-per-batch", type=int, default=16)
    ap.add_argument("--batches-per-session", type=int, default=6)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--backend", default="native",
                    help="native | dual | device")
    ap.add_argument("--faults", default="kill_primary",
                    help="comma list of " + "|".join(CHAOS_ACTIONS))
    ap.add_argument("--restart-after", type=float, default=2.0,
                    metavar="S", help="kill -> respawn delay")
    ap.add_argument("--gray", type=float, default=3.0, metavar="S",
                    help="SIGSTOP duration")
    ap.add_argument("--no-disk-fault", action="store_true",
                    help="skip the WAL flip on the first restart")
    ap.add_argument("--ingress", action="store_true",
                    help="front every replica with the ingress gateway")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--deadline", type=float, default=600.0, metavar="S")
    ap.add_argument("--kill-cluster", action="store_true",
                    help="federation mode: spawn --federation-regions "
                         "whole clusters, SIGKILL every replica of one "
                         "region mid-settlement (federation/live.py)")
    ap.add_argument("--federation-regions", type=int, default=2)
    ap.add_argument("--payments", type=int, default=24,
                    help="cross-region origin pendings per region")
    ap.add_argument("--commitment-interval", type=int, default=8)
    ap.add_argument("--jax-platform", default="cpu",
                    help="TB_JAX_PLATFORM for the servers ('' = inherit)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the full report as JSON")
    args = ap.parse_args()

    faults = tuple(f for f in args.faults.split(",") if f)
    for f in faults:
        if f not in CHAOS_ACTIONS:
            ap.error(f"unknown fault {f!r} (have {CHAOS_ACTIONS})")

    def log(*a):
        print("[chaos]", *a, file=sys.stderr, flush=True)

    if args.kill_cluster:
        from tigerbeetle_tpu.federation.live import run_federation_chaos

        report = run_federation_chaos(
            regions=args.federation_regions,
            replica_count=args.replicas,
            payments=args.payments,
            commitment_interval=args.commitment_interval,
            restart_after_s=args.restart_after,
            backend=args.backend,
            seed=args.seed,
            deadline_s=args.deadline,
            jax_platform=args.jax_platform or None,
            log=log,
        )
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
            log(f"report -> {args.json}")
        print(json.dumps(report, indent=1, sort_keys=True))
        ok = (
            report["conservation"]["ok"]
            and all(v["checked"] > 0
                    for v in report["stream_verify"].values())
        )
        log("PASS" if ok else "FAIL")
        return 0 if ok else 1

    report = run_chaos(
        n_sessions=args.sessions,
        conns=args.conns,
        n_accounts=args.accounts,
        events_per_batch=args.events_per_batch,
        batches_per_session=args.batches_per_session,
        replica_count=args.replicas,
        backend=args.backend,
        faults=faults,
        restart_after_s=args.restart_after,
        gray_s=args.gray,
        disk_fault_on_restart=not args.no_disk_fault,
        ingress=args.ingress,
        seed=args.seed,
        deadline_s=args.deadline,
        jax_platform=args.jax_platform or None,
        log=log,
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"report -> {args.json}")
    print(json.dumps(report, indent=1, sort_keys=True))
    ok = (
        report["lost_events"] == 0
        and report["conservation_ok"]
        and report["cdc"]["dup_ids"] == 0
    )
    log("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
