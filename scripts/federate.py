#!/usr/bin/env python
"""federate: the declarative N-region federation entry point.

One topology knob (`--regions`), two execution modes over the same
cross-ledger scenario (origin pendings escrowed on the source region, a
settlement agent posting mirror/resolve legs, device-computed commitment
chains verified from the CDC stream by an external consumer):

  sim    the seed-deterministic composite (federation/sim.py): every
         region a full in-process simulated cluster, seeded settlement-
         agent crashes, one region killed wholesale mid-settlement;
         conservation + stream verification proven on recovery. The
         replay contract is the seed alone.

  live   real clusters (federation/live.py): one TCP replica-set per
         region, JSONL CDC tails, the settlement agent on the fault-
         tolerant client runtime; optionally SIGKILL every replica of
         one region mid-settlement and restart from disk.

  python scripts/federate.py sim --seed 7 --regions 2
  python scripts/federate.py live --regions 2 --replicas 3 --kill
  python scripts/federate.py sim --json report.json

Exit 0 iff conservation holds and every region's stream verified with at
least one checkpoint (the same PASS bar as scripts/chaos.py
--kill-cluster and the tier-1 federation tests).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def _passes(report: dict) -> bool:
    verify = report.get("stream_verify") or {}
    return bool(
        report["conservation"]["ok"]
        and verify
        and all(v["checked"] > 0 for v in verify.values())
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("mode", choices=("sim", "live"))
    ap.add_argument("--regions", type=int, default=2,
                    help="federation size (each region a full cluster)")
    ap.add_argument("--replicas", type=int, default=3,
                    help="replica count per region")
    ap.add_argument("--commitment-interval", type=int, default=0,
                    help="checkpoint-commitment spacing in ops "
                         "(0 = the mode's default)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the full report as JSON")
    sim = ap.add_argument_group("sim mode")
    sim.add_argument("--ticks", type=int, default=2600)
    sim.add_argument("--no-region-kill", action="store_true",
                     help="skip the whole-region mid-settlement kill")
    live = ap.add_argument_group("live mode")
    live.add_argument("--payments", type=int, default=24,
                      help="cross-region origin pendings per region")
    live.add_argument("--kill", action="store_true",
                      help="SIGKILL every replica of one region "
                           "mid-settlement, restart from disk")
    live.add_argument("--restart-after", type=float, default=1.5,
                      metavar="S", help="kill -> respawn delay")
    live.add_argument("--backend", default="native",
                      help="native | dual | device")
    live.add_argument("--deadline", type=float, default=600.0,
                      metavar="S")
    live.add_argument("--jax-platform", default="cpu",
                      help="TB_JAX_PLATFORM for the servers "
                           "('' = inherit)")
    args = ap.parse_args()

    def log(*a):
        print("[federate]", *a, file=sys.stderr, flush=True)

    if args.mode == "sim":
        sys.path.insert(0, ".")
        import tests.conftest  # noqa: F401 — CPU platform before jax

        from tigerbeetle_tpu.federation.sim import run_federation_sim

        report = run_federation_sim(
            args.seed,
            n_regions=args.regions,
            ticks=args.ticks,
            replica_count=args.replicas,
            region_kill=not args.no_region_kill,
            **({"commitment_interval": args.commitment_interval}
               if args.commitment_interval else {}),
        )
        # JSON-shape parity with live mode: region keys as strings
        report["stream_verify"] = {
            str(k): v for k, v in (report["stream_verify"] or {}).items()
        }
    else:
        from tigerbeetle_tpu.federation.live import run_federation_chaos

        report = run_federation_chaos(
            regions=args.regions,
            replica_count=args.replicas,
            payments=args.payments,
            kill_cluster=args.kill,
            restart_after_s=args.restart_after,
            backend=args.backend,
            seed=args.seed,
            deadline_s=args.deadline,
            jax_platform=args.jax_platform or None,
            log=log,
            **({"commitment_interval": args.commitment_interval}
               if args.commitment_interval else {}),
        )

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"report -> {args.json}")
    print(json.dumps(report, indent=1, sort_keys=True))
    ok = _passes(report)
    log("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
