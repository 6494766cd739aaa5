"""Standalone load/latency frontier sweep driver.

Runs benchmark.run_frontier against a fresh live server and writes its
JSON report:

  python scripts/frontier.py out.json
  python scripts/frontier.py --steps 50000,100000,200000 \
      --backend dual --step-s 8 out.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("out", help="write the frontier JSON segment here")
    p.add_argument("--steps", default="25000,50000,100000,200000,400000",
                   help="offered-load ladder, events/s, comma-separated")
    p.add_argument("--step-s", type=float, default=6.0)
    p.add_argument("--batch", type=int, default=2048)
    p.add_argument("--sessions", type=int, default=32)
    p.add_argument("--backend", default="dual",
                   help="server backend (dual | native)")
    p.add_argument("--sample-every", type=int, default=1,
                   help="server-side latency sampling (1 = every request)")
    p.add_argument("--jax-platform", default="",
                   help="pin the server's JAX platform (e.g. cpu)")
    args = p.parse_args()

    from tigerbeetle_tpu.benchmark import run_frontier

    out = run_frontier(
        steps=tuple(int(x) for x in args.steps.split(",") if x),
        step_s=args.step_s,
        batch=args.batch,
        sessions=args.sessions,
        backend=args.backend,
        sample_every=args.sample_every,
        jax_platform=args.jax_platform or None,
        log=lambda *a: print("[frontier]", *a, file=sys.stderr),
    )
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    steps = out.get("steps", [])
    for s in steps:
        print(
            f"offered {s['offered_tps']:>9}/s  achieved "
            f"{s['achieved_tps']:>10}/s  p50 {s['p50_ms']:>8}ms  p99 "
            f"{s['p99_ms']:>8}ms  shed {s['shed_rate']:>6}  "
            f"dominant {s['dominant_leg']}"
        )
    print(f"peak {out.get('peak_achieved_tps')}/s  knee "
          f"{out.get('saturation_offered_tps')}  accounted "
          f"{(out.get('breakdown') or {}).get('accounted_ratio')}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
