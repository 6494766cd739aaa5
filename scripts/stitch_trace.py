#!/usr/bin/env python
"""Stitch per-process trace dumps into ONE cluster-causal Perfetto file.

Each replica of a cluster dumps its own span ring (`start --trace`,
SIGTERM; or SIGQUIT's `<trace>.quit.json`) with local pid 0. This tool
merges N such dumps: input i becomes pid i (named after its file), and
every span tagged with an op's trace id (vsr/header.py trace_id — spans
carry it as args `trace`/`traces`) becomes a Perfetto FLOW, so clicking
one leg of an op in the merged file draws its whole causal tree across
processes: ingress -> fuse/quorum -> journal write -> commit -> reply ->
CDC emit -> device apply.

The device's timeline is not stitched in here: a server that holds a
chip writes these same spans (as `tb.*`) into the profiler's own trace,
beside the kernels, on one clock (`start --device-trace <dir>`).

Usage:
    python scripts/stitch_trace.py --out cluster.json \
        r0.trace.json r1.trace.json r2.trace.json

The output is canonical JSON (sorted keys, fixed separators): stitching
byte-identical inputs — e.g. two same-seed simulator replays — yields
byte-identical output, so stitched traces can be diffed like any other
deterministic artifact.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tigerbeetle_tpu.tracer import stitch  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(
        description="merge per-process trace dumps into one "
        "Perfetto-loadable file with cross-process flow events"
    )
    ap.add_argument("inputs", nargs="+",
                    help="trace dumps, one per process (pid = input order)")
    ap.add_argument("--out", required=True, help="merged output path")
    args = ap.parse_args()

    event_lists = []
    labels = []
    for path in args.inputs:
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"] if isinstance(doc, dict) else doc
        event_lists.append(events)
        labels.append(os.path.basename(path))
    merged = stitch(event_lists, labels=labels)
    with open(args.out, "w") as f:
        json.dump({"traceEvents": merged}, f, sort_keys=True,
                  separators=(",", ":"))
    flows = sum(1 for e in merged if e.get("ph") in ("s", "t", "f"))
    ids = len({e["id"] for e in merged if e.get("ph") in ("s", "t", "f")})
    print(
        f"stitched {len(args.inputs)} dump(s): {len(merged)} events, "
        f"{flows} flow legs across {ids} op trace id(s) "
        f"-> {args.out}",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
