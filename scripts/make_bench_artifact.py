"""Assemble a BENCH_r0N.json driver-shaped artifact from a bench.py run.

The driver's artifacts (`BENCH_r0*.json`) wrap one repeated `python
bench.py` invocation as {n, cmd, rc, tail, parsed}. When a round's
artifact is produced in-session instead (the driver hasn't run since
r05), this script builds the same shape from a captured run and adds the
provenance fields an honest off-rig artifact needs — the platform, the
size-reduction env knobs, segment failures, the compile-cache story —
all through the shared provenance module (tigerbeetle_tpu/artifact.py,
also the PRODDAY emitter's wrapper, so the two artifacts cannot drift).

Usage:
  python scripts/make_bench_artifact.py OUT.json STDOUT STDERR RC 'ENV...'
"""

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from tigerbeetle_tpu.artifact import wrap_artifact  # noqa: E402


def main() -> int:
    out_path, stdout_path, stderr_path, rc, env = sys.argv[1:6]
    parsed = None
    with open(stdout_path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    for ln in reversed(lines):  # bench prints the summary JSON last
        try:
            parsed = json.loads(ln)
            break
        except json.JSONDecodeError:
            continue
    if parsed is None:
        print("no JSON summary in stdout — bench did not finish", file=sys.stderr)
        return 1
    with open(stderr_path) as f:
        tail = f.read()[-8000:]
    # Segment-health summary: a live segment that died mid-run leaves
    # nulls in the summary (r06's failover flake) — name the incomplete
    # segments in the artifact itself so a null reads as "segment
    # failed", never as "measured zero".
    incomplete = []
    if parsed.get("failover_recovery_ms") is None:
        incomplete.append("failover")
    if not parsed.get("frontier_steps"):
        incomplete.append("frontier")
    elif len(parsed["frontier_steps"]) < 4:
        incomplete.append("frontier_short_ladder")
    if parsed.get("cross_ledger_tps") is None:
        incomplete.append("cross_ledger")
    artifact = wrap_artifact(
        cmd=f"env {env} python bench.py", rc=int(rc), env=env, tail=tail,
        parsed=parsed, segments_incomplete=incomplete,
        # the platform bench.py observed and printed, or none
        backend=(parsed.get("device") or {}).get("platform"),
    )
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
