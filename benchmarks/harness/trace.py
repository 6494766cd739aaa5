"""Reduction of a profiler trace (.xplane.pb) to what the per-layer
metrics and the breakdown read. Run as a child process
(`python benchmarks/harness/trace.py <dir>`), after the server has exited,
pinned to the CPU, so that the benchmark's own process stays off JAX. Prints one JSON
object. Checked on a small recorded trace in benchmarks/tests.

Times are seconds; event times count from the start of the trace.

The traced window is the span the profiler COLLECTED (`collected_s`: the
earliest start to the latest end over every event of every plane), and a
chip's busy time is a union of intervals inside it, so 0 < busy_s <=
collected_s whatever the chip does. The stamps the control thread takes
around start_trace/stop_trace are on another clock and around another span
(the profiler goes on collecting a few ms after it is asked to stop): they
only cross-check the collected span (`traced_window_s`).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# a collected span this far (share) from the stamps' is a failed trace
STAMPS_TOLERANCE = 0.05


def union_s(intervals: list) -> tuple[float, list]:
    """(covered seconds, merged intervals) of [start, end) pairs."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


_SHAPE = re.compile(r"[a-z]\w*\[[\d,]*\]")


def short_op(name: str) -> str:
    """`%while.6 = (u32[]..., u32[1048577,32]...) while(...)` ->
    `%while.6 u32[16777217,32]`: the op and the largest array it touches
    (an HLO line runs to thousands of characters)."""
    if " = " not in name:
        return name[:120]
    lhs = name.split(" = ", 1)[0]

    def elements(shape: str) -> int:
        n = 1
        for d in shape[shape.index("[") + 1:-1].split(","):
            n *= int(d) if d else 1
        return n

    shapes = _SHAPE.findall(name)
    return f"{lhs} {max(shapes, key=elements)}" if shapes else lhs


def plane_ends(planes: list) -> list:
    """[plane, earliest start, latest end] of every plane that has events."""
    out = []
    for p in planes:
        ev = [e for ln in p["lines"] for e in ln["events"]]
        if ev:
            out.append([p["name"], min(s for _n, s, _d in ev),
                        max(s + d for _n, s, d in ev)])
    return out


def traced_window_s(reduced: dict, stamps_s: float) -> float:
    """The traced window a run reports and divides by: the collected span.
    The stamps only guard it: a profiler that dropped half its window must
    not pass as a short one."""
    collected = reduced["collected_s"]
    if abs(collected - stamps_s) > STAMPS_TOLERANCE * stamps_s:
        raise RuntimeError(
            f"the profiler collected {collected:.6f} s where the stamps around "
            f"it span {stamps_s:.6f} s: more than {STAMPS_TOLERANCE:.0%} apart; "
            f"planes {reduced.get('plane_ends')}")
    return collected


def reduce_planes(planes: list, top: int = 10) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_s,
    dur_s), ...]}]}]. Device planes are the `/device:TPU:n` ones; a chip's
    busy time is the union of its `XLA Ops` intervals (or, where that line
    is absent, of all its events). The collected span runs over the host
    planes too: the program's `tb.*` spans are on them, so a chip that
    idles at an edge of the span is idle inside it."""
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    hosts = [p for p in planes if p["name"].startswith("/host:")]
    ends = plane_ends(planes)
    if not devices or not ends:
        return {"error": "no /device:TPU plane, or no event, in the trace",
                "planes": [p["name"] for p in planes]}
    c0, c1 = min(a for _p, a, _b in ends), max(b for _p, _a, b in ends)
    busy, op_time, mod_time, mod_count = [], {}, {}, {}
    gaps: list = []
    first = last = None
    for dev in devices:
        lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
        ops = lines.get(OPS_LINE)
        if ops is None:
            ops = [e for ev in lines.values() for e in ev]
        covered, merged = union_s([[s, s + d] for _n, s, d in ops])
        busy.append(covered)
        if merged:
            first = merged[0][0] if first is None else min(first, merged[0][0])
            last = merged[-1][1] if last is None else max(last, merged[-1][1])
        for (a0, a1), (b0, _b1) in zip(merged, merged[1:]):
            gaps.append((b0 - a1, a1, b0))
        for name, _s, d in ops:
            name = short_op(name)
            op_time[name] = op_time.get(name, 0.0) + d
        for name, _s, d in lines.get(MODULES_LINE, ()):
            mod_time[name] = mod_time.get(name, 0.0) + d
            mod_count[name] = mod_count.get(name, 0) + 1
    gaps.sort(reverse=True)
    named_gaps: dict = {}
    host_events = [(f"{ln['name'].split('/')[0]}:{n}", s, s + d)
                   for h in hosts for ln in h["lines"]
                   for n, s, d in ln["events"] if d > 5e-5]
    for length, a, b in gaps[:200]:
        best, best_overlap = "unattributed", 0.0
        for name, s, e in host_events:
            overlap = min(b, e) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        named_gaps[best] = named_gaps.get(best, 0.0) + length
    n = len(devices)

    def ranked(table: dict) -> list:
        return sorted(([k, v / n] for k, v in table.items()),
                      key=lambda kv: -kv[1])

    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "collected_s": c1 - c0, "collected_first_s": c0, "collected_last_s": c1,
        "first_s": first, "last_s": last,  # the devices' ops
        "plane_ends": ends,
        "device_ops": ranked(op_time)[:top],
        "modules": [[k, v, mod_count[k] / n] for k, v in ranked(mod_time)],
        "launches": sum(mod_count.values()) / n,
        "idle_gaps": ranked(named_gaps)[:top],
        "idle_gap_total_s": sum(g[0] for g in gaps) / n,
    }


def read_xplane(path: str) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main(argv: list) -> int:
    found = sorted(glob.glob(os.path.join(argv[1], "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        print(json.dumps({"error": f"no .xplane.pb under {argv[1]}"}))
        return 1
    planes = read_xplane(found[-1])
    out = reduce_planes(planes)
    out["xplane_bytes"] = os.path.getsize(found[-1])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
