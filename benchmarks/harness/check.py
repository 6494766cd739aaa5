"""The comparison that decides `correct`.

What the timed path produced (every reply of every create and lookup
request of the run, the rows read back after the window, and in the
follower configuration the chip's own state digest from the server's
finalize) is compared with the plain reference replaying the same
requests in commit order. Every number is an exact count, so every limit
is 0 (see PERF.md section 2 for the readings).
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.ledger_ref import CONTROLS, ReferenceLedger
from benchmarks.reference.wire_types import (
    ACCOUNT_DTYPE,
    CREATE_TRANSFERS_RESULT_DTYPE,
    TRANSFER_DTYPE,
    Operation,
)

_CREATE_A = int(Operation.create_accounts)
_CREATE_T = int(Operation.create_transfers)
_LOOKUP_A = int(Operation.lookup_accounts)
_LOOKUP_T = int(Operation.lookup_transfers)
FP_FIELDS = ("accounts_fp", "transfers_fp", "accounts", "transfers",
             "commit_timestamp")


def _ids(body: bytes) -> list[int]:
    raw = np.frombuffer(body, dtype=np.uint64)
    lo, hi = raw[0::2].tolist(), raw[1::2].tolist()
    return [l | (h << 64) for l, h in zip(lo, hi)]


def replay(answered: list, fault: str | None = None, fault_at: int = 0):
    """The reference's answer to each answered request, in commit order,
    and its final state digest. `answered` is sorted by op."""
    ref = ReferenceLedger(fault=fault, fault_at=fault_at)
    out = []
    for r in answered:
        if r.operation == _CREATE_A or r.operation == _CREATE_T:
            dtype = ACCOUNT_DTYPE if r.operation == _CREATE_A else TRANSFER_DTYPE
            rows = np.frombuffer(r.body, dtype=dtype)
            sparse = ref.execute(r.operation, r.ts, rows)
            res = np.zeros(len(sparse), dtype=CREATE_TRANSFERS_RESULT_DTYPE)
            if sparse:
                res["index"] = [i for i, _ in sparse]
                res["result"] = [c for _, c in sparse]
            out.append(res.tobytes())
        elif r.operation == _LOOKUP_A:
            out.append(ref.lookup_account_rows(_ids(r.body)))
        elif r.operation == _LOOKUP_T:
            out.append(ref.lookup_transfer_rows(_ids(r.body)))
        else:
            raise ValueError(f"operation {r.operation} has no reference")
    return out, ref.fingerprint(), ref


def judge(records: list, answered: list, got: list, got_fp: dict | None,
          want: list, want_fp: dict, follower: bool,
          server_verdict: dict) -> dict:
    """name -> (number compared, its limit)."""
    creates = lookups = bad_creates = bad_lookups = 0
    first_bad = None
    failed_by_design = 0
    for r, g, w in zip(answered, got, want):
        is_create = r.operation in (_CREATE_A, _CREATE_T)
        if is_create:
            creates += 1
            failed_by_design += len(w) // 8
        else:
            lookups += 1
        if g != w:
            if is_create:
                bad_creates += 1
            else:
                bad_lookups += 1
            first_bad = first_bad or {
                "op": r.op, "cls": r.cls, "phase": r.phase,
                "want_bytes": len(w), "got_bytes": len(g),
            }
    # conservation over the account rows read back after the window
    sums = [0, 0, 0, 0]
    readback_rows = 0
    for r, g in zip(answered, got):
        if r.phase == "after":
            readback_rows += len(g) // 128
            if r.operation == _LOOKUP_A:
                rows = np.frombuffer(g, dtype=ACCOUNT_DTYPE)
                for k, f in enumerate(("debits_posted", "credits_posted",
                                       "debits_pending", "credits_pending")):
                    sums[k] += int(rows[f + "_lo"].sum(dtype=object)) + (
                        int(rows[f + "_hi"].sum(dtype=object)) << 64)
    ops = [r.op for r in answered]
    numbers = {
        "reply_mismatches": (bad_creates, 0),
        "lookup_mismatches": (bad_lookups, 0),
        "unanswered": (sum(1 for r in records if r.reply is None), 0),
        "duplicate_ops": (len(ops) - len(set(ops)), 0),
        "conservation_gap": (abs(sums[0] - sums[1]) + abs(sums[2] - sums[3]), 0),
        "readback_rows_short": (0 if readback_rows > 0 else 1, 0),
    }
    if follower:
        fp = got_fp or {}
        numbers["chip_digest_fields_off"] = (
            sum(1 for f in FP_FIELDS if fp.get(f) != want_fp[f]), 0)
        numbers["chip_parity_unverified"] = (
            0 if server_verdict.get("verified") is True
            and server_verdict.get("hash_log_ok") is True
            and not server_verdict.get("error") else 1, 0)
    numbers["server_exit_nonzero"] = (
        0 if server_verdict.get("exit_code") == 0 else 1, 0)
    detail = {
        "creates_compared": creates, "lookups_compared": lookups,
        "events_failed_by_design": failed_by_design,
        "readback_rows": readback_rows, "first_bad": first_bad,
    }
    return {"numbers": numbers, "detail": detail}


def is_correct(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())


def compare(records: list, follower: bool, chip_fp: dict | None,
            server_verdict: dict, controls: tuple = ()) -> dict:
    """Judge the run; with `controls`, also judge each control (the
    reference with one guarantee broken, put in the program's place):
    its verdict must come out as not correct."""
    answered = sorted((r for r in records if r.reply is not None),
                      key=lambda r: r.op)
    want, want_fp, ref = replay(answered)
    got = [r.reply for r in answered]
    out = judge(records, answered, got, chip_fp, want, want_fp, follower,
                server_verdict)
    out["reference"] = {"fast_batches": ref.fast_batches,
                        "scalar_batches": ref.scalar_batches,
                        "fast_events": ref.fast_events,
                        "scalar_events": ref.scalar_events}
    window_creates = [i for i, r in enumerate(
        [r for r in answered if r.operation == _CREATE_T]) if r.phase == "window"]
    for name in controls:
        if name not in CONTROLS:
            raise ValueError(f"unknown control {name!r}")
        at = window_creates[len(window_creates) // 2] if window_creates else 0
        c_got, c_fp, _ = replay(answered, fault=name, fault_at=at)
        verdict = dict(server_verdict, verified=True, hash_log_ok=True,
                       error=None, exit_code=0)
        c = judge(records, answered, c_got, c_fp, want, want_fp, follower, verdict)
        out.setdefault("controls", {})[name] = {
            "correct": is_correct(c["numbers"]),
            "numbers": {k: v for k, (v, _l) in c["numbers"].items()},
        }
    return out
