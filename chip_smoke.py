#!/usr/bin/env python3
"""chip_smoke.py — the served commit path on the chip, from a clean checkout.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded backend on a 2x2 host

Drives format -> start -> TCP sessions -> replies -> lookups through the
entry points a user calls (`python -m tigerbeetle_tpu format|start`), at the
CLI's own default table geometry (2^20 account / 2^24 transfer slots), with
a state a deployment of that geometry would hold: 409,500 accounts and over
a million transfers in wire batches of 8190 from eight sessions, generated
from --seed, including one batch each of the shapes that leave the fast
tier (two-phase pend -> post/void, same-batch pend -> post, linked chains
that fail, duplicate ids, a debits_must_not_exceed_credits account).

THIS process never initialises a JAX backend: it is the load generator
(numpy, sockets, models/oracle.py). The chip belongs to one process at a
time, so the device line printed last comes from the SERVER that held it
(its `[device]` line), and one server runs at a time.

One JSON line per phase on stdout, so a failure names its phase:

  tree     versions, environment, `make -C native` (the only build)
  dual     start --backend dual: native engine replies, the chip follows
  device   start --backend device: the chip on the reply path
  restart  SIGKILL that server, start it again on the same file, read back
  probe    dispatch/h2d before and after a first d2h fetch (never in `ok`)

`correct` for a serving phase = every sparse result code and every looked-up
row equals models/oracle.py replaying the same requests in commit order,
conservation holds, the server exits 0 on SIGTERM, and (dual) its [stats]
shows hash-log/fingerprint parity verified with no applier error.

With `--chips 4` it runs exactly `start --backend sharded --shards 4` at the
per-shard default geometry under the same stream, and the oracle.

The LAST stdout line is the contract's `{"ok": true, "device": {...}}` and
is printed only when every phase passed on a TPU. Any failure exits non-zero
and prints no such line.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import importlib.metadata
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH = 8190
SESSIONS = 8
# default load: 50 account batches (409,500 of the 524,288 the 2^20-slot
# table admits) and 123 bulk transfer batches (1,007,370) + 7 shaped ones
ACCOUNT_BATCHES = 50
BULK_BATCHES = 123


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(*a) -> None:
    print("[smoke]", *a, file=sys.stderr, flush=True)


def _version(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def phase_tree() -> dict:
    """Versions + environment as found, and the ONE native build, before
    any child exists (server and load generator then find the library)."""
    gxx = subprocess.run(["g++", "--version"], capture_output=True, text=True)
    out = {
        "python": sys.version.split()[0],
        "jax": _version("jax"),
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
        "env": {
            k: os.environ.get(k)
            for k in ("JAX_PLATFORMS", "TB_JAX_PLATFORM",
                      "JAX_COMPILATION_CACHE_DIR")
        },
        "g++": (gxx.stdout.splitlines() or [gxx.stderr.strip()])[0],
    }
    t0 = time.monotonic()
    make = subprocess.run(
        ["make", "-s", "-C", os.path.join(HERE, "native")],
        capture_output=True, text=True,
    )
    out["native_build_s"] = round(time.monotonic() - t0, 1)
    if make.returncode != 0:
        raise RuntimeError(
            f"make -C native failed ({make.returncode}):\n"
            f"{make.stdout}{make.stderr}"
        )
    return out


# ----------------------------------------------------------------------
# the seeded stream
# ----------------------------------------------------------------------

def build_stream(seed: int, account_batches: int, bulk_batches: int,
                 BATCH: int = BATCH):
    """(account bodies, per-session transfer queues, lookup id batches).
    Everything is a pure function of the arguments."""
    import numpy as np

    from tigerbeetle_tpu.types import (
        ACCOUNT_DTYPE,
        TRANSFER_DTYPE,
        AccountFlags,
        TransferFlags as TF,
    )

    rng = np.random.default_rng(seed)
    n_accounts = account_batches * BATCH
    limit_id = n_accounts  # the one debits_must_not_exceed_credits account
    plain = n_accounts - 1  # bulk traffic uses accounts 1..plain

    accounts = []
    for b in range(account_batches):
        arr = np.zeros(BATCH, dtype=ACCOUNT_DTYPE)
        arr["id_lo"] = np.arange(1 + b * BATCH, 1 + (b + 1) * BATCH,
                                 dtype=np.uint64)
        arr["ledger"] = 1
        arr["code"] = 1
        arr["user_data_64"] = rng.integers(0, 1 << 40, BATCH, dtype=np.uint64)
        accounts.append(arr)
    accounts[-1]["flags"][-1] = int(
        AccountFlags.debits_must_not_exceed_credits
    )
    assert int(accounts[-1]["id_lo"][-1]) == limit_id

    next_base = [1_000_000_000]

    def plain_batch(n=BATCH, flags=0):
        arr = np.zeros(n, dtype=TRANSFER_DTYPE)
        base = next_base[0]
        next_base[0] += 10_000
        # id_order=reversed, as the reference benchmark submits them
        arr["id_lo"] = np.arange(base + n - 1, base - 1, -1, dtype=np.uint64)
        dr = rng.integers(1, plain + 1, size=n, dtype=np.uint64)
        off = rng.integers(1, plain, size=n, dtype=np.uint64)
        arr["debit_account_id_lo"] = dr
        arr["credit_account_id_lo"] = (dr - 1 + off) % plain + 1
        arr["amount_lo"] = rng.integers(1, 1001, size=n, dtype=np.uint64)
        arr["ledger"] = 1
        arr["code"] = 1
        arr["flags"] = flags
        return arr

    def resolve(pend, flags):
        arr = np.zeros(len(pend), dtype=TRANSFER_DTYPE)
        base = next_base[0]
        next_base[0] += 10_000
        arr["id_lo"] = np.arange(base, base + len(pend), dtype=np.uint64)
        arr["pending_id_lo"] = pend["id_lo"]
        arr["flags"] = flags
        return arr

    half = BATCH // 2
    # A/B: a pending batch, then a batch that posts one half and voids the
    # other (the fast_pv tier)
    pend = plain_batch(flags=int(TF.pending))
    post_void = resolve(pend, int(TF.post_pending_transfer))
    post_void["flags"][half:] = int(TF.void_pending_transfer)
    # C: same-batch pend -> post (the 2-wave stepper)
    same = plain_batch(flags=0)
    same["flags"][:half] = int(TF.pending)
    same[half:] = resolve(same[:half], int(TF.post_pending_transfer))
    # D: 20 linked chains of 3 among plain lanes, every other chain broken
    # by a zero amount (waves + the serial RESIDUE kernel)
    some_linked = plain_batch()
    for c in range(min(20, BATCH // 12)):
        some_linked["flags"][3 * c: 3 * c + 2] = int(TF.linked)
        if c % 2:
            some_linked["amount_lo"][3 * c + 1] = 0
    # E: nothing but linked chains of 3, every 7th broken (the whole-batch
    # SERIAL scan — the 8.5 GiB-temp program)
    all_linked = plain_batch()
    all_linked["flags"][:] = int(TF.linked)
    all_linked["flags"][2::3] = 0
    all_linked["amount_lo"][1::21] = 0
    # F: 100 ids submitted twice in one batch (second sees `exists`)
    dups = plain_batch()
    n_dup = min(100, BATCH // 8)
    dups[-n_dup:] = dups[:n_dup]
    # G: the limit account is credited 100, then debited 150 -> the debit
    # must fail exceeds_credits, in commit order
    limit = plain_batch()
    limit["credit_account_id_lo"][10] = limit_id
    limit["amount_lo"][10] = 100
    limit["debit_account_id_lo"][half] = limit_id
    limit["amount_lo"][half] = 150
    for lane in (10, half):  # keep debit != credit on the edited lanes
        side = "debit" if lane == 10 else "credit"
        if int(limit[f"{side}_account_id_lo"][lane]) == limit_id:
            limit[f"{side}_account_id_lo"][lane] = 1
    shaped = [pend, post_void, same, some_linked, all_linked, dups, limit]

    bulk = [plain_batch() for _ in range(bulk_batches)]
    queues = [bulk[s::SESSIONS] for s in range(SESSIONS)]
    # session 0 sends the shaped batches, in order, in the middle of its
    # bulk share, while the other sessions keep their bulk flowing
    mid = len(queues[0]) // 2
    queues[0] = queues[0][:mid] + shaped + queues[0][mid:]

    def id_batch(lo):
        out = np.zeros(2 * len(lo), dtype=np.uint64)
        out[0::2] = lo
        return out

    lookups_a = [id_batch(a["id_lo"]) for a in accounts]
    lookups_t = [id_batch(t["id_lo"]) for t in shaped + bulk[:3]]
    lookups_t.append(id_batch(  # ids nobody created: an empty reply
        np.arange(5, 5 + BATCH, dtype=np.uint64)
    ))
    return accounts, queues, lookups_a, lookups_t


# ----------------------------------------------------------------------
# server + sessions
# ----------------------------------------------------------------------

def child_env() -> dict:
    pp = os.environ.get("PYTHONPATH", "")
    return dict(os.environ, PYTHONPATH=f"{HERE}:{pp}" if pp else HERE,
                TB_PARENT_WATCHDOG="1")


class Server:
    """One `python -m tigerbeetle_tpu start` child and what it printed."""

    def __init__(self, backend: str, path: str, extra: tuple = ()):
        from tigerbeetle_tpu.benchmark import free_port, wait_listening

        self.port = free_port()
        self.backend = backend
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tigerbeetle_tpu", "start",
             "--addresses", f"127.0.0.1:{self.port}",
             "--backend", backend, *extra, path],
            cwd=HERE, env=child_env(), start_new_session=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        head = wait_listening(self.proc, backend, note)
        self.boot_s = time.monotonic() - t0
        self.device = None
        for line in head:
            if line.startswith("[device] "):
                self.device = json.loads(line[len("[device] "):])
        self.stats: dict | None = None
        self._have_stats = threading.Event()
        self.tail: collections.deque = collections.deque(maxlen=60)
        self._drain = threading.Thread(target=self._drain_stdout, daemon=True)
        self._drain.start()

    def _drain_stdout(self) -> None:
        # keep reading: an unread pipe fills and blocks the server's print
        for line in self.proc.stdout:
            for mark in ("[stats] ", "[quit] stats "):
                if line.startswith(mark):
                    try:
                        self.stats = json.loads(line[len(mark):])
                        self._have_stats.set()
                    except ValueError:
                        self.tail.append(line)
                    break
            else:
                self.tail.append(line)

    def terminate(self, timeout: float = 700.0) -> int | None:
        """SIGTERM -> the server prints [stats] (dual: after draining the
        applier and verifying parity) and exits with its verdict."""
        self.proc.terminate()
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        self.kill()
        self._drain.join(timeout=10)
        return rc

    def quit_stats(self, timeout: float = 120.0) -> None:
        """SIGQUIT: the server dumps its counters (`[quit] stats`) and
        KEEPS RUNNING — how a server that is about to be SIGKILLed still
        tells its compiles, tiers and peak memory."""
        self.proc.send_signal(signal.SIGQUIT)
        self._have_stats.wait(timeout)

    def kill(self) -> None:
        from tigerbeetle_tpu.benchmark import kill_process_group

        kill_process_group(self.proc)
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass

    def summary(self) -> dict:
        """The phase line's server half: boot, compiles, peak memory."""
        s = self.stats or {}
        cs = s.get("compile_sentinel") or {}
        dev = s.get("device") or {}
        return {
            "boot_s": round(self.boot_s, 1),
            "compiles": {
                "total": cs.get("total"),
                "post_warmup": cs.get("post_warmup"),
                "cache_requests": cs.get("cache_requests"),
                "cache_hits": cs.get("cache_hits"),
                "cache_misses": cs.get("cache_misses"),
                "per_fn": cs.get("per_fn"),
            },
            "peak_bytes_in_use": dev.get("peak_bytes_in_use"),
            "bytes_in_use_at_boot": (self.device or {}).get("bytes_in_use"),
            "tiers": s.get("split"),
        }


Record = collections.namedtuple("Record", "op ts operation body reply")


def drive(sessions, queues, quiet_s: float = 600.0) -> list:
    """Each session keeps one request in flight from its own queue of
    (operation, body); returns a Record per request. The reply header
    carries the op number and the prepare timestamp the cluster assigned,
    which is all the oracle needs to replay in commit order. Gives up
    after `quiet_s` without ANY reply (an on-demand compile or a sharded
    serial batch takes minutes, not that long)."""
    records = []
    pos = [0] * len(sessions)
    inflight: dict[int, tuple] = {}

    def send(i: int) -> None:
        if pos[i] < len(queues[i]):
            operation, body = queues[i][pos[i]]
            pos[i] += 1
            sessions[i].client.request(operation, body)
            inflight[i] = (operation, body)

    for i in range(len(sessions)):
        send(i)
    t_last = time.monotonic()
    while inflight:
        progressed = False
        for i in list(inflight):
            s = sessions[i]
            s.pump()
            if s.client.done:
                header, reply = s.client.take_reply()
                operation, body = inflight.pop(i)
                records.append(Record(
                    header.op, header.timestamp, operation, body, reply
                ))
                send(i)
                progressed = True
                t_last = time.monotonic()
            else:
                s.ticker.advance(time.monotonic())
        if not progressed:
            if time.monotonic() - t_last > quiet_s:
                raise TimeoutError(
                    f"no reply for {quiet_s:.0f}s "
                    f"({len(records)} replies so far)"
                )
            time.sleep(0.0002)
    return records


def open_sessions(port: int, base_id: int, n: int) -> list:
    from tigerbeetle_tpu.benchmark import _BenchClient

    sessions = [_BenchClient(base_id + i, port) for i in range(n)]
    for s in sessions:
        s.register()
    return sessions


def close_sessions(sessions) -> None:
    for s in sessions:
        s.bus.drop_connections()


# ----------------------------------------------------------------------
# the reference and the comparison
# ----------------------------------------------------------------------

def replay_oracle(records):
    """models/oracle.py over the create requests in commit (op) order with
    the cluster's timestamps: (oracle, code mismatches, events)."""
    import numpy as np

    from tigerbeetle_tpu.models.oracle import OracleStateMachine
    from tigerbeetle_tpu.state_machine import decode_results
    from tigerbeetle_tpu.types import ACCOUNT_DTYPE, TRANSFER_DTYPE, Operation

    oracle = OracleStateMachine()
    mismatches = []
    events = failed = 0
    for r in sorted(records, key=lambda r: r.op):
        dtype = (ACCOUNT_DTYPE if r.operation == Operation.create_accounts
                 else TRANSFER_DTYPE)
        rows = np.frombuffer(r.body, dtype=dtype)
        want = oracle.execute(r.operation, r.ts, rows)
        got = decode_results(r.reply, r.operation)
        events += len(rows)
        failed += len(want)
        if want != got:
            mismatches.append({
                "op": r.op, "want": want[:4], "got": got[:4],
                "n_want": len(want), "n_got": len(got),
            })
    return oracle, mismatches, events, failed


def compare_lookups(oracle, lookups) -> dict:
    """Looked-up rows vs the oracle's, byte for byte, and conservation
    over every account row the server returned."""
    import numpy as np

    from tigerbeetle_tpu.types import ACCOUNT_DTYPE, Operation

    rows = bad = 0
    first_bad = None
    sums = [0, 0, 0, 0]  # debits/credits posted, debits/credits pending
    for r in lookups:
        ids = np.frombuffer(r.body, dtype=np.uint64)
        ids = [int(lo) | (int(hi) << 64) for lo, hi in zip(ids[0::2], ids[1::2])]
        if r.operation == Operation.lookup_accounts:
            want = oracle.lookup_accounts(ids)
            got = np.frombuffer(r.reply, dtype=ACCOUNT_DTYPE)
            for k, f in enumerate(("debits_posted", "credits_posted",
                                   "debits_pending", "credits_pending")):
                sums[k] += int(got[f + "_lo"].sum(dtype=object)) + (
                    int(got[f + "_hi"].sum(dtype=object)) << 64
                )
        else:
            want = oracle.lookup_transfers(ids)
        want_bytes = b"".join(x.to_np().tobytes() for x in want)
        rows += len(want)
        if want_bytes != r.reply:
            bad += 1
            first_bad = first_bad or {
                "op": r.op, "want_rows": len(want),
                "got_rows": len(r.reply) // 128,
            }
    return {
        "rows": rows, "bad_replies": bad, "first_bad": first_bad,
        "conservation": sums[0] == sums[1] and sums[2] == sums[3],
        "posted_total": sums[0], "pending_total": sums[2],
    }


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def format_file(path: str) -> None:
    fmt = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster", "0",
         "--replica", "0", "--replica-count", "1", path],
        cwd=HERE, env=child_env(), capture_output=True, text=True,
        timeout=300,
    )
    if fmt.returncode != 0:
        raise RuntimeError(f"format failed: {fmt.stdout}{fmt.stderr}")


def load_and_read(server: Server, stream, pool) -> dict:
    """The seeded stream through eight sessions, then the lookups; the
    oracle replays on a worker thread while the lookups are on the wire."""
    from tigerbeetle_tpu.types import Operation

    accounts, queues, lookups_a, lookups_t = stream
    sessions = open_sessions(server.port, 0xC0000, SESSIONS)
    try:
        t0 = time.monotonic()
        created = drive(sessions, [
            [(Operation.create_accounts, a.tobytes())
             for a in accounts[s::SESSIONS]]
            for s in range(SESSIONS)
        ])
        t_accounts = time.monotonic() - t0
        t0 = time.monotonic()
        created += drive(sessions, [
            [(Operation.create_transfers, t.tobytes()) for t in q]
            for q in queues
        ])
        t_transfers = time.monotonic() - t0
        replay = pool.submit(replay_oracle, created)
        t0 = time.monotonic()
        reads = (
            [(Operation.lookup_accounts, ids.tobytes()) for ids in lookups_a]
            + [(Operation.lookup_transfers, ids.tobytes())
               for ids in lookups_t]
        )
        looked = drive(sessions, [reads[s::SESSIONS] for s in range(SESSIONS)])
        t_lookups = time.monotonic() - t0
    finally:
        close_sessions(sessions)
    return {
        "replay": replay, "looked": looked,
        "load": {
            "accounts": sum(len(a) for a in accounts),
            "transfers": sum(len(t) for q in queues for t in q),
            "create_requests": len(created),
            "lookup_requests": len(looked),
            "sessions": SESSIONS,
            "accounts_s": round(t_accounts, 2),
            "transfers_s": round(t_transfers, 2),
            "lookups_s": round(t_lookups, 2),
        },
    }


def judge(out: dict, run: dict) -> bool:
    """Fill `out` with the comparison against the oracle; True = correct."""
    oracle, mismatches, events, failed = run["replay"].result()
    cmp = compare_lookups(oracle, run["looked"])
    out["load"] = run["load"]
    out["oracle"] = {
        "events": events, "events_failed_by_design": failed,
        "code_mismatches": len(mismatches), "first_mismatch": mismatches[:1],
        **cmp,
    }
    return (
        not mismatches and failed > 0 and cmp["bad_replies"] == 0
        and cmp["conservation"] and cmp["rows"] > 0
    )


def judge_exit(out: dict, server: Server, rc, need_tiers: bool) -> bool:
    """The server's own verdict: exit code, [stats], device parity, and
    that the wave and serial tiers really ran on the device."""
    out.update(server.summary())
    out["exit_code"] = rc
    stats = server.stats
    if stats is None:
        out["error"] = "no [stats] line; last output: " + "".join(
            list(server.tail)[-12:]
        )
        return False
    ok = rc == 0
    shadow = stats.get("device_shadow")
    if server.backend == "dual":
        hl = (shadow or {}).get("hash_log") or {}
        out["parity"] = {
            "verified": (shadow or {}).get("verified"),
            "error": (shadow or {}).get("error"),
            "hash_log_ops": hl.get("ops"),
            "hash_log_ok": hl.get("ok"),
            "shadow_batches": (shadow or {}).get("shadow_batches"),
        }
        ok = ok and shadow is not None and shadow.get("verified") is True \
            and not shadow.get("error") and hl.get("ok") is True
    if need_tiers:
        per_fn = (stats.get("compile_sentinel") or {}).get("per_fn") or {}
        tiers = stats.get("split") or {}
        wave = any(k.startswith("wave_stepper_") for k in per_fn) \
            and tiers.get("waves", 0) > 0
        serial = tiers.get("serial", 0) > 0 \
            and tiers.get("residue_events", 0) > 0 \
            and "commit_transfers_residue" in per_fn
        out["tiers_ran"] = {"wave": wave, "serial": serial}
        ok = ok and wave and serial
    return ok


def phase_serving(backend: str, workdir: str, stream, pool,
                  extra: tuple = ()) -> tuple[dict, Server | None, dict]:
    """format + start + load + lookups against one backend. Returns the
    phase line, the still-running server, and the run (for `restart`)."""
    out: dict = {}
    path = os.path.join(workdir, f"{backend}.tigerbeetle")
    format_file(path)
    server = Server(backend, path, extra)
    out["device"] = server.device
    try:
        run = load_and_read(server, stream, pool)
    except BaseException:
        out["server_tail"] = "".join(list(server.tail)[-12:])
        server.kill()
        raise
    return out, server, run


def run_phase(name: str, fn) -> dict:
    """Run one phase, print its line, never raise."""
    t0 = time.monotonic()
    out: dict = {"phase": name, "ok": False, "seconds": 0.0}  # line order
    try:
        out["ok"] = bool(fn(out))
    except Exception as e:  # the phase line carries the failure
        out["error"] = f"{type(e).__name__}: {e}"[-4000:]
    out["seconds"] = round(time.monotonic() - t0, 1)
    emit(out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=22)
    # rehearsal knobs (CPU shake-out at a tiny size): a run that uses any
    # of them can never print the final ok line
    ap.add_argument("--rehearse-slots-log2", default="",
                    help="ACCOUNT,TRANSFER slots log2 (rehearsal only)")
    ap.add_argument("--rehearse-batches", default="",
                    help="ACCOUNT,BULK batch counts (rehearsal only)")
    ap.add_argument("--rehearse-batch", type=int, default=BATCH,
                    help="events per batch (rehearsal only)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "tigerbeetle_tpu")):
        print("chip_smoke.py: the tigerbeetle_tpu package is not beside "
              "this script — nothing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    rehearsal = bool(args.rehearse_slots_log2 or args.rehearse_batches
                     or args.rehearse_batch != BATCH)
    extra: tuple = ()
    if args.rehearse_slots_log2:
        a, t = args.rehearse_slots_log2.split(",")
        extra = ("--account-slots-log2", a, "--transfer-slots-log2", t)
    n_acct_b, n_bulk_b = ACCOUNT_BATCHES, BULK_BATCHES
    if args.rehearse_batches:
        n_acct_b, n_bulk_b = map(int, args.rehearse_batches.split(","))

    def tree(out: dict) -> bool:
        out.update(phase_tree())
        return True

    results = [run_phase("tree", tree)]
    if not results[0]["ok"]:
        return 1
    stream = build_stream(args.seed, n_acct_b, n_bulk_b, args.rehearse_batch)
    workdir = tempfile.mkdtemp(prefix="tb_chip_smoke_")
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    device = None
    live: list[Server] = []

    def serving(backend: str, extra_args: tuple, then_restart: bool):
        """The phase body for one backend (+ the restart phase after it)."""
        def body(out: dict) -> bool:
            nonlocal device
            part, server, run = phase_serving(
                backend, workdir, stream, pool, extra + extra_args
            )
            live.append(server)
            out.update(part)
            device = server.device
            correct = judge(out, run)
            if then_restart:
                # the acknowledged writes are read back after a SIGKILL,
                # so this server never prints [stats]: SIGQUIT makes it
                # dump the same counters first
                server.quit_stats()
                server.kill()
                correct = judge_exit(
                    out, server, 0, need_tiers=True
                ) and correct
                out["exit_code"] = "SIGKILL"
                restart_inputs["run"] = run
                restart_inputs["path"] = os.path.join(
                    workdir, f"{backend}.tigerbeetle"
                )
                return correct
            rc = server.terminate()
            # the sharded ledger has two tiers (all-or-nothing hazard
            # check) and no wave stepper: there `tiers` just reports them
            ok = judge_exit(out, server, rc, need_tiers=backend != "sharded")
            if backend == "sharded":
                tiers = out.get("tiers") or {}
                ok = ok and tiers.get("fast", 0) > 0 \
                    and tiers.get("serial", 0) > 0
            return ok and correct
        return body

    restart_inputs: dict = {}

    def restart(out: dict) -> bool:
        from tigerbeetle_tpu.types import Operation

        if "path" not in restart_inputs:
            raise RuntimeError("the device phase left no file to restart on")
        server = Server("device", restart_inputs["path"], extra)
        live.append(server)
        out["device"] = server.device
        before = {
            r.body: r.reply for r in restart_inputs["run"]["looked"]
            if r.operation == Operation.lookup_accounts
        }
        # a FRESH client id: re-registering a known id gets the stored
        # reply of its original session
        sessions = open_sessions(server.port, 0xD0000, SESSIONS)
        try:
            reads = [(Operation.lookup_accounts, body) for body in before]
            again = drive(
                sessions, [reads[s::SESSIONS] for s in range(SESSIONS)]
            )
        finally:
            close_sessions(sessions)
        same = sum(before[r.body] == r.reply for r in again)
        out["read_back"] = {
            "lookup_requests": len(again), "byte_identical": same,
            "rows": sum(len(r.reply) // 128 for r in again),
        }
        rc = server.terminate()
        # the replayed WAL runs the same tiers again: the restarted
        # server's sentinel must name them too
        return judge_exit(out, server, rc, need_tiers=True) \
            and same == len(again) == len(before) and len(before) > 0

    def probe(out: dict) -> bool:
        done = subprocess.run(
            [sys.executable, os.path.join(HERE, "scripts", "probe_device.py")],
            cwd=HERE, env=child_env(), capture_output=True, text=True,
            timeout=300,
        )
        if done.returncode != 0:
            raise RuntimeError((done.stdout + done.stderr)[-2000:])
        out.update(json.loads(done.stdout.strip().splitlines()[-1]))
        return True

    def no_chip() -> bool:
        # the server's own refusal (cli.serving_device): nothing below can
        # pass without the chip, so stop at the phase that named it
        return "no TPU" in results[-1].get("error", "")

    try:
        if args.chips == 4:
            results.append(run_phase("sharded", serving(
                "sharded", ("--shards", "4"), then_restart=False
            )))
        else:
            results.append(run_phase("dual", serving("dual", (), False)))
            if not no_chip():
                results.append(
                    run_phase("device", serving("device", (), True))
                )
                results.append(run_phase("restart", restart))
                run_phase("probe", probe)  # printed, never part of `ok`
    finally:
        for server in live:
            server.kill()  # idempotent: stop every process we started
        pool.shutdown(wait=False, cancel_futures=True)
        shutil.rmtree(workdir, ignore_errors=True)

    failed = [r["phase"] for r in results if not r["ok"]]
    if failed:
        note(f"FAILED phases: {failed}")
        return 1
    if rehearsal or not device or device.get("platform") != "tpu" \
            or device.get("count") != args.chips:
        note(f"all phases passed, but not the real thing: rehearsal="
             f"{rehearsal} device={device} — no ok line")
        return 3
    emit({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }})
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # leave through os._exit: a worker thread still replaying the oracle
    # after a failed phase must not hold the process open
    os._exit(code)
