"""Two-phase payments (PR 30): the stream the cell
`twophase_onpath.twophase_sat16` sends, committed by the DeviceLedger the
way the replica commits a full queue (a fuse probe over the run at the
head, then the head batch alone), against the benchmark's plain reference.

Contracts under test:

- result codes of every batch, every transfer and account row read back,
  and the final state digests equal the reference's, on the cell's own
  stream and on what the cell leaves to the tests: voids, a batch resolved
  twice, a post ahead of its pending batch, an expired pending transfer;
- the planner's tiers on the cell's stream are `fast` and `fast_pv` only,
  `ledger.tier.*` counts each LAUNCHED batch once while
  `ledger.plan_calls` counts every call (rolled-back fuse probes too), and
  the pending registry's gauge returns to 32 batches' worth;
- the schedule: 32 pending requests, then alternating; from request 97 on
  a post resolves the batch 65 requests back; nothing is posted twice;
- the algorithm's bytes of a pending and of a post batch, by hand;
- (PR 31) the `fast_pv` program looks each lane's EFFECTIVE accounts up
  once (the pending's for a post or void, the event's own elsewhere): posts
  that name their pending's accounts, posts that name others (codes 27 / 28,
  nothing moves), plain and post lanes in one launch; and the probes of
  both programs, counted while they are traced.
"""

import argparse
import json
import os

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — CPU platform before jax init
from benchmarks.harness import roofline_twophase, traffic
from benchmarks.reference.ledger_ref import ReferenceLedger
from benchmarks.reference.wire_types import TransferFlags as TF
from tigerbeetle_tpu.metrics import CATALOG, COMMIT_TIERS, Metrics
from tigerbeetle_tpu.tracer import NULL_TRACER
from tigerbeetle_tpu.types import CreateTransferResult as R
from tigerbeetle_tpu.types import Operation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 64
CONFIG = {"batch_events": BATCH, "accounts": 300, "id_order": "reversed"}
FP_FIELDS = ("accounts_fp", "transfers_fp", "accounts", "transfers",
             "commit_timestamp")
PV = int(TF.post_pending_transfer | TF.void_pending_transfer)


def twophase_mix(**post) -> dict:
    mix = traffic.load_traffic("twophase_sat16")
    mix["classes"]["post"][0].update(post)
    return mix


def take(stream, n: int) -> list:
    return [stream.next_create()[1] for _ in range(n)]


def post_of(pending: np.ndarray, first_id: int) -> np.ndarray:
    out = np.zeros(len(pending), dtype=pending.dtype)
    out["id_lo"] = np.arange(first_id, first_id + len(pending), dtype=np.uint64)
    out["pending_id_lo"] = pending["id_lo"]
    out["flags"] = int(TF.post_pending_transfer)
    return out


# -- the harness: one stream through both, everything compared -----------


def commit_like_the_replica(led, batches: list, window: int = 16) -> list:
    """vsr/replica.py _maybe_commit_pipeline with `window` requests
    queued: try to fuse the run at the head of the queue; turned down,
    launch the head batch alone and try again one batch on. Dense codes a
    batch."""
    dense, i = [], 0
    while i < len(batches):
        run = batches[i:i + window]
        pendings = led.try_execute_group_async(run) if len(run) > 1 else None
        if pendings is None:
            ts, arr = run[0]
            pendings = [led.execute_async(Operation.create_transfers, ts, arr)]
        dense.extend(led.drain(p) for p in pendings)
        i += len(pendings)
    led.check_fault()
    return dense


def run_both(arrays: list, gap_ns=None):
    """(ledger, its registry, reference, dense codes): the account load
    and `arrays` committed by both. `gap_ns[i]` is added to the clock
    before batch i."""
    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models.ledger import DeviceLedger

    led = DeviceLedger(process=ConfigProcess(account_slots_log2=10,
                                             transfer_slots_log2=14))
    m = Metrics()
    led.instrument(m, NULL_TRACER)
    ref = ReferenceLedger()
    ts = 10**15
    accounts = traffic.Stream(twophase_mix(), CONFIG, 1).account_batches()
    for acc in accounts:
        ts += len(acc) + 3
        assert led.execute(Operation.create_accounts, ts, acc) == \
            ref.execute(Operation.create_accounts, ts, acc) == []
    batches = []
    for i, arr in enumerate(arrays):
        ts += len(arr) + 3 + (gap_ns or {}).get(i, 0)
        batches.append((ts, arr))
    dense = commit_like_the_replica(led, batches)
    for (ts, arr), codes in zip(batches, dense):
        want = ref.execute(Operation.create_transfers, ts, arr)
        assert [(i, c) for i, c in enumerate(codes) if c] == want
    ids = [int(i) for arr in arrays for i in arr["id_lo"]]
    assert led.lookup_rows(Operation.lookup_transfers, ids) == \
        ref.lookup_transfer_rows(ids)
    return led, m, ref, dense


def account_rows(led, ref) -> bytes:
    """Every account row, equal on both sides."""
    ids = list(range(1, CONFIG["accounts"] + 1))
    got = led.lookup_rows(Operation.lookup_accounts, ids)
    assert got == ref.lookup_account_rows(ids)
    assert len(got) == 128 * len(ids)
    return got


def assert_digests_equal(led, ref) -> None:
    got, want = led.fingerprint(), ref.fingerprint()
    assert {f: int(got[f]) for f in FP_FIELDS} == {f: want[f] for f in FP_FIELDS}


def balances(rows: bytes) -> dict:
    from benchmarks.reference.wire_types import ACCOUNT_DTYPE

    a = np.frombuffer(rows, dtype=ACCOUNT_DTYPE)
    return {f: int(a[f + "_lo"].sum(dtype=object))
            for f in ("debits_pending", "credits_pending", "debits_posted",
                      "credits_posted")}


def unresolved_amount(arrays: list, resolves_that_fail: int = 0) -> int:
    """The amounts of the pending batches no post or void has resolved
    (the stream resolves the oldest first, a whole batch at a time)."""
    pending = [a for a in arrays if int(a["flags"][0]) == int(TF.pending)]
    resolved = len(arrays) - len(pending) - resolves_that_fail
    return sum(int(a["amount_lo"].sum()) for a in pending[resolved:])


# -- the cell's stream, and what the cell leaves to the tests ------------


def case_cell():
    arrays = take(traffic.Stream(twophase_mix(), CONFIG, 2**31 + 30), 100)
    return arrays, None, {}


def case_voids():
    arrays = take(traffic.Stream(twophase_mix(post_share=0.5), CONFIG, 31), 100)
    return arrays, None, {}


def case_posted_twice():
    """The post of batch 0 (request 33) sent again under fresh ids: every
    event fails, and no balance moves."""
    arrays = take(traffic.Stream(twophase_mix(), CONFIG, 32), 40)
    assert (arrays[33]["pending_id_lo"] == arrays[0]["id_lo"]).all()
    again = post_of(arrays[0], 77_000_000)
    return arrays + [again], None, {
        40: [int(R.pending_transfer_already_posted)] * BATCH}


def case_post_before_pending():
    """A dropped request that came back late: the post commits before the
    batch it resolves."""
    st = traffic.Stream(twophase_mix(), CONFIG, 33)
    arrays = take(st, 34)
    late = st._plain(BATCH, 88_000_000)
    late["flags"] = int(TF.pending)
    post = post_of(late, 88_100_000)
    return arrays + [post, late], None, {
        34: [int(R.pending_transfer_not_found)] * BATCH, 35: [0] * BATCH}


def case_expired():
    """A pending batch with a timeout of one second, posted two seconds
    of cluster time later."""
    st = traffic.Stream(twophase_mix(), CONFIG, 34)
    arrays = take(st, 34)
    short = st._plain(BATCH, 99_000_000)
    short["flags"] = int(TF.pending)
    short["timeout"] = 1
    post = post_of(short, 99_100_000)
    return arrays + [short, post], {35: 2 * 10**9}, {
        34: [0] * BATCH, 35: [int(R.pending_transfer_expired)] * BATCH}


def other_account(ids: np.ndarray) -> np.ndarray:
    """Another plain account than `ids` (1..299; 300 is the limit account)."""
    return ids % np.uint64(CONFIG["accounts"] - 1) + np.uint64(1)


def case_posts_name_their_pendings_accounts():
    """Every post carries debit and credit account ids of its own, equal to
    its pending transfer's: codes 27 / 28 compare ids and pass."""
    arrays = take(traffic.Stream(twophase_mix(), CONFIG, 2**31 + 35), 60)
    for k in range(33, 60, 2):
        pending = arrays[(k - 33) // 2]  # the oldest unresolved batch
        assert (arrays[k]["pending_id_lo"] == pending["id_lo"]).all()
        for f in ("debit_account_id_lo", "credit_account_id_lo"):
            arrays[k][f] = pending[f]
    return arrays, None, {k: [0] * BATCH for k in range(33, 60, 2)}


def case_posts_name_other_accounts():
    """Posts whose own account ids differ from the pending's: by lane, a
    wrong debit (27), a wrong credit (28), both wrong (27 comes first), both
    right (0); in the next post an account that does not exist (27 still:
    ids are compared, no row is read). A failed post moves no balance, of
    the pending's accounts or of the ones it names."""
    arrays = take(traffic.Stream(twophase_mix(), CONFIG, 36), 40)
    post, pending = arrays[33], arrays[0]
    lane = np.arange(BATCH)
    dr, cr = pending["debit_account_id_lo"], pending["credit_account_id_lo"]
    post["debit_account_id_lo"] = np.where(
        np.isin(lane % 4, (0, 2)), other_account(dr), dr)
    post["credit_account_id_lo"] = np.where(
        np.isin(lane % 4, (1, 2)), other_account(cr), cr)
    arrays[35]["debit_account_id_lo"][::2] = 10**9
    d, c = (int(R.pending_transfer_has_different_debit_account_id),
            int(R.pending_transfer_has_different_credit_account_id))
    assert (d, c) == (27, 28)
    return arrays, None, {33: [d, c, d, 0] * (BATCH // 4),
                          35: [d, 0] * (BATCH // 2), 37: [0] * BATCH}


def case_plain_and_posts_share_a_launch():
    """Batches that mix plain (or pending) creates with posts of
    registry-known pendings, lane by lane: one `fast_pv` launch in which a
    plain lane's account keys are its own and a post lane's its pending's,
    and each changes only those."""
    st = traffic.Stream(twophase_mix(), CONFIG, 2**31 + 37)
    arrays = take(st, 40)
    even = np.arange(BATCH) % 2 == 0
    # request 33: the posts of batch 0's even lanes between plain creates
    mixed = st._plain(BATCH, 55_000_000)
    mixed[even] = arrays[33][even]
    arrays[33] = mixed
    # after the stream: the posts of batch 0's odd lanes between pending
    # creates, over the accounts the even lanes' pendings hold
    later = st._plain(BATCH, 56_000_000)
    later["flags"] = int(TF.pending)
    later["debit_account_id_lo"] = arrays[0]["debit_account_id_lo"][::-1]
    later["credit_account_id_lo"] = arrays[0]["credit_account_id_lo"][::-1]
    posts = post_of(arrays[0], 56_100_000)
    posts["credit_account_id_lo"] = arrays[0]["credit_account_id_lo"]
    later[~even] = posts[~even]
    return arrays + [later], None, {33: [0] * BATCH, 40: [0] * BATCH}


def still_pending(arrays: list, dense: list) -> int:
    """What the result codes say is still pending: every pending create that
    succeeded, less every one a successful post or void resolved."""
    amount, total = {}, 0
    for arr, codes in zip(arrays, dense):
        for ev, code in zip(arr, codes):
            if code:
                continue
            if int(ev["flags"]) & int(TF.pending):
                amount[int(ev["id_lo"])] = int(ev["amount_lo"])
                total += int(ev["amount_lo"])
            elif int(ev["flags"]) & PV:
                total -= amount.pop(int(ev["pending_id_lo"]))
    return total


@pytest.mark.parametrize("case", [
    case_cell, case_voids, case_posted_twice, case_post_before_pending,
    case_expired, case_posts_name_their_pendings_accounts,
    case_posts_name_other_accounts, case_plain_and_posts_share_a_launch],
    ids=lambda f: f.__name__[5:])
def test_twophase_stream_equals_the_reference(case):
    arrays, gap_ns, expected = case()
    led, m, ref, dense = run_both(arrays, gap_ns)
    rows = account_rows(led, ref)
    assert_digests_equal(led, ref)
    for i, codes in expected.items():
        assert dense[i] == codes, (i, sorted(set(dense[i])))
    total = balances(rows)
    assert total["debits_pending"] == total["credits_pending"]
    assert total["debits_posted"] == total["credits_posted"]
    c = m.snapshot()["counters"]
    assert sum(c[f"ledger.tier.{t}"] for t in COMMIT_TIERS) == len(arrays)
    if case in (case_posts_name_their_pendings_accounts,
                case_posts_name_other_accounts,
                case_plain_and_posts_share_a_launch):
        # a lane that failed moved nothing, and every batch that holds a
        # post or void lane, mixed or not, was ONE launch of `fast_pv`
        assert total["debits_pending"] == still_pending(arrays, dense)
        n_pv = sum(1 for a in arrays if (a["flags"] & np.uint16(PV)).any())
        assert c["ledger.tier.fast_pv"] == n_pv
        assert c["ledger.tier.fast"] == len(arrays) - n_pv
    if case in (case_cell, case_voids, case_posted_twice):
        # what is still pending is what no post or void has resolved, once:
        # the second resolution of batch 0 moved nothing
        assert total["debits_pending"] == unresolved_amount(
            arrays, resolves_that_fail=int(case is case_posted_twice))
    if case is not case_cell:
        return
    # the cell's own stream: no failure, two tiers, the probes counted
    assert all(code == 0 for codes in dense for code in codes)
    assert c["ledger.tier.waves"] == c["ledger.tier.serial"] == 0
    n_post = sum(1 for a in arrays if int(a["flags"][0]) & int(TF.post_pending_transfer))
    assert n_post == (100 - 32) // 2 and c["ledger.tier.fast_pv"] == n_post
    assert c["ledger.tier.fast"] == len(arrays) - n_post
    stats = led.hazards.plan_stats  # the rolled-back probes left no mark
    assert (stats["fast"], stats["fast_pv"]) == (
        c["ledger.tier.fast"], c["ledger.tier.fast_pv"])
    assert c["ledger.group_probe_rejected"] >= n_post
    assert c["ledger.plan_calls"] > 2 * len(arrays)
    h = m.snapshot()["histograms"]["ledger.plan_us"]
    # one reading a probe, one a solo launch (plan + registry), one a group
    assert h["count"] >= c["ledger.group_probe_rejected"] + 2 * n_post
    # the ramp's 33 pending batches were fused; from then on nothing is
    account_batches = -(-CONFIG["accounts"] // BATCH)
    assert c["device.commit_batches"] == 100 + account_batches
    assert c["device.commit_launches"] == account_batches + 3 + (100 - 33)
    # the last request is a post: 32 batches are unresolved, and the
    # registry holds exactly their transfers
    g = m.snapshot()["gauges"]["ledger.pending_registry_rows"]
    assert g == len(led.hazards.pending_accounts) == 32 * BATCH


def test_a_rejected_probe_counts_calls_but_no_tier():
    """One pending batch and its post, queued together: the probe plans
    both and is turned down, then each is planned again and launched."""
    st = traffic.Stream(twophase_mix(behind=1), CONFIG, 5)
    arrays = take(st, 2)  # a pending batch, then the post of it
    assert int(arrays[1]["flags"][0]) == int(TF.post_pending_transfer)
    _led, m, _ref, dense = run_both(arrays)
    assert dense == [[0] * BATCH, [0] * BATCH]
    c = m.snapshot()["counters"]
    assert c["ledger.group_probe_rejected"] == 1
    assert c["ledger.plan_calls"] == 2 + 1 + 1
    assert (c["ledger.tier.fast"], c["ledger.tier.fast_pv"]) == (1, 1)
    assert m.snapshot()["gauges"]["ledger.pending_registry_rows"] == 0


# -- the probes of a program, counted while it is traced ------------------


A_LOG2, T_LOG2 = 10, 14  # the account and the transfer table of the probe test


@pytest.mark.parametrize("mode,probes", [
    ("fast", [(A_LOG2, 2 * BATCH), (T_LOG2, BATCH)]),
    # the pending row, each lane's effective accounts ONCE, the transfer id
    ("fast_pv", [(T_LOG2, BATCH), (A_LOG2, 2 * BATCH), (T_LOG2, BATCH)]),
])
def test_commit_program_probes_the_account_table_once(monkeypatch, mode, probes):
    """A window lookup is the chip's time in this kernel (PERF.md section
    5), so the count is held by tracing, not by timing: (table, lanes) of
    every `ht.lookup`, debit and credit sides in one 2B-lane call."""
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.constants import ConfigProcess
    from tigerbeetle_tpu.models import ledger

    process = ConfigProcess(account_slots_log2=A_LOG2, transfer_slots_log2=T_LOG2)
    calls = []
    lookup = ledger.ht.lookup

    def counted(key4, rows, cap_log2, *a, **kw):
        assert rows.shape[0] == (1 << cap_log2) + 1
        calls.append((cap_log2, key4.shape[0]))
        return lookup(key4, rows, cap_log2, *a, **kw)

    monkeypatch.setattr(ledger.ht, "lookup", counted)
    sds = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda st, rows, n, ts: ledger.LedgerKernels(process)._commit_transfers(
            st, {"rows": rows}, n, ts, mode=mode),
        jax.eval_shape(lambda: ledger.init_state(process)),
        sds((BATCH, ledger.ROW_WORDS), jnp.uint32), sds((), jnp.int32),
        sds((), jnp.uint64))
    assert calls == probes


# -- the schedule ---------------------------------------------------------


def test_schedule_ramp_then_alternation_and_a_fixed_distance():
    cfg = dict(CONFIG, accounts=10_000)

    def schedule(seed: int, n: int = 200):
        st = traffic.Stream(twophase_mix(), cfg, seed)
        return [st.next_create() for _ in range(n)], st

    (a, st), (b, _) = schedule(2**31 + 9), schedule(7)
    kinds = ["post" if int(arr["flags"][0]) & int(TF.post_pending_transfer)
             else "pending" for _cls, arr in a]
    assert kinds[:33] == ["pending"] * 33  # 32 of the ramp, then the cycle
    assert kinds[32:] == ["pending", "post"] * 84
    assert all(int(arr["flags"][0]) == int(TF.pending)
               for (_c, arr), kind in zip(a, kinds) if kind == "pending")
    posted = set()
    unresolved = 0
    for k, ((_cls, arr), kind) in enumerate(zip(a, kinds)):
        assert len(arr) == BATCH
        if kind == "pending":
            unresolved += 1
        else:
            unresolved -= 1
            target = next(j for j in range(k) if (
                a[j][1]["id_lo"] == arr["pending_id_lo"]).all())
            assert target not in posted
            posted.add(target)
            assert k - target == (65 if k >= 97 else 33 + (k - 33) // 2)
        if k >= 32:
            assert unresolved in (32, 33), (k, unresolved)
    assert len(st.pending) == unresolved
    # the seed draws accounts, amounts and user data, nothing else
    seeded = {"debit_account_id_lo", "credit_account_id_lo", "amount_lo",
              "user_data_64"}
    for (cls_a, x), (cls_b, y) in zip(a, b):
        assert cls_a == cls_b
        for f in x.dtype.names:
            if f not in seeded:
                assert (x[f] == y[f]).all(), f
    assert any((x["amount_lo"] != y["amount_lo"]).any()
               for (_a, x), (_b, y) in zip(a, b))


# -- the algorithm's bytes ------------------------------------------------


def test_twophase_bytes_against_a_hand_count():
    # empty tables, one probe each. A pending create is a plain one: 128 B
    # written + one 16 B key probed, two accounts each probed (16 B), read
    # and written (256 B)
    assert roofline_twophase.pending_bytes(1, 0.0, 0.0) == 128 + 16 + 2 * (256 + 16)
    # a post: its pending row probed (16 B) and read (128 B) besides
    assert roofline_twophase.post_bytes(1, 0.0, 0.0) == (128 + 16) + 688
    assert roofline_twophase.pending_bytes(8190, 0.0, 0.0) == 8190 * 688
    assert roofline_twophase.post_bytes(8190, 0.0, 0.0) == 8190 * 832
    # half-full transfer table: 1.5 probes to find, 2.5 to insert (Knuth)
    assert roofline_twophase.post_bytes(1, 0.0, 0.5) == (
        128 + 1.5 * 16 + 128 + 2.5 * 16 + 2 * (256 + 16))


def test_twophase_roofline_reads_the_spans_launches_by_class():
    rec = argparse.Namespace
    body = {kind: np.zeros(1, dtype=take(traffic.Stream(
        twophase_mix(), CONFIG, 1), 1)[0].dtype) for kind in ("pending", "post")}
    body["pending"]["flags"] = int(TF.pending)
    body["post"]["flags"] = int(TF.post_pending_transfer)
    records = [rec(operation=int(Operation.create_transfers), op=10 + i,
                   done=1.0 + 0.07 * i, events=8190, error=None,
                   body=body["post" if i % 2 else "pending"].tobytes())
               for i in range(40)]
    ctx = {
        "records": records, "device": {"kind": "TPU v5 lite"},
        "config": {"batch_events": 8190, "accounts": 10_000,
                   "account_slots_log2": 20, "transfer_slots_log2": 24},
        # the span opens at 2.0 s: the 15th create is the first in it
        "trace_span": {"t_a": 2.0, "t_b": 2.5},
        "trace": {"modules": [
            ["jit__commit_transfers(1)", 0.192, 3.0],  # pending: 64 ms each
            ["jit__commit_transfers(2)", 0.300, 4.0],  # post: 75 ms each
            ["jit_s(3)", 0.00007, 7.0],  # the summary: commits no batch
            ["jit__lookup_accounts(4)", 0.01, 1.0]]},
    }
    # 7 launches from record 15 on: post first, so 4 post and 3 pending
    assert roofline_twophase.span_classes(ctx, 7) == (3 * 8190, 4 * 8190)
    load_a, load_t = 10_000 / 2**20, 40 * 8190 / 2**24
    want = (roofline_twophase.pending_bytes(3 * 8190, load_a, load_t)
            + roofline_twophase.post_bytes(4 * 8190, load_a, load_t))
    share = roofline_twophase.twophase_kernels_roofline(ctx)
    assert share == pytest.approx(100 * want / 819e9 / 0.49207)
    assert 0 < share < 100
    # no trace, nothing to read: left out, never 0
    assert roofline_twophase.twophase_kernels_roofline({**ctx, "trace": None}) is None


# -- the catalog, and the files the cells are made of ---------------------


@pytest.mark.parametrize("name,kind", [
    ("ledger.plan_us", "histogram"), ("ledger.plan_calls", "counter"),
    ("ledger.group_probe_rejected", "counter"),
    ("ledger.pending_registry_rows", "gauge"),
    *[(f"{prefix}.{tier}", "counter") for tier in COMMIT_TIERS
      for prefix in ("ledger.tier", "device.tier_busy_s",
                     "device.tier_batches_done")],
])
def test_planner_metric_names_are_cataloged(name, kind):
    assert name in CATALOG, name
    assert CATALOG[name][0] == kind and CATALOG[name][2]


def test_benchmark_json_names_the_new_cells_and_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    two, rate = (cells["twophase_onpath.twophase_sat16"],
                 cells["default_onpath.plain_rate"])
    assert (two["config"], two["traffic"], two["chips"]) == (
        "twophase_onpath", "twophase_sat16", 1)
    assert (rate["config"], rate["traffic"], rate["chips"]) == (
        "default_onpath", "plain_rate", 1)
    for w in (two, rate):
        assert 0 < len(w["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "twophase_onpath")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    plain = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "default_onpath.json")))
    for key in ("backend", "clients_max", "account_slots_log2",
                "transfer_slots_log2", "accounts", "batch_events", "id_order",
                "grid_mb", "checkpoint_interval_ops", "replicas"):
        assert config[key] == plain[key], key
    with open(os.path.join(REPO, "benchmarks", "rates",
                           "default_onpath.plain_rate.json")) as f:
        assert json.load(f)["per_second"] > 0
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("plans_per_batch.sat", "plan_ms_per_batch.sat",
                 "pending_registry_rows.sat", "kernel_ms_pv_window.sat",
                 "twophase_kernels_roofline.sat"):
        # the planner's two are read by the linked cell too (PR 37)
        shared = name in ("plans_per_batch.sat", "plan_ms_per_batch.sat")
        assert per_layer[name]["workloads"] == [two["name"]] + (
            ["linked_onpath.linked3_sat16"] if shared else [])
        assert per_layer[name]["moves"] == "committed_tps"
    assert two["name"] not in per_layer["commit_kernels_roofline.sat"]["workloads"]
    assert two["name"] not in per_layer["group_fill.sat"]["workloads"]
    assert rate["name"] not in per_layer["apply_lag_ms.rate"]["workloads"]
    assert set(per_layer["window_compiles"]["workloads"]).isdisjoint(
        {two["name"], rate["name"]})
