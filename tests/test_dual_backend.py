"""Dual-commit verification seam: the native C++ engine and the JAX
DeviceLedger must agree on a single order-independent state fingerprint and
on a chained digest of the dense reply-code stream.

This is the machinery behind `--backend dual` (the dual durable
server): the native engine serves replies at host speed while the device
applies the SAME prepares asynchronously (h2d only); at shutdown one
scalar fetch proves the device state bit-identical (reference seam:
src/state_machine.zig:508-540 — determinism is the consensus invariant,
extended here across heterogeneous engines).
"""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import ConfigProcess
from tigerbeetle_tpu.models.ledger import (
    DeviceLedger,
    fold_reply_codes,
    fold_reply_codes_np,
)
from tigerbeetle_tpu.models.native_ledger import NativeLedger
from tigerbeetle_tpu.testing.workload import WorkloadGenerator
from tigerbeetle_tpu.types import Operation


def _run_pair(seed: int, n_batches: int = 10, batch: int = 64):
    """Drive the same random workload through both engines; return
    (native, device, native_fold, device_fold_scalar)."""
    import jax
    import jax.numpy as jnp

    gen = WorkloadGenerator(seed)
    nat = NativeLedger(12, 14)
    dev = DeviceLedger(
        process=ConfigProcess(account_slots_log2=12, transfer_slots_log2=14),
        mode="auto",
    )
    fold = jax.jit(fold_reply_codes)
    chk_dev = jnp.uint64(0)
    chk_nat = 0
    for b in range(n_batches):
        if b % 3 == 0:
            op, events = gen.gen_accounts_batch(batch)
        else:
            op, events = gen.gen_transfers_batch(batch)
        nat.prepare(op, len(events))
        dev.prepare(op, len(events))
        assert nat.prepare_timestamp == dev.prepare_timestamp
        ts = nat.prepare_timestamp
        arr = (
            types.accounts_to_np(events)
            if op == Operation.create_accounts
            else types.transfers_to_np(events)
        )
        pn = nat.execute_async(op, ts, arr)
        pd = dev.execute_async(op, ts, arr)
        chk_dev = fold(chk_dev, pd.results, jnp.int32(len(events)))
        pn.wait()
        chk_nat = fold_reply_codes_np(chk_nat, pn.codes)
        # codes agree batch-by-batch too (the stronger per-batch check —
        # the fold is what the production server uses because it needs
        # no d2h until shutdown)
        assert nat.drain(pn) == dev.drain(pd), f"seed {seed} batch {b}"
    dev.check_fault()
    return nat, dev, chk_nat, int(np.asarray(chk_dev))


@pytest.mark.parametrize("seed", [3, 17, 44])
def test_fingerprint_and_code_fold_parity(seed):
    nat, dev, chk_nat, chk_dev = _run_pair(seed)
    assert chk_nat == chk_dev, "reply-code stream digests diverged"
    fn = nat.fingerprint()
    fd = dev.fingerprint()
    assert fn["accounts"] == fd["accounts"]
    assert fn["transfers"] == fd["transfers"]
    assert fn["accounts_fp"] == fd["accounts_fp"], "account state diverged"
    assert fn["transfers_fp"] == fd["transfers_fp"], "transfer state diverged"
    assert fn["commit_timestamp"] == fd["commit_timestamp"]


def test_fingerprint_detects_divergence():
    """One flipped balance on one engine must flip the fingerprint (the
    check is only as good as its sensitivity)."""
    nat, dev, _, _ = _run_pair(3, n_batches=4)
    # two fresh accounts + one transfer applied to the NATIVE engine only
    accts = [
        types.Account(id=77_000_001, ledger=1, code=1),
        types.Account(id=77_000_002, ledger=1, code=1),
    ]
    nat.prepare(Operation.create_accounts, 2)
    assert nat.execute_dense(
        Operation.create_accounts, nat.prepare_timestamp, accts
    ) == [0, 0]
    fp_before = nat.fingerprint()["accounts_fp"]
    t = types.Transfer(
        id=77_000_003, debit_account_id=77_000_001,
        credit_account_id=77_000_002, amount=1, ledger=1, code=1,
    )
    nat.prepare(Operation.create_transfers, 1)
    assert nat.execute_dense(
        Operation.create_transfers, nat.prepare_timestamp, [t]
    ) == [0]
    assert nat.fingerprint()["accounts_fp"] != fp_before


def test_code_fold_order_sensitivity():
    """The chained fold must distinguish permuted batch orders and permuted
    lanes (hash_log semantics: the STREAM is the contract)."""
    a = np.array([0, 0, 5, 0], dtype=np.uint32)
    b = np.array([0, 7, 0, 0], dtype=np.uint32)
    ab = fold_reply_codes_np(fold_reply_codes_np(0, a), b)
    ba = fold_reply_codes_np(fold_reply_codes_np(0, b), a)
    assert ab != ba
    perm = fold_reply_codes_np(0, a[::-1].copy())
    assert perm != fold_reply_codes_np(0, a)


# ---------------------------------------------------------------------
# the dual-commit applier (`--backend dual`): the replica enqueues
# committed ops at finalize; per-op hash-log rings localize divergence;
# checkpoint/restart recovers device parity via snapshot row install;
# bounded-lag backpressure throttles admission through the regulator.
# ---------------------------------------------------------------------


def _valid_accounts(start: int, n: int) -> np.ndarray:
    a = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
    a["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    a["ledger"] = 1
    a["code"] = 1
    return a


def _valid_transfers(start: int, n: int, flags: int = 0,
                     pend_ids=None) -> np.ndarray:
    x = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    x["id_lo"] = np.arange(start, start + n, dtype=np.uint64)
    x["debit_account_id_lo"] = 1 + np.arange(n) % 9
    x["credit_account_id_lo"] = 1 + (np.arange(n) + 1) % 9
    x["amount_lo"] = 1
    x["ledger"] = 1
    x["code"] = 1
    x["flags"] = flags
    if pend_ids is not None:
        x["pending_id_lo"] = pend_ids
        x["debit_account_id_lo"] = 0
        x["credit_account_id_lo"] = 0
        x["amount_lo"] = 0
    return x


def _drive_follower(led, op, arr, op_no: int) -> None:
    """One committed op through the follower seam, the way the replica
    does it: native execute (reply path), then apply_commit at finalize
    with the native dense codes."""
    led.prepare(op, len(arr))
    ts = led.prepare_timestamp
    p = led.execute_async(op, ts, arr)
    led.drain(p)
    led.apply_commit(op_no, op, ts, arr, p.codes,
                     prepare_checksum=0xABCD_0000 + op_no)


def test_dual_follower_parity_mixed_workload_with_fused_runs():
    """(a) Bit-exact parity after a seeded mixed workload — accounts,
    simple transfers, two-phase pend->post — with FORCED fused apply runs
    (a brief applier stall queues consecutive create_transfers ops, so
    the loop coalesces them into group dispatches)."""
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    led = DualLedger(12, 14)
    op_no = 0
    op_no += 1
    _drive_follower(led, Operation.create_accounts,
                    _valid_accounts(1, 16), op_no)
    # stall one apply turn: the ops below queue up behind it and the
    # loop MUST coalesce them into at least one fused group dispatch
    led._test_apply_delay_s = 0.3
    for g in range(5):
        op_no += 1
        _drive_follower(led, Operation.create_transfers,
                        _valid_transfers(1000 + 64 * g, 64), op_no)
    led._test_apply_delay_s = 0.0
    # drain before the two-phase ops: a pending-flagged batch in the
    # same apply stretch would (correctly) veto fusion for the run
    assert led.drain_applier(500)
    pend = _valid_transfers(5000, 32, flags=2)  # pending
    op_no += 1
    _drive_follower(led, Operation.create_transfers, pend, op_no)
    post = _valid_transfers(6000, 32, flags=4, pend_ids=pend["id_lo"])
    op_no += 1
    _drive_follower(led, Operation.create_transfers, post, op_no)
    # seeded generator tail: mixed valid/invalid events through the same
    # stream (codes on both sides must match failure for failure)
    gen = WorkloadGenerator(13)
    for b in range(4):
        op, events = (
            gen.gen_accounts_batch(32) if b % 2 == 0
            else gen.gen_transfers_batch(32)
        )
        arr = (
            types.accounts_to_np(events)
            if op == Operation.create_accounts
            else types.transfers_to_np(events)
        )
        op_no += 1
        _drive_follower(led, op, arr, op_no)
    report = led.finalize(timeout=500)
    assert report["verified"] is True, report
    assert report["shadow_batches"] == op_no
    assert report["hash_log"]["ok"] is True
    assert report["hash_log"]["ops"] == op_no
    assert report["hash_log"]["first_divergent_op"] is None
    assert report["shadow"]["groups"] >= 1, (
        "forced fused apply runs never coalesced", report["shadow"]
    )


def test_dual_follower_hash_log_names_first_divergent_op():
    """(c) A deliberate fault injected into the device applier at op K
    fails the end-of-run check AT exactly op K (hash-log check-mode
    semantics: the ring names the op, not just 'digests differ')."""
    from tigerbeetle_tpu.models.dual_ledger import (
        DualLedger,
        raise_on_parity_divergence,
    )
    from tigerbeetle_tpu.testing.hash_log import HashLogDivergence

    led = DualLedger(12, 14)
    led._test_corrupt_apply_op = 4
    op_no = 0
    op_no += 1
    _drive_follower(led, Operation.create_accounts,
                    _valid_accounts(1, 16), op_no)
    for g in range(6):
        op_no += 1
        _drive_follower(led, Operation.create_transfers,
                        _valid_transfers(1000 + 32 * g, 32), op_no)
    report = led.finalize(timeout=500)
    assert report["verified"] is False
    assert report["hash_log"]["ok"] is False
    assert report["hash_log"]["first_divergent_op"] == 4, report["hash_log"]
    # the divergent op's PREPARE checksum ties back to the consensus
    # stream (the hash_log recording / WAL carry the same value)
    assert report["hash_log"]["prepare"] == hex(0xABCD_0000 + 4)
    with pytest.raises(HashLogDivergence) as exc:
        raise_on_parity_divergence(report)
    assert exc.value.op == 4
    assert exc.value.kind == "device-apply"


def test_dual_follower_checkpoint_restart_mid_lag():
    """(b) A checkpoint taken MID-APPLY-LAG drains the applier first;
    a crash-restart over the surviving storage re-seeds the device from
    the native snapshot (row install, h2d only), replays the WAL tail
    through the apply seam, and ends bit-exact."""
    from tigerbeetle_tpu.models.dual_ledger import DualLedger
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(
        replica_count=1,
        backend_factory=lambda: DualLedger(12, 14),
    )
    r = cluster.replicas[0]
    assert r._dual_apply
    c = cluster.add_client()
    _h, body = cluster.execute(
        c, Operation.create_accounts, _valid_accounts(1, 10).tobytes()
    )
    assert body == b""
    for g in range(3):
        _h, body = cluster.execute(
            c, Operation.create_transfers,
            _valid_transfers(100 + 32 * g, 32).tobytes(),
        )
        assert body == b""
    # build real lag, then checkpoint: the checkpoint must drain it
    r.ledger._test_apply_delay_s = 0.2
    for g in range(3):
        cluster.execute(
            c, Operation.create_transfers,
            _valid_transfers(500 + 32 * g, 32).tobytes(),
        )
    assert r.ledger.apply_lag_ops() > 0, "test never built apply lag"
    r.ledger._test_apply_delay_s = 0.0
    r.checkpoint()
    assert r.ledger.apply_lag_ops() == 0, (
        "checkpoint must drain the device applier"
    )
    # a post-checkpoint op leaves a WAL tail for restart to replay
    cluster.execute(
        c, Operation.create_transfers, _valid_transfers(700, 32).tobytes()
    )
    r2 = cluster.restart_replica(0)
    assert r2.commit_min > r2.checkpoint_op  # the tail replayed
    # the restarted replica's device follows again: new commits + parity
    c2 = cluster.add_client()
    # includes a post of a RESTORED pending (exercises the installed
    # fulfill column, not just row images)
    pend = _valid_transfers(800, 16, flags=2)
    _h, body = cluster.execute(
        c2, Operation.create_transfers, pend.tobytes()
    )
    assert body == b""
    _h, body = cluster.execute(
        c2, Operation.create_transfers,
        _valid_transfers(900, 16, flags=4,
                         pend_ids=pend["id_lo"]).tobytes(),
    )
    assert body == b""
    assert r2.ledger.drain_applier(500)
    report = r2.ledger.finalize(timeout=500)
    assert report["verified"] is True, report
    assert report["hash_log"]["ok"] is True


def test_dual_follower_backpressure_bounds_lag():
    """Sustained overload against a deliberately slow applier: the lag
    excess feeds ingress_occupancy, the PR-6 credit regulator sheds, and
    the lag stays bounded by window + pipeline cap instead of growing
    with offered load."""
    import time

    from tigerbeetle_tpu.ingress import CreditRegulator
    from tigerbeetle_tpu.models.dual_ledger import DualLedger
    from tigerbeetle_tpu.testing.cluster import Cluster

    cluster = Cluster(
        replica_count=1,
        backend_factory=lambda: DualLedger(
            12, 14, lag_window=2
        ),
    )
    r = cluster.replicas[0]
    c = cluster.add_client()
    cluster.execute(
        c, Operation.create_accounts, _valid_accounts(1, 10).tobytes()
    )
    cluster.execute(
        c, Operation.create_transfers, _valid_transfers(100, 8).tobytes()
    )
    r.ledger._test_apply_delay_s = 0.25
    reg = CreditRegulator(r)
    _used, cap = r.ingress_occupancy()
    shed = admitted = 0
    max_lag = 0
    for g in range(12):
        if not reg.try_admit():
            shed += 1
            reg.drain()  # observe fresh occupancy next attempt
            time.sleep(0.02)
        else:
            cluster.execute(
                c, Operation.create_transfers,
                _valid_transfers(1000 + 8 * g, 8).tobytes(),
            )
            admitted += 1
        max_lag = max(max_lag, r.ledger.apply_lag_ops())
    assert shed > 0, "regulator never shed under applier overload"
    assert admitted > 0
    # bounded: lag never exceeds the window plus one pipeline cap of
    # already-admitted work
    assert max_lag <= r.ledger.lag_window + cap, (max_lag, cap)
    r.ledger._test_apply_delay_s = 0.0
    assert r.ledger.drain_applier(500)
    report = r.ledger.finalize(timeout=500)
    assert report["verified"] is True, report


def test_apply_lag_counts_items_not_op_distance():
    """Regression: lag is enqueued-minus-applied ITEMS (one per create
    op), not op-number distance — interleaved non-create ops and the
    post-restart op jump must not read as phantom lag and shed
    admission."""
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    led = DualLedger(12, 14)
    led._test_apply_delay_s = 0.5  # hold the applier so lag is visible
    # a WAL-tail replay after restart starts at a large op number
    _drive_follower(led, Operation.create_accounts,
                    _valid_accounts(1, 8), 100_000)
    _drive_follower(led, Operation.create_transfers,
                    _valid_transfers(100, 8), 100_050)  # 49 lookups between
    assert led.apply_lag_ops() <= 2, led.apply_lag_ops()
    led._test_apply_delay_s = 0.0
    assert led.drain_applier(500)
    assert led.apply_lag_ops() == 0
    assert led.finalize(timeout=500)["verified"] is True


def test_group_ring_fold_dump_slot_no_collision():
    """Regression: inactive lanes of a partially-filled fused group are
    routed to the ring's DUMP slot. Scattering their stale read-back at a
    real slot instead would race an active op whose slot collides
    (op % APPLY_RING == 0 landed on slot 0 with inactive lanes' zero
    idxs) — duplicate-index .at[].set is order-undefined, so a correct
    run could report a fabricated first_divergent_op."""
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.models.dual_ledger import (
        APPLY_RING,
        _fold_group_ring_fn,
    )
    from tigerbeetle_tpu.models.ledger import fold_reply_codes

    k, n_pad = 4, 8
    codes = jnp.arange(k * n_pad + 1, dtype=jnp.uint32)
    ns = jnp.array([5, 0, 0, 0], dtype=jnp.int32)
    active = jnp.array([True, False, False, False])
    # op 4096 -> slot 0; inactive lanes -> the dump slot (APPLY_RING)
    idxs = jnp.array([0, APPLY_RING, APPLY_RING, APPLY_RING],
                     dtype=jnp.int32)
    ring = jnp.full(APPLY_RING + 1, 999, dtype=jnp.uint64)
    chk0 = jnp.uint64(7)
    expect = int(np.asarray(
        jax.jit(fold_reply_codes)(chk0, codes[:n_pad], ns[0])
    ))
    chk, ring2 = _fold_group_ring_fn(k, n_pad)(
        chk0, ring, idxs, codes, ns, active
    )
    assert int(np.asarray(chk)) == expect
    assert int(np.asarray(ring2)[0]) == expect, (
        "slot 0 lost the active op's chain value to an inactive lane"
    )


def test_fused_run_ring_slot_collision_last_wins():
    """Regression: two ACTIVE ops in ONE fused apply run whose op
    numbers are congruent mod APPLY_RING (>4096 non-create ops between
    two queued creates) must not race the device-ring scatter — the
    earlier op routes to the dump slot so both rings deterministically
    keep the LAST op per slot, and a correct run stays verified."""
    from tigerbeetle_tpu.models.dual_ledger import APPLY_RING, DualLedger

    led = DualLedger(12, 14)
    _drive_follower(led, Operation.create_accounts,
                    _valid_accounts(1, 16), 1)
    assert led.drain_applier(500)
    # stall one apply turn so the two colliding transfers coalesce into
    # one fused run
    led._test_apply_delay_s = 0.3
    _drive_follower(led, Operation.create_transfers,
                    _valid_transfers(1000, 64), 10)
    _drive_follower(led, Operation.create_transfers,
                    _valid_transfers(2000, 64), 10 + APPLY_RING)
    led._test_apply_delay_s = 0.0
    report = led.finalize(timeout=500)
    assert report["verified"] is True, report
    assert report["hash_log"]["ok"] is True, report["hash_log"]
    # both sides kept ONE entry for the shared slot (the later op)
    assert report["hash_log"]["ops"] == 2  # accounts slot + shared slot


def test_dual_follower_install_resets_nonempty_device():
    """Regression: a state-sync-shaped restore installs a snapshot onto a
    device that ALREADY applied ops — the install must reset the device
    tables first or every already-present key claims a second slot and
    the fingerprints diverge forever."""
    from tigerbeetle_tpu.models.dual_ledger import DualLedger

    led_a = DualLedger(12, 14)
    op_no = 0
    op_no += 1
    _drive_follower(led_a, Operation.create_accounts,
                    _valid_accounts(1, 10), op_no)
    op_no += 1
    _drive_follower(led_a, Operation.create_transfers,
                    _valid_transfers(100, 16), op_no)
    snap = led_a.snapshot_bytes()
    assert led_a.finalize(timeout=500)["verified"] is True

    # a second follower applies a DIFFERENT history, then adopts the
    # snapshot (the state-sync jump shape)
    led_b = DualLedger(12, 14)
    op_no_b = 0
    op_no_b += 1
    _drive_follower(led_b, Operation.create_accounts,
                    _valid_accounts(1, 10), op_no_b)
    op_no_b += 1
    _drive_follower(led_b, Operation.create_transfers,
                    _valid_transfers(5000, 16), op_no_b)
    assert led_b.drain_applier(500)
    led_b.restore_bytes(snap)
    # post-jump traffic, including rows the PRE-jump history also held
    op_no_b += 1
    _drive_follower(led_b, Operation.create_transfers,
                    _valid_transfers(200, 16), op_no_b)
    report = led_b.finalize(timeout=500)
    assert report["verified"] is True, report
    assert report["hash_log"]["ok"] is True


def test_dual_server_end_to_end_commit_cycle():
    """CI smoke (satellite): one dual-mode commit cycle end-to-end under
    JAX_PLATFORMS=cpu — real `--backend dual` server process, TCP
    clients, fused group commits, SIGTERM parity report with the hash-log
    ring green."""
    from tigerbeetle_tpu.benchmark import run_e2e

    out = run_e2e(
        n_accounts=200,
        n_transfers=64 * 8,
        batch=64,
        clients=4,
        warmup_batches=1,
        jax_platform="cpu",
        backend="dual",
    )
    shadow = out.get("device_shadow")
    assert shadow is not None, out.get("server_stats")
    assert shadow["verified"] is True, shadow
    assert shadow["hash_log"]["ok"] is True, shadow
    assert shadow["hash_log"]["ops"] >= 9
    assert shadow["shadow_batches"] >= 9  # accounts + warmup + timed
    d = shadow["code_stream_digest"]
    assert d["native"] == d["device"]
    assert out["durable_tps"] > 0
    assert out.get("device_hash_log_ok") is True
    # the applier's gauges surfaced through the registry snapshot
    assert out.get("device_lag_ops") is not None
    # what benchmarks/ reads off a dual server's last [stats] by name
    # (benchmarks/harness/readers.py): a rename fails here, not on the chip
    assert "fingerprint_device" in shadow
    m = out["server_stats"]["metrics"]
    assert {"shadow.batches", "shadow.groups", "shadow.solo"} <= set(
        m["counters"]
    )
    assert "shadow.device_lag_ops" in m["gauges"]


def test_native_group_execute_matches_serial():
    """try_execute_group_async == k sequential execute_async calls, code
    for code and fingerprint for fingerprint."""
    gen = WorkloadGenerator(9)
    _op, accts = gen.gen_accounts_batch(64)
    a = NativeLedger(12, 14)
    b = NativeLedger(12, 14)
    arr = types.accounts_to_np(accts)
    for led in (a, b):
        led.prepare(Operation.create_accounts, len(arr))
        led.execute_dense(Operation.create_accounts, led.prepare_timestamp, arr)

    items = []
    for _g in range(5):
        _o, events = gen.gen_transfers_batch(48)
        for led in (a, b):
            led.prepare(Operation.create_transfers, len(events))
        items.append((a.prepare_timestamp, types.transfers_to_np(events)))

    pendings = a.try_execute_group_async(items)
    assert pendings is not None and len(pendings) == 5
    serial = [
        b.execute_dense(Operation.create_transfers, ts, arr)
        for ts, arr in items
    ]
    for p, want in zip(pendings, serial):
        assert a.drain(p) == want
    assert a.fingerprint() == b.fingerprint()
    # single-item groups fall back
    assert a.try_execute_group_async(items[:1]) is None
