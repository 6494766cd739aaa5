"""The served path's kernels, compiled for a described TPU v5e at the CLI's
default table geometry (2^20 account / 2^24 transfer slots, batch pad 8192).

The only file in the suite that describes the chip. The sandbox has no
accelerator, but the TPU's compiler is installed and compiles for a chip
that is described and not attached (`jax.experimental.topologies`): what it
refuses here — a program that does not fit the device's memory, a
partitioning it cannot do — costs no chip time. Nothing RUNS, so these
tests say nothing about results or speed, and a compile that passes is not
a chip run.

Each case asserts the compile succeeds and that `argument + temp` stays
under a stated share of the chip's 16 GB: the serial tier's scan carries the
whole table (8.5 GiB of temp beside the 2.4 GiB state), so it is the program
one more bit of --transfer-slots-log2 pushes off the chip, and that number
is guarded here.

The topology is described INSIDE a module-scoped fixture (only the worker
that runs this file loads the TPU library; nothing at import, not autouse,
not in conftest.py), and the persistent compilation cache is off around the
compiles: such an executable is written to it but cannot be read back
without a chip.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tigerbeetle_tpu.constants import BATCH_PAD, DEFAULT_PROCESS
from tigerbeetle_tpu.models import dual_ledger, ledger
from tigerbeetle_tpu.parallel import mesh as pmesh

HBM_BYTES = 16e9  # one TPU v5e chip (Google Cloud documentation, "TPU v5e")
N_PAD = BATCH_PAD
assert N_PAD == 8192 and DEFAULT_PROCESS.transfer_slots_log2 == 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of `tree` placed on `sharding` (no array is ever made on a
    described device: it cannot hold one)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _single_chip_args(one_chip):
    state = _on(
        one_chip, jax.eval_shape(lambda: ledger.init_state(DEFAULT_PROCESS))
    )
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    return SimpleNamespace(
        state=state,
        rows=sds((N_PAD, ledger.ROW_WORDS), jnp.uint32),
        n=sds((), jnp.int32),
        ts=sds((), jnp.uint64),
        sds=sds,
    )


def _lower_single(name, a):
    kernels = ledger.get_kernels(DEFAULT_PROCESS)
    # the steppers are DeviceLedger methods that read only self.kernels:
    # steer them with a stand-in instead of allocating a 2.4 GiB ledger
    stand_in = SimpleNamespace(kernels=kernels)
    if name.startswith("commit_transfers_"):
        return kernels.commit_transfers.fn.lower(
            a.state, {"rows": a.rows}, a.n, a.ts,
            mode=name.removeprefix("commit_transfers_"),
        )
    if name == "commit_accounts_fast":
        return kernels.commit_accounts.fn.lower(
            a.state, {"rows": a.rows}, a.n, a.ts, mode="fast"
        )
    if name == "group_stepper_16x8192":
        return ledger.DeviceLedger._group_stepper(stand_in, 16, N_PAD).fn.lower(
            a.state, a.sds((16, N_PAD, ledger.ROW_WORDS), jnp.uint32),
            a.sds((16,), jnp.int32), a.sds((16,), jnp.uint64), a.n,
        )
    if name == "wave_stepper_2_fast":
        return ledger.DeviceLedger._wave_stepper(
            stand_in, 2, N_PAD, "fast"
        ).fn.lower(
            a.state, a.rows, a.sds((2, N_PAD), jnp.bool_), a.n, a.ts
        )
    if name == "fold_group_ring_16x8192":
        return dual_ledger._fold_group_ring_fn(16, N_PAD).fn.lower(
            a.ts, a.sds((dual_ledger.APPLY_RING + 1,), jnp.uint64),
            a.sds((16,), jnp.int32), a.sds((16 * N_PAD + 1,), jnp.uint32),
            a.sds((16,), jnp.int32), a.sds((16,), jnp.bool_),
        )
    raise AssertionError(name)


# (program, ceiling on argument + temp as a share of the chip's 16 GB).
# Measured here for the described v5e (GiB): state argument 2.38; temp
# 0.07-0.09 for the fast tiers and steppers, 8.5 for the serial scan.
SINGLE_CHIP = [
    ("commit_transfers_fast", 0.25),
    ("commit_transfers_fast_pv", 0.25),
    ("commit_transfers_serial", 0.80),
    ("commit_accounts_fast", 0.25),
    ("group_stepper_16x8192", 0.25),
    ("fold_group_ring_16x8192", 0.01),
    ("wave_stepper_2_fast", 0.25),
]


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


@pytest.mark.parametrize("name,share", SINGLE_CHIP, ids=[n for n, _ in SINGLE_CHIP])
def test_single_chip_program_compiles_and_fits(one_chip, name, share):
    compiled = _lower_single(name, _single_chip_args(one_chip)).compile()
    used = _footprint(compiled)
    assert used < share * HBM_BYTES, (
        f"{name}: argument + temp = {used / 2**30:.2f} GiB exceeds "
        f"{share:.0%} of a v5e chip"
    )
    if name == "commit_transfers_serial":
        # the guarded number: the scan's carry is the whole table. If this
        # ever drops a lot, lower the ceiling above; if it grows, the
        # default geometry no longer fits one chip's serial tier.
        assert used > 0.5 * HBM_BYTES


def test_the_linked_cells_launch_is_the_guarded_serial_program(one_chip):
    """What `linked_onpath.linked3_sat16` launches for every batch of 8190
    (PR 37): the batch pads to the 8192 lanes compiled above; the state is
    donated, so the tables' 2.38 GiB alias the outputs and the scan's temp
    is all the launch adds; the steps and the rollback inside a step are
    two `while` loops; and the launch's second program, the two-word
    summary of its results, is a few KiB."""
    from types import SimpleNamespace as NS

    led = NS(pad_to=N_PAD)
    assert ledger.HostLedgerBase._pad_for(led, 8190) == N_PAD
    a = _single_chip_args(one_chip)
    compiled = _lower_single("commit_transfers_serial", a).compile()
    m = compiled.memory_analysis()
    state_bytes = m.argument_size_in_bytes - N_PAD * ledger.ROW_WORDS * 4
    assert m.alias_size_in_bytes > 0.99 * state_bytes
    text = compiled.as_text()
    assert "input_output_alias" in text and text.count(" while(") >= 2
    summarize = ledger.HostLedgerBase._summarize_fn(
        NS(kernels=ledger.get_kernels(DEFAULT_PROCESS)))
    small = summarize.fn.lower(
        a.sds((N_PAD,), jnp.uint32), a.sds((), jnp.uint32), a.n).compile()
    assert _footprint(small) < 1 << 20


def test_sharded_fast_tier_and_state_allocate_per_shard(topo):
    """The 4-device mesh: commit_transfers_fast compiles with the state
    sharded, and the state's allocator writes each device only its own
    quarter (nothing is built whole on one chip and scattered)."""
    devs = np.array(topo.devices)
    assert devs.size == 4
    mesh = Mesh(devs, ("shard",))
    program = pmesh.sharded_state_program(mesh, DEFAULT_PROCESS)
    alloc = program.lower().compile()
    per_device = alloc.memory_analysis().output_size_in_bytes
    single = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(
            jax.eval_shape(lambda: ledger.init_state(DEFAULT_PROCESS))
        )
    )
    assert abs(per_device - single) < 0.01 * single, (per_device, single)

    kernels = pmesh.ShardedLedgerKernels(mesh, DEFAULT_PROCESS)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(program), alloc.output_shardings,
    )
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)  # noqa: E731
    compiled = kernels.commit_transfers_fast.fn.lower(
        state, {"rows": sds((N_PAD, ledger.ROW_WORDS), jnp.uint32)},
        sds((), jnp.int32), sds((), jnp.uint64),
    ).compile()
    assert _footprint(compiled) < 0.25 * HBM_BYTES
    assert "all-reduce" in compiled.as_text()


def test_sharded_summary_compiles_on_the_mesh_without_a_collective(topo):
    """What a sharded launch adds since PR 35: the two-word summary (failure
    count, fault) over the program's replicated results and the replicated
    fault word. Every chip holds both, so the summary needs no collective
    and is a few KiB a chip."""
    from types import SimpleNamespace

    mesh = Mesh(np.array(topo.devices), ("shard",))
    kernels = pmesh.ShardedLedgerKernels(mesh, DEFAULT_PROCESS)
    summarize = ledger.HostLedgerBase._summarize_fn(SimpleNamespace(kernels=kernels))
    assert kernels._summarize_cache is summarize
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)  # noqa: E731
    compiled = summarize.fn.lower(
        sds((N_PAD,), jnp.uint32), sds((), jnp.uint32), sds((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
    packed, summary = compiled.output_shardings
    assert packed.is_fully_replicated and summary.is_fully_replicated
    assert _footprint(compiled) < 1 << 20
