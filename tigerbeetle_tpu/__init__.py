"""tigerbeetle_tpu — a TPU-native double-entry accounting database framework.

A ground-up redesign of the capabilities of TigerBeetle (reference:
/root/reference, Zig) for TPU hardware:

- The batched ledger commit path (create_accounts / create_transfers /
  lookup_* — reference src/state_machine.zig) executes as JAX kernels over
  struct-of-arrays batches, with the account + transfer stores resident in
  HBM as open-addressing hash tables.
- u128 balances/ids are exact two-limb (2 x u64) arithmetic on device.
- Batches with no intra-batch conflicts take a fully vectorized path; batches
  with serial dependencies (duplicate ids, linked chains, balancing
  transfers, balance-limit accounts, in-batch pending references) fall back
  to an exact sequential lax.scan kernel. Result codes are bit-exact vs. the
  reference state machine in both paths.
- Multi-chip scaling shards the HBM tables over a `jax.sharding.Mesh`
  (see tigerbeetle_tpu.parallel).

The surrounding systems layers (VSR consensus, WAL/superblock durability,
LSM indexes, message bus, deterministic simulator) live in vsr/, lsm/, io/,
testing/ as host-side runtime around the device state machine.

NOTE: importing this package enables jax_enable_x64 (u64 limbs are the
native word of the whole framework).
"""

import os as _os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the 8192-batch commit kernels take
# seconds each to compile and a cold `start --backend dual` compiles about
# ten of them before it serves. With the cache the first process compiles
# and every later one loads from disk. The directory is placed from
# OUTSIDE when JAX_COMPILATION_CACHE_DIR is set (jax reads the variable
# itself; this package then sets no directory in code, and server children
# inherit it, so one run's processes share one cache); otherwise it is
# <checkout>/.jax_cache, always (the path is part of the cache key — a
# directory that moves never hits).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from tigerbeetle_tpu import constants, types  # noqa: E402,F401

__version__ = "0.1.0"
