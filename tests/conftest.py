"""Test env: force JAX onto a virtual 8-device CPU mesh.

The suite runs on the CPU: the platform is pinned through jax.config after
import — BEFORE any backend initialization — so it holds whatever the
environment says. Servers the tests spawn take `TB_JAX_PLATFORM=cpu` (or
inherit `JAX_PLATFORMS=cpu`); `start` refuses a device backend that fell
back to the CPU unasked. Execution on the chip is exercised by
chip_smoke.py, not the unit suite (SURVEY.md §4: deterministic in-process
testing is the primary harness).
"""

import os

# -- .jax_cache size guard (the PR-10 mitigation for the rotating
# native-abort class): an ACCUMULATED persistent compilation cache
# correlates strongly with mid-run native aborts/corruption on this
# sandbox (PR 10: 1/10 full-suite completions with a ~17 MB cache vs 3/3
# after clearing). Clear it at session start once it grows past ~16 MB so
# every tier-1 run starts from the known-good cache state. Runs BEFORE
# jax import. The directory resolves as tigerbeetle_tpu/__init__ resolves
# it: a JAX_COMPILATION_CACHE_DIR given from outside is the caller's — the
# guard never deletes inside it — else <checkout>/.jax_cache.
# TB_JAX_CACHE_GUARD=0 disables (e.g. to bisect the cache itself).
# TB_JAX_CACHE_GUARD_MB overrides the threshold (default 16 — unchanged;
# raise it to study an accumulated cache, lower it to force a clear).
_CACHE_GUARD_MAX_BYTES = int(
    float(os.environ.get("TB_JAX_CACHE_GUARD_MB", 16)) * 1024 * 1024
)
_CACHE_GUARD_TRIPPED = False

if (
    os.environ.get("TB_JAX_CACHE_GUARD", "1") != "0"
    and not os.environ.get("JAX_COMPILATION_CACHE_DIR")
):
    _cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    if os.path.isdir(_cache_dir):
        _size = 0
        _entries = []
        for _root, _dirs, _files in os.walk(_cache_dir):
            for _f in _files:
                _p = os.path.join(_root, _f)
                try:
                    _size += os.path.getsize(_p)
                except OSError:
                    continue
                _entries.append(_p)
        if _size > _CACHE_GUARD_MAX_BYTES:
            import sys as _sys

            _CACHE_GUARD_TRIPPED = True
            for _p in _entries:
                try:
                    os.remove(_p)
                except OSError:
                    pass
            print(
                f"[conftest] cleared .jax_cache ({_size / 1e6:.1f} MB > "
                f"{_CACHE_GUARD_MAX_BYTES / 1e6:.0f} MB guard; see PR 10 "
                "native-abort mitigation)",
                file=_sys.stderr,
            )

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# -- CI tiering (VERDICT r5 weak #6): the whole suite runs in the default
# `pytest -q` — hiding the consensus/e2e surface behind an opt-in tier let
# a replica regression ship default-green. The modules below still carry
# the `nightly` marker so `pytest -m nightly` keeps selecting the heavy
# slice, but nothing deselects it by default; only `slow` (the 8190-batch
# CPU tests) stays opt-in (pytest.ini addopts).

import pytest  # noqa: E402

NIGHTLY_MODULES = {
    "test_process.py",        # real server processes over TCP
    "test_cluster.py",        # 3-replica in-process clusters
    "test_cluster_spill.py",
    "test_mesh_replica.py",   # 8-device mesh behind a replica
    "test_simulator.py",      # long-seed VOPR runs
    "test_wal_grid_repair.py",  # device-backend sim seeds (compile-bound)
    "test_dual_backend.py",   # dual-commit e2e servers
    "test_async_client.py",   # async ABI e2e servers
    "test_adversarial_replies.py",
    "test_c_abi_sequence.py",
    "test_go_client.py",
    "test_durability.py",     # kill-9 / crash-restart server cycles
    "test_fuzz.py",
    "test_production_scale.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.basename in NIGHTLY_MODULES:
            item.add_marker(pytest.mark.nightly)


def pytest_sessionfinish(session, exitstatus):
    # The guard runs before jax import, so the cost of a clear — every
    # kernel recompiled from scratch — can only be counted at session
    # end, via the compile sentinel (models/ledger.py). A tripped guard
    # followed by a big compile count IS the PR-10 pathology made
    # visible; a tripped guard with few compiles means the suite slice
    # barely touched the device stack.
    if not _CACHE_GUARD_TRIPPED:
        return
    import sys as _sys

    _mod = _sys.modules.get("tigerbeetle_tpu.models.ledger")
    if _mod is None:
        return
    _snap = _mod.COMPILE_SENTINEL.snapshot()
    print(
        f"\n[conftest] cache guard tripped this session: "
        f"{_snap['total']} fresh compile(s) observed by the sentinel "
        f"({', '.join(f'{k}x{v}' for k, v in sorted(_snap['per_fn'].items())) or 'none'})",
        file=_sys.stderr,
    )
