"""Artifact provenance: the fields the prodday artifact must carry so
no number can be mistaken for a chip number.

Each artifact stamps:

- the platform block (the platform JAX reported to the run — observed,
  never defaulted — machine, python, and whether the numbers were
  measured on the chip),
- segment health (`segments_incomplete`: a null in the summary must
  read as "segment failed", never "measured zero"),
- the compile-cache story (`.jax_cache` size at run start / run end /
  artifact assembly, plus the in-process compile-sentinel totals — a
  poisoned cache is the known sandbox pathology, see models/ledger.py
  and the tests/conftest.py guard).

The prodday emitter (`scripts/prodday.py`) builds its wrapper through
`wrap_artifact()`.
"""

from __future__ import annotations

import os
import platform as _platform


def jax_cache_bytes() -> int:
    """Current on-disk size of the persistent compilation cache — the
    directory the package resolved (JAX_COMPILATION_CACHE_DIR when given
    from outside, else <checkout>/.jax_cache)."""
    import jax

    total = 0
    for root, _dirs, files in os.walk(jax.config.jax_compilation_cache_dir):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def platform_block(backend: str | None = None) -> dict:
    """Where the run's device work happened: `backend` is the platform JAX
    REPORTED for it (`jax.devices()[0].platform`, as the run itself printed
    it) or None when the run did not observe one — never a default. Only a
    `tpu` backend makes absolute rates comparable to chip rounds; from any
    other run the quotable signals are same-run ratios, spreads, parity
    booleans and pass/fail verdicts."""
    return {
        "backend": backend,
        "machine": _platform.machine(),
        "python": _platform.python_version(),
        "note": (
            "measured on the chip" if backend == "tpu"
            else "not measured on the chip; not comparable to chip rounds"
        ),
    }


def jax_cache_block(parsed: dict) -> dict:
    """The run's recompile story: cache size at run start/end (recorded
    by the run itself) plus at artifact assembly — cache churn between
    run and packaging is itself visible."""
    return {
        "bytes_at_artifact": jax_cache_bytes(),
        "bytes_run_start": parsed.get("jax_cache_bytes_start"),
        "bytes_run_end": parsed.get("jax_cache_bytes_end"),
        "compile_sentinel": parsed.get("compile_sentinel"),
    }


def wrap_artifact(cmd: str, rc: int, env: str, tail: str, parsed: dict,
                  segments_incomplete: list[str], n: int = 1,
                  backend: str | None = None) -> dict:
    """The common driver-shaped wrapper {n, cmd, rc, platform, env,
    tail, segments_incomplete, jax_cache, parsed}."""
    return {
        "n": n,
        "cmd": cmd,
        "rc": int(rc),
        "platform": platform_block(backend=backend),
        "env": env,
        "tail": tail,
        "segments_incomplete": segments_incomplete,
        "jax_cache": jax_cache_block(parsed),
        "parsed": parsed,
    }
