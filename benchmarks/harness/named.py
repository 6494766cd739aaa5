"""benchmarks/<folder>/<name>.py, found by the name a data file gives: a
metric's reader (`read(ctx)`), a batch modifier (`apply(stream, mod, arr,
base)`) or an account draw (`draw(rng, n, accounts, params)`). A later PR
adds such a file and the entry that names it, and edits nothing."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LOADED: dict = {}


def named(folder: str, name: str):
    key = (folder, name)
    if key not in _LOADED:
        path = os.path.join(HERE, folder, f"{name}.py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"{folder}: {name!r} has no file at {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.{folder}.{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[key] = mod
    return _LOADED[key]
