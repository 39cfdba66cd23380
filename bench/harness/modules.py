"""Finds a benchmark module by name: ``bench/<kind>/<name>.py``, for the
kinds ``schemas``, ``ops`` and ``metrics``. A later cell adds a module as
a new file and edits none that is there."""
from __future__ import annotations

import functools
import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


def load(kind: str, name: str):
    return _load(BENCH / kind / f"{name}.py")


@functools.lru_cache(maxsize=None)
def _load(path: pathlib.Path):
    if not path.exists():
        raise FileNotFoundError(f"no module {path.stem!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
