"""The operation kind ``expression``: one of the paper's 12 Wisconsin
expressions (AFrame §IV-B). A traffic mix asks for it as
``{"op": "expression", "id": 1..12}``.

Every operation kind is a module of ``bench/ops/`` that the harness finds
by the ``op`` name and that supplies the same five functions: ``draw``
(its arguments, from the seeded generator), ``label``, ``call`` (the
system under test), ``answer`` (the plain reference) and ``same`` (the
comparison). Arguments are hashable, so the reference answers each
distinct call once.

Copied from ``benchmarks/wisconsin_bench.py`` (``EXPRESSIONS``,
``AFrameVariant``, ``NumpyEager``) and ``chip_smoke.py`` (``_canon``).
"""
from __future__ import annotations

import numpy as np


def draw(spec: dict, rng) -> tuple:
    return (spec["id"], literals(spec["id"], rng))


def label(args: tuple) -> str:
    return f"expression.{args[0]}"


def call(system, args: tuple):
    return run_program(*args, system.frames)


def answer(reference, args: tuple):
    return run_reference(*args, reference.data["data"],
                         reference.data.get("data_r"),
                         num=reference.num, count=reference.count)


def same(args: tuple, got, want) -> bool:
    return equal(canon(args[0], got), canon(args[0], want))


def literals(expr: int, rng) -> tuple:
    """The seeded literals of one call, drawn as the paper's benchmark
    draws them."""
    if expr == 3:
        return (int(rng.integers(10)), int(rng.integers(5)), int(rng.integers(2)))
    if expr == 10:
        return (int(rng.integers(10)),)
    if expr == 11:
        a, b = int(rng.integers(100)), int(rng.integers(100))
        return (min(a, b), max(a, b))
    return ()


# -- the program: AFrame calls ------------------------------------------------


def run_program(expr: int, lits: tuple, frames: dict):
    """One call through ``AFrame``; ``frames`` maps dataset names to frames."""
    d = frames["data"]
    if expr == 1:
        return len(d)
    if expr == 2:
        return d[["two", "four"]].head()
    if expr == 3:
        x, y, z = lits
        return len(d[(d["ten"] == x) & (d["twentyPercent"] == y) & (d["two"] == z)])
    if expr == 4:
        return d.groupby("oddOnePercent").agg("count")
    if expr == 5:
        return d["stringu1"].map(str.upper).head()
    if expr == 6:
        return d["unique1"].max()
    if expr == 7:
        return d["unique1"].min()
    if expr == 8:
        return d.groupby("twenty")["four"].agg("max")
    if expr == 9:
        return d.sort_values("unique1", ascending=False).head()
    if expr == 10:
        return d[d["ten"] == lits[0]].head()
    if expr == 11:
        x, y = lits
        return len(d[(d["onePercent"] >= x) & (d["onePercent"] <= y)])
    if expr == 12:
        return len(d.merge(frames["data_r"], left_on="unique1",
                           right_on="unique1"))
    raise ValueError(f"no Wisconsin expression {expr}")


# -- the plain reference: numpy over the generated columns --------------------


def run_reference(expr: int, lits: tuple, data: dict, data_r: dict = None,
                  num=lambda a: a, count=lambda n: int(n)):
    """The same expression in numpy. ``num`` casts a numeric column and
    ``count`` a count before use: the identity for the reference, a lower
    precision for the control."""
    if expr == 1:
        return count(len(data["unique1"]))
    if expr == 2:
        return {k: num(data[k][:5]) for k in ("two", "four")}
    if expr == 3:
        x, y, z = lits
        m = (num(data["ten"]) == x) & (num(data["twentyPercent"]) == y) \
            & (num(data["two"]) == z)
        return count(m.sum())
    if expr == 4:
        keys, c = np.unique(num(data["oddOnePercent"]), return_counts=True)
        return {"oddOnePercent": keys, "count": np.array([count(v) for v in c])}
    if expr == 5:
        col = data["stringu1"][:5]
        return np.where((col >= ord("a")) & (col <= ord("z")), col - 32, col)
    if expr == 6:
        return num(data["unique1"]).max()
    if expr == 7:
        return num(data["unique1"]).min()
    if expr == 8:
        tw, fo = num(data["twenty"]), num(data["four"])
        return {int(g): fo[tw == g].max() for g in np.unique(tw)}
    if expr == 9:
        order = np.argsort(num(data["unique1"]), kind="stable")[::-1][:5]
        return {k: (num(v[order]) if v.ndim == 1 else v[order])
                for k, v in data.items()}
    if expr == 10:
        first = np.flatnonzero(num(data["ten"]) == lits[0])[:5]
        return {k: (num(v[first]) if v.ndim == 1 else v[first])
                for k, v in data.items()}
    if expr == 11:
        x, y = lits
        col = num(data["onePercent"])
        return count(((col >= x) & (col <= y)).sum())
    if expr == 12:
        left = num(data["unique1"])
        right = np.sort(num(data_r["unique1"]))
        lo = np.searchsorted(right, left, "left")
        hi = np.searchsorted(right, left, "right")
        return count((hi - lo).sum())
    raise ValueError(f"no Wisconsin expression {expr}")


# -- one comparable form ------------------------------------------------------


def canon(expr: int, out):
    """Both sides of one expression in one comparable form."""
    if expr == 4:
        if isinstance(out, dict):
            keys = np.asarray(out["oddOnePercent"]).astype(np.float64)
            return {"oddOnePercent": np.sort(keys),
                    "count": np.asarray(out["count"]).astype(np.float64)[
                        np.argsort(keys, kind="stable")]}
    if expr == 8:
        if isinstance(out, dict) and "twenty" in out:
            return {float(k): float(v) for k, v in zip(out["twenty"],
                                                       out["max_four"])}
        return {float(k): float(v) for k, v in out.items()}
    if expr == 5:
        return np.asarray(out["stringu1"] if isinstance(out, dict) else out)
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return float(np.asarray(out))


def equal(got, want) -> bool:
    """``got`` holds every key of ``want`` with the same values; numbers
    compare by value, whatever their dtype."""
    if isinstance(want, dict):
        if not isinstance(got, dict) or not set(want) <= set(got):
            return False
        return all(equal(got[k], want[k]) for k in want)
    if isinstance(want, np.ndarray):
        got = np.asarray(got)
        if got.shape != want.shape:
            return False
        if want.dtype.kind in "iufV":  # V: the control's bfloat16
            return bool(np.array_equal(got.astype(np.float64),
                                       want.astype(np.float64)))
        return bool(np.array_equal(got, want))
    return got == want
