"""The Wisconsin generator the benchmark owns (DeWitt's schema as AFrame
§IV-A uses it), copied from ``repro.data.wisconsin`` so that a change to the
program cannot change the data it is measured on. A configuration names
this schema (``"schema": "wisconsin"``) and the harness finds the module by
that name.

It returns plain numpy columns and, per column, the statistics the original
attaches (``lo``, ``hi``, ``distinct``, ``sorted_ascending``); the harness
turns those into the program's ``Table``. ``string4`` is built vectorised.
At ``string_width=16`` the bytes equal ``repro.data.wisconsin.generate``;
the benchmark's configurations use the published 52-byte strings, which
keep the same characters and pad further with spaces.
"""
from __future__ import annotations

import numpy as np

STR4 = ("AAAAxxxx", "HHHHxxxx", "OOOOxxxx", "VVVVxxxx")
STRING_COLUMNS = ("stringu1", "stringu2", "string4")


def _stringu(values: np.ndarray, prefix: str, width: int) -> np.ndarray:
    """A 7-character base-26 rendering of each value after one prefix
    letter, space padded to ``width`` bytes."""
    out = np.full((len(values), width), ord(" "), dtype=np.uint8)
    out[:, 0] = ord(prefix)
    v = values.astype(np.int32)
    for pos in range(7):
        out[:, 7 - pos] = ord("a") + (v % 26)
        v = v // 26
    return out


def _string4(n: int, width: int) -> np.ndarray:
    table = np.full((len(STR4), width), ord(" "), dtype=np.uint8)
    for i, s in enumerate(STR4):
        table[i, :len(s)] = np.frombuffer(s.encode("ascii"), np.uint8)
    return table[np.arange(n) % len(STR4)]


KEY = "unique2"


def generate(rows: int, seed, string_width: int = 52, first=None):
    """(columns, stats): ``rows`` Wisconsin rows with unique keys;
    ``unique1`` is a seeded permutation, ``unique2`` runs 0..rows-1.

    ``first`` keeps only the rows with ``unique2 < first``: DeWitt's
    Bprime, a tenth of relation B whose ``unique1`` values are a random
    subset of B's, is ``first=rows // 10``."""
    rng = np.random.default_rng(seed)
    unique1 = rng.permutation(rows).astype(np.int32)
    if first is not None:
        unique1 = unique1[:first]
    unique2 = np.arange(len(unique1), dtype=np.int32)
    one_percent = unique1 % 100
    cols = {
        "unique1": unique1,
        "unique2": unique2,
        "two": unique1 % 2,
        "four": unique1 % 4,
        "ten": unique1 % 10,
        "twenty": unique1 % 20,
        "onePercent": one_percent,
        "tenPercent": unique1 % 10,
        "twentyPercent": unique1 % 5,
        "fiftyPercent": unique1 % 2,
        "unique3": unique1.copy(),
        "evenOnePercent": one_percent * 2,
        "oddOnePercent": one_percent * 2 + 1,
        "stringu1": _stringu(unique1, "A", string_width),
        "stringu2": _stringu(unique2, "B", string_width),
        "string4": _string4(len(unique1), string_width),
    }
    if first is not None:
        return cols, _measured_stats(cols)
    top = rows - 1
    stats = {
        "unique1": dict(lo=0, hi=top, distinct=rows),
        "unique2": dict(lo=0, hi=top, distinct=rows,
                        sorted_ascending=True),
        "two": dict(lo=0, hi=1, distinct=2),
        "four": dict(lo=0, hi=3, distinct=4),
        "ten": dict(lo=0, hi=9, distinct=10),
        "twenty": dict(lo=0, hi=19, distinct=20),
        "onePercent": dict(lo=0, hi=99, distinct=100),
        "tenPercent": dict(lo=0, hi=9, distinct=10),
        "twentyPercent": dict(lo=0, hi=4, distinct=5),
        "fiftyPercent": dict(lo=0, hi=1, distinct=2),
        "unique3": dict(lo=0, hi=top, distinct=rows),
        "evenOnePercent": dict(lo=0, hi=198, distinct=100),
        "oddOnePercent": dict(lo=1, hi=199, distinct=100),
        "stringu1": dict(is_string=True, distinct=rows),
        "stringu2": dict(is_string=True, distinct=rows),
        "string4": dict(is_string=True, distinct=4),
    }
    return cols, stats


def _measured_stats(cols: dict) -> dict:
    """The same statistics read off the columns of a subset."""
    stats = {}
    for k, v in cols.items():
        if v.ndim == 2:
            stats[k] = dict(is_string=True,
                            distinct=len(np.unique(v, axis=0)) if k == "string4"
                            else len(v))
        else:
            stats[k] = dict(lo=int(v.min()), hi=int(v.max()),
                            distinct=len(np.unique(v)))
    stats[KEY]["sorted_ascending"] = True
    return stats
