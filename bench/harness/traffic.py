"""The one traffic generator. A traffic mix is a data file,
``bench/traffic/<mix>.json``:

    {"loop": "closed, one client", "datasets": ["data", ...],
     "round": [{"op": <kind>, ...}, ...],
     "warmup_rounds": w, "trace_seconds": t}

``datasets`` are the configuration's datasets that the mix reads; only
those are loaded. The generator repeats the round without end. Each
operation kind is a module, ``bench/ops/<kind>.py``, that draws the
operation's arguments from one seeded generator, so the same seed gives
the same sequence, and every seed the same kinds in the same order. The
first ``warmup_rounds`` rounds warm up; the window takes as many of the
rest as it lasts. Drawing an operation's arguments takes microseconds.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
from typing import Iterator

import numpy as np

from bench.harness import modules


# The seed's stream for operation arguments; a configuration's datasets
# draw from streams numbered below it.
TRAFFIC_STREAM = 1000


@dataclasses.dataclass(frozen=True)
class Op:
    kind: str
    args: tuple = ()
    round: int = 0

    @property
    def module(self):
        return modules.load("ops", self.kind)

    @property
    def label(self) -> str:
        return self.module.label(self.args)


def load(path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    for spec in mix["round"]:
        modules.load("ops", spec["op"])  # an unknown kind fails here
    return mix


def stream(mix: dict, seed: int) -> Iterator[Op]:
    """The mix's operations, round after round, without end."""
    rng = np.random.default_rng([seed, TRAFFIC_STREAM])
    for r in itertools.count():
        for spec in mix["round"]:
            mod = modules.load("ops", spec["op"])
            yield Op(spec["op"], mod.draw(spec, rng), r)
