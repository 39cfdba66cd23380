"""The comparison that decides ``correct``, driven through the whole
harness on the CPU at a small size (the chip check is skipped): the
program passes, each cell's control fails, and so does each fault the cell
can have, planted under the timed path."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import runner  # noqa: E402

SMALL = {"datasets": {"data": {"stream": 0, "rows": 8192},
                      "data_r": {"stream": 1, "rows": 8192, "first": 819}}}
CELLS = [c["name"] for c in runner.benchmark()["workloads"]]


def _run(cell, control=None):
    """One run of ``cell`` on an 8192-row ``data`` and an 819-row Bprime."""
    return runner.run(cell, 2 ** 31 + 99, 0.05, False, control=control,
                      config_overrides=SMALL, log=lambda *_: None)


def _control(cell):
    bm = runner.benchmark()
    return runner.load_config(bm, runner.cell_entry(bm, cell)["config"])["control"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = _run(cell, control=_control(cell))
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def _drop_half_on_load(monkeypatch):
    from repro.engine.session import Session
    from repro.engine.table import Table

    orig = Session.create_dataset

    def half(self, name, table, *a, **kw):
        n = table.num_rows // 2
        cols = {k: np.asarray(v)[:n] for k, v in table.columns.items()}
        return orig(self, name, Table(cols, table.meta), *a, **kw)

    monkeypatch.setattr(Session, "create_dataset", half)


def _alter_answers(monkeypatch):
    from repro.engine.session import Session

    orig = Session.execute

    def altered(self, plan):
        out = orig(self, plan)
        if isinstance(out, dict):
            out = dict(out)
            k = next(iter(out))
            a = np.array(out[k], copy=True)
            a.reshape(-1)[:1] += 1
            out[k] = a
            return out
        return out + 1

    monkeypatch.setattr(Session, "execute", altered)


def _join_counts_left_rows(monkeypatch):
    """merge_join_count answers with the left side's length, as a kernel
    that counted rows instead of matches would."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "merge_join_count",
                        lambda lkeys, rkeys, nl, nr, backend=None: nl)


FAULTS = [
    ("wisconsin-xl.scan-mix", _drop_half_on_load),
    ("wisconsin-xl.scan-mix", _alter_answers),
    ("wisconsin-xl.join", _drop_half_on_load),
    ("wisconsin-xl.join", _alter_answers),
    ("wisconsin-xl.join", _join_counts_left_rows),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(cell)
    assert not res["correct"], res["checks"]
