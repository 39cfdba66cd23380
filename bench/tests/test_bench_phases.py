"""CPU tests of the program's phases in a profiler trace
(``bench/harness/phases.py``) and the per-layer metrics that read them:
gap labels, the idle split by phase, module names for ops outside every
call, the readers on a hand-built trace, and a traced harness run whose
real profiler output holds the program's spans on a host plane."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import phases, runner, trace as tr  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BM["workloads"]]
NEW = ("frontend_ms_per_query", "dispatch_ms_per_query", "fetch_ms_per_query")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def _call(start):
    """One traced call of expression 9 at ``start`` (ns): 100 ns of front
    end, a query of bind 50, execute 300 (run 250: gather 20, dispatch 30,
    wait 200) and a fetch of 400, then 50 ns of front end."""
    s = start
    return [
        _ev(HOST, "python", "bench.expression.9", s, 1000),
        _ev(HOST, "python", "repro/session.query", s + 100, 850),
        _ev(HOST, "python", "repro/session.bind", s + 100, 50),
        _ev(HOST, "python", "repro/session.execute", s + 150, 300),
        _ev(HOST, "python", "repro/session.execute.run", s + 200, 250),
        _ev(HOST, "python", "repro/session.execute.gather", s + 200, 20),
        _ev(HOST, "python", "repro/session.execute.dispatch", s + 220, 30),
        _ev(HOST, "python", "repro/session.execute.wait", s + 250, 200),
        _ev(HOST, "python", "repro/session.fetch", s + 500, 400),
        _ev(DEV, "XLA Modules", "jit_aframe_table_1a2b3c4d(7)", s + 240, 200),
        _ev(DEV, "XLA Ops", "fusion.1", s + 240, 200),
    ]


def test_program_spans_are_the_repro_host_events():
    events = _call(0) + [_ev(DEV, "XLA Ops", "repro/not.a.span", 0, 1)]
    names = [phases.phase(e) for e in phases.program_spans(events)]
    assert names[0] == "session.query" and len(names) == 8
    assert all(not e.plane.startswith("/device:")
               for e in phases.program_spans(events))


def test_idle_gaps_name_the_program_span():
    """A gap inside ``repro/session.fetch`` is labelled with the call and
    the phase; a gap under no program span keeps the label the harness's
    own reduction gives it."""
    events = _call(0)
    gaps = phases.idle_gaps(events)
    # [440, 1000): 560 ns, its middle (720) inside the fetch [500, 900)
    assert gaps[0] == ["bench.expression.9/session.fetch",
                       pytest.approx(560e-9)]
    # [0, 240): 240 ns, its middle (120) inside session.bind [100, 150)
    assert gaps[1] == ["bench.expression.9/session.bind",
                       pytest.approx(240e-9)]
    bare = [e for e in events if not e.name.startswith("repro/")]
    assert phases.idle_gaps(bare) == tr.idle_gaps(bare)
    assert phases.idle_gaps(bare)[0] == ["bench.expression.9",
                                         pytest.approx(560e-9)]


def test_idle_split_by_phase_covers_every_idle_second():
    events = _call(0)
    split = phases.idle_by_phase(events)
    assert split == {
        phases.OUTSIDE: pytest.approx((100 + 50) * 1e-9),  # [0,100) [950,1000)
        "session.bind": pytest.approx(50e-9),
        "session.execute": pytest.approx(50e-9),
        "session.execute.gather": pytest.approx(20e-9),
        "session.execute.wait": pytest.approx(10e-9),   # [440, 450)
        "session.execute.dispatch": pytest.approx(20e-9),  # [220, 240)
        "session.query": pytest.approx(100e-9),  # [450, 500) [900, 950)
        "session.fetch": pytest.approx(400e-9),
    }
    idle = 1000e-9 - tr.busy_s(events)
    assert sum(split.values()) == pytest.approx(idle)


def test_ops_outside_every_call_are_named_by_their_module():
    events = _call(0) + [
        _ev(DEV, "XLA Modules", "jit_aframe_scalar_00ff00ff(3)", 2000, 100),
        _ev(DEV, "XLA Ops", "%sort.7 = s32[8] sort(%p)", 2000, 60),
        _ev(DEV, "XLA Ops", "copy-done", 3000, 5),
    ]
    assert phases.top_device_ops(events) == [
        ["bench.expression.9/fusion.1", pytest.approx(200e-9)],
        ["jit_aframe_scalar_00ff00ff/sort.7", pytest.approx(60e-9)],
        ["copy-done", pytest.approx(5e-9)]]


def test_readers_on_a_hand_built_trace():
    events = _call(0) + _call(2000)
    run = runner.Run(cell="x", seed=0, seconds=1, events=events)
    read = {m: runner.load_reader(m)(run) for m in NEW}
    assert read["frontend_ms_per_query"] == pytest.approx(150e-6)
    assert read["dispatch_ms_per_query"] == pytest.approx(50e-6)
    assert read["fetch_ms_per_query"] == pytest.approx(400e-6)
    # a program that puts no span in the trace: every reader is left out
    bare = runner.Run(cell="x", seed=0, seconds=1, events=[
        e for e in events if not e.name.startswith("repro/")])
    assert all(runner.load_reader(m)(bare) is None for m in NEW)


def test_new_metrics_list_both_cells():
    for m in BM["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == CELLS and m["source"] == "program_span"
            assert m["moves"] == "queries_per_s"


SMALL = {"datasets": {"data": {"stream": 0, "rows": 8192},
                      "data_r": {"stream": 1, "rows": 8192, "first": 819}}}


@pytest.fixture
def captured(monkeypatch, tmp_path):
    """The events ``runner.run`` reads from the real profiler output, which
    goes to a directory of this test's own."""
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path / "trace")
    seen = []
    load = tr.load

    def keep(trace_dir):
        seen.extend(load(trace_dir))
        return seen
    monkeypatch.setattr(tr, "load", keep)
    return seen


def test_traced_run_puts_program_spans_on_the_trace_clock(captured):
    """A traced harness run on the CPU: the profiler's own output holds
    ``repro/session.query`` on a host plane, inside the harness's call
    spans, and the line carries the three metrics that read them."""
    res = runner.run(CELLS[0], 2 ** 31 + 21, 0.3, True, config_overrides=SMALL,
                     log=lambda *_: None)
    assert res["correct"] and res["failed"] == 0
    queries = [e for e in phases.program_spans(captured)
               if phases.phase(e) == "session.query"]
    assert queries and all(not e.plane.startswith("/device:") for e in queries)
    calls = tr.host_spans(captured)
    assert all(any(c.start_ns <= q.start_ns and q.end_ns <= c.end_ns
                   for c in calls) for q in queries)
    assert set(NEW) <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] >= 0 for m in NEW)


def test_traced_run_without_program_spans_leaves_the_metrics_out(captured):
    """A program that annotates nothing (telemetry off) runs traced to its
    end; the three metrics are left out of its line, never 0."""
    from repro.runtime import telemetry as tel

    tel.set_enabled(False)
    try:
        res = runner.run(CELLS[0], 2 ** 31 + 22, 0.3, True,
                         config_overrides=SMALL, log=lambda *_: None)
    finally:
        tel.set_enabled(True)
    assert res["correct"] and not phases.program_spans(captured)
    assert not set(NEW) & set(res["metrics"])
    assert "load_s" in res["metrics"]
