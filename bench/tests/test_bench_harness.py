"""CPU tests of the benchmark harness: discovery by name, the copied
generator, the traffic generator, the work functions, the trace reduction,
a mesh named by a configuration, and the refusal to run without a TPU.
Nothing here loads a TPU library."""
import itertools
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import modules, runner, trace as tr, traffic, work  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BM["workloads"]]
wisconsin = modules.load("schemas", "wisconsin")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    entry = runner.cell_entry(BM, cell)
    config = runner.load_config(BM, entry["config"])
    assert config["name"] == entry["config"]
    mix = runner.load_traffic(entry["traffic"])
    assert mix["round"] and set(mix["datasets"]) <= set(config["datasets"])
    for traced in (False, True):
        specs = runner.metrics_for(BM, cell, traced)
        assert specs, (cell, traced)
        for m in specs:
            assert callable(runner.load_reader(m["name"]))
    names = {m["name"] for m in runner.metrics_for(BM, cell, False)}
    assert "setup_s" in names and len(names) >= 2
    moved = {m["moves"] for m in runner.metrics_for(BM, cell, True)}
    assert moved <= names | {"setup_s"}


def test_every_metric_has_a_reader():
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert callable(runner.load_reader(m["name"])), m["name"]
    for m in BM["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS), m["name"]
    bm = {"per_layer": [{"name": "x", "moves": "setup_s"}], "end_to_end": []}
    with pytest.raises(KeyError):
        runner.metrics_for(bm, CELLS[0], True)


NEW_SCHEMA = '''
import numpy as np

KEY = "k"


def generate(rows, seed):
    rng = np.random.default_rng(seed)
    k = np.arange(rows, dtype=np.int32)
    v = rng.integers(0, 7, rows).astype(np.int32)
    return {"k": k, "v": v}, {"k": dict(lo=0, hi=rows - 1, distinct=rows,
                                        sorted_ascending=True),
                              "v": dict(lo=0, hi=6, distinct=7)}
'''

NEW_OP = '''
def draw(spec, rng):
    return (int(rng.integers(spec["below"])),)


def label(args):
    return "count_below"


def call(system, args):
    d = system.frames["t"]
    return len(d[d["v"] < args[0]])


def answer(reference, args):
    return reference.count((reference.num(reference.data["t"]["v"]) < args[0]).sum())


def same(args, got, want):
    return float(got) == float(want)
'''


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of the benchmark whose files a test may add to."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__", ".*"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BM))
    monkeypatch.setattr(runner, "ROOT", tmp_path)
    monkeypatch.setattr(modules, "BENCH", tmp_path / "bench")
    return tmp_path


def test_new_files_alone_are_found(bench_copy):
    """A schema, an operation kind, a configuration, a mix and a metric,
    each added as a new file, with one new entry each in BENCHMARK.json,
    make a cell that runs and is checked without an edit to any file."""
    b = bench_copy / "bench"
    (b / "schemas/tiny.py").write_text(NEW_SCHEMA)
    (b / "ops/count_below.py").write_text(NEW_OP)
    (b / "configs/tiny-deploy.json").write_text(json.dumps(
        {"name": "tiny-deploy", "schema": "tiny",
         "datasets": {"t": {"stream": 0, "rows": 4096}},
         "layout": {"closed": True, "primary": "k", "indexes": []},
         "session": {"mode": "kernel"}}))
    (b / "traffic/below.json").write_text(json.dumps(
        {"datasets": ["t"], "round": [{"op": "count_below", "below": 8}],
         "warmup_rounds": 1, "trace_seconds": 1}))
    (b / "metrics/calls_per_s.py").write_text(
        "def read(run):\n    return len(run.timed()) / run.window_s\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "tiny-deploy", "source": "x", "why": "x",
                          "file": "bench/configs/tiny-deploy.json", "reduced": []})
    bm["workloads"].append({"name": "tiny-deploy.below", "config": "tiny-deploy",
                            "traffic": "below", "chips": 1, "why": "x"})
    bm["end_to_end"].append({"name": "calls_per_s", "unit": "calls/s",
                             "better": "higher", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["tiny-deploy.below"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bm))
    res = runner.run("tiny-deploy.below", 2 ** 31 + 3, 0.2, False,
                     log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "calls_per_s"}
    assert "calls_per_s" not in {m["name"] for m in
                                 runner.metrics_for(bm, CELLS[0], False)}


SMALL = {"datasets": {"data": {"stream": 0, "rows": 8192},
                      "data_r": {"stream": 1, "rows": 8192, "first": 819}}}


@pytest.mark.parametrize("cell", CELLS)
def test_nothing_compiles_in_the_window(cell):
    """The warm-up covers every shape the window uses: no program and no
    XLA compile happens inside the window (on the CPU, at a small size)."""
    logged = []
    res = runner.run(cell, 2 ** 31 + 11, 0.5, False, config_overrides=SMALL,
                     log=lambda line: logged.append(json.loads(line)))
    window = next(x["window"] for x in logged if "window" in x)
    assert res["correct"] and window["operations"] > 0
    assert window["program_compiles"] == 0 and window["xla_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_result_line(cell, capsys):
    """A traced run's result line carries the contract's keys, the cell's
    per-layer metrics that a CPU run can read, the traced window, and the
    numbers compared as the last key; the check lines go last on stderr."""
    res = runner.run(cell, 2 ** 31 + 12, 0.3, True, config_overrides=SMALL,
                     log=lambda *_: None)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "breakdown", "checks"]
    assert res["correct"] and res["failed"] == 0
    per_layer = {m["name"] for m in runner.metrics_for(BM, cell, True)}
    assert {"load_s", "host_ms_per_query"} <= set(res["metrics"]) <= per_layer
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    runner.print_result(res)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == json.loads(json.dumps(res))
    assert err.strip().splitlines()[-1] == "check wrong_answers: 0 (limit 0)"


def test_unknown_names_fail_before_a_run(tmp_path):
    """A mix that names an operation kind with no module, or a metric with
    no reader, fails when it is loaded, not inside the window."""
    mix = tmp_path / "bad.json"
    mix.write_text(json.dumps({"datasets": ["data"], "round": [{"op": "nope"}],
                               "warmup_rounds": 1, "trace_seconds": 1}))
    with pytest.raises(FileNotFoundError):
        traffic.load(mix)
    with pytest.raises(FileNotFoundError):
        runner.load_reader("no_such_metric")


def test_copied_generator_equals_the_program_generator():
    from repro.data import wisconsin as program

    want = program.generate(10_000, seed=42)
    cols, stats = wisconsin.generate(10_000, 42, string_width=16)
    assert set(cols) == set(want.columns)
    for k, v in want.columns.items():
        a = np.asarray(v)
        assert cols[k].dtype == a.dtype and np.array_equal(cols[k], a), k
        m = want.meta[k]
        s = stats[k]
        assert (s.get("lo"), s.get("hi"), s.get("distinct"),
                s.get("is_string", False), s.get("sorted_ascending", False)) == \
            (m.lo, m.hi, m.distinct, m.is_string, m.sorted_ascending), k


def test_published_width_keeps_the_characters():
    narrow, _ = wisconsin.generate(1000, 7, string_width=16)
    wide, _ = wisconsin.generate(1000, 7, string_width=52)
    for k in wisconsin.STRING_COLUMNS:
        assert wide[k].shape == (1000, 52)
        assert np.array_equal(wide[k][:, :16], narrow[k])
        assert (wide[k][:, 16:] == ord(" ")).all()


def test_bprime_is_a_random_tenth_of_b():
    b, _ = wisconsin.generate(10_000, [9, 1])
    bp, stats = wisconsin.generate(10_000, [9, 1], first=1000)
    for k, v in bp.items():
        assert np.array_equal(v, b[k][:1000]), k
    assert np.array_equal(bp["unique2"], np.arange(1000))
    u1 = bp["unique1"]
    assert len(np.unique(u1)) == 1000 and u1.max() > 9000 and u1.min() < 1000
    assert stats["unique1"] == dict(lo=int(u1.min()), hi=int(u1.max()),
                                    distinct=1000)
    assert stats["unique2"]["sorted_ascending"] and stats["string4"]["distinct"] == 4
    a, _ = wisconsin.generate(10_000, [9, 0])
    assert np.isin(a["unique1"], u1).sum() == 1000      # the join's count


@pytest.mark.parametrize("mix_name", ["scan-mix", "join"])
def test_traffic_is_deterministic_per_seed(mix_name):
    mix = runner.load_traffic(mix_name)
    big = 2 ** 31 + 12345

    def take(seed):
        return list(itertools.islice(traffic.stream(mix, seed), 300))

    a, b, c = take(big), take(big), take(big + 1)
    assert a == b
    assert [(o.kind, o.args[0], o.round) for o in a] == \
        [(o.kind, o.args[0], o.round) for o in c]      # same work on every seed
    if any(o.args[1] for o in a):                      # literals drawn
        assert a != c
    assert a[-1].round == (300 - 1) // len(mix["round"])   # rounds repeat


def test_work_functions_on_known_shapes():
    fc = work.filter_count(5_000_000, 3)
    assert fc.bytes == 60_000_000 and fc.ops == 30_000_000
    mj = work.merge_join_count(5_000_000, 500_000)
    assert mj.bytes == 22_000_000
    peak = work.peaks("TPU v5 lite")
    assert mj.least_s(peak) == pytest.approx(22e6 / 819e9)
    with pytest.raises(KeyError):
        work.peaks("no such chip")


def test_plan_work_reads_kernel_nodes_by_name():
    class KernelRangeCount:
        dataset = "data"
        cols = ("ten", "two")
        children = ()

    class JoinCountOp:
        kernel = True

        def __init__(self, *kids):
            self.children = kids

    class TableScan:
        children = ()

        def __init__(self, dataset):
            self.dataset = dataset

    class Project:
        def __init__(self, *kids):
            self.children = kids

    rows = {"data": 1000, "data_r": 100}
    plan = JoinCountOp(KernelRangeCount(), Project(TableScan("data_r")))
    got = sorted((w.kernel, w.bytes) for w in work.plan_work(plan, rows))
    assert got == [("filter_count", 8000.0), ("merge_join_count", 4400.0)]
    assert work.plan_work(None, rows) == []


def _ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_trace_reduction_on_a_hand_built_trace():
    events = [
        _ev(HOST, "python", "bench.expression.3", 0, 1000),
        _ev(HOST, "python", "bench.expression.4", 1000, 1000),
        _ev(DEV, "XLA Ops", "fusion.1", 100, 200),
        _ev(DEV, "XLA Ops", "filter_count.2", 250, 250),  # the custom call
        _ev(DEV, "XLA Ops", "pad.4", 500, 0),  # padding inside jit(filter_count)
        _ev(DEV, "XLA Ops", "fusion.1", 1100, 100),
        _ev(DEV, "XLA Modules", "jit_query", 100, 400),   # not an op line
        _ev(HOST, "python", "other", 0, 2000),
    ]
    assert tr.busy_s(events) == pytest.approx(500e-9)  # [100,500) + [1100,1200)
    assert tr.kernel_s(events, "filter_count") == pytest.approx(250e-9)
    assert tr.kernel_s(events, "merge_join_count") == 0
    # ops are named by the call they ran in; XLA numbers ops per program
    assert tr.top_device_ops(events) == [
        ["bench.expression.3/filter_count.2", pytest.approx(250e-9)],
        ["bench.expression.3/fusion.1", pytest.approx(200e-9)],
        ["bench.expression.4/fusion.1", pytest.approx(100e-9)],
        ["bench.expression.3/pad.4", 0.0]]
    gaps = tr.idle_gaps(events)
    assert gaps[0] == ["bench.expression.4", pytest.approx(800e-9)]  # [1200, 2000)
    assert gaps[1:] == [["bench.expression.3", pytest.approx(600e-9)],   # [500, 1100)
                        ["bench.expression.3", pytest.approx(100e-9)]]   # [0, 100)
    run = runner.Run(cell="x", seed=0, seconds=1, events=events,
                     trace_window_s=2000e-9, device_kind="TPU v5 lite")
    assert run.idle_share() == pytest.approx(75.0)
    assert tr.busy_s([e for e in events if e.plane == HOST]) is None


def test_trace_reduction_reads_hlo_text_names():
    """On a TPU an op event's name is its whole HLO text; the kernel and
    the breakdown are read by the operation's name alone."""
    events = [
        _ev(DEV, "XLA Ops", "%filter_count.2 = s32[1,1]{1,0:T(1,128)} "
            "custom-call(s32[1,5001216]{1,0} %bitcast.3), "
            'custom_call_target="tpu_custom_call"', 0, 300),
        _ev(DEV, "XLA Ops", "%filter_count.2 = s32[1,1]{1,0:T(1,128)} "
            "custom-call(s32[1,5001216]{1,0} %bitcast.3)", 400, 100),
        _ev(DEV, "XLA Ops", "%merge_join_count_pad.1 = s32[8] pad(%p)", 500, 50),
        _ev(DEV, "XLA Ops", "%merge_join_count.7 = s32[1,1] custom-call(%a)",
            600, 700),
    ]
    assert tr.op_name(events[0].name) == "filter_count.2"
    assert tr.op_name("fusion.1") == "fusion.1"
    assert tr.kernel_s(events, "filter_count") == pytest.approx(400e-9)
    assert tr.kernel_s(events, "merge_join_count") == pytest.approx(700e-9)
    assert tr.top_device_ops(events) == [
        ["merge_join_count.7", pytest.approx(700e-9)],
        ["filter_count.2", pytest.approx(400e-9)],
        ["merge_join_count_pad.1", pytest.approx(50e-9)]]


def test_mesh_named_by_the_configuration():
    """A configuration that names a mesh runs row-partitioned over it: here
    four CPU devices stand in for a four-chip host."""
    code = textwrap.dedent("""
        import json, sys
        sys.path[:0] = ["src", "."]
        import jax
        from bench.harness import runner
        from bench.harness.system import System
        made = []
        init = System.__init__
        def spy(self, config):
            init(self, config)
            made.append(self.session.mesh.devices.size)
        System.__init__ = spy
        small = {"datasets": {"data": {"stream": 0, "rows": 8192}},
                 "mesh": {"data": 4, "model": 1}}
        res = runner.run("wisconsin-xl.scan-mix", 7, 0.1, False,
                         config_overrides=small, log=lambda *_: None)
        print(json.dumps({"correct": res["correct"], "devices": made,
                          "count": res["device"]["count"]}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "devices": [4], "count": 4}


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "wisconsin-xl.scan-mix", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
