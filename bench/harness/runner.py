"""Runs one cell of ``BENCHMARK.json`` once: set-up, the timed window, the
comparison with the reference, the metrics, and the result line.

Everything is found by name: the cell's configuration in the file its
``configs`` entry names, the configuration's schema in
``bench/schemas/<schema>.py``, the cell's traffic in
``bench/traffic/<traffic>.json``, each operation kind in
``bench/ops/<op>.py`` and each metric's reader in
``bench/metrics/<metric>.py``. Nothing here branches on a cell's name.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
import shutil
import sys
import time
from typing import Optional

from bench.harness import modules, trace as tr, traffic, work
from bench.harness.reference import Reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / "bench" / ".trace"


# -- discovery by name ----------------------------------------------------------


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_entry(bm: dict, name: str) -> dict:
    for c in bm["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_config(bm: dict, name: str) -> dict:
    return json.loads((ROOT / config_entry(bm, name)["file"]).read_text())


def load_traffic(name: str) -> dict:
    return traffic.load(modules.BENCH / "traffic" / f"{name}.json")


def load_reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return modules.load("metrics", metric).read


def metrics_for(bm: dict, cell: str, traced: bool) -> list[dict]:
    """The end-to-end metrics a cell reports (those that list the cell, and
    those that list no cells, as ``setup_s``), or with a trace the
    per-layer metrics that list the cell; every per-layer metric lists its
    cells."""
    if traced:
        return [m for m in bm["per_layer"] if cell in m["workloads"]]
    return [m for m in bm["end_to_end"] if cell in m.get("workloads", (cell,))]


# -- what a run records ----------------------------------------------------------


@dataclasses.dataclass
class Record:
    op: traffic.Op
    t0: float
    t1: float = 0.0
    result: object = None
    error: Optional[str] = None
    timed: bool = False
    traced: bool = False
    work: tuple = ()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: str
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    records: list = dataclasses.field(default_factory=list)
    spans: dict = dataclasses.field(default_factory=dict)     # name -> [s]
    events: list = dataclasses.field(default_factory=list)    # trace events
    trace_window_s: float = 0.0
    telemetry: dict = dataclasses.field(default_factory=dict)  # traced deltas
    device_kind: str = ""

    def timed(self, kinds=None) -> list[Record]:
        return [r for r in self.records if r.timed
                and (kinds is None or r.op.kind in kinds)]

    def traced(self) -> list[Record]:
        return [r for r in self.records if r.traced]

    def host_ms_per_execute(self) -> Optional[float]:
        """Mean of ``session.execute`` minus ``session.execute.run`` over the
        executions in the traced window, in ms."""
        def total(prefix):
            rows = [v for k, v in self.telemetry.items()
                    if k == prefix or k.startswith(prefix + "{")]
            return sum(c for c, _ in rows), sum(s for _, s in rows)

        n, whole = total("session.execute_seconds")
        _, run = total("session.execute.run_seconds")
        return (whole - run) / n * 1e3 if n else None

    def roofline(self, kernel: str) -> Optional[float]:
        """Least time of the traced calls' ``kernel`` work over the device
        time the trace gives that kernel, in %."""
        spent = tr.kernel_s(self.events, kernel)
        if spent <= 0:
            return None
        peak = work.peaks(self.device_kind)
        least = sum(w.least_s(peak) for r in self.traced()
                    for w in r.work if w.kernel == kernel)
        return 100.0 * least / spent if least > 0 else None

    def idle_share(self) -> Optional[float]:
        busy = tr.busy_s(self.events)
        if busy is None or self.trace_window_s <= 0:
            return None
        return 100.0 * (1.0 - busy / self.trace_window_s)


class _CompileCounter:
    """Counts XLA compiles (persistent-cache loads included) while on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self.on = False  # counting starts with the window
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.n += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._seen)


def _histograms() -> dict:
    from repro.runtime import telemetry as tel

    hists = tel.registry().snapshot(include_spans=False)["histograms"]
    return {k: (h["count"], h["sum"]) for k, h in hists.items()
            if k.startswith("session.execute")}


def _delta(before: dict, after: dict) -> dict:
    return {k: (c - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
            for k, (c, s) in after.items()}


def generate(config: dict, names, seed: int) -> dict:
    """name -> (columns, stats) of each dataset in ``names``, from the
    configuration's schema; each dataset draws from its own ``stream`` of
    the seed, and its other keys are the schema's generator arguments."""
    schema = modules.load("schemas", config["schema"])
    out = {}
    for name in names:
        spec = dict(config["datasets"][name])
        stream = spec.pop("stream")
        out[name] = schema.generate(seed=[seed, stream],
                                    **config.get("schema_args", {}), **spec)
    return out


# -- one run ------------------------------------------------------------------------


def run(cell: str, seed: int, seconds: float, traced: bool, *,
        t_start: Optional[float] = None, control: Optional[str] = None,
        config_overrides: Optional[dict] = None, log=print) -> dict:
    """Runs ``cell`` once and returns its result line as a dict; the
    numbers compared, each beside its limit, are under ``checks``.

    ``control`` puts the reference, computed in that control's precision,
    in the program's place; ``config_overrides`` shrinks a configuration
    for tests on the CPU."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    bm = benchmark()
    entry = cell_entry(bm, cell)
    config = {**load_config(bm, entry["config"]), **(config_overrides or {})}
    mix = load_traffic(entry["traffic"])
    metric_specs = metrics_for(bm, cell, traced)
    readers = {m["name"]: load_reader(m["name"]) for m in metric_specs}
    device = jax.devices()[0]
    rec = Run(cell=cell, seed=seed, seconds=seconds,
              device_kind=device.device_kind)

    # set-up: the datasets the mix reads, from the seed, then the warm-up
    generated = generate(config, mix["datasets"], seed)
    data = {name: cols for name, (cols, _) in generated.items()}
    rows = {name: len(next(iter(cols.values()))) for name, cols in data.items()}
    if control is None:
        from bench.harness.system import System

        target = System(config)
        for name, (cols, stats) in generated.items():
            t0 = time.perf_counter()
            target.load(name, cols, stats)
            rec.spans.setdefault("load", []).append(time.perf_counter() - t0)

        def call(op):
            return op.module.call(target, op.args)
    else:
        target = None
        stand_in = Reference(data, control=control)

        def call(op):
            return stand_in.answer(op, op.module)
    del generated

    ops = traffic.stream(mix, seed)
    n_warm = mix["warmup_rounds"] * len(mix["round"])
    try:
        for op in itertools.islice(ops, n_warm):
            t0 = time.perf_counter()
            result = call(op)
            rec.records.append(Record(op, t0, time.perf_counter(), result))
        compiles0 = target.compiles() if target else 0
        xla_compiles = _CompileCounter()

        # the window: closed loop, one client, ends at the first completion
        # after `seconds`
        trace_s = mix["trace_seconds"]
        tracing = done_tracing = False
        tel0 = tr0 = None
        t_window = time.perf_counter()
        rec.setup_s = t_window - t_start
        deadline = t_window + seconds
        trace_from = t_window + max(0.0, (seconds - trace_s) / 2)
        if traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        t1 = t_window
        xla_compiles.on = True
        for op in ops:
            if traced and not tracing and not done_tracing and t1 >= trace_from:
                tel0 = _histograms()
                jax.profiler.start_trace(str(TRACE_DIR))
                tracing, tr0 = True, time.perf_counter()
            elif tracing and t1 >= tr0 + trace_s:
                rec.trace_window_s = time.perf_counter() - tr0
                jax.profiler.stop_trace()
                rec.telemetry = _delta(tel0, _histograms())
                tracing, done_tracing = False, True
            r = Record(op, time.perf_counter(), timed=True, traced=tracing)
            try:
                if tracing:
                    with jax.profiler.TraceAnnotation(tr.HOST_PREFIX + op.label):
                        r.result = call(op)
                    if target is not None:
                        r.work = tuple(work.plan_work(target.last_physical(),
                                                      rows))
                else:
                    r.result = call(op)
            except Exception as e:  # an operation the program failed
                r.error = f"{type(e).__name__}: {e}"
            r.t1 = t1 = time.perf_counter()
            rec.records.append(r)
            if t1 >= deadline:
                break
        rec.window_s = t1 - t_window
        xla_compiles.close()
        if tracing:
            rec.trace_window_s = time.perf_counter() - tr0
            jax.profiler.stop_trace()
            rec.telemetry = _delta(tel0, _histograms())
        compiles = target.compiles() - compiles0 if target else 0
        memory_peak = target.memory_peak_bytes() if target else 0
        log(json.dumps({"window": {"seconds": rec.window_s,
                                   "operations": len(rec.timed()),
                                   "program_compiles": compiles,
                                   "xla_compiles": xla_compiles.n,
                                   "setup_s": rec.setup_s}}))
    finally:
        if target is not None:
            target.close()
        del target

    # after the window, with the program's state freed: every answer,
    # warm-up included, against the plain reference
    t_check = time.perf_counter()
    checks = _check(rec, data)
    log(json.dumps({"check": {"answers": len(rec.records),
                              "seconds": time.perf_counter() - t_check}}))

    if traced:
        rec.events = tr.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in metric_specs:
        value = readers[m["name"]](rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    timed_records = rec.timed()
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(timed_records),
        "failed": sum(r.error is not None for r in timed_records),
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    if traced:
        busy = tr.busy_s(rec.events)
        result["device"]["busy_s"] = busy if busy is not None else 0.0
        result["device"]["window_s"] = rec.trace_window_s
        result["breakdown"] = {"device_ops": tr.top_device_ops(rec.events),
                               "idle_gaps": tr.idle_gaps(rec.events)}
    result["checks"] = checks
    return result


def _check(rec: Run, data: dict) -> dict:
    """Counts the answers, warm-up included, that differ from the exact
    reference or never came."""
    ref = Reference(data)
    wrong = 0
    for r in rec.records:
        mod = r.op.module
        wrong += r.error is not None or not mod.same(
            r.op.args, r.result, ref.answer(r.op, mod))
    return {"wrong_answers": {"value": wrong, "limit": 0}}


def print_result(result: dict) -> None:
    """The numbers compared as the last lines on standard error, and the
    result as the last line on standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
