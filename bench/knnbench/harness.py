"""The benchmark's harness: find a cell by name, run it, print its line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* a cell: an entry of ``workloads`` in ``BENCHMARK.json`` (its
  configuration, its traffic mix, its chips);
* a configuration: the ``file`` of its entry in ``configs``
  (``bench/configs/<config>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``, the parameters of one
  of the general drivers in ``bench/drivers/<driver>.py``, which the mix
  names under ``"driver"``;
* a metric: ``bench/metrics/<metric>.py``, whose ``read(run)`` takes the
  number from the run's record and returns None where it finds nothing.

A run is one process: set-up (the world, the program, a warm-up of every
shape the window uses), the measured window, then, with the window closed
and the program's state freed, the comparison with the plain reference.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

from . import checks as checks_mod
from . import trace as trace_mod

__all__ = [
    "ROOT", "Cell", "load_cell", "load_module", "metric_entries",
    "CompileCounter", "use_compile_cache", "device_peaks", "require_devices",
    "run_cell", "result_line", "NoDevice",
]

ROOT = Path(__file__).resolve().parents[2]
SPAN_PREFIX = "bench."


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class Cell:
    """One cell as the files name it: its entry, configuration and mix."""

    def __init__(self, root: Path, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                           f"{sorted(cells)}")
        self.root = root
        self.bench = bench
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = json.loads(
            (root / self.config_entry["file"]).read_text())
        self.traffic_name = self.entry["traffic"]
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{self.traffic_name}.json")
            .read_text())
        self.chips = int(self.entry["chips"])

    def rehearsal(self) -> "Cell":
        """This cell at the tiny size its files give under ``"rehearsal"``,
        for a run on the CPU (the tests); nothing it gives is a device
        number."""
        small = object.__new__(Cell)
        small.__dict__.update(self.__dict__)
        small.config = _merged(self.config, self.config.get("rehearsal", {}))
        small.traffic = _merged(self.traffic,
                                self.traffic.get("rehearsal", {}))
        return small

    def driver(self):
        return load_module(
            self.root / "bench" / "drivers" / f"{self.traffic['driver']}.py")

    def metrics(self, kind: str) -> list[dict]:
        return metric_entries(self.bench, self.name, kind)


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(base.get(key), dict):
            out[key] = _merged(base[key], val)
        else:
            out[key] = val
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return Cell(root, bench, name)


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    path = Path(path)
    spec = importlib.util.spec_from_file_location(
        f"knnbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_entries(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports.

    A metric with a ``workloads`` key is reported in the cells it lists.
    Without one, an end-to-end metric is reported everywhere, and a
    per-layer metric wherever the end-to-end metric it ``moves`` is.
    """
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def applies(m):
        if "workloads" in m:
            return cell in m["workloads"]
        if kind == "per_layer":
            return applies(e2e[m["moves"]])
        return True

    return [m for m in bench[kind] if applies(m)]


class CompileCounter:
    """Counts programs JAX builds (compiled or loaded from the cache).

    A ``jax.monitoring`` listener; only events while ``armed`` count, so
    the harness arms it for the measured window alone, where it should
    read 0.
    """

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.traces = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if not self.armed:
            return
        if event == self.BACKEND:
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == self.TRACE:
            self.traces += 1


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout.

    ``JAX_COMPILATION_CACHE_DIR`` where it is set, otherwise ``.jax_cache``
    at the root of the checkout.  Every program is kept, however small, so
    a run after the first loads all that it needs.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip of ``device_kind`` ``kind``, from
    ``bench/peaks.json``; a kind that is not in the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise NoDevice(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json: {sorted(table['devices'])}")
    return table["devices"][kind]


def require_devices(chips: int, platform: str = "tpu"):
    """The cell's devices; raises NoDevice where JAX finds too few, or a
    chip whose peaks the benchmark does not know."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(f"JAX finds no {platform} (platform "
                       f"{devices[0].platform!r}); this benchmark measures "
                       "only on the chip")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devices)}")
    device_peaks(devices[0].device_kind)
    return devices[:chips]


def device_memory_peak(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where it is reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


@contextlib.contextmanager
def _maybe_trace(enabled: bool):
    """Profile the window into a temporary directory; yields a holder
    whose ``path`` names the ``.xplane.pb`` once the block has ended."""

    class Holder:
        path = None
        reduced = None

    h = Holder()
    if not enabled:
        yield h
        return
    import jax

    with tempfile.TemporaryDirectory(prefix="knnbench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=_profile_options())
        try:
            yield h
        finally:
            jax.profiler.stop_trace()
        found = sorted(Path(d).rglob("*.xplane.pb"))
        h.path = found[-1] if found else None
        h.reduced = (trace_mod.reduce_xplane(
            h.path, window_span=SPAN_PREFIX + "window")
            if h.path else None)


def span_factory(enabled: bool):
    """``span(name)``: a host span in the profiler's trace, or nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float, log=None,
             around_window=contextlib.nullcontext) -> dict:
    """Set up, measure and check one run; returns the result's fields.

    ``devices`` are the chips the cell uses (the rehearsal passes CPU
    devices); ``t_start`` is the host clock when the process began, from
    which set-up is counted; ``around_window()`` is a context the window
    runs in (the tests plant faults there).
    """
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    counter = CompileCounter()
    driver = cell.driver()
    span = span_factory(trace)
    state = driver.setup(cell, seed, devices, log)
    setup_s = time.perf_counter() - t_start
    log(f"# set-up {setup_s:.3f} s")
    with _maybe_trace(trace) as tr:
        counter.armed = True
        with span("window"), around_window():
            driver.window(state, seconds, span)
        counter.armed = False
    log(f"# compiles inside the window: {counter.compiles} "
        f"(jaxpr traces {counter.traces})"
        + (f" {sorted(set(counter.names))}" if counter.names else ""))
    memory_peak = device_memory_peak(devices)
    run = driver.record(state)
    if run.get("tick_s"):
        ts = sorted(run["tick_s"])
        log(f"# window: {len(ts)} ticks in {run['window_s']:.3f} s; tick "
            f"min {ts[0]:.3f} median {ts[len(ts) // 2]:.3f} "
            f"max {ts[-1]:.3f} s")
    run.update(setup_s=setup_s, memory_peak_bytes=memory_peak,
               compiles_in_window=counter.compiles,
               trace=tr.reduced if trace else None)
    driver.release(state)
    verdict = check(cell, driver, state, seed, log)
    return dict(run=run, verdict=verdict)


def check(cell: Cell, driver, state, seed: int, log) -> dict:
    """The comparison with the plain reference, after the window."""
    t0 = time.perf_counter()
    k = int(cell.config["service"]["k"])
    checks, stats = checks_mod.compare(driver.answers(state, seed), k)
    attempted, failed = driver.tally(state)
    log(f"# check: {stats['rows']} rows returned or due, {stats['drawn']} "
        f"against the brute force, d2 rounding {stats['form']}, "
        f"{time.perf_counter() - t0:.2f} s")
    return dict(checks=checks, attempted=attempted, failed=failed)


def read_metrics(cell: Cell, kind: str, run: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the cell's metrics of one kind;
    a reader that finds nothing leaves its metric out."""
    out = {}
    for m in cell.metrics(kind):
        reader = load_module(cell.root / "bench" / "metrics"
                             / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = dict(correct=bool(correct), attempted=int(attempted),
               failed=int(failed), metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def finish(cell: Cell, out: dict, devices, trace: bool) -> str:
    """The run's result line; the numbers compared go last to stderr."""
    run, verdict = out["run"], out["verdict"]
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in verdict["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices),
                  memory_peak_bytes=run["memory_peak_bytes"])
    breakdown = None
    if trace:
        metrics = read_metrics(cell, "per_layer", run)
        tr = run["trace"] or {}
        device.update(busy_s=tr.get("busy_s"), window_s=tr.get("window_s"))
        breakdown = dict(device_ops=tr.get("device_ops", []),
                         idle_gaps=tr.get("idle_gaps", []))
    else:
        metrics = read_metrics(cell, "end_to_end", run)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result_line(correct=correct, attempted=verdict["attempted"],
                       failed=verdict["failed"], metrics=metrics,
                       device=device, checks=checks, breakdown=breakdown)


def rehearse(name: str, seed: int, seconds: float, *, trace: bool = False,
             root: Path = ROOT, chips: int | None = None, log=None,
             around_window=contextlib.nullcontext) -> dict:
    """Drive one cell end to end on the CPU at its rehearsal size.

    For the tests: the look for a chip is skipped and the cell's files give
    their ``"rehearsal"`` sizes.  Returns the run's record and verdict with
    ``correct``; it prints no result line and no device metric.
    """
    import jax

    cell = load_cell(name, root).rehearsal()
    devices = jax.devices()[:chips or cell.chips]
    out = run_cell(cell, seed, seconds, trace, devices=devices,
                   t_start=time.perf_counter(),
                   log=log or (lambda msg: None), around_window=around_window)
    out["correct"] = all(v <= lim for v, lim in
                         out["verdict"]["checks"].values())
    return out
