"""The benchmark harness: finds a cell's configuration, traffic, driver and
per-layer metric readers by name, runs one measured window and prints the
result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    bench/configs/<config>.json     a deployment: text, sizes, index knobs
    bench/traffic/<traffic>.json    the mix; its "driver" names the window
    bench/drivers/<driver>.py       setup / window / release / check
    bench/metrics/<metric>.py       SOURCE and read(ctx) -> number or None

A driver module provides ``setup(ctx)``, ``window(ctx, state)``,
``release(ctx, state)`` and ``check(ctx, state)``; see ``drivers/``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
PEAKS = BENCH / "peaks.json"


class HarnessError(Exception):
    """The run cannot be made (no chip, a missing file, a bad name)."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a Python file by path (metric files carry dots in their
    names, so they are not importable by module name)."""
    if not path.is_file():
        raise HarnessError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"no such file: {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""

    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    traffic_name: str
    driver: object        # bench/drivers/<driver>.py
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # metric entries this cell reports with --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, bench_dir: pathlib.Path = BENCH,
              benchmark: pathlib.Path | None = None) -> Cell:
    spec = _read_json(benchmark or bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise HarnessError(f"unknown workload {workload!r}; "
                           f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = _read_json(bench_dir.parent / cfg_entry["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        traffic_name=w["traffic"], driver=driver,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    return load_module(bench_dir / "metrics" / f"{name}.py")


def index_config(config: dict):
    """The program's ``BWTIndexConfig`` with the configuration's knobs."""
    from repro.configs.bwt_index import CONFIG

    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["index"].items()}
    return CONFIG.replace(n=config["n"], sigma=config["sigma"], **kw)


class CompileClock:
    """Programs JAX compiles (or loads from the persistent cache), counted
    from its backend-compile events, with their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.by_fun = collections.Counter()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
            self.by_fun[kw.get("fun_name", "?")] += 1


class GcClock:
    """Python garbage collections while armed: count by generation and
    pause seconds (a full collection stops every thread, the client's
    too)."""

    def __init__(self):
        self.by_gen = collections.Counter()
        self.pauses: list[float] = []
        self._t0 = None

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self.by_gen[info["generation"]] += 1
            self._t0 = None

    def arm(self, on: bool) -> None:
        if on:
            gc.callbacks.append(self._on)
        else:
            gc.callbacks.remove(self._on)

    def summary(self) -> str:
        p = self.pauses
        return (f"{dict(self.by_gen)} collections, pauses total "
                f"{sum(p) * 1e3} ms, max {max(p, default=0.0) * 1e3} ms")


class Tracer:
    """Starts and stops the profiler around the part of the window a driver
    chooses; records the host-clock bounds of the traced interval."""

    def __init__(self, enabled: bool, directory: pathlib.Path):
        self.enabled = enabled
        self.directory = directory
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.enabled or self.t0 is not None:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.enabled or self.t0 is None or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def span(self, name: str, **attrs):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name, **attrs)


@dataclasses.dataclass
class Ctx:
    """What a driver and the metric readers see of a run."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    tracer: Tracer
    clock: CompileClock | None = None
    device: object = None
    fault: str | None = None
    # filled by the driver: host spans/counters of the traced interval
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    # filled by the harness after a traced run
    device_trace: object = None
    peaks: dict | None = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def n(self) -> int:
        return int(self.cell.config["n"])

    def rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence([int(self.seed), *salt]))


def peaks_for(device_kind: str) -> dict:
    table = _read_json(PEAKS)["devices"]
    if device_kind not in table:
        raise HarnessError(f"no peaks for device kind {device_kind!r} in "
                           f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def _merge(base: dict, extra: dict) -> None:
    """Update ``base`` with ``extra``, merging nested dicts key by key
    (the rehearsal tests shrink a cell this way)."""
    for k, v in extra.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v


def _fmt(x):
    return int(x) if isinstance(x, (bool, np.bool_)) else x


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, bench_dir: pathlib.Path = BENCH,
        require_tpu: bool = True, overrides: dict | None = None,
        fault: str | None = None) -> int:
    """One run of one cell; prints the result as the last line of
    standard output and returns the exit code."""
    src = bench_dir.parent / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program's sources are not under {src}",
              file=sys.stderr)
        return 2
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        cell = load_cell(workload, bench_dir)
    except (HarnessError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    overrides = dict(overrides or {})
    _merge(cell.config, overrides.pop("config", {}))
    _merge(cell.traffic, overrides.pop("traffic", {}))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"bench: no TPU (JAX found {dev.platform}); the benchmark "
              "measures only on the chip", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    clock = CompileClock()
    tracer = Tracer(trace, bench_dir.parent / ".bench_trace" / workload)
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
              tracer=tracer, clock=clock, device=dev,
              fault=fault)
    if require_tpu:
        ctx.peaks = peaks_for(dev.device_kind)
    log(f"bench: {workload} seed={seed} seconds={seconds} trace={int(trace)}"
        f" on {dev.device_kind} x{len(devices)}; jax {jax.__version__};"
        f" compile cache {cache_dir}")

    state = cell.driver.setup(ctx)
    # what set-up made lives through the window: keep it out of the
    # collector's full passes
    gc.collect()
    gc.freeze()
    c0, s0, f0 = clock.count, clock.seconds, clock.by_fun.copy()
    setup_s = time.perf_counter() - t_process
    log(f"bench: setup {setup_s} s, compiled or loaded {c0} programs in "
        f"{s0} s; persistent cache {compile_cache.stats()}")
    # HBM the window holds, read at its open and its close, unless the
    # driver knows its window's peak: the allocator's peak counts the
    # set-up's work, which the window need not repeat
    in_use = [int((dev.memory_stats() or {}).get("bytes_in_use", 0))]
    gc_clock = GcClock()
    gc_clock.arm(True)
    e2e = cell.driver.window(ctx, state)
    gc_clock.arm(False)
    window_compiles = clock.count - c0
    tracer.stop()
    stats = dev.memory_stats() or {}
    in_use.append(int(stats.get("bytes_in_use", 0)))
    peak = int(e2e.get("memory_peak_bytes", max(in_use)))
    log(f"bench: compilations inside the window {window_compiles} "
        f"({clock.seconds - s0} s)"
        + (f": {dict(clock.by_fun - f0)}" if window_compiles else ""))
    log(f"bench: gc inside the window: {gc_clock.summary()}")
    log(f"bench: HBM in use at the window's open and close: {in_use} "
        f"bytes of {stats.get('bytes_limit', 0)}; the allocator's peak "
        f"since start, set-up included: {stats.get('peak_bytes_in_use', 0)};"
        f" reported: {peak}")
    cell.driver.release(ctx, state)
    gc.unfreeze()
    checks, attempted, failed = cell.driver.check(ctx, state)

    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    extra = {}
    if trace:
        reduce = load_module(bench_dir / "trace" / "reduce.py",
                             "bench_trace_reduce")
        summary = reduce.summarize(tracer.directory, (tracer.t0, tracer.t1),
                                   host_prefix="bench.")
        ctx.device_trace = summary
        if summary is not None and summary.busy_s > 0:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            extra["breakdown"] = summary.breakdown()
        for m in cell.per_layer:
            value = metric_reader(m["name"], bench_dir).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    correct = all(v <= lim for v, lim in checks.values())
    for name, (v, lim) in checks.items():
        print(f"check {name} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    line.update(extra)
    line["checks"] = {k: {"value": _fmt(v), "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)
    return 0
