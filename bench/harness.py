"""One run of one benchmark cell.

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``; the configuration file names its architecture, whose
module ``configs/<architecture>.py`` builds the system under test and whose
``configs/<architecture>_ref.py`` is the plain reference. Each metric is a
reader ``metrics/<name>.py``, or ``metrics/<base>.py`` for a metric
``<base>.<part>`` split by the end-to-end metric it moves. Adding a cell, a configuration, a traffic mix
or a metric adds files and entries and edits none.

A run: check the chip; make the weights from the seed; build the system;
warm each (program shape, bucket) the traffic uses with one real call; play
the traffic through the program's ``MicroBatchAggregator`` into
``Executor.generate_bucketed``; read the metrics; free the system; compare
a seeded sample of the window's answers with the reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import ExitStack, contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import traffic as traffic_mod  # noqa: E402

POOL = "sd3"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def pin_compile_cache():
    """Keep JAX's persistent compile cache at a fixed path inside the
    checkout, and the TPU runtime's log files off (they would go to a fixed
    path under /tmp). JAX reads the variables when it is imported, and the
    program's ``enable_compile_cache()`` defers to the first; call this
    before importing JAX."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or fewer chips than, the cell
    asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic and the metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(cell["chips"]), config, traffic,
                mine(spec["end_to_end"]), mine(spec["per_layer"]))


_modules: dict = {}


def load_module(path: Path):
    """Import a benchmark file by its path, once per process (so its jitted
    functions keep their compiled programs from run to run)."""
    path = Path(path).resolve()
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(
            "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


@dataclass
class Batch:
    t0: float
    t1: float
    n: int
    bucket: int
    flops: float


@dataclass
class Run:
    """What a run leaves for the metric readers. Times are seconds from
    the window's opening on the host clock."""

    seconds: float
    backlog: bool
    setup_s: float
    requests: list
    batches: List[Batch]
    window_close: float
    peak: dict
    memory_peak_bytes: Optional[int] = None
    trace: Optional[dict] = None

    def window_requests(self):
        if self.backlog:
            return [r for r in self.requests
                    if r.dispatch is not None and 0.0 <= r.dispatch
                    and r.done is not None and r.done <= self.window_close]
        return [r for r in self.requests if 0.0 <= r.due < self.seconds]

    def window_batches(self) -> List[Batch]:
        return [b for b in self.batches
                if b.t0 >= 0.0 and b.t1 <= self.window_close]

    def traced_batches(self) -> list:
        if self.trace is None:
            return []
        return [s for s in self.trace["spans"]
                if s["name"] == "generate_bucketed" and s["batch"] is not None]


class CompileCounter:
    """Counts backend compiles through ``jax.monitoring``; one listener per
    process, whatever the number of runs in it."""

    count = 0
    _installed = False

    @classmethod
    def install(cls):
        if not cls._installed:
            import jax

            def listen(event: str, duration: float, **_):
                if event == BACKEND_COMPILE:
                    cls.count += 1

            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._installed = True


def check_device(chips: int, peaks: dict, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's default device is {dev.platform} "
                     f"({dev.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    if require_tpu and dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in the peaks "
                     f"table {sorted(peaks)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Spans:
    """Host spans around each call into the program: with a profiler
    running they go into its trace as ``bench:<name>``."""

    def __init__(self):
        self.annotate = False

    @contextmanager
    def __call__(self, name: str, **stats):
        with ExitStack() as stack:
            if self.annotate:
                import jax

                stack.enter_context(
                    jax.profiler.TraceAnnotation("bench:" + name, **stats))
            yield


class Tracer:
    """Profiles the first ``trace_s`` seconds or ``trace_batches`` batches
    of the window; the profiler stops once the window has closed."""

    def __init__(self, traffic: dict, spans: Spans, keep: Optional[Path]):
        self.trace_s = traffic.get("trace_s")
        self.trace_batches = traffic.get("trace_batches")
        self.spans = spans
        self.keep = keep
        self.dir: Optional[Path] = None
        self.window = None
        self.batches = 0
        self.t0 = 0.0

    def start(self, now: float):
        import jax

        self.dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.window = jax.profiler.TraceAnnotation("bench:window")
        self.window.__enter__()
        self.spans.annotate = True
        self.t0 = now

    def after_batch(self, now: float):
        if self.window is None:
            return
        self.batches += 1
        if ((self.trace_batches and self.batches >= self.trace_batches)
                or (self.trace_s and now - self.t0 >= self.trace_s)):
            self.close_window()

    def close_window(self):
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None
            self.spans.annotate = False

    def finish(self) -> Optional[dict]:
        import jax

        import trace_reduce as trace_mod

        if self.dir is None:
            return None
        self.close_window()
        jax.profiler.stop_trace()
        try:
            path = trace_mod.find_xplane(self.dir)
            if self.keep is not None:
                self.keep.parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(path, self.keep)
            return trace_mod.reduce(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def serve(system, reqs, traffic: dict, seconds: float, config: dict,
          flops_of, spans: Spans, tracer: Optional[Tracer],
          rel: Callable[[], float]):
    """Play ``reqs`` through the program's aggregator into the system.
    Open loop: every request due before ``seconds`` is served and waited
    for. Backlog: the window closes at the first batch completion after
    ``seconds``. Returns (batches, window close)."""
    from repro.core.context import Request as ProgramRequest
    from repro.serving.runtime.batching import MicroBatchAggregator
    from repro.serving.runtime.events import WorkItem

    backlog = traffic_mod.is_backlog(traffic)
    buckets = tuple(traffic["buckets"])
    agg = MicroBatchAggregator(POOL, buckets=buckets,
                               linger_s=float(traffic["linger_s"]))
    arm_idx = {a["label"]: a["idx"] for a in config["arms"]}
    by_rid = {r.rid: r for r in reqs}
    batches: List[Batch] = []
    i, n = 0, len(reqs)
    close = 0.0
    while True:
        now = rel()
        if tracer is not None and tracer.dir is None and now >= 0.0:
            tracer.start(now)
        with spans("aggregator"):
            while i < n and reqs[i].due <= now:
                r = reqs[i]
                item = WorkItem(
                    req=ProgramRequest(rid=r.rid, arrival=r.due,
                                       complexity=0.0, wants_text=False,
                                       rtt_ms=0.0, battery=1.0,
                                       pref_speed=0.0,
                                       prompt_seed=r.prompt_seed),
                    arm_idx=arm_idx[r.arm], phase="relay", pool=POOL,
                    steps=config["steps"])
                agg.push(item, r.due)
                i += 1
            got = agg.next_batch(now)
            if got is None and agg.depth():
                deadline = agg.flush_deadline()
                if deadline is not None and now >= deadline:
                    got = agg.next_batch(now, force=True)
        if got is None:
            if i >= n and agg.depth() == 0:
                close = max((r.done for r in reqs if r.done is not None),
                            default=0.0)
                break
            wake = reqs[i].due if i < n else math.inf
            if agg.depth():
                wake = min(wake, agg.flush_deadline())
            with spans("arrival_wait"):
                time.sleep(max(0.0, wake - rel()))
            continue
        items, bucket = got
        batch_reqs = [by_rid[it.rid] for it in items]
        arm = batch_reqs[0].arm
        t0 = rel()
        with spans("generate_bucketed", batch=len(batches)):
            out = system.serve(arm, [r.prompt_seed for r in batch_reqs],
                               buckets)
        t1 = rel()
        for k, r in enumerate(batch_reqs):
            r.dispatch, r.done, r.batch = t0, t1, len(batches)
            r.output = out[k]
            r.ok = bool(np.all(np.isfinite(out[k])))
        batches.append(Batch(t0, t1, len(items), bucket,
                             len(items) * flops_of(arm)))
        if tracer is not None:
            tracer.after_batch(t1)
        if backlog and t1 >= seconds:
            close = t1
            break
    return batches, close


def warm(system, traffic: dict, seed: int):
    """One real call per (program shape, bucket) that the traffic uses."""
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0x5EED)
    for label in system.distinct_shapes(sorted(traffic["arms"])):
        for b in traffic["buckets"]:
            seeds = rng.integers(0, traffic_mod.PROMPT_SEED_LIMIT, size=b)
            system.serve(label, seeds, traffic["buckets"])


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm((got - ref).ravel())
                 / max(np.linalg.norm(ref.ravel()), 1e-30))


def check(cell: Cell, ref_mod, params, reqs: list, seed: int,
          fp8: bool = False) -> dict:
    """Compare a seeded sample of the window's answers with the reference:
    the widest relative L2 gap of a served final latent."""
    done = [r for r in reqs if r.ok]
    k = min(int(cell.traffic["check_sample"]), len(done))
    rng = np.random.default_rng((int(seed) % (1 << 64)) ^ 0xC4EC)
    pick = sorted(rng.choice(len(done), size=k, replace=False)) if k else []
    sample = [done[j] for j in pick]
    ref = ref_mod.generate(cell.config, params,
                           [(r.arm, r.prompt_seed) for r in sample])
    errs = [rel_err(r.output, ref[j]) for j, r in enumerate(sample)]
    out = {"latent_rel_err_max": max(errs) if errs else math.inf,
           "sampled": k}
    if fp8:
        ctl = ref_mod.generate(cell.config, params,
                               [(r.arm, r.prompt_seed) for r in sample],
                               fp8=True)
        out["control_rel_err_max"] = max(rel_err(ctl[j], ref[j])
                                         for j in range(k))
    return out


def metric_reader(name: str) -> Path:
    """``metrics/<name>.py``, else the reader of the quantity ``name``
    splits (``metrics/<base>.py`` for ``<base>.<part>``)."""
    path = BENCH / "metrics" / f"{name}.py"
    return path if path.is_file() else BENCH / "metrics" / (
        name.split(".")[0] + ".py")


def metric_value(name: str, run: Run):
    return load_module(metric_reader(name)).read(run)


@dataclass
class Built:
    """A cell's system under test, built and warmed from one seed."""

    device: dict
    peak: dict
    model: object
    ref_mod: object
    params: dict
    system: object


def build(cell: Cell, seed: int, chip: bool = True,
          system_hook: Optional[Callable] = None) -> Built:
    """Check the chip, make the weights from ``seed``, build the system and
    warm every shape the cell's traffic uses. ``chip=False`` (the CPU
    tests) skips the look for a TPU and the persistent compile cache;
    ``system_hook`` wraps the built system (the tests break it there)."""
    import jax

    peaks = json.loads((BENCH / "peaks.json").read_text())
    device = check_device(cell.chips, peaks, chip)
    root = BENCH.parent
    if not (root / "src" / "repro").is_dir():
        raise FileNotFoundError(f"the system under test is not at "
                                f"{root / 'src' / 'repro'}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    if chip:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    CompileCounter.install()

    bad = set(cell.traffic["buckets"]) - set(cell.config["buckets"])
    if bad:
        raise ValueError(f"traffic buckets {sorted(bad)} are not among the "
                         f"configuration's {cell.config['buckets']}")
    arch = cell.config["architecture"]
    model = load_module(BENCH / "configs" / f"{arch}.py")
    ref_mod = load_module(BENCH / "configs" / f"{arch}_ref.py")
    params = model.init_params(cell.config, seed)
    jax.block_until_ready(params)
    system = model.System(cell.config, params)
    if system_hook is not None:
        system = system_hook(system)
    warm(system, cell.traffic, seed)
    return Built(device, peaks.get(device["kind"], {}), model, ref_mod,
                 params, system)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, chip: bool = True,
             keep_trace: Optional[Path] = None, control: bool = False,
             system_hook: Optional[Callable] = None) -> dict:
    """One run. Returns the result line's object with ``checks`` last.
    ``control`` also reads the reference's float8 control on the sample;
    ``chip`` and ``system_hook`` are :func:`build`'s."""
    import jax

    b = build(cell, seed, chip, system_hook)
    device, peak, model, system = b.device, b.peak, b.model, b.system
    reqs = [r for r in traffic_mod.requests(cell.traffic, seed, seconds)
            if r.due < seconds]
    flops = {a["label"]: model.request_flops(cell.config, a["relay_step"])
             for a in cell.config["arms"]}
    ramp = 0.0 if traffic_mod.is_backlog(cell.traffic) else float(
        cell.traffic.get("ramp_s", 0.0))

    compiles_before = CompileCounter.count
    t_traffic = time.perf_counter()
    setup_s = t_traffic - t_start
    t_open = t_traffic + ramp

    def rel():
        return time.perf_counter() - t_open

    spans = Spans()
    tracer = Tracer(cell.traffic, spans, keep_trace) if trace else None
    batches, close = serve(system, reqs, cell.traffic, seconds, cell.config,
                           flops.__getitem__, spans, tracer, rel)
    compiles_in_window = CompileCounter.count - compiles_before
    mem = memory_peak_bytes()
    reduced = tracer.finish() if tracer is not None else None

    run = Run(seconds=seconds,
              backlog=traffic_mod.is_backlog(cell.traffic),
              setup_s=setup_s, requests=reqs, batches=batches,
              window_close=close, peak=peak, memory_peak_bytes=mem,
              trace=reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = metric_value(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    attempted = run.window_requests()
    failed = sum(1 for r in attempted if not r.ok)

    # free the program's state before the reference runs beside the weights
    system.close()
    del system, b.system
    gc.collect()
    jax.clear_caches()
    t_check = time.perf_counter()
    readings = check(cell, b.ref_mod, b.params, attempted, seed,
                     fp8=control)
    reference_s = time.perf_counter() - t_check
    limit = float(cell.config["check"]["limit"])
    checks = {
        "latent_rel_err_max": {"value": readings["latent_rel_err_max"],
                               "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (failed == 0 and len(attempted) > 0
               and readings["latent_rel_err_max"] <= limit)
    result = {
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": failed,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=mem),
        "compiles_in_window": compiles_in_window,
        "sampled": readings["sampled"],
        "reference_s": reference_s,
    }
    if "control_rel_err_max" in readings:
        result["control_rel_err_max"] = readings["control_rel_err_max"]
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result
