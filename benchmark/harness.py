"""One run of one cell: set-up, the measured window, the metrics, and
the check of what the window produced.

Driven by data.  ``BENCHMARK.json`` names a cell's configuration and
traffic mix and lists which metrics the cell reports; the harness finds
the files by those names and holds no list of its own:

* configuration  -> the ``file`` its ``configs`` entry gives, with its
  plain reference ``reference/<config>.py``;
* traffic mix    -> ``traffic/<traffic>.json``, read by the generator
  ``traffic/<generator>.py`` that the file names;
* end-to-end metric -> ``end_to_end/<metric>.py``;
* per-layer metric -> ``layer_metrics/<metric>.py``.

From the program it takes the system under test (``readImages``,
``DeepImageFeaturizer``, ``set_zoo_model``), its counters
(``engine.metrics``, ``compile_cache.stats()``) and nothing else.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional

from benchmark import correct, trace_reduce
from benchmark.layer_metrics import JobSpan, Observations
from benchmark.traffic import OUTPUT_COL, make_stage

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR_NAME = ".bench_work"          # in the checkout, git-ignored


class BenchmarkError(Exception):
    """The run cannot be made: no result line, non-zero exit."""


# -- finding things by name -------------------------------------------------

class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]     # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Any:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def _named(entries: List[Dict[str, Any]], name: str, what: str,
           ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"unknown {what} {name!r}; BENCHMARK.json has "
        f"{sorted(e['name'] for e in entries)}")


def _safe_name(name: str, what: str) -> str:
    if not name or not all(c.isalnum() or c in "_.-" for c in name) \
            or name.startswith("."):
        raise BenchmarkError(f"{what} name {name!r} is not a plain name")
    return name


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = _named(bench["workloads"], name, "workload")
    config_entry = _named(bench["configs"], entry["config"], "configuration")
    config = _read_json(os.path.join(root, config_entry["file"]))
    traffic_name = _safe_name(entry["traffic"], "traffic")
    traffic_path = os.path.join(root, bench["paths"][0], "traffic",
                                traffic_name + ".json")
    if not os.path.isfile(traffic_path):
        raise BenchmarkError(f"unknown traffic mix {traffic_name!r}: "
                             f"no {traffic_path}")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic_name=traffic_name, traffic=_read_json(traffic_path),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def _find_module(package: str, name: str, what: str):
    _safe_name(name, what)
    try:
        return importlib.import_module(f"benchmark.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{package}.{name}":
            raise
        raise BenchmarkError(
            f"unknown {what} {name!r}: no benchmark/{package}/{name}.py"
        ) from None


def find_generator(name: str):
    return _find_module("traffic", name, "traffic generator")


def find_reader(name: str):
    return _find_module("layer_metrics", name, "per-layer metric")


def find_end_to_end(name: str):
    return _find_module("end_to_end", name, "end-to-end metric")


def reference_of(config: Dict[str, Any]) -> str:
    """The module under ``reference/`` that the configuration names as
    its plain reference (two statements of one model share one)."""
    return _safe_name(os.path.splitext(os.path.basename(
        config["reference"]))[0], "reference")


def load_peak(device_kind: str, peaks_path: Optional[str] = None
              ) -> Dict[str, Any]:
    peaks = _read_json(peaks_path or os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in peaks:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in the table of peaks "
            f"({sorted(peaks)}): add its row with its source, no default")
    return peaks[device_kind]


# -- the system under test --------------------------------------------------

def program_environment(config: Dict[str, Any]) -> Dict[str, str]:
    """The environment that makes the program run at the precision the
    configuration STATES: derived from ``compute_dtype`` and
    ``matmul_precision``, so the two cannot drift apart.  float32 is
    the product's default (nothing set); the program has no switch for
    a matmul precision other than the chip's default."""
    if config["matmul_precision"] != "default":
        raise BenchmarkError(
            f"{config['name']}: matmul_precision "
            f"{config['matmul_precision']!r} is stated, and the program "
            f"has a switch for 'default' alone")
    if config["compute_dtype"] == "float32":
        return {}
    return {"SPARKDL_ZOO_COMPUTE_DTYPE": config["compute_dtype"]}


def check_stated_precision(config: Dict[str, Any], control: bool) -> None:
    """The program's own reading of its environment agrees with what
    the configuration states (a control that switches a lower precision
    of the program on is the one run in which it must not)."""
    from sparkdl_tpu.transformers.named_image import zoo_compute_dtype_name

    runs, states = zoo_compute_dtype_name(), config["compute_dtype"]
    if (runs == states) == control:
        raise BenchmarkError(
            f"{config['name']} states {states}, the program reads its "
            f"environment as {runs}" + (" in a control run" * control))


def scrub_environment(keep: Optional[Dict[str, str]] = None) -> List[str]:
    """The configuration states the product's defaults: every
    ``SPARKDL_*`` switch of the caller's shell is taken out (``keep`` is
    what a control run then switches on); returns what was removed."""
    removed = sorted(k for k in os.environ if k.startswith("SPARKDL_"))
    for k in removed:
        del os.environ[k]
    os.environ.update(keep or {})
    return removed


def check_devices(chips: int, platform: Optional[str]) -> Dict[str, Any]:
    """The device as JAX reports it; refuses another platform than
    ``platform`` or another number of chips than the cell asks for."""
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if platform is not None and stamp["platform"] != platform:
        raise BenchmarkError(
            f"JAX found platform {stamp['platform']!r}, not {platform!r}: "
            f"this benchmark measures the chip and nothing else")
    if stamp["count"] != chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX found {stamp['count']}")
    return stamp


def to_program_variables(config: Dict[str, Any], weights: Dict[str, Any]):
    """The reference's seeded weights in the shape of the program's own
    variable tree (names and shapes from ``abstract_variables``, no
    values): every weight is used exactly once and fits its leaf."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops import reference_module
    from sparkdl_tpu.models import get_model_spec

    ref = reference_module(reference_of(config))
    used = set()

    def leaf(path, spec):
        keys = tuple(str(getattr(k, "key", k)) for k in path)
        if any(k in ref.UNUSED_BY_FEATURIZER for k in keys):
            return jnp.zeros(spec.shape, spec.dtype)
        name = ref.reference_name(keys)
        w = weights[name]
        if tuple(w.shape) != tuple(spec.shape):
            raise BenchmarkError(f"{'/'.join(keys)}: program wants "
                                 f"{spec.shape}, reference has {w.shape}")
        used.add(name)
        return w

    abstract = get_model_spec(config["model_name"]).abstract_variables()
    tree = jax.tree_util.tree_map_with_path(leaf, abstract)
    if used != set(weights):
        raise BenchmarkError("reference weights the program has no leaf "
                             f"for: {sorted(set(weights) - used)[:5]}")
    return tree


def install_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Draw the configuration's weights from the seed (one jitted call
    on the device) and serve them as the zoo model; returns them for
    the reference."""
    from benchmark.flops import reference_module
    from benchmark.reference import net as refnet
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.transformers.named_image import set_zoo_model

    ref = reference_module(reference_of(config))
    h, w = config["input_height"], config["input_width"]
    if (h, w) != tuple(ref.INPUT_HW):
        raise BenchmarkError(f"{config['name']}: the configuration states "
                             f"{h}x{w}, its reference {ref.INPUT_HW}")
    declared = refnet.declare(ref.forward, (1, h, w, 3))
    weights = refnet.draw_weights(declared.params, seed)
    spec = get_model_spec(config["model_name"])
    set_zoo_model(config["model_name"], spec.build(),
                  to_program_variables(config, weights))
    return weights


def stage_engine(config: Dict[str, Any], batch_size: int):
    """The engine the timed stage runs on (process-wide, per model, cut,
    batch and compute dtype)."""
    return make_stage(config, batch_size).engine()


def read_counters(engine) -> Dict[str, float]:
    from sparkdl_tpu.parallel import compile_cache

    counters = dict(engine.metrics.snapshot_raw()["counters"])
    for k, v in compile_cache.stats().items():
        counters[f"compile_cache.{k}"] = float(v)
    return counters


def counter_deltas(before: Dict[str, float], after: Dict[str, float]
                   ) -> Dict[str, float]:
    return {k: after[k] - before.get(k, 0.0) for k in after}


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip: the TPU runtime counts the
    buffers (arguments, results: ``peak_bytes_in_use``) apart from what
    it reserves for the compiled programs' scratch
    (``peak_bytes_reserved``), and the chip's memory holds both while a
    program runs.  0 where the backend reports neither, as the CPU's."""
    import jax

    def peak(device):
        stats = device.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))

    return int(max(peak(d) for d in jax.local_devices()))


def free_program_state() -> None:
    from sparkdl_tpu.parallel.engine import clear_engine_jit_cache
    from sparkdl_tpu.transformers import named_image

    named_image.clear_model_caches()
    clear_engine_jit_cache()
    gc.collect()


# -- the window -------------------------------------------------------------

class Window(NamedTuple):
    start: float                   # on the benchmark's clock
    seconds: float                 # window start to the last job's end
    jobs: List[JobSpan]            # finished jobs
    kept: List[correct.KeptJob]    # the sample of result frames
    attempted: int                 # images
    failed: int                    # images of jobs that raised
    job_cpu_s: List[float]         # the process's CPU seconds, by job


def run_window(traffic, seconds: float, seed: int) -> Window:
    """One caller, jobs back to back, until ``seconds`` are up; the job
    in flight then is finished and counted."""
    sample = correct.JobSample(seed)
    jobs: List[JobSpan] = []
    job_cpu_s: List[float] = []
    attempted = failed = 0
    clock = time.perf_counter
    t0 = end = clock()
    k = 0
    while end - t0 < seconds:
        which = k % len(traffic.inputs)
        attempted += traffic.job_images
        start, cpu0 = clock(), time.process_time()
        try:
            result = traffic.run_job(traffic.inputs[which])
        except Exception as e:          # the job's images have failed
            failed += traffic.job_images
            print(f"job {k} raised {type(e).__name__}: {e}", file=sys.stderr)
            result = None
        end = clock()
        if result is not None:
            jobs.append(JobSpan(start, end, traffic.job_images, result.spans))
            job_cpu_s.append(time.process_time() - cpu0)
            sample.offer(correct.KeptJob(k, which, result.frame))
        k += 1
    return Window(t0, end - t0, jobs, sample.jobs(), attempted, failed,
                  job_cpu_s)


class DeviceTrace:
    """A profiler trace of the device alone, around the window, with the
    one marker program that ties the host's clock to the trace's
    (``trace_reduce``)."""

    def __init__(self, trace_dir: str):
        import jax
        import jax.numpy as jnp

        def bench_clock_marker(x):    # its name is trace_reduce.CLOCK_MARKER
            return x + 1

        self.dir = trace_dir
        self._marker = jax.jit(bench_clock_marker)
        self._one = jnp.zeros((8, 128), jnp.float32)
        self._marker(self._one).block_until_ready()      # compiled in set-up
        self.marker_host_s = 0.0

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        t0 = time.perf_counter()
        self._marker(self._one).block_until_ready()
        self.marker_host_s = (t0 + time.perf_counter()) / 2

    def stop_and_reduce(self, window: "Window", chips: int
                        ) -> trace_reduce.Reduced:
        import jax

        jax.profiler.stop_trace()
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(self.dir))
        offset = trace_reduce.clock_offset_ns(trace, self.marker_host_s * 1e9)
        spans = []
        for job in window.jobs:
            at = job.start
            for name, seconds in job.spans.items():
                spans.append((f"bench.{name}", at * 1e9 + offset,
                              seconds * 1e9))
                at += seconds
        t0 = window.start * 1e9 + offset
        return trace_reduce.reduce_trace(
            trace, chips, (t0, t0 + window.seconds * 1e9), spans)


# -- one run ----------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             process_start: float, platform: Optional[str] = "tpu",
             control: bool = False,
             peaks_path: Optional[str] = None,
             root: str = ROOT) -> Dict[str, Any]:
    """Run ``cell`` once and return the result line's object.
    ``platform=None`` takes whatever device JAX has (the tests' CPU).
    ``control=True`` is the control of ``correct``, never a measured
    run: the configuration's ``control`` either switches a
    lower-precision path of the program on (``env``) or puts
    the reference, computed one precision lower, in the program's place
    (``reference``); it has to come out not correct."""
    how = cell.config["control"] if control else {}
    scrubbed = scrub_environment({**program_environment(cell.config),
                                  **how.get("env", {})})
    if scrubbed:
        print(f"taken out of the environment: {scrubbed}", file=sys.stderr)
    from sparkdl_tpu.parallel import compile_cache

    check_stated_precision(cell.config, control and "env" in how)
    if compile_cache.configure_default() is None:
        raise BenchmarkError("the persistent compile cache did not come up")
    device = check_devices(cell.chips, platform)
    peak = load_peak(device["kind"], peaks_path)
    # found before anything is measured: an unknown name costs no chip time
    readers = {m["name"]: (find_reader if trace else find_end_to_end)(
        m["name"]) for m in (cell.per_layer if trace else cell.end_to_end)}
    generator = find_generator(cell.traffic["generator"])
    clock = time.perf_counter
    marks = [("imports_and_device", clock() - process_start)]

    def mark(name: str) -> None:
        marks.append((name, clock() - process_start - sum(
            s for _, s in marks)))

    work = os.path.join(root, WORK_DIR_NAME, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        weights = install_weights(cell.config, seed)
        mark("weights")
        traffic = generator.build(cell.traffic, cell.config, seed, work)
        mark("traffic")
        # one warm job over a small input of the traffic's kind: the
        # cell's one dispatch shape compiles (or loads from the cache)
        # and the host pools start
        warm = traffic.run_job(traffic.warm_input)
        if len(warm.frame) != traffic.warm_images:
            raise BenchmarkError(f"the warm job returned {len(warm.frame)} "
                                 f"rows for {traffic.warm_images} images")
        del warm
        engine = stage_engine(cell.config, traffic.batch_size)
        gc.collect()
        mark("warm_job")
        tracer = DeviceTrace(os.path.join(work, "trace")) if trace else None
        before = read_counters(engine)
        if tracer:
            tracer.start()
        setup_s = clock() - process_start
        window = run_window(traffic, seconds, seed)
        counters = counter_deltas(before, read_counters(engine))
        memory_peak = memory_peak_bytes()
        images_done = sum(j.images for j in window.jobs)
        reduced = (tracer.stop_and_reduce(window, cell.chips) if tracer
                   else None)
        obs = Observations(window_s=window.seconds, jobs=window.jobs,
                           counters=counters, config=cell.config, peak=peak,
                           chips=cell.chips, trace=reduced, setup_s=setup_s)

        # the check: program state freed first, then the plain reference
        del engine
        free_program_state()
        images = traffic.reference_images()
        reference = correct.reference_features(
            reference_of(cell.config), weights, images)
        stand_in = None
        if how.get("kind") == "reference":
            stand_in = correct.reference_features(
                reference_of(cell.config), weights, images,
                operands=how["operands"])
        checks, rows_compared = correct.compare(
            window.kept, traffic.row_sources, reference,
            cell.config["limits"], OUTPUT_COL, stand_in)
        # the counters that the per-layer metrics read are those of the
        # engine that did the window's work
        checks["engine_rows_off"] = {
            "value": abs(counters.get("engine.rows", 0.0) - images_done),
            "limit": 0}
        failed = window.failed + int(checks["rows_off"]["value"])
    finally:
        shutil.rmtree(work, ignore_errors=True)

    values = {name: reader.read(obs) for name, reader in readers.items()}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in (cell.per_layer if trace else cell.end_to_end)
               if values[m["name"]] is not None}
    result: Dict[str, Any] = {
        "correct": correct.passed(checks) and window.failed == 0,
        "attempted": window.attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "control": control,
        "jobs": len(window.jobs),
        "job_seconds": [round(j.end - j.start, 4) for j in window.jobs],
        "job_cpu_seconds": [round(s, 4) for s in window.job_cpu_s],
        "setup_seconds": {name: round(s, 3) for name, s in marks},
        "compiles_in_window": counters.get("compile_cache.hits", 0.0)
        + counters.get("compile_cache.misses", 0.0),
        "rows_compared": rows_compared,
        "traffic": traffic.facts,
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduced.device_ops],
            "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    result["checks"] = checks           # last in the line
    return result
