"""Benchmark suite: the five BASELINE.md configs plus the serving-stack
configs, one JSON line each.

Output contract: every line is a JSON object
    {"config": ..., "metric": ..., "value": N, "unit": ...,
     "vs_baseline": N|null, "baseline": {"ips": N, "basis": ...}|null,
     "env_bound": ...|null,
     "device": {"platform": ..., "kind": ..., "count": N}}
``device`` is the device of the PROCESS THAT MEASURED the line, as JAX
reports it there (``jax.devices()[0].platform``, ``.device_kind``,
``len(jax.devices())``).  The HEADLINE (config #1, device-resident
InceptionV3 featurization images/sec/chip) is printed LAST so a
parse-the-final-line driver keeps seeing one series.

Process model: one process for each chip.  A chip belongs to one
process at a time, so every config that needs the chip runs HERE, in
this process, one after another (configs 1-5, "serving", "fleet").  The
chip-free configs ("pipeline", "streaming", "cache", "ragged", "twin",
"headfanout" — a deterministic sleep stands in for their device, and
their metric names say so) run in children pinned to ``JAX_PLATFORMS=
cpu``, and all of them run FIRST: nothing this process does before its
first chip config initialises a JAX backend (configuring the compile
cache included), and no child is ever started once a chip config has
(``_run_json_subprocess`` refuses).

Failure policy: a measurement path that finds no accelerator FAILS — no
config reruns on the CPU under the same metric name.  A config that
raises prints a stamped ``{"config", "error", "device"}`` line to
stdout, its traceback to stderr, and the run goes on so the other
configs still report; ``main()`` then returns non-zero.

Measurement methodology:

* Device-resident configs use K model applications inside ONE jit
  program (``lax.scan`` over a stacked input) with a single scalar
  fetch, so one dispatch and one D2H fetch amortise over K steps; it
  slightly UNDERestimates steady state (no step overlap).
* End-to-end config #1 measures the code users actually run: JPEG bytes
  -> host decode+resize (native core when it built) -> streaming engine
  -> host feature vectors.

``vs_baseline``: the reference publishes no numbers (BASELINE.md); each
line carries its own denominator in a ``baseline`` object
(``{"ips": N, "basis": ...}``) — sourced for InceptionV3 (~875
images/sec/GPU, the era-typical single-V100 TF-1.x batch-inference rate
implied by the north-star's 8xV100 cluster) and FLOP-SCALED from it for
the other reference zoo models (XLA cost_analysis FLOPs, BASELINE.md
appendix).  Lines with no defensible denominator (rows/sec, tuning
throughput, beyond-reference models) report vs_baseline null.
``env_bound`` marks the chip-free configs' synthetic device.

Env knobs: SPARKDL_BENCH_CONFIGS (comma list, default
"1,1e2e,2,3,4,5,serving,fleet,pipeline,streaming,cache,ragged,twin,
headfanout"), SPARKDL_BENCH_BATCH (128), SPARKDL_BENCH_STEPS (20),
SPARKDL_BENCH_DTYPE (bfloat16|float32), SPARKDL_BENCH_SERVING_REQUESTS
(512), SPARKDL_BENCH_TRACE (default 1: per-config span tracing; each
line carries ``metrics_snapshot`` + ``trace_artifact``),
SPARKDL_BENCH_TRACE_DIR (artifact dir, default artifacts/bench_traces),
SPARKDL_BENCH_ARTIFACT (crash-safe JSONL rider, default
artifacts/bench_lines.jsonl: every printed line is fsync-appended so a
killed run still leaves valid JSONL for every completed config),
SPARKDL_FAULTS (fault injection; every line is stamped ``faults:
none|<spec>`` so chaos runs can never pass as clean perf numbers).  The
compile cache lives where ``parallel.compile_cache.configure_default``
puts it: ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
``.compile_cache/`` inside the checkout.  Per-config lines that drive
the streaming engine also carry the pipeline stage-stall ledger
(``pipeline_stages``) so host-vs-device boundedness is visible per run.
"""

from __future__ import annotations

import io
import json
import os
import sys
import time
import traceback

import numpy as np

from sparkdl_tpu.parallel.mesh import device_stamp
from sparkdl_tpu.utils.metrics import Metrics

V100_BASELINE_IPS = 875.0

# XLA cost_analysis FLOPs per image (bf16, fused preprocess, this repo's
# models at their native input sizes) — the scaling basis for per-model
# V100 denominators; derivation in BASELINE.md "Appendix: per-model
# denominators".  Pinned FALLBACK values only: the live numbers come
# from the committed program lockfile below (graftcheck measures the
# exact programs this bench runs), and tests/test_graftcheck.py fails
# when the two disagree beyond tolerance — so a program change that
# moves real FLOPs cannot silently keep a stale denominator.
_ZOO_GFLOP_FALLBACK = {
    "InceptionV3": 10.997,  # 299x299
    "ResNet50": 7.522,      # 224x224
    "VGG16": 29.972,        # 224x224
    "VGG19": 37.951,        # 224x224
    "Xception": 16.799,     # 299x299
}


def _zoo_gflop_per_img():
    """Per-model GF/img: PROGRAMS.lock.json (the audited featurize
    programs) where present, pinned fallback otherwise.  Restricted to
    the reference zoo — beyond-reference models keep vs_baseline null
    even though the lockfile audits them too."""
    from sparkdl_tpu.analysis.program.lockfile import zoo_gflop_per_img

    locked = zoo_gflop_per_img()
    return {model: locked.get(model, fallback)
            for model, fallback in _ZOO_GFLOP_FALLBACK.items()}


ZOO_GFLOP_PER_IMG = _zoo_gflop_per_img()


def v100_baseline(model):
    """(denominator_ips, basis) for a reference zoo model; (None, None)
    when no defensible number exists (beyond-reference models)."""
    if model == "InceptionV3":
        return V100_BASELINE_IPS, (
            "sourced: era-typical single-V100 TF-1.x InceptionV3 batch "
            "inference (~875 img/s)")
    g = ZOO_GFLOP_PER_IMG.get(model)
    if g is None:
        return None, None
    g_inc = ZOO_GFLOP_PER_IMG["InceptionV3"]
    ips = V100_BASELINE_IPS * g_inc / g
    return ips, (
        f"flop-scaled from sourced InceptionV3 875 img/s x "
        f"({g_inc:.3f} / {g:.3f} GF/img, XLA cost_analysis); "
        f"conservative for depthwise models (era cuDNN ran them below "
        f"FLOP parity)"
        if model == "Xception" else
        f"flop-scaled from sourced InceptionV3 875 img/s x "
        f"({g_inc:.3f} / {g:.3f} GF/img, XLA cost_analysis)")


BATCH = int(os.environ.get("SPARKDL_BENCH_BATCH", "128"))
STEPS = int(os.environ.get("SPARKDL_BENCH_STEPS", "20"))
DTYPE = os.environ.get("SPARKDL_BENCH_DTYPE", "bfloat16")

# Per-config observability (sparkdl_tpu.obs): main() gives every config
# a FRESH Metrics registry — counters/timings from earlier configs in
# the same run must never leak into a later config's JSON line — plus a
# per-config span-trace artifact (Chrome trace JSON under TRACE_DIR;
# subprocess configs inherit SPARKDL_TRACE=<subdir> and flush their
# own).  emit() then attaches BOTH to the line: ``metrics_snapshot``
# (stable schema, obs.export.metrics_snapshot) and ``trace_artifact``
# (the path), so driver records carry per-stage breakdowns, not just
# headline throughput.  SPARKDL_BENCH_TRACE=0 disables the tracing half
# (the fresh per-config registry always applies).
BENCH_TRACE = os.environ.get("SPARKDL_BENCH_TRACE", "1").strip().lower() \
    not in ("0", "false", "off", "no")
TRACE_DIR = os.environ.get(
    "SPARKDL_BENCH_TRACE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "artifacts", "bench_traces"))

_CONFIG_OBS = {"metrics": None, "trace_artifact": None}


def _config_metrics() -> Metrics:
    """The per-config registry main() provisioned, or a private one when
    a bench fn runs outside main() (unit tests, direct calls)."""
    m = _CONFIG_OBS.get("metrics")
    return m if m is not None else Metrics()


def _begin_config_obs(key: str) -> None:
    _CONFIG_OBS["metrics"] = Metrics()
    _CONFIG_OBS["trace_artifact"] = None
    if not BENCH_TRACE:
        return
    from sparkdl_tpu import obs

    if key in _CHIPLESS_CONFIGS:
        # subprocess configs trace themselves: the child sees
        # SPARKDL_TRACE=<subdir> and atexit-flushes trace_<pid>.json.
        # Pre-create the dir so the advertised path exists even if the
        # child records nothing.
        path = os.path.join(TRACE_DIR, key)
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            path = None  # read-only checkout: don't advertise a path
        _CONFIG_OBS["trace_artifact"] = path
    else:
        path = os.path.join(TRACE_DIR, f"trace_{key}.json")
        try:
            os.makedirs(TRACE_DIR, exist_ok=True)
        except OSError:
            path = None  # read-only checkout: don't advertise a path
        _CONFIG_OBS["trace_artifact"] = path
    obs.configure(enabled=True)  # fresh tracer => empty ring per config


def _end_config_obs(key: str) -> None:
    m = _CONFIG_OBS.get("metrics")
    _CONFIG_OBS["metrics"] = None
    path = _CONFIG_OBS.get("trace_artifact")
    _CONFIG_OBS["trace_artifact"] = None
    if not BENCH_TRACE:
        return
    try:
        from sparkdl_tpu import obs

        if path and path.endswith(".json"):
            # ALWAYS write the advertised artifact — an empty
            # traceEvents list is still a valid, openable Chrome trace,
            # so a driver following the line's path never 404s
            os.makedirs(os.path.dirname(path), exist_ok=True)
            obs.write_chrome_trace(path, obs.get_tracer().snapshot())
        if m is not None and any(m.snapshot_raw().values()):
            os.makedirs(TRACE_DIR, exist_ok=True)
            obs.write_metrics_jsonl(
                os.path.join(TRACE_DIR, "metrics.jsonl"), m,
                extra={"config": key})
    except OSError:
        pass  # a read-only checkout must not fail the bench


_LINES = {}
_LAST_PRINTED = [None]

# Crash-safe driver artifact (ISSUE 4): the driver's stdout capture is
# gone when the process is killed mid-run, so every printed line is ALSO
# appended to an on-disk JSONL artifact with an fsync per record
# (utils.jsonl.CrashSafeJsonlWriter): a SIGKILL at any instant leaves
# valid JSONL for every config that completed.
# ``SPARKDL_BENCH_ARTIFACT`` overrides the path; a read-only checkout
# disables the writer rather than failing the bench.
from sparkdl_tpu.utils.jsonl import CrashSafeJsonlWriter

ARTIFACT_PATH = os.environ.get(
    "SPARKDL_BENCH_ARTIFACT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "artifacts", "bench_lines.jsonl"))

_ARTIFACT = CrashSafeJsonlWriter(ARTIFACT_PATH)


def _print_line(line):
    _LAST_PRINTED[0] = line
    print(line, flush=True)
    _ARTIFACT.write_line(line)


def emit(config, metric, value, unit, baseline_model=None, env_bound=None,
         extra=None):
    """One self-describing JSON line.  ``baseline_model`` resolves the
    per-model denominator (vs_baseline = value / denominator); lines with
    no defensible denominator emit vs_baseline null.  FLOP-scaled lines
    also carry ``vs_sourced_anchor`` (value / the single sourced 875
    anchor) so the denominator-method sensitivity is visible in the JSON
    itself, not only in BASELINE.md prose.  ``env_bound`` names what
    stands in for the device in a chip-free config.  ``extra`` merges
    additional self-describing fields into the record (e.g. the serving
    config's p50/p99 latency) without touching the core keys; a config
    measured in a child passes the CHILD's ``device`` stamp through it,
    every other line is stamped with this process's device."""
    denom, basis = v100_baseline(baseline_model) if baseline_model else (
        None, None)
    from sparkdl_tpu.faults import current_spec

    rec = {
        "config": config, "metric": metric, "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": (round(float(value) / denom, 3)
                        if denom is not None else None),
        "baseline": ({"ips": round(denom, 1), "basis": basis}
                     if denom is not None else None),
        "env_bound": env_bound,
        # chaos stamp: a bench line produced under fault injection must
        # never be mistaken for a clean perf number — the active plan's
        # canonical SPARKDL_FAULTS spec, or "none"
        "faults": current_spec() or "none",
    }
    if basis is not None and basis.startswith("flop-scaled"):
        rec["vs_sourced_anchor"] = round(float(value) / V100_BASELINE_IPS, 3)
    for k, v in (extra or {}).items():
        if k in rec:  # extra merges, never shadows, the contract keys
            raise ValueError(f"emit extra field {k!r} collides with a "
                             f"core contract key")
        rec[k] = v
    if "device" not in rec:
        rec["device"] = device_stamp()
    # per-config observability riders (main() provisions them; absent
    # when a bench fn runs standalone): the config's own Metrics
    # snapshot and the span-trace artifact path.  ``extra`` wins — a
    # config that measured its metrics in a subprocess (serving) passes
    # the child's snapshot through extra and the parent's empty
    # registry must not shadow it.
    m = _CONFIG_OBS.get("metrics")
    if m is not None and "metrics_snapshot" not in rec:
        from sparkdl_tpu.obs.export import metrics_snapshot

        snap = metrics_snapshot(m)
        if any(snap.values()):
            rec["metrics_snapshot"] = snap
    # the SLO rider (ISSUE 9): one-shot whole-run burn-rate rating of
    # whatever default objectives the config's series support — the
    # "did the run meet its objectives?" verdict next to the raw
    # numbers.  extra wins for subprocess configs (the child's registry
    # held the traffic; see metrics_snapshot above).
    if m is not None and "slo" not in rec:
        from sparkdl_tpu.obs.slo import slo_snapshot

        slo = slo_snapshot(m)
        if slo is not None:
            rec["slo"] = slo
    # the pad-overhead rider (ISSUE 11, the prep step ROADMAP item 2's
    # ragged batching asks for): the GC004 pad-waste bounds from the
    # committed PROGRAMS.lock.json (analytic, per zoo model) next to
    # the MEASURED pad-row fraction from whatever metrics snapshot this
    # line carries (parent registry or a subprocess child's — the
    # engine.rows/engine.pad_rows ledger and the serving fill ratio),
    # so every line shows what pad-to-bucket tax the run actually paid
    # against what the lockfile says the bucket plan can cost.
    if "pad_overhead" not in rec:
        pad = _pad_overhead_rider(rec.get("metrics_snapshot"))
        if pad is not None:
            rec["pad_overhead"] = pad
    # the HBM/sharding rider (ISSUE 14), next to pad_overhead: the
    # committed lockfile's replicated-param byte budgets (GC005's
    # analytic view — what a chip WOULD pay per model fully replicated,
    # and what the audited tensor-parallel programs pay per chip)
    # beside the LIVE engine's mesh shape and measured per-chip param
    # bytes (the engine.mesh_*/engine.*_param_bytes gauges), so every
    # line shows the one-weight-copy-per-chip cost against what the
    # sharding policy actually placed.
    if "sharding" not in rec:
        shard = _sharding_rider(rec.get("metrics_snapshot"))
        if shard is not None:
            rec["sharding"] = shard
    # the cost rider (ISSUE 18), next to the riders above: per-tenant
    # spend breakdown + the regression sentinel's verdict from the
    # process-default CostLedger (SPARKDL_COST gate — absent when cost
    # attribution is off; extra wins for subprocess configs whose
    # ledger lived in the child).
    if "cost" not in rec:
        from sparkdl_tpu.obs.cost import cost_rider, get_default

        cost = cost_rider(get_default())
        if cost is not None:
            rec["cost"] = cost
    ta = _CONFIG_OBS.get("trace_artifact")
    if ta is not None and "trace_artifact" not in rec:
        rec["trace_artifact"] = ta
    line = json.dumps(rec)
    _LINES[config] = line
    _print_line(line)


_PAD_LOCK_CACHE: list = []


def _lockfile_pad_budgets():
    """GC004's pad-waste view of the committed lockfile, computed once
    per process: for each zoo model, the audited serving bucket set and
    the analytic worst-case pad fractions — ``interior_worst_frac`` (a
    request count one past bucket ``i`` pads to bucket ``i+1``:
    ``(b_{i+1} - b_i - 1) / b_{i+1}``) and ``floor_frac`` (a 1-row
    request padded to the smallest bucket).  Import-light: reads the
    lockfile with the same stdlib-json loader bench's FLOP denominators
    use; missing/corrupt lockfile degrades to ``{}``."""
    if _PAD_LOCK_CACHE:
        return _PAD_LOCK_CACHE[0]
    budgets = {}
    try:
        from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                           pad_worst_fracs,
                                                           read_lockfile)

        doc = read_lockfile(DEFAULT_LOCKFILE)
        groups = {}
        for name, rec in doc.get("programs", {}).items():
            model, bucket = rec.get("model"), rec.get("bucket")
            if (name.startswith("zoo/") and rec.get("kind") == "dispatch"
                    and model and bucket):
                groups.setdefault(model, set()).add(int(bucket))
        for model, buckets in sorted(groups.items()):
            bs = sorted(buckets)
            # the ONE GC004 formula spelling (shared with
            # analysis.program.audit.pad_waste_audit)
            interior, floor = pad_worst_fracs(bs)
            budgets[model] = {
                "buckets": bs,
                "interior_worst_frac": round(interior, 4),
                "floor_frac": round(floor, 4),
            }
    except (OSError, ValueError, KeyError):
        budgets = {}
    _PAD_LOCK_CACHE.append(budgets)
    return budgets


_SHARD_LOCK_CACHE: list = []


def _lockfile_sharding_budgets():
    """GC005's HBM view of the committed lockfile, computed once per
    process: per audited program group, the replicated-param bytes a
    chip pays under that program's layout, the per-chip bytes of its
    tensor-parallel-sharded leaves, and the mesh axes it was audited
    on.  Zoo models are folded to their largest-bucket dispatch record
    (one entry per model); the ``serving/wide_dense`` programs — the
    synthetic budget-busters ISSUE 14 ships sharded — ride whole, with
    the sharded-vs-replicated byte ratio that proves the HBM claim.
    Import-light (stdlib json, same loader as the FLOP denominators);
    missing/corrupt lockfile degrades to ``{}``."""
    if _SHARD_LOCK_CACHE:
        return _SHARD_LOCK_CACHE[0]
    budgets = {}
    try:
        from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                           read_lockfile)

        doc = read_lockfile(DEFAULT_LOCKFILE)
        zoo_best = {}
        sharded = {}
        for name, rec in doc.get("programs", {}).items():
            summary = rec.get("sharding_summary") or {}
            if not summary:
                continue
            model, rows = rec.get("model"), rec.get("rows") or 0
            if name.startswith("zoo/") and model:
                prev = zoo_best.get(model)
                if prev is None or rows > prev[0]:
                    zoo_best[model] = (rows, summary, rec.get("mesh_axes"))
            shards = summary.get("param_shards")
            if shards and shards.get("sharded_leaves"):
                repl = int(summary.get("replicated_bytes", 0))
                shard_bytes = int(shards["sharded_bytes_per_chip"])
                per_chip = repl + shard_bytes
                # replicated-equivalent total: the sharded leaves split
                # on the model axis (the default-rule layout), so the
                # one-copy-per-chip cost is their per-chip bytes x the
                # model axis size
                model_axis = int((rec.get("mesh_axes") or {}).get(
                    "model", 1))
                full = repl + shard_bytes * model_axis
                sharded[name] = {
                    "mesh_axes": rec.get("mesh_axes"),
                    "replicated_param_bytes_per_chip": full,
                    "sharded_param_bytes_per_chip": per_chip,
                    "sharded_vs_replicated_ratio": (
                        round(per_chip / full, 4) if full else 1.0),
                }
        models = {}
        for model, (rows, summary, axes) in sorted(zoo_best.items()):
            models[model] = {
                "replicated_param_bytes_per_chip": int(
                    summary.get("replicated_bytes", 0)),
                "mesh_axes": axes,
            }
        if models or sharded:
            budgets = {"zoo": models, "sharded_programs": sharded}
    except (OSError, ValueError, KeyError):
        budgets = {}
    _SHARD_LOCK_CACHE.append(budgets)
    return budgets


def _sharding_rider(snapshot):
    """The per-line ``sharding`` rider: lockfile HBM budgets + whatever
    the line's metrics snapshot measured from live engines (the
    ``engine.mesh_data_axis``/``engine.mesh_model_axis`` and
    ``engine.replicated_param_bytes``/``engine.param_bytes_per_chip``
    gauges every InferenceEngine sets at construction).  None only when
    BOTH halves are empty."""
    lock = _lockfile_sharding_budgets()
    measured = {}
    gauges = (snapshot or {}).get("gauges", {})
    if "engine.mesh_model_axis" in gauges:
        replicated = int(gauges.get("engine.replicated_param_bytes", 0.0))
        per_chip = int(gauges.get("engine.param_bytes_per_chip", 0.0))
        measured = {
            "mesh_shape": {
                "data": int(gauges.get("engine.mesh_data_axis", 1.0)),
                "model": int(gauges.get("engine.mesh_model_axis", 1.0)),
            },
            "replicated_param_bytes_per_chip": replicated,
            "sharded_param_bytes_per_chip": per_chip,
        }
        if replicated:
            measured["sharded_vs_replicated_ratio"] = round(
                per_chip / replicated, 4)
    if not lock and not measured:
        return None
    return {"lockfile": lock or None, "measured": measured or None}


def _pad_overhead_rider(snapshot):
    """The per-line ``pad_overhead`` rider: lockfile analytic bounds +
    whatever pad accounting the line's metrics snapshot measured (the
    engine's rows/pad_rows ledger; the serving batch fill ratio when
    the config ran the online path).  None only when BOTH halves are
    empty (no lockfile and no measurements)."""
    lock = _lockfile_pad_budgets()
    measured = {}
    counters = (snapshot or {}).get("counters", {})
    rows = float(counters.get("engine.rows", 0.0))
    pad_rows = float(counters.get("engine.pad_rows", 0.0))
    if rows + pad_rows > 0:
        measured["rows"] = int(rows)
        measured["pad_rows"] = int(pad_rows)
        measured["pad_row_frac"] = round(pad_rows / (rows + pad_rows), 4)
    fill = (snapshot or {}).get("histograms", {}).get(
        "serving.batch_fill_ratio")
    if fill and fill.get("count"):
        measured["serving_fill_mean"] = fill["mean"]
        measured["serving_pad_frac"] = round(1.0 - fill["mean"], 4)
    if not lock and not measured:
        return None
    return {"lockfile": lock, "measured": measured or None}


class NoAcceleratorError(RuntimeError):
    """A config that measures the chip found none."""


#: Set once a chip config has started in this process: from then on it
#: holds (or has tried to take) the chip, and starts no child.  Nothing
#: before that point may initialise a JAX backend.
_CHIP_CONFIGS_STARTED = [False]


def require_accelerator():
    """Gate of every chip config: a device metric is never measured on,
    or printed from, the CPU backend.  ``device_stamp`` initialises the
    backend, which is why main() starts every chip-free child first and
    why this closes the door on children before it asks."""
    _CHIP_CONFIGS_STARTED[0] = True
    stamp = device_stamp()
    if stamp["platform"] == "cpu":
        raise NoAcceleratorError(
            f"JAX reports {stamp['count']} {stamp['kind']!r} device(s) on "
            f"platform 'cpu': no accelerator, so no device metric")
    return stamp


#: appended to every child's code: stamp the child's own device and
#: print its result dict ``out`` as the last stdout line
_CHILD_REPORT = r"""
from sparkdl_tpu.parallel.mesh import device_stamp
out["device"] = device_stamp()
print(json.dumps(out))
"""


def _run_json_subprocess(code: str, timeout_s: int, env=None):
    """Run chip-free ``code`` (which leaves its result in a dict named
    ``out``) in a child Python pinned to the CPU backend; return the
    dict, stamped with the child's device.

    Refuses once a chip config has started in this process: main() runs
    every child first.

    Popen + bounded reap, not subprocess.run: run()'s post-timeout
    kill() is followed by an UNBOUNDED wait(), which blocks forever if
    the child is stuck in an uninterruptible kernel sleep.  A child
    that ignores SIGKILL for 10s is abandoned (own session, reaped by
    init eventually) and the timeout propagates."""
    import subprocess

    if _CHIP_CONFIGS_STARTED[0]:
        raise RuntimeError(
            "bench child refused: a chip config has started in this "
            "process, which holds the chip (chip-free configs run first)")
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    # the child imports the package beside this file, whatever the cwd
    proc = subprocess.Popen([sys.executable, "-c", code + _CHILD_REPORT],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env,
                            cwd=os.path.dirname(os.path.abspath(__file__)),
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            pass  # stuck in D state: abandon, don't hang the bench
        raise
    if proc.returncode != 0:
        tail = (err or "").strip().splitlines()
        raise RuntimeError(
            f"bench subprocess failed (rc={proc.returncode}): "
            f"{tail[-1] if tail else '<no stderr>'}")
    lines = (out or "").strip().splitlines()
    if not lines:
        raise RuntimeError("bench subprocess produced no output")
    return json.loads(lines[-1])


def _compute_dtype():
    import jax.numpy as jnp

    return jnp.bfloat16 if DTYPE == "bfloat16" else jnp.float32


def _zoo_fn(name, featurize):
    """(fn, variables, (h, w)) for a zoo model with fused preprocess."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import get_model_spec

    spec = get_model_spec(name)
    module = spec.build()
    variables = spec.init_variables()
    pre = spec.preprocess
    cdt = _compute_dtype()

    def fn(v, x):
        # outputs stay in compute dtype: D2H consumers cast host-side
        # (engine output_host_dtype) — bf16->f32 is exact, half the bytes
        xf = pre(x).astype(cdt)
        return module.apply(v, xf, train=False, features=featurize)

    return fn, variables, spec.input_size


def measure_scan(fn, variables, h, w, batch, steps, distinct=4,
                 metrics=None):
    """images/sec/chip via steps-in-one-program.

    The scan iterates ``steps`` times over a small ROTATING corpus of
    ``distinct`` device-resident batches (index ``t % distinct``), so the
    one dispatch and the one scalar fetch amortize over many steps
    without the host corpus / H2D upload growing with ``steps``.  The
    conv compute cannot be CSE'd across iterations: the operand differs
    per step and the loop body executes per iteration."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkdl_tpu.parallel.engine import InferenceEngine

    eng = InferenceEngine(fn, variables, device_batch_size=batch,
                          compute_dtype=_compute_dtype())
    rng = np.random.default_rng(0)
    distinct = min(distinct, steps)
    big = (rng.random((distinct, eng.device_batch_size, h, w, 3)) * 255
           ).astype(np.uint8)
    sh = NamedSharding(eng.mesh, P(None, "data"))
    xd = jax.device_put(big, sh)

    def scan_fn(v, xs):
        def body(c, t):
            x = jax.lax.dynamic_index_in_dim(xs, t % distinct, 0,
                                             keepdims=False)
            return c + jnp.mean(fn(v, x)), None

        return jax.lax.scan(body, jnp.float32(0),
                            jnp.arange(steps, dtype=jnp.int32))[0]

    # no donation: the same stacked input is re-dispatched (warm + timed)
    g = jax.jit(scan_fn, in_shardings=(eng._replicated, sh),
                donate_argnums=())
    float(g(eng.variables, xd))  # warm: compile + one run
    t0 = time.perf_counter()
    float(g(eng.variables, xd))  # one dispatch, one scalar fetch
    elapsed = time.perf_counter() - t0
    if metrics is not None:  # the numbers behind the headline, exported
        metrics.record_time("bench.scan", elapsed)
        metrics.incr("bench.images", steps * eng.device_batch_size)
    return steps * eng.device_batch_size / elapsed / eng.num_devices


def _jpeg_corpus(n, height=375, width=500):
    """n distinct in-memory JPEGs (flowers-like sizes)."""
    from PIL import Image

    rng = np.random.default_rng(7)
    blobs = []
    base = (rng.random((height, width, 3)) * 255).astype(np.uint8)
    for i in range(n):
        arr = base.copy()
        arr[:8, :8, 0] = i % 251
        buf = io.BytesIO()
        Image.fromarray(arr, "RGB").save(buf, format="JPEG", quality=90)
        blobs.append(buf.getvalue())
    return blobs


def bench_config1_device():
    # 2x steps: the dispatch + scalar fetch cost is fixed regardless of
    # K — more steps = closer to steady state.
    fn, variables, (h, w) = _zoo_fn("InceptionV3", featurize=True)
    ips = measure_scan(fn, variables, h, w, BATCH, STEPS * 2,
                       metrics=_config_metrics())
    emit("1", "InceptionV3 ImageNet featurization throughput", ips,
         "images/sec/chip", baseline_model="InceptionV3")


def bench_config1_e2e():
    """The user path: JPEG bytes -> decode+resize -> streaming featurize."""
    from sparkdl_tpu.image.io import decodeResizeBatch
    from sparkdl_tpu.parallel.engine import InferenceEngine
    from sparkdl_tpu.parallel.pipeline import pipeline_stage_summary

    fn, variables, (h, w) = _zoo_fn("InceptionV3", featurize=True)
    eng = InferenceEngine(fn, variables, device_batch_size=BATCH,
                          compute_dtype=_compute_dtype(),
                          output_host_dtype=np.float32,
                          metrics=_config_metrics())
    n = int(os.environ.get("SPARKDL_BENCH_E2E_IMAGES", "384"))
    blobs = _jpeg_corpus(n)

    def chunks():
        for off in range(0, n, eng.device_batch_size):
            batch, _ok = decodeResizeBatch(
                blobs[off:off + eng.device_batch_size], h, w)
            yield batch

    # warm the compile so e2e measures steady state, not compilation
    w0, _ = decodeResizeBatch(blobs[:eng.device_batch_size], h, w)
    list(eng.map_batches([w0]))
    # the pipelined engine's prepare thread pulls the decode iterator
    t0 = time.perf_counter()
    outs = list(eng.map_batches(chunks()))
    elapsed = time.perf_counter() - t0
    rows = sum(o.shape[0] for o in outs)
    assert rows == n
    ips = rows / elapsed / eng.num_devices
    emit("1-e2e", "InceptionV3 featurization from JPEG bytes (host decode)",
         ips, "images/sec/chip", baseline_model="InceptionV3",
         extra={"pipeline_stages": pipeline_stage_summary(eng.metrics)})


def bench_config2():
    # MobileNetV2 is the beyond-reference zoo extension (PERF.md fleet);
    # it has no era denominator -> vs_baseline null.  Distinct config
    # keys per model (ADVICE r3): a driver keyed by config sees all five.
    for name in ("ResNet50", "Xception", "VGG16", "VGG19", "MobileNetV2"):
        fn, variables, (h, w) = _zoo_fn(name, featurize=False)
        steps = STEPS * 2  # amortize the fixed dispatch + fetch cost
        ips = measure_scan(fn, variables, h, w, BATCH, steps,
                           metrics=_config_metrics())
        emit(f"2-{name}", f"DeepImagePredictor {name} batch inference", ips,
             "images/sec/chip", baseline_model=name)


def bench_config3():
    """KerasTransformer on a user Keras model (MLP over vector rows)."""
    import keras
    from keras import layers

    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.transformers.tensor import KerasTransformer

    dim, n = 784, 16384
    model = keras.Sequential([
        layers.Input((dim,)),
        layers.Dense(512, activation="relu"),
        layers.Dense(256, activation="relu"),
        layers.Dense(10, activation="softmax"),
    ])
    path = "/tmp/sparkdl_bench_mlp.keras"
    model.save(path)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    df = DataFrame({"features": [row for row in x]})
    t = KerasTransformer(inputCol="features", outputCol="preds",
                         modelFile=path, batchSize=8192)
    t.transform(df)  # warm: conversion + compile
    t0 = time.perf_counter()
    out = t.transform(df)
    elapsed = time.perf_counter() - t0
    assert len(out) == n
    m = _config_metrics()
    m.record_time("bench.transform", elapsed)
    m.incr("bench.rows", n)
    emit("3", "KerasTransformer user-MLP rows/sec", n / elapsed, "rows/sec")


def bench_config4():
    """Registered image UDF scoring an image-struct column."""
    import pyarrow as pa

    import jax.numpy as jnp

    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image.schema import imageArrayToStruct, imageSchema
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.udf.registry import register_image_udf, udf_registry

    spec = get_model_spec("InceptionV3")
    module = spec.build()
    variables = spec.init_variables()
    pre = spec.preprocess
    cdt = _compute_dtype()

    def fn(v, x):  # x float32 [0,255] RGB from the UDF converter stage
        xf = pre(x.astype(jnp.uint8)).astype(cdt)
        # probs stay bf16 on the wire (half the D2H bytes); the UDF
        # layer casts host-side
        return module.apply(v, xf, train=False, features=False)

    mf = ModelFunction(fn=fn, variables=variables)
    h, w = spec.input_size
    register_image_udf("bench_inception_udf", mf, input_size=(h, w),
                       batch_size=BATCH)
    n = int(os.environ.get("SPARKDL_BENCH_UDF_IMAGES", "128"))
    rng = np.random.default_rng(5)
    structs = [imageArrayToStruct(
        (rng.random((h, w, 3)) * 255).astype(np.uint8), origin=f"r{i}")
        for i in range(n)]
    df = DataFrame({"image": pa.array(structs, type=imageSchema)})
    udf_registry.apply("bench_inception_udf", df, "image", "probs")  # warm
    t0 = time.perf_counter()
    out = udf_registry.apply("bench_inception_udf", df, "image", "probs")
    elapsed = time.perf_counter() - t0
    assert len(out) == n
    m = _config_metrics()
    m.record_time("bench.udf_apply", elapsed)
    m.incr("bench.images", n)
    emit("4", "registerKerasImageUDF-style image UDF scoring", n / elapsed,
         "images/sec", baseline_model="InceptionV3")


def bench_config5():
    """Estimator hyperparameter fan-out: fitMultiple over a param grid."""
    import tempfile

    import jax.numpy as jnp
    from PIL import Image

    from sparkdl_tpu.estimators import ImageFileEstimator
    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction

    rng = np.random.default_rng(11)
    d = tempfile.mkdtemp(prefix="sparkdl_bench_est_")
    n, hw = 256, 32
    paths = []
    for i in range(n):
        p = os.path.join(d, f"img_{i:04d}.jpg")
        Image.fromarray(
            (rng.random((hw, hw, 3)) * 255).astype(np.uint8), "RGB"
        ).save(p, format="JPEG")
        paths.append(p)
    labels = [[1.0, 0.0] if i % 2 == 0 else [0.0, 1.0] for i in range(n)]
    df = DataFrame({"uri": paths, "label": labels})

    def loader(uri):
        img = Image.open(uri).convert("RGB")
        return np.asarray(img, dtype=np.float32) / 255.0

    w0 = rng.normal(0, 0.01, (hw * hw * 3, 2)).astype(np.float32)

    def fn(v, x):
        logits = jnp.asarray(x).reshape(x.shape[0], -1) @ v["w"]
        return jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1,
                                         keepdims=True)

    est = ImageFileEstimator(
        inputCol="uri", outputCol="preds", labelCol="label",
        modelFunction=ModelFunction(fn=fn, variables={"w": w0}),
        imageLoader=loader, optimizer="sgd",
        loss="categorical_crossentropy",
        # steps_per_execution: k steps per compiled dispatch (identical
        # math, parity-tested) — one launch + one loss fetch per k
        fitParams={"epochs": 2, "steps_per_execution": 4},
        batchSize=64)
    maps = [{est.fitParams: {"epochs": 2, "steps_per_execution": 4}},
            {est.fitParams: {"epochs": 2, "steps_per_execution": 4},
             est.batchSize: 128}]
    est.fit(df, [maps[0]])  # warm: decode + compile
    t0 = time.perf_counter()
    models = est.fit(df, maps)
    elapsed = time.perf_counter() - t0
    assert len(models) == len(maps)
    epochs_total = 2 * len(maps)
    m = _config_metrics()
    m.record_time("bench.fit", elapsed)
    m.incr("bench.train_images", n * epochs_total)
    emit("5", "ImageFileEstimator param-grid tuning throughput",
         n * epochs_total / elapsed, "train-images/sec")


def _toy_image_fn(v, x):
    """The serving/fleet configs' synthetic image model: 32x32x3 uint8
    -> 64 tanh features (one dense layer)."""
    import jax.numpy as jnp

    xf = jnp.asarray(x, jnp.float32).reshape((x.shape[0], -1)) / 255.0
    return jnp.tanh(xf @ v["w"])


def bench_serving():
    """Online serving end to end (admission -> dynamic micro-batching ->
    bucketed engine dispatch -> future demux) on the synthetic image
    model: dynamic-batching throughput + p50/p99 latency.  Runs in THIS
    process, on the accelerator the other chip configs use."""
    from sparkdl_tpu.serving import Server

    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.05, (32 * 32 * 3, 64)).astype(np.float32)
    n = int(os.environ.get("SPARKDL_BENCH_SERVING_REQUESTS", "512"))
    x = (rng.random((n, 32, 32, 3)) * 255).astype(np.uint8)
    m = _config_metrics()
    with Server(_toy_image_fn, {"w": w}, max_batch_size=64, max_wait_ms=2.0,
                max_queue=n + 64, metrics=m) as srv:
        srv.warmup(x[0])  # compile every bucket before timing
        t0 = time.perf_counter()
        futs = [srv.submit(x[i]) for i in range(n)]
        for f in futs:
            f.result()
        elapsed = time.perf_counter() - t0
    fill = m.histograms.get("serving.batch_fill_ratio", [])
    emit("serving",
         "async dynamic-batching serving throughput (synthetic model)",
         n / elapsed, "images/sec",
         extra={
             "p50_ms": round(
                 1e3 * m.percentile("serving.request_latency", 50), 2),
             "p99_ms": round(
                 1e3 * m.percentile("serving.request_latency", 99), 2),
             "batch_fill_ratio": (round(sum(fill) / len(fill), 3)
                                  if fill else None),
             "num_requests": n,
         })


def bench_fleet():
    """Multi-tenant fleet front door end to end (routing -> tenant
    admission -> per-version server -> demux): mixed-tenant throughput +
    p50/p99 with a zero-downtime version swap mid-run; the line also
    records the swap's no-recompile verdict.  Runs in THIS process, like
    "serving"."""
    from sparkdl_tpu.serving import Fleet, TenantQuota
    from sparkdl_tpu.serving.errors import (QueueFullError,
                                            ServiceUnavailableError)

    rng = np.random.default_rng(0)
    w1 = {"w": rng.normal(0, 0.05, (32 * 32 * 3, 64)).astype(np.float32)}
    w2 = {"w": rng.normal(0, 0.05, (32 * 32 * 3, 64)).astype(np.float32)}
    n = int(os.environ.get("SPARKDL_BENCH_FLEET_REQUESTS", "512"))
    x = (rng.random((n, 32, 32, 3)) * 255).astype(np.uint8)
    tenants = ("gold", "silver", "bronze")
    m = _config_metrics()
    fleet = Fleet(max_batch_size=64, max_wait_ms=2.0, max_queue=n + 64,
                  quotas={"bronze": TenantQuota(rate_per_s=1e9)}, metrics=m)
    try:
        fleet.add_model("m", _toy_image_fn, w1, warm_example=x[0])
        fleet.add_version("m", w2)
        t0 = time.perf_counter()
        futs, shed = [], 0
        for i in range(n):
            if i == n // 3:  # roll the version under load
                fleet.start_rollout("m", canary_fraction=0.25,
                                    warm_example=x[0])
            if i == 2 * n // 3:
                report = fleet.promote("m")
            try:
                futs.append(fleet.submit("m", x[i], tenant=tenants[i % 3]))
            except (QueueFullError, ServiceUnavailableError):
                # a loaded host can outrun the dispatcher: the submit
                # loop hits the priority-shed pressure thresholds (or
                # the queue bound) before the batcher drains — count
                # it, keep measuring
                shed += 1
        for f in futs:
            f.result()
        elapsed = time.perf_counter() - t0
        final_version = fleet.deployed_version("m")
    finally:
        fleet.close()
    emit("fleet",
         "multi-tenant fleet serving with mid-run version hot-swap "
         "(synthetic models)",
         len(futs) / elapsed, "images/sec",
         extra={
             "p50_ms": round(
                 1e3 * m.percentile("fleet.request_latency", 50), 2),
             "p99_ms": round(
                 1e3 * m.percentile("fleet.request_latency", 99), 2),
             "num_requests": len(futs),
             "shed": shed,
             "swap_no_recompile": bool(report["no_recompile"]),
             "canary_requests": int(
                 m.counters.get("fleet.canary_requests", 0)),
             "final_version": final_version,
         })


def _run_chipless(code: str):
    """One chip-free config's child: CPU-pinned by the runner, tracing
    itself into this config's artifact subdir (atexit flush)."""
    env = dict(os.environ)
    ta = _CONFIG_OBS.get("trace_artifact")
    if ta:
        env["SPARKDL_TRACE"] = ta
    return _run_json_subprocess(code, timeout_s=480, env=env)


# Synthetic-device pipeline bench child: the overlap proof without the
# chip — the "device" is a deterministic sleep standing in for a blocking
# dispatch round trip, so it measures the pipeline layer itself.
_PIPELINE_BENCH = r"""
import json
from sparkdl_tpu.obs.export import metrics_snapshot
from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark
from sparkdl_tpu.utils.metrics import Metrics
m = Metrics()
out = synthetic_overlap_benchmark(metrics=m)
out["metrics_snapshot"] = metrics_snapshot(m)
"""


def bench_pipeline():
    """Pipelined host/device overlap on the synthetic slow device:
    speedup vs the calling-thread path (``pipeline=False``) plus the
    per-stage stall/occupancy ledger.  The tier-1 contract
    (tests/test_pipeline.py) asserts >= 1.5x on this same benchmark."""
    prof = _run_chipless(_PIPELINE_BENCH)
    emit("pipeline",
         "pipelined host/device overlap speedup (synthetic slow device)",
         prof["speedup"], "x vs serial path",
         env_bound="synthetic: deterministic sleep device on host CPU "
                   "(measures the pipeline layer, not the chip)",
         extra={
             "device": prof["device"],
             "serial_s": round(float(prof["serial_s"]), 3),
             "pipelined_s": round(float(prof["pipelined_s"]), 3),
             "dispatch_ms": prof["dispatch_ms"],
             "prepare_ms": prof["prepare_ms"],
             "n_batches": prof["n_batches"],
             "pipeline_stages": prof["stages"],
             # the CHILD's registry (see bench_serving)
             **({"metrics_snapshot": prof["metrics_snapshot"]}
                if prof.get("metrics_snapshot") else {}),
         })


# Content-addressed inference cache child (ISSUE 11): chip-free by
# design, like "pipeline" — the device is a deterministic sleep, so the
# line measures the cache/coalescing layer (digest, single-flight, LRU)
# under a seeded Zipfian replay, the repetitive-traffic shape ROADMAP
# item 5 names.  The line carries the analytic hit floor next to the
# measured hit rate and the bit-identical verdict, so the speedup is
# self-auditing.
_CACHE_BENCH = r"""
import json, os
from sparkdl_tpu.serving.cache import zipfian_cache_benchmark
out = zipfian_cache_benchmark(
    n_requests=int(os.environ.get("SPARKDL_BENCH_CACHE_REQUESTS", "160")),
    universe=int(os.environ.get("SPARKDL_BENCH_CACHE_UNIVERSE", "16")),
    dispatch_ms=float(os.environ.get("SPARKDL_BENCH_CACHE_DISPATCH_MS",
                                     "10.0")))
"""


def bench_cache():
    """Content-addressed result cache + single-flight coalescing under
    a seeded Zipfian replay on the synthetic slow device: speedup vs
    the uncached serving path, with the measured hit rate pinned
    against the replay's analytic floor and a bit-identical-outputs
    verdict."""
    prof = _run_chipless(_CACHE_BENCH)
    emit("cache",
         "content-addressed inference cache speedup under Zipfian "
         "replay (synthetic slow device)",
         prof["speedup"], "x vs uncached serving path",
         env_bound="synthetic: deterministic sleep device on host CPU "
                   "(measures the cache/coalescing layer, not the chip)",
         extra={
             "device": prof["device"],
             "n_requests": prof["n_requests"],
             "universe": prof["universe"],
             "zipf_s": prof["zipf_s"],
             "hit_rate": prof["hit_rate"],
             "analytic_hit_rate": prof["analytic_hit_rate"],
             "uncached_s": prof["uncached_s"],
             "cached_s": prof["cached_s"],
             "uncached_dispatches": prof["uncached_dispatches"],
             "cached_dispatches": prof["cached_dispatches"],
             "bit_identical": prof["bit_identical"],
             "cache_entries": prof["cache_entries"],
             "cache_bytes": prof["cache_bytes"],
         })


# Exactly-once streaming ingestion child (ISSUE 8): chip-free by
# design, like "pipeline" — it measures the streaming/journal layer
# (poll -> journal intent -> pipelined score -> atomic artifact ->
# fsync commit), not the chip.  Two phases: an injected crash in the
# output->commit window mid-stream (the exactly-once window), then the
# MEASURED clean resume — so every line carries recovery/redelivery
# stats and a bit-identical-vs-batch-oracle verdict alongside the
# throughput number.
_STREAMING_BENCH = r"""
import json, os, tempfile, time
import numpy as np
from sparkdl_tpu import faults, streaming
from sparkdl_tpu.obs.export import metrics_snapshot
from sparkdl_tpu.obs.slo import slo_snapshot
from sparkdl_tpu.parallel.engine import InferenceEngine
from sparkdl_tpu.utils.metrics import Metrics

def _fn(variables, x):
    import jax.numpy as jnp
    return jnp.tanh(x @ variables["w"])

rng = np.random.default_rng(12)
variables = {"w": rng.normal(size=(64, 32)).astype(np.float32)}
n_chunks = int(os.environ.get("SPARKDL_BENCH_STREAM_CHUNKS", "48"))
rows = 64
payloads = [rng.normal(size=(rows, 64)).astype(np.float32)
            for _ in range(n_chunks)]
eng = InferenceEngine(_fn, variables, device_batch_size=rows)
base = tempfile.mkdtemp(prefix="sparkdl_stream_bench_")
jp = os.path.join(base, "journal.jsonl")
out_dir = os.path.join(base, "out")

# phase 1: crash mid-run between output write and journal commit
sc1 = streaming.StreamScorer(
    eng, streaming.MemorySource(payloads, finished=True),
    journal_path=jp, out_dir=out_dir, pipeline=True)
crash_at = max(2, n_chunks // 2)
crashed = False
with faults.active(faults.FaultPlan.parse(
        f"stream.commit:error:exc=fatal,at={crash_at}")):
    try:
        sc1.run()
    except faults.InjectedFatalError:
        crashed = True

# phase 2: the measured clean resume (no faults active)
m = Metrics()
sc2 = streaming.StreamScorer(
    eng, streaming.MemorySource(payloads, finished=True),
    journal_path=jp, out_dir=out_dir, pipeline=True, metrics=m)
t0 = time.perf_counter()
s2 = sc2.run()
resume_s = time.perf_counter() - t0
got = streaming.assemble_outputs(jp, out_dir)
oracle = np.concatenate(
    [np.asarray(o) for o in eng.map_batches(payloads, pipeline=False)],
    axis=0)
out = {
    "ips": round(s2["chunks_scored"] * rows / resume_s, 1),
    "chunks": n_chunks,
    "rows_per_chunk": rows,
    "crashed_mid_run": crashed,
    "resume_offset": s2["resume_offset"],
    "redeliveries": s2["redeliveries"],
    "duplicates_suppressed": s2["duplicates_suppressed"],
    "recovery_bit_identical": bool(np.array_equal(got, oracle)),
    "resume_s": round(resume_s, 3),
    "watermark": s2["watermark"],
    "lag_s_final": sc2.health()["lag_s"],
    "metrics_snapshot": metrics_snapshot(m),
    "slo": slo_snapshot(m),
}
"""


def bench_streaming():
    """Exactly-once streaming ingestion envelope: rows/sec through the
    journal'd pipelined path on the RESUME leg of a crash-resume cycle
    (the worst case — replay + dedupe + fresh chunks), with the
    redelivery/lag/recovery ledger stamped on the line."""
    prof = _run_chipless(_STREAMING_BENCH)
    emit("streaming",
         "exactly-once streaming resume throughput (injected "
         "output->commit crash, journal'd replay)",
         prof["ips"], "rows/sec",
         env_bound="synthetic: in-memory source + fsync'd journal on "
                   "host CPU (measures the streaming/journal layer, "
                   "not the chip)",
         extra={
             "device": prof["device"],
             "chunks": prof["chunks"],
             "rows_per_chunk": prof["rows_per_chunk"],
             "crashed_mid_run": prof["crashed_mid_run"],
             "resume_offset": prof["resume_offset"],
             "redeliveries": prof["redeliveries"],
             "duplicates_suppressed": prof["duplicates_suppressed"],
             "recovery_bit_identical": prof["recovery_bit_identical"],
             "resume_s": prof["resume_s"],
             "watermark": prof["watermark"],
             "lag_s_final": prof["lag_s_final"],
             # the CHILD's registry (see bench_serving)
             **({"metrics_snapshot": prof["metrics_snapshot"]}
                if prof.get("metrics_snapshot") else {}),
             **({"slo": prof["slo"]} if prof.get("slo") else {}),
         })


_RAGGED_BENCH = r"""
import json, os
from sparkdl_tpu.parallel import compile_cache
from sparkdl_tpu.serving.batcher import ragged_arrival_benchmark
out = ragged_arrival_benchmark(
    n_bursts=int(os.environ.get("SPARKDL_BENCH_RAGGED_BURSTS", "10")),
    dispatch_ms=float(os.environ.get("SPARKDL_BENCH_RAGGED_DISPATCH_MS",
                                     "8.0")))
out["compile_cache"] = compile_cache.state()  # non-null when the env
# places or enables the cache — a warm dir makes this line's compile
# half a restart-cost measurement too
"""


def bench_ragged():
    """Continuous ragged batching under a seeded mixed-size arrival
    replay on the synthetic slow device (ISSUE 13): measured pad-row
    reduction vs the flush-on-full baseline (the engine's
    rows/pad_rows ledger), mean fill-ratio movement, and a
    bit-identical-outputs verdict — the serving-side half of the
    raw-speed pass, chip-free by construction."""
    prof = _run_chipless(_RAGGED_BENCH)
    saved = prof["pad_rows_saved"]
    emit("ragged",
         "ragged-batching pad-row reduction under mixed-size arrival "
         "replay (synthetic slow device)",
         saved, "pad rows saved vs flush-on-full baseline",
         env_bound="synthetic: deterministic sleep device on host CPU "
                   "(measures the batcher/bucket layer, not the chip)",
         extra={
             "device": prof["device"],
             "n_requests": prof["n_requests"],
             "n_bursts": prof["n_bursts"],
             "bucket_sizes": prof["bucket_sizes"],
             "dispatch_ms": prof["dispatch_ms"],
             "flush_pad_frac": prof["flush_pad_frac"],
             "ragged_pad_frac": prof["ragged_pad_frac"],
             "flush_fill_mean": prof["flush"]["fill_mean"],
             "ragged_fill_mean": prof["ragged"]["fill_mean"],
             "ragged_topoff_rows": prof["ragged"]["topoff_rows"],
             "bit_identical": prof["bit_identical"],
             "compile_cache": prof.get("compile_cache"),
         })


_TWIN_BENCH = r"""
import json, time
from sparkdl_tpu.twin import (DEFAULT_TENANT_QUOTA, QuotaAutoscaler,
                              ScenarioConfig, run_day)
cfg = ScenarioConfig()  # the canonical 288-tick, 64-tenant seeded day
t0 = time.perf_counter()
res = run_day(cfg, policy=QuotaAutoscaler(DEFAULT_TENANT_QUOTA))
wall_s = time.perf_counter() - t0
s = res.scores
out = {
    "wall_s": round(wall_s, 3),
    "virtual_day_s": cfg.ticks * cfg.tick_s,
    "offered": s["offered"],
    "submitted": s["submitted"],
    "shed": s["shed"],
    "tenants_active": s["tenants_active"],
    "slo_minutes": s["slo_minutes"],
    "breach_ticks": s["breach_ticks"],
    "goodput": s["goodput"],
    "fairness": s["fairness"],
    "cache_hit_rate": s["cache_hit_rate"],
    "stream_commits": s["stream_commits"],
    "event_digest": res.event_digest,
    "requests_per_wall_s": round(s["offered"] / wall_s, 1),
}
"""


def bench_twin():
    """Traffic-twin day replay (ISSUE 16): the canonical seeded day
    (~160k virtual requests, 64 tenants, flash crowd + retry storm)
    driven through a REAL fleet on virtual time with the adaptive
    policy in the loop.  Headline is simulated-requests/sec of wall
    time — the 'replay a day in tier-1 seconds' compression ratio —
    with the day's SLO-minutes/goodput/fairness/cache-hit scorecard
    and the byte-stable event digest stamped alongside."""
    prof = _run_chipless(_TWIN_BENCH)
    emit("twin",
         "traffic-twin canonical day replay throughput (virtual-time "
         "fleet, adaptive policy in the loop)",
         prof["requests_per_wall_s"], "simulated requests/sec",
         env_bound="synthetic: virtual-clock fleet on host CPU "
                   "(measures the twin/control-loop layer, not the "
                   "chip)",
         extra={
             "device": prof["device"],
             "wall_s": prof["wall_s"],
             "virtual_day_s": prof["virtual_day_s"],
             "offered": prof["offered"],
             "submitted": prof["submitted"],
             "shed": prof["shed"],
             "tenants_active": prof["tenants_active"],
             "slo_minutes": prof["slo_minutes"],
             "breach_ticks": prof["breach_ticks"],
             "goodput": prof["goodput"],
             "fairness": prof["fairness"],
             "cache_hit_rate": prof["cache_hit_rate"],
             "stream_commits": prof["stream_commits"],
             "event_digest": prof["event_digest"],
         })


_HEADFANOUT_BENCH = r"""
import json, os
from sparkdl_tpu.serving.cache import head_fanout_benchmark
out = head_fanout_benchmark(
    n_requests=int(os.environ.get("SPARKDL_BENCH_FANOUT_REQUESTS", "160")),
    universe=int(os.environ.get("SPARKDL_BENCH_FANOUT_UNIVERSE", "16")),
    tenants=int(os.environ.get("SPARKDL_BENCH_FANOUT_TENANTS", "64")),
    dispatch_ms=float(os.environ.get("SPARKDL_BENCH_FANOUT_DISPATCH_MS",
                                     "10.0")))
"""


def bench_headfanout():
    """Shared-backbone head fan-out (ISSUE 17): a seeded Zipf-content
    64-tenant replay on the synthetic slow backbone.  Headline is the
    warm-path p50 reduction vs the full-model-per-request baseline;
    stamped alongside: the backbone dispatch ratio (dispatches ==
    distinct content digests proves featurize-once), head-only warm
    p50/p99, the stacked head bank's per-chip HBM bytes, and the
    bit-identical-vs-per-tenant-oracle verdict."""
    prof = _run_chipless(_HEADFANOUT_BENCH)
    emit("headfanout",
         "shared-backbone head fan-out warm-path p50 reduction under "
         "Zipf-content multi-tenant replay (synthetic slow backbone)",
         prof["p50_reduction"], "fraction of full-model p50 removed",
         env_bound="synthetic: deterministic sleep backbone on host CPU "
                   "(measures the feature-cache/head-bank layer, not "
                   "the chip)",
         extra={
             "device": prof["device"],
             "n_requests": prof["n_requests"],
             "universe": prof["universe"],
             "tenants": prof["tenants"],
             "zipf_s": prof["zipf_s"],
             "distinct": prof["distinct"],
             "backbone_dispatches": prof["backbone_dispatches"],
             "baseline_dispatches": prof["baseline_dispatches"],
             "dispatch_ratio": prof["dispatch_ratio"],
             "baseline_p50_ms": prof["baseline_p50_ms"],
             "baseline_p99_ms": prof["baseline_p99_ms"],
             "warm_p50_ms": prof["warm_p50_ms"],
             "warm_p99_ms": prof["warm_p99_ms"],
             "feature_hits": prof["feature_hits"],
             "bank_param_bytes_per_chip": prof["bank_param_bytes_per_chip"],
             "bank_capacity": prof["bank_capacity"],
             "bank_mode": prof["bank_mode"],
             "bit_identical": prof["bit_identical"],
         })


BENCHES = {
    "1": bench_config1_device,
    "1e2e": bench_config1_e2e,
    "2": bench_config2,
    "3": bench_config3,
    "4": bench_config4,
    "5": bench_config5,
    "serving": bench_serving,
    "fleet": bench_fleet,
    "pipeline": bench_pipeline,
    "streaming": bench_streaming,
    "cache": bench_cache,
    "ragged": bench_ragged,
    "twin": bench_twin,
    "headfanout": bench_headfanout,
}


# Configs that never need the chip: "pipeline", "cache", and "ragged"
# simulate their device with a deterministic sleep, "streaming" measures
# the journal'd crash-resume path on synthetic in-memory chunks, "twin"
# replays a whole virtual-clock day through a real fleet on the CPU
# backend, and "headfanout" measures the feature-cache + stacked-head-bank
# layer on a deterministic sleep backbone.  Each runs in a CPU-pinned
# child; everything else measures the accelerator in this process.
_CHIPLESS_CONFIGS = ("pipeline", "streaming", "cache", "ragged", "twin",
                     "headfanout")


def _error_line(key, exc, device):
    """A failed config's record: stamped like any other line, traceback
    on stderr."""
    traceback.print_exception(type(exc), exc, exc.__traceback__,
                              file=sys.stderr)
    _print_line(json.dumps({"config": key, "error": repr(exc)[:300],
                            "device": device}))


def main() -> int:
    """Run the configured benches; 0 iff every one of them reported."""
    _ARTIFACT.reset()  # fresh crash-safe JSONL rider for this run
    default = ("1,1e2e,2,3,4,5,serving,fleet,pipeline,streaming,cache,"
               "ragged,twin,headfanout")
    keys = [k.strip() for k in
            os.environ.get("SPARKDL_BENCH_CONFIGS", default).split(",")]
    keys = [k for k in keys if k in BENCHES]
    # chip-free children FIRST (stable): once this process initialises
    # its accelerator backend it starts no child at all.  After them the
    # headline ("1") keeps its place at the front of the chip configs —
    # a run cut short has already printed it — and is re-emitted last.
    keys.sort(key=lambda k: k not in _CHIPLESS_CONFIGS)
    failed = []
    for key in keys:
        chipless = key in _CHIPLESS_CONFIGS
        try:
            _begin_config_obs(key)
            if not chipless:
                require_accelerator()
            BENCHES[key]()
        except NoAcceleratorError as e:
            # every remaining config needs the chip too: one line, stop
            failed.append(key)
            _error_line(key, e, device_stamp())
            break
        # graftlint: allow=SDL003 reason=run boundary: the failure is printed as a stamped error line with its traceback and fails the run's exit code; the other configs still report
        except Exception as e:
            failed.append(key)
            # a failed child leaves this process without a backend, and
            # stamping the line must not initialise one ahead of the
            # children still to come
            _error_line(key, e, None if chipless else device_stamp())
        finally:
            _end_config_obs(key)
    # bench-owned tracer state must not leak into the embedding process
    # (contract tests import bench and call main() in-process)
    if BENCH_TRACE:
        from sparkdl_tpu import obs

        obs.configure_from_env()
    # end on the headline metric whenever it was measured (even if later
    # configs errored) for a parse-the-final-line driver
    if "1" in _LINES and _LAST_PRINTED[0] != _LINES["1"]:
        _print_line(_LINES["1"])
    if failed:
        print(f"bench: FAILED configs: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    from sparkdl_tpu.parallel import compile_cache

    compile_cache.configure_default()
    sys.exit(main())
