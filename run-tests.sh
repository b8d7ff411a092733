#!/usr/bin/env bash
# Test gate for sparkdl_tpu (SURVEY.md C18 equivalent of python/run-tests.sh).
#
# Runs the full suite on a virtual 8-device CPU mesh (the conftest sets
# XLA_FLAGS/JAX_PLATFORMS); exits non-zero on any failure. Run this before
# every snapshot/commit of substance — a red suite must never ship.
#
# Tier-1 (the driver's gate) is `-m 'not slow'` over tests/: the serving
# suite (tests/test_serving.py) is CPU-only and carries no slow marks, so
# the online path sits inside the tier-1 gate by construction — the check
# below keeps that wiring from silently regressing if the file moves.
# Likewise tests/test_pipeline.py carries the pipelined-execution overlap
# contract (synthetic 100 ms slow device on the CPU backend, >= 1.5x vs
# pipeline=False, bit-identical outputs): fast, chip-free, tier-1.
#
# Everything that needs the real chip lives OUTSIDE this gate:
# `python chip_smoke.py` on the chip machine is the bring-up proof (it
# fails here by design — no accelerator), benchmark/run.py is the
# measurement.
#
# Usage: ./run-tests.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")"
if [[ ! -f tests/test_serving.py ]]; then
  echo "FATAL: tests/test_serving.py missing — the serving subsystem" \
       "would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_pipeline.py ]]; then
  echo "FATAL: tests/test_pipeline.py missing — the pipelined execution" \
       "layer's overlap + parity contract would ship unasserted" >&2
  exit 1
fi
if [[ ! -f tests/test_obs.py ]]; then
  echo "FATAL: tests/test_obs.py missing — the observability layer" \
       "(span tracing, exporters, exemplars) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_faults.py ]]; then
  echo "FATAL: tests/test_faults.py missing — the fault-injection layer" \
       "(chaos e2e, breaker, PipelineStageError, kill-the-driver)" \
       "would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_fleet.py ]]; then
  echo "FATAL: tests/test_fleet.py missing — the fleet subsystem" \
       "(registry, zero-downtime rollout, tenant admission, chaos" \
       "swap test) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_stream_ingest.py ]]; then
  echo "FATAL: tests/test_stream_ingest.py missing — the streaming" \
       "subsystem (journal exactly-once, crash resume, stall watchdog," \
       "SIGKILL chaos) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_flight.py ]]; then
  echo "FATAL: tests/test_flight.py missing — the incident-observability" \
       "layer (flight recorder, SLO burn-rate engine, blackbox timeline," \
       "SIGKILL durability, headline causal-chain chaos) would ship" \
       "untested" >&2
  exit 1
fi
if [[ ! -f tests/test_cache.py ]]; then
  echo "FATAL: tests/test_cache.py missing — the inference-cache layer" \
       "(single-flight coalescing, Zipfian replay benchmark, hot-swap" \
       "survival, corruption re-check) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_mesh_shard.py ]]; then
  echo "FATAL: tests/test_mesh_shard.py missing — the mesh-sharded" \
       "inference core (partition rules, sharded-vs-replicated parity," \
       "GC005 HBM proof, ragged mesh alignment) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_analysis.py ]]; then
  echo "FATAL: tests/test_analysis.py missing — the graftlint rules and" \
       "lock-order checker would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_graftcheck.py ]]; then
  echo "FATAL: tests/test_graftcheck.py missing — the program auditor" \
       "(GC rules, lockfile contract, repo-audits-clean gate) would" \
       "ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_twin.py ]]; then
  echo "FATAL: tests/test_twin.py missing — the traffic-twin subsystem" \
       "(virtual-time determinism, closed-loop policy/placement) would" \
       "ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_headfanout.py ]]; then
  echo "FATAL: tests/test_headfanout.py missing — the head fan-out tier" \
       "(featurize-once replay, no-backbone-recompile hot-swap, feature" \
       "cache survival, bank fallback modes) would ship untested" >&2
  exit 1
fi
if [[ ! -f tests/test_cost.py ]]; then
  echo "FATAL: tests/test_cost.py missing — the cost-attribution layer" \
       "(conservation proof, regression sentinel, cardinality bound," \
       "cost.attr degrade site) would ship untested" >&2
  exit 1
fi

# graftlint stage (ISSUE 5): the repo's own invariants (joined threads,
# lockset discipline, registered fault sites, paired spans, monotonic
# timing — rule table in README "Static analysis") checked statically
# over the whole stack.  Must exit 0 with every allow-pragma carrying a
# reason; stdlib-ast only, so the 15 s wall guard is generous (~3 s in
# practice, no jax init).
echo "== graftlint static analysis =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu tools bench.py \
  chip_smoke.py

# graftcheck program audit (ISSUE 6): every compiled program the stack
# constructs (full zoo x serving bucket plan, train steps, sepconv
# kernels) lowered abstractly on CPU and checked against the committed
# PROGRAMS.lock.json fingerprints (rules GC000-GC005: donation, bf16
# dtype leaks, retrace keys, pad-waste budget, sharding).  Must exit 0;
# any drift names the GC rule that moved.  The sweep itself runs in
# ~35 s chip-free (acceptance budget: under 60 s); the 90 s wall guard
# covers loaded CI hosts.  Regenerate after a reviewed program change:
#   python tools/graftcheck.py --write-baseline
echo "== graftcheck program audit =="
timeout -k 10 90 python tools/graftcheck.py

python -m pytest tests/ -q --durations=10 "$@"

# Fault-suite stage (ISSUE 4 satellite): re-run the chaos suite with
# SPARKDL_FAULTS SET in the environment — the tests install their own
# plans over it, but the env gate itself (parse at first inject, restore
# via faults.active) is then exercised for real, and the benign bounded
# sleep rule proves a spec'd site on the engine hot path doesn't corrupt
# results.
echo "== fault-injection suite (SPARKDL_FAULTS active) =="
# -k: skip the SIGKILL bench-subprocess test on this second pass — it
# sets its own SPARKDL_FAULTS in the child, so re-running it here adds
# minutes of wall time and zero env-gate coverage.
# SPARKDL_LOCKCHECK=1 (ISSUE 5): the chaos pass doubles as the lock-
# order probe — every stack lock becomes an analysis.lockcheck wrapper
# and the injected schedules (stalls, crashes, queue storms) drive the
# acquisition-order graph; a cycle fails the suite loudly.
SPARKDL_FAULTS="seed=1;engine.dispatch:sleep:ms=1,times=3" \
  SPARKDL_LOCKCHECK=1 \
  python -m pytest tests/test_faults.py -q -k "not sigkill"

# Fleet stage (ISSUE 7 satellite): re-run the fleet suite — headline
# chaos rollout included — with SPARKDL_FAULTS exported so the env gate
# carries real fleet.* rules (the tests install their own plans over
# it), and with SPARKDL_LOCKCHECK=1 so the four new fleet locks
# (registry/state/admission/rollout) feed the lock-order graph under
# injected swap/canary/admission schedules.  Wall-guarded: the suite
# runs in ~10 s; 300 s covers loaded CI hosts.
echo "== fleet serving suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=2;fleet.canary:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_fleet.py -q
# graftlint self-check scoped to the new package (named locks only,
# SDL001-SDL007 clean, no pragmas): the whole-stack pass above already
# covers it, but a scoped run pins the fleet package's own cleanliness
# even if the wide target list ever changes.
echo "== graftlint fleet package self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/serving/fleet \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py

# Streaming stage (ISSUE 8 satellite): re-run the streaming-ingestion
# suite with SPARKDL_FAULTS carrying real stream.* rules (the tests
# install their own plans over it, but the env gate itself is then
# exercised, and the benign bounded sleep at stream.source proves a
# spec'd rule on the poll loop stalls without corrupting exactly-once
# accounting) and SPARKDL_LOCKCHECK=1 so the streaming locks
# (stream.journal/stream.state/stream.health/stream.source.feed) feed
# the lock-order graph under injected stall/crash/replay schedules.
# -k: the SIGKILL headline sets its own SPARKDL_FAULTS in its child —
# re-running it here adds subprocess wall time and zero env-gate
# coverage (same policy as the fault-suite stage above).
echo "== streaming ingestion suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=3;stream.source:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_stream_ingest.py -q \
  -k "not sigkill"
# scoped self-check, same rationale as the fleet one: the streaming
# package must stay SDL001-SDL007 clean with no pragmas.
echo "== graftlint streaming package self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/streaming \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py

# Cache stage (ISSUE 11 satellite): re-run the cache suite with
# SPARKDL_FAULTS carrying real cache.* rules (the tests install their
# own plans over it, but the env gate itself is then exercised, and the
# benign bounded sleep at cache.stampede proves a spec'd rule on the
# single-flight leader path delays without corrupting results or
# coalescing accounting) and SPARKDL_LOCKCHECK=1 so the new
# serving.cache lock feeds the lock-order graph under injected
# hit-corruption/stampede schedules.  Wall-guarded like the fleet and
# streaming stages.
echo "== inference-cache suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=4;cache.stampede:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_cache.py -q
# scoped self-check, same rationale as the fleet/streaming ones: the
# cache module must stay SDL001-SDL008 clean with no pragmas of its own
echo "== graftlint cache module self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/serving/cache.py \
  sparkdl_tpu/utils/digest.py \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py

# Raw-speed stage (ISSUE 13): the ragged-batching + persistent-compile-
# cache pass re-proven under chaos and overhead bounds.
#   (a) the ragged suite re-runs with SPARKDL_FAULTS carrying a real
#       batch.* rule (the tests install their own plans over it, but
#       the env gate itself is then exercised, and the benign bounded
#       sleep at batch.topoff proves a spec'd rule on the top-off pull
#       delays without corrupting fill accounting or results) and
#       SPARKDL_LOCKCHECK=1 so the batcher condition + engine locks
#       feed the lock-order graph under injected top-off schedules;
#   (b) the compile-cache suite re-runs the cross-process restart
#       proof (process A populates, process B serves with ZERO fresh
#       compiles, a tampered fingerprint forces a clean classified
#       recompile);
#   (c) the batcher-overhead guard: when traffic is bucket-aligned
#       (no ragged win available), the ragged path must stay within
#       the established 1.35x sleep-math bound — the ragged machinery
#       may only ever remove pad rows, never add dispatch overhead.
echo "== raw-speed suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=5;batch.topoff:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_ragged.py -q
echo "== compile-cache cross-process proof =="
SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_compile_cache.py -q
echo "== batcher-overhead guard (ragged idle) =="
env -u SPARKDL_FAULTS python - <<'PY'
import json
import time

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.serving.server import Server

faults.clear()


def fn(v, x):
    import jax.numpy as jnp

    return jnp.tanh(x * v["s"] + 0.25)


rng = np.random.default_rng(5)
rows = [rng.normal(size=(8,)).astype(np.float32) for _ in range(6 * 32)]
dispatch_s = 0.05
srv = Server(fn, {"s": np.float32(2.0)}, max_batch_size=32,
             max_wait_ms=5, bucket_sizes=[32], max_inflight_batches=1,
             ragged=True, cache=False)
try:
    srv.warmup(rows[0])  # compile BEFORE the sleep wrap
    for b in srv.bucket_sizes:
        eng = srv._engine_for(b)
        real = eng.run_padded

        def slow(batch, _real=real):
            time.sleep(dispatch_s)
            return _real(batch)

        eng.run_padded = slow
    t0 = time.perf_counter()
    futs = [srv.submit(r) for r in rows]
    for f in futs:
        f.result(timeout=60)
    wall = time.perf_counter() - t0
finally:
    srv.close()
ideal = (len(rows) // 32) * dispatch_s
print(json.dumps({"ideal_s": round(ideal, 3),
                  "ragged_wall_s": round(wall, 3)}))
assert wall <= 1.35 * ideal, (
    f"ragged serving wall {wall:.3f}s exceeds 1.35x the {ideal:.3f}s "
    f"sleep-math ideal on bucket-aligned traffic — the ragged path has "
    f"grown per-dispatch overhead")
print("batcher-overhead guard ok")
PY

# Scoped self-check, same rationale as the fleet/streaming/cache ones:
# the raw-speed modules (ragged batcher + persistent compile cache)
# must stay SDL001-SDL008 clean with no new unreasoned pragmas.
echo "== graftlint raw-speed modules self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/serving/batcher.py \
  sparkdl_tpu/parallel/compile_cache.py \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py

# Mesh-sharded stage (ISSUE 14): the tensor-parallel weight-sharding
# core re-proven under chaos, lockfile pinning, and an overhead bound.
#   (a) the mesh-shard suite re-runs with SPARKDL_FAULTS carrying a
#       real engine rule (the tests install their own plans over it,
#       but the env gate itself is then exercised, and the benign
#       bounded sleep at engine.dispatch proves a spec'd rule on the
#       sharded dispatch path delays without corrupting the
#       sharded-vs-replicated parity) and SPARKDL_LOCKCHECK=1 so the
#       engine/batcher locks feed the lock-order graph while sharded
#       engines construct and serve;
#   (b) a scoped graftlint self-check over the sharding core;
#   (c) the sharded-path overhead guard: a tensor-parallel server over
#       a sleep-wrapped device must stay within the established 1.35x
#       sleep-math bound — the sharding machinery resolves rules ONCE
#       at engine construction and may never add per-dispatch cost.
echo "== mesh-sharded suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=6;engine.dispatch:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_mesh_shard.py -q
echo "== graftlint mesh-sharding modules self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/parallel/mesh.py \
  sparkdl_tpu/parallel/engine.py \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py
echo "== sharded-path overhead guard =="
env -u SPARKDL_FAULTS python - <<'PY'
import json
import os
import time

# the guard needs a model axis: pin the 8-device virtual topology
# BEFORE jax initializes its backend (the conftest does this for the
# pytest half; this heredoc runs bare)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.parallel import mesh as mesh_lib
from sparkdl_tpu.serving.server import Server

faults.clear()


def fn(v, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ v["dense"]["kernel"] + v["dense"]["bias"])


rng = np.random.default_rng(6)
variables = {"dense": {
    "kernel": rng.normal(size=(8, 8)).astype(np.float32),
    "bias": rng.normal(size=(8,)).astype(np.float32)}}
rows = [rng.normal(size=(8,)).astype(np.float32) for _ in range(6 * 32)]
dispatch_s = 0.05
mesh = mesh_lib.get_mesh(model_parallel=4)  # dp2 x tp4
srv = Server(fn, variables, mesh=mesh, max_batch_size=32, max_wait_ms=5,
             bucket_sizes=[32], max_inflight_batches=1, ragged=True,
             cache=False,
             partition_rules=mesh_lib.default_partition_rules)
try:
    assert srv.warmup(rows[0]) is None
    info = srv.sharding_info()
    assert info["sharded"], info  # the guard must exercise the TP path
    for b in srv.bucket_sizes:
        eng = srv._engine_for(b)
        real = eng.run_padded

        def slow(batch, _real=real):
            time.sleep(dispatch_s)
            return _real(batch)

        eng.run_padded = slow
    t0 = time.perf_counter()
    futs = [srv.submit(r) for r in rows]
    for f in futs:
        f.result(timeout=60)
    wall = time.perf_counter() - t0
finally:
    srv.close()
ideal = (len(rows) // 32) * dispatch_s
print(json.dumps({"ideal_s": round(ideal, 3),
                  "sharded_wall_s": round(wall, 3),
                  "mesh": info["mesh_shape"]}))
assert wall <= 1.35 * ideal, (
    f"tensor-parallel serving wall {wall:.3f}s exceeds 1.35x the "
    f"{ideal:.3f}s sleep-math ideal — the sharded dispatch path has "
    f"grown per-dispatch overhead")
print("sharded-path overhead guard ok")
PY

# Cache-overhead guard (ISSUE 11 satellite): with SPARKDL_CACHE unset
# the serving stack must be exactly as fast as before the cache
# landed.  Same shape as the disabled-tracing/inject/recorder guards:
# (a) the synthetic slow-device benchmark stays within the established
# 1.35x sleep-math bound with no cache configured (the engine hot path
# gained only the pad-row ledger — two counter incrs per piece); (b)
# the disabled-path probe, serving.cache.get_default(), is one
# module-global read + identity check within 10x a no-op and under
# 5us, the established bar.
echo "== cache-overhead guard =="
env -u SPARKDL_CACHE python - <<'PY'
import json
import timeit

import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark
from sparkdl_tpu.serving import cache as serving_cache

serving_cache.configure(None)  # SPARKDL_CACHE unset equivalent
res = synthetic_overlap_benchmark()
ideal = res["n_batches"] * max(res["prepare_ms"], res["dispatch_ms"]) / 1e3
print(json.dumps({"ideal_s": ideal, "pipelined_s": res["pipelined_s"],
                  "speedup": res["speedup"]}))
assert res["pipelined_s"] <= 1.35 * ideal, (
    f"cache-disabled pipelined wall {res['pipelined_s']:.3f}s exceeds "
    f"1.35x the {ideal:.1f}s ideal — the SPARKDL_CACHE-unset path is "
    f"no longer near-zero cost")
assert res["speedup"] >= 1.5, res


def noop():
    return None


n = 200_000
t_probe = timeit.timeit(serving_cache.get_default, number=n)
t_noop = timeit.timeit(noop, number=n)
print(json.dumps({"probe_us": round(t_probe / n * 1e6, 3),
                  "noop_us": round(t_noop / n * 1e6, 3)}))
# generous bound (loaded CI hosts): the disabled default-cache probe
# within 10x a no-op call AND under 5us absolute — the established bar
assert t_probe / n < 5e-6 and t_probe < 10 * t_noop + 0.05, (
    f"disabled cache probe costs {t_probe / n * 1e6:.2f}us/call "
    f"(no-op: {t_noop / n * 1e6:.2f}us)")
print("cache-overhead guard ok")
PY

# Tracing-overhead guard (ISSUE 3 satellite): the synthetic slow-device
# benchmark must show that (a) DISABLED tracing (SPARKDL_TRACE=0) adds
# ~nothing — the pipelined wall stays within a small factor of the
# sleep-math ideal (n_batches x max(prepare, dispatch) = the untraced
# baseline this benchmark has asserted since PR 2) — and (b) with
# tracing ON the >= 1.5x overlap contract still holds.  Sleep-dominated
# on the CPU backend, so the factors are deterministic on any host.
echo "== tracing-overhead guard =="
python - <<'PY'
import json

import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import obs
from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark

obs.configure(enabled=False)          # SPARKDL_TRACE=0 equivalent
off = synthetic_overlap_benchmark()
obs.configure(enabled=True)           # SPARKDL_TRACE=1 equivalent
on = synthetic_overlap_benchmark()
obs.configure_from_env()
ideal = off["n_batches"] * max(off["prepare_ms"], off["dispatch_ms"]) / 1e3
print(json.dumps({"ideal_s": ideal,
                  "untraced_pipelined_s": off["pipelined_s"],
                  "traced_pipelined_s": on["pipelined_s"],
                  "untraced_speedup": off["speedup"],
                  "traced_speedup": on["speedup"]}))
assert off["pipelined_s"] <= 1.35 * ideal, (
    f"disabled-tracing pipelined wall {off['pipelined_s']:.3f}s exceeds "
    f"1.35x the {ideal:.1f}s untraced ideal — the SPARKDL_TRACE=0 path "
    f"is no longer near-zero cost")
assert off["speedup"] >= 1.5, off
assert on["speedup"] >= 1.5, (
    f"overlap contract broken WITH tracing on: {on['speedup']:.2f}x < 1.5x")
print("tracing-overhead guard ok")
PY

# Fault-injection overhead guard (ISSUE 4 satellite): with SPARKDL_FAULTS
# unset the inject() sites threaded through the hot paths must add no
# measurable overhead.  Two checks, same style as the SPARKDL_TRACE=0
# guard: (a) the synthetic slow-device benchmark — whose prepare/
# dispatch/gather loops all cross injection sites — stays within 1.35x
# of the sleep-math ideal with injection disabled; (b) the disabled
# inject() call itself stays within an order of magnitude of a plain
# no-op call (it is one global read + None check).
echo "== fault-injection overhead guard =="
env -u SPARKDL_FAULTS python - <<'PY'
import json
import timeit

import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark

faults.clear()  # SPARKDL_FAULTS unset equivalent
res = synthetic_overlap_benchmark()
ideal = res["n_batches"] * max(res["prepare_ms"], res["dispatch_ms"]) / 1e3
print(json.dumps({"ideal_s": ideal, "pipelined_s": res["pipelined_s"],
                  "speedup": res["speedup"]}))
assert res["pipelined_s"] <= 1.35 * ideal, (
    f"injection-sites-disabled pipelined wall {res['pipelined_s']:.3f}s "
    f"exceeds 1.35x the {ideal:.1f}s ideal — the disabled inject() path "
    f"is no longer near-zero cost")
assert res["speedup"] >= 1.5, res


def noop(site):
    return None


n = 200_000
t_inject = timeit.timeit(lambda: faults.inject("engine.dispatch"),
                         number=n)
t_noop = timeit.timeit(lambda: noop("engine.dispatch"), number=n)
print(json.dumps({"inject_us": round(t_inject / n * 1e6, 3),
                  "noop_us": round(t_noop / n * 1e6, 3)}))
# generous bound (loaded CI hosts): disabled inject within 10x a no-op
# call AND under 5us absolute
assert t_inject / n < 5e-6 and t_inject < 10 * t_noop + 0.05, (
    f"disabled inject() costs {t_inject / n * 1e6:.2f}us/call "
    f"(no-op: {t_noop / n * 1e6:.2f}us)")
print("fault-injection overhead guard ok")
PY

# Streaming-overhead guard (ISSUE 8): with no stream rules active and
# SPARKDL_TRACE=0, the streaming runner's per-chunk cost over a raw
# map_batches pass is its durability work only — three journal fsyncs
# plus one atomic artifact write per chunk — bounded absolutely, in the
# same spirit as the disabled-tracing/disabled-inject guards above
# (the generous bound covers loaded CI hosts and slow fsync media).
echo "== streaming-overhead guard =="
env -u SPARKDL_FAULTS python - <<'PY'
import json
import os
import tempfile
import time

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults, obs, streaming
from sparkdl_tpu.parallel.engine import InferenceEngine

obs.configure(enabled=False)   # SPARKDL_TRACE=0 equivalent
faults.clear()                 # SPARKDL_FAULTS unset equivalent


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"])


rng = np.random.default_rng(3)
variables = {"w": rng.normal(size=(16, 8)).astype(np.float32)}
eng = InferenceEngine(_fn, variables, device_batch_size=32)
n = 64
payloads = [rng.normal(size=(32, 16)).astype(np.float32)
            for _ in range(n)]
for _ in eng.map_batches(payloads, pipeline=False):  # warm the program
    pass
t0 = time.perf_counter()
for _ in eng.map_batches(payloads, pipeline=False):
    pass
direct_s = time.perf_counter() - t0
base = tempfile.mkdtemp(prefix="sparkdl_stream_guard_")
sc = streaming.StreamScorer(
    eng, streaming.MemorySource(payloads, finished=True),
    journal_path=os.path.join(base, "j.jsonl"),
    out_dir=os.path.join(base, "out"), pipeline=False)
t0 = time.perf_counter()
summary = sc.run()
stream_s = time.perf_counter() - t0
obs.configure_from_env()
per_chunk_ms = max(0.0, stream_s - direct_s) / n * 1e3
print(json.dumps({"direct_s": round(direct_s, 3),
                  "stream_s": round(stream_s, 3),
                  "per_chunk_overhead_ms": round(per_chunk_ms, 3)}))
assert summary["chunks_scored"] == n, summary
assert per_chunk_ms < 25.0, (
    f"streaming runner adds {per_chunk_ms:.2f}ms/chunk over raw "
    f"map_batches with journaling's durability floor expected under "
    f"25ms — the disabled-faults/untraced streaming path has grown "
    f"non-durability overhead")
print("streaming-overhead guard ok")
PY

# Recorder-overhead guard (ISSUE 9 satellite): with SPARKDL_BLACKBOX
# unset the flight_emit() sites threaded through state-change paths
# must add no measurable overhead.  Same shape as the SPARKDL_TRACE=0
# and disabled-inject guards above: (a) the synthetic slow-device
# benchmark stays within the established 1.35x sleep-math bound with
# the recorder OFF; (b) with the recorder ON the >= 1.5x overlap
# contract still holds (the recorder only sees state CHANGES, never
# per-batch traffic, so tier-1 wall time is unaffected); (c) the
# disabled emit() call itself stays within an order of magnitude of a
# plain no-op call (one module-global read + identity check).
echo "== flight-recorder overhead guard =="
env -u SPARKDL_BLACKBOX python - <<'PY'
import json
import timeit

import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu.obs import flight
from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark

flight.configure(enabled=False)        # SPARKDL_BLACKBOX unset equivalent
off = synthetic_overlap_benchmark()
flight.configure(enabled=True)         # SPARKDL_BLACKBOX=1 equivalent
on = synthetic_overlap_benchmark()
flight.configure(enabled=False)
ideal = off["n_batches"] * max(off["prepare_ms"], off["dispatch_ms"]) / 1e3
print(json.dumps({"ideal_s": ideal,
                  "recorder_off_pipelined_s": off["pipelined_s"],
                  "recorder_on_pipelined_s": on["pipelined_s"],
                  "recorder_off_speedup": off["speedup"],
                  "recorder_on_speedup": on["speedup"]}))
assert off["pipelined_s"] <= 1.35 * ideal, (
    f"recorder-off pipelined wall {off['pipelined_s']:.3f}s exceeds "
    f"1.35x the {ideal:.1f}s ideal — the SPARKDL_BLACKBOX-unset path "
    f"is no longer near-zero cost")
assert off["speedup"] >= 1.5, off
assert on["speedup"] >= 1.5, (
    f"overlap contract broken WITH the recorder on: "
    f"{on['speedup']:.2f}x < 1.5x")


def noop(name):
    return None


n = 200_000
t_emit = timeit.timeit(lambda: flight.emit("health.degraded"), number=n)
t_noop = timeit.timeit(lambda: noop("health.degraded"), number=n)
print(json.dumps({"emit_us": round(t_emit / n * 1e6, 3),
                  "noop_us": round(t_noop / n * 1e6, 3)}))
# generous bound (loaded CI hosts): disabled emit within 10x a no-op
# call AND under 5us absolute — the faults.inject guard's exact bar
assert t_emit / n < 5e-6 and t_emit < 10 * t_noop + 0.05, (
    f"disabled flight.emit() costs {t_emit / n * 1e6:.2f}us/call "
    f"(no-op: {t_noop / n * 1e6:.2f}us)")
print("flight-recorder overhead guard ok")
PY

# Scoped self-check, same rationale as the fleet/streaming ones: the
# obs package (now carrying the recorder + SLO engine) must stay
# SDL001-SDL008 clean with no pragmas, with the flight-event catalog
# read explicitly from its one source of truth.
echo "== graftlint obs package self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/obs \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py

# Traffic-twin stage (ISSUE 16): the virtual-time load simulator and
# its closed control loops re-proven under chaos and a speed guard.
#   (a) the twin suite re-runs with SPARKDL_FAULTS carrying real twin.*
#       rules (the tests install their own plans over it, but the env
#       gate itself is then exercised: the bounded twin.tick sleep must
#       stretch only WALL time — byte determinism is asserted inside
#       the suite) and SPARKDL_LOCKCHECK=1 so the twin.clock lock feeds
#       the lock-order graph nested inside the serving locks;
#   (b) a scoped graftlint self-check over the new package;
#   (c) the speed guard: the canonical seeded day (>=100k virtual
#       requests across >=50 tenants against a REAL fleet) must run
#       TWICE, byte-identical, inside a pinned wall budget — the
#       "tier-1 seconds for a simulated day" acceptance bar.  Measured
#       ~13 s/run on an idle host; 120 s per run is the loaded-CI
#       ceiling before this counts as a performance regression.
echo "== traffic-twin suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=7;twin.tick:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_twin.py -q -m 'not slow'
echo "== graftlint twin package self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/twin \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py
echo "== traffic-twin speed guard (canonical day, twice) =="
env -u SPARKDL_FAULTS timeout -k 10 300 python - <<'PY'
import json
import time

import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.twin import (DEFAULT_TENANT_QUOTA, QuotaAutoscaler,
                              ScenarioConfig, run_day)

faults.clear()
BUDGET_S = 120.0
cfg = ScenarioConfig()  # the canonical 288-tick, 64-tenant day
walls = []
results = []
for _ in range(2):
    t0 = time.perf_counter()
    results.append(run_day(cfg, policy=QuotaAutoscaler(
        DEFAULT_TENANT_QUOTA)))
    walls.append(time.perf_counter() - t0)
r1, r2 = results
print(json.dumps({"wall_s": [round(w, 2) for w in walls],
                  "offered": r1.scores["offered"],
                  "tenants": r1.scores["tenants_active"],
                  "slo_minutes": r1.scores["slo_minutes"],
                  "goodput": r1.scores["goodput"],
                  "digest": r1.event_digest[:16]}))
assert r1.scores["offered"] >= 100_000, r1.scores
assert r1.scores["tenants_active"] >= 50, r1.scores
assert r1.event_digest == r2.event_digest, (
    "two runs of the canonical seeded day diverged — the twin's "
    "determinism contract is broken")
assert r1.scores == r2.scores
assert max(walls) <= BUDGET_S, (
    f"canonical day took {max(walls):.1f}s (budget {BUDGET_S:.0f}s) — "
    f"a simulated day no longer fits tier-1-compatible wall time")
print("traffic-twin speed guard ok")
PY

# Head fan-out stage (ISSUE 17): the shared-backbone serving tier
# re-proven under chaos, lock checking, and an overhead bound.
#   (a) the fan-out suite re-runs with SPARKDL_FAULTS carrying a real
#       head.dispatch rule (the tests install their own plans over it,
#       but the env gate itself is then exercised: a bounded sleep at
#       the head pass must stretch only wall time, never correctness)
#       and SPARKDL_LOCKCHECK=1 so the new named locks
#       (engine.headbank, serving.headfanout.swap) feed the lock-order
#       graph nested inside the serving and cache locks;
#   (b) a scoped graftlint self-check over the fan-out surfaces;
#   (c) the fan-out overhead guard: the full submit→featurize→head
#       path over a sleep-wrapped backbone must land within the
#       established 1.35x sleep-math bound — the gather/vmap head pass
#       and the feature probe may never add per-dispatch cost.
echo "== head fan-out suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=8;head.dispatch:sleep:ms=1,times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_headfanout.py -q
echo "== graftlint head fan-out modules self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/serving/server.py \
  sparkdl_tpu/serving/cache.py sparkdl_tpu/serving/fleet \
  sparkdl_tpu/parallel/engine.py \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py
echo "== head fan-out overhead guard =="
env -u SPARKDL_FAULTS timeout -k 10 300 python - <<'PY'
import json
import time

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.parallel.engine import head_fanout_backbone_fn
from sparkdl_tpu.serving.server import HeadFanoutServer

faults.clear()
rng = np.random.default_rng(8)
variables = {"backbone": rng.normal(size=(12, 16)).astype(np.float32)}
heads = {f"t{i:02d}": {
    "kernel": rng.normal(size=(16, 4)).astype(np.float32),
    "bias": rng.normal(size=(4,)).astype(np.float32)}
    for i in range(64)}
rows = [rng.normal(size=(12,)).astype(np.float32) for _ in range(6 * 32)]
dispatch_s = 0.05
# cache OFF: every request must ride the full backbone+head path, so
# the bound measures the fan-out machinery itself, not the cache win
srv = HeadFanoutServer(head_fanout_backbone_fn, variables, cache=False,
                       max_batch_size=32, max_wait_ms=5,
                       bucket_sizes=[32], max_inflight_batches=1,
                       max_queue=len(rows) + 16)
try:
    for t, h in heads.items():
        srv.add_head(t, h)
    srv.warmup(rows[0])
    srv.warm_head(np.zeros(16, np.float32))
    for b in srv.bucket_sizes:
        eng = srv.backbone._engine_for(b)
        real = eng.run_padded

        def slow(batch, _real=real):
            time.sleep(dispatch_s)
            return _real(batch)

        eng.run_padded = slow
    tenants = sorted(heads)
    t0 = time.perf_counter()
    futs = [srv.submit(r, tenants[i % len(tenants)])
            for i, r in enumerate(rows)]
    for f in futs:
        f.result(timeout=60)
    wall = time.perf_counter() - t0
finally:
    srv.close()
ideal = (len(rows) // 32) * dispatch_s
print(json.dumps({"ideal_s": round(ideal, 3),
                  "fanout_wall_s": round(wall, 3),
                  "tenants": len(tenants)}))
assert wall <= 1.35 * ideal, (
    f"fan-out serving wall {wall:.3f}s exceeds 1.35x the "
    f"{ideal:.3f}s sleep-math ideal — the head fan-out path has "
    f"grown per-request overhead")
print("head fan-out overhead guard ok")
PY

# Cost-ledger stage (ISSUE 18): the hardware-attribution layer and its
# regression sentinel re-proven under chaos, lock checking, and the
# overhead bounds.
#   (a) the cost suite re-runs with SPARKDL_FAULTS carrying a real
#       cost.attr rule (the tests install their own plans over it, but
#       the env gate itself is then exercised: an injected attribution
#       error must degrade to the error counters, never fail a request
#       or corrupt results) and SPARKDL_LOCKCHECK=1 so the new named
#       locks (obs.cost, obs.cost.configure) feed the lock-order graph
#       nested inside the serving/engine locks;
#   (b) a scoped graftlint self-check over the ledger + the showback
#       CLI;
#   (c) the cost-overhead guard: with SPARKDL_COST unset the serving
#       stack must stay within the established 1.35x sleep-math bound
#       (attribution off means ONE resolve at server construction,
#       zero per-dispatch work), and a disabled ledger's record_batch()
#       must stay within 10x a no-op call — the disabled-tracing/
#       inject/recorder guards' exact bar.
echo "== cost-ledger suite (SPARKDL_FAULTS active) =="
SPARKDL_FAULTS="seed=9;cost.attr:error:times=2" \
  SPARKDL_LOCKCHECK=1 \
  timeout -k 10 300 python -m pytest tests/test_cost.py -q
echo "== graftlint cost modules self-check =="
timeout -k 5 15 python tools/graftlint.py sparkdl_tpu/obs/cost.py \
  tools/costreport.py \
  --sites-file sparkdl_tpu/faults/sites.py \
  --events-file sparkdl_tpu/obs/flight.py
echo "== cost-overhead guard =="
env -u SPARKDL_FAULTS -u SPARKDL_COST python - <<'PY'
import json
import time
import timeit

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults
from sparkdl_tpu.obs import cost as cost_module
from sparkdl_tpu.obs.cost import CostLedger
from sparkdl_tpu.serving.server import Server

faults.clear()
cost_module.configure(None)  # SPARKDL_COST unset equivalent


def fn(v, x):
    import jax.numpy as jnp

    return jnp.tanh(x * v["s"] + 0.25)


rng = np.random.default_rng(9)
rows = [rng.normal(size=(8,)).astype(np.float32) for _ in range(6 * 32)]
dispatch_s = 0.05
srv = Server(fn, {"s": np.float32(2.0)}, max_batch_size=32,
             max_wait_ms=5, bucket_sizes=[32], max_inflight_batches=1,
             cache=False)
try:
    srv.warmup(rows[0])  # compile BEFORE the sleep wrap
    for b in srv.bucket_sizes:
        eng = srv._engine_for(b)
        real = eng.run_padded

        def slow(batch, _real=real):
            time.sleep(dispatch_s)
            return _real(batch)

        eng.run_padded = slow
    t0 = time.perf_counter()
    futs = [srv.submit(r, tenant=f"t{i % 8}") for i, r in enumerate(rows)]
    for f in futs:
        f.result(timeout=60)
    wall = time.perf_counter() - t0
finally:
    srv.close()
ideal = (len(rows) // 32) * dispatch_s
print(json.dumps({"ideal_s": round(ideal, 3),
                  "cost_off_wall_s": round(wall, 3)}))
assert wall <= 1.35 * ideal, (
    f"attribution-off serving wall {wall:.3f}s exceeds 1.35x the "
    f"{ideal:.3f}s sleep-math ideal — the SPARKDL_COST-unset path is "
    f"no longer near-zero cost")

disabled = CostLedger(enabled=False)
tenant_rows = {"a": 8}


def charge():
    disabled.record_batch(model="m", bucket=8, tenant_rows=tenant_rows,
                          device_s=0.001)


def noop():
    return None


n = 200_000
t_probe = timeit.timeit(cost_module.get_default, number=n)
t_charge = timeit.timeit(charge, number=n)
t_noop = timeit.timeit(noop, number=n)
print(json.dumps({"probe_us": round(t_probe / n * 1e6, 3),
                  "disabled_record_us": round(t_charge / n * 1e6, 3),
                  "noop_us": round(t_noop / n * 1e6, 3)}))
# generous bounds (loaded CI hosts): the disabled default-ledger probe
# and a disabled ledger's record_batch() each within 10x a no-op call
# AND under 5us absolute — the established bar
assert t_probe / n < 5e-6 and t_probe < 10 * t_noop + 0.05, (
    f"disabled cost probe costs {t_probe / n * 1e6:.2f}us/call "
    f"(no-op: {t_noop / n * 1e6:.2f}us)")
assert t_charge / n < 5e-6 and t_charge < 10 * t_noop + 0.05, (
    f"disabled record_batch() costs {t_charge / n * 1e6:.2f}us/call "
    f"(no-op: {t_noop / n * 1e6:.2f}us)")
print("cost-overhead guard ok")
PY
