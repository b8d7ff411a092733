"""Compile-once guarantees across param maps and folds (VERDICT round 1,
Missing/Weak #3 — SURVEY.md §7 hard part #5), and — since ISSUE 13 —
compile-once guarantees across PROCESS RESTARTS: the persistent
compilation cache (``parallel.compile_cache``, ``SPARKDL_COMPILE_CACHE``)
keyed on the committed ``PROGRAMS.lock.json``.

A tuning grid must not pay one XLA compile per (map, fold): the TrainStep
cache keys on (predict fn, loss, optimizer, mesh) and jax.jit's own
executable cache de-duplicates equal batch shapes, so the whole grid
compiles once.  Same for inference: fitted models over one fn share the
compiled program.  And a fleet redeploy / serving cold-start over an
unchanged lockfile must not re-jit at all — the subprocess-restart test
below is the cross-process half of PR 7's hot-swap recompile-free proof.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sparkdl_tpu.parallel import compile_cache

from sparkdl_tpu.estimators import (CrossValidator, ImageFileEstimator,
                                    MulticlassClassificationEvaluator)
from sparkdl_tpu.frame import DataFrame
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.parallel import train as train_lib
from sparkdl_tpu.parallel.engine import InferenceEngine, clear_engine_jit_cache
from sparkdl_tpu.parallel.train import (clear_train_step_cache,
                                        fit_data_parallel, make_train_step)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_train_step_cache()
    clear_engine_jit_cache()
    yield
    clear_train_step_cache()
    clear_engine_jit_cache()


def _counting_predict():
    traces = []

    def predict(p, xb):
        import jax.numpy as jnp

        traces.append(1)  # increments once per TRACE, not per step
        return jnp.asarray(xb).reshape(xb.shape[0], -1) @ p["w"]

    return predict, traces


def test_make_train_step_returns_same_object_for_same_key():
    import optax

    predict, _ = _counting_predict()
    opt = optax.sgd(0.1)
    s1 = make_train_step(predict, "mse", opt)
    s2 = make_train_step(predict, "mse", opt)
    assert s1 is s2
    # different loss -> different step
    s3 = make_train_step(predict, "mae", opt)
    assert s3 is not s1


def test_repeated_fits_trace_once():
    import optax

    predict, traces = _counting_predict()
    opt = optax.sgd(0.1)
    x = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
    y = (x @ np.ones((4, 1), np.float32))

    params = {"w": np.zeros((4, 1), np.float32)}
    fit_data_parallel(predict, params, x, y, optimizer=opt, loss="mse",
                      batch_size=8, epochs=2)
    first = len(traces)
    assert first >= 1
    # 3 more fits, same shapes/opt/loss: ZERO new traces
    for _ in range(3):
        fit_data_parallel(predict, params, x, y, optimizer=opt, loss="mse",
                          batch_size=8, epochs=1)
    assert len(traces) == first


def test_default_optimizer_is_stable_across_fits():
    predict, traces = _counting_predict()
    x = np.zeros((16, 4), np.float32)
    y = np.zeros((16, 1), np.float32)
    params = {"w": np.zeros((4, 1), np.float32)}
    fit_data_parallel(predict, params, x, y, loss="mse", batch_size=8,
                      epochs=1)
    first = len(traces)
    fit_data_parallel(predict, params, x, y, loss="mse", batch_size=8,
                      epochs=1)
    assert len(traces) == first  # optimizer=None resolved to one instance


def _loader(uri):
    from PIL import Image

    img = Image.open(uri).convert("RGB").resize((8, 8))
    return np.asarray(img, dtype=np.float32) / 255.0


def test_grid_times_folds_compiles_once(fixture_images):
    """4 param maps x 3 folds + the final refit: one trace total for the
    train step and one for inference."""
    import jax.numpy as jnp

    train_traces = []
    rng = np.random.default_rng(0)
    variables = {"w": rng.normal(0, 0.01, (8 * 8 * 3, 2)).astype(np.float32)}

    def fn(v, xb):
        train_traces.append(1)
        logits = xb.reshape(xb.shape[0], -1) @ v["w"]
        return jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1,
                                         keepdims=True)

    mf = ModelFunction(fn=fn, variables=variables)
    paths = fixture_images["paths"] * 8  # 24 rows
    labels = [i % 2 for i in range(len(paths))]
    df = DataFrame({
        "uri": paths,
        "label": [[1.0, 0.0] if l == 0 else [0.0, 1.0] for l in labels],
        "labelIdx": np.asarray(labels, np.int64),
    })

    est = ImageFileEstimator(
        inputCol="uri", outputCol="preds", labelCol="label",
        modelFunction=mf, imageLoader=_loader, optimizer="sgd",
        loss="categorical_crossentropy", fitParams={"epochs": 1},
        batchSize=8)
    maps = [{est.fitParams: {"epochs": e, "seed": s}}
            for e in (1, 2) for s in (0, 1)]  # 4 maps
    ev = MulticlassClassificationEvaluator(predictionCol="preds",
                                           labelCol="labelIdx")
    cv = CrossValidator(estimator=est, estimatorParamMaps=maps,
                        evaluator=ev, numFolds=3)
    model = cv.fit(df)
    assert len(model.avgMetrics) == 4
    # fn traces: once for the train step (inside value_and_grad) and once
    # for the inference engine — NOT once per (map, fold).
    assert len(train_traces) <= 3, (
        f"expected <=3 traces for 4 maps x 3 folds, got {len(train_traces)}")


# ---------------------------------------------------------------------------
# persistent compilation cache (ISSUE 13): compile-once across restarts
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a restarted serving process: build a Server over a tiny fn, warm one
#: bucket, serve a fixed replay, and report the persistent-cache state,
#: hit/miss counters, and an output digest on stdout.
_CHILD = """
import hashlib, json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from sparkdl_tpu.serving.server import Server
from sparkdl_tpu.parallel import compile_cache

def fn(v, x):
    import jax.numpy as jnp
    return jnp.tanh(x * v["s"] + 0.25)

rng = np.random.default_rng(7)
rows = [rng.normal(size=(6,)).astype(np.float32) for _ in range(6)]
with Server(fn, {{"s": np.float32(3.0)}}, max_batch_size=8,
            max_wait_ms=2, bucket_sizes=[8], cache=False) as srv:
    srv.warmup(rows[0])
    outs = [np.asarray(srv.predict(r)) for r in rows]
digest = hashlib.sha256(b"".join(o.tobytes() for o in outs)).hexdigest()
print(json.dumps({{"state": compile_cache.state(),
                   "stats": compile_cache.stats(),
                   "digest": digest}}))
"""


def _run_restart(cache_dir, placed=False):
    env = dict(os.environ)
    env.pop("SPARKDL_COMPILE_CACHE", None)
    env.pop(compile_cache.PLACED_DIR_ENV, None)
    env[compile_cache.PLACED_DIR_ENV if placed
        else "SPARKDL_COMPILE_CACHE"] = str(cache_dir)
    env.pop("SPARKDL_FAULTS", None)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=_REPO)],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture
def _fresh_compile_cache_state(monkeypatch):
    # the tests that resolve the environment start from one that places
    # nothing (an explicit ``configure(dir)`` means ``dir`` regardless)
    monkeypatch.delenv(compile_cache.PLACED_DIR_ENV, raising=False)
    yield
    compile_cache._reset_for_tests()


def test_restart_serves_lockfile_pinned_programs_with_zero_fresh_compiles(
        tmp_path):
    """THE cross-process proof: process A compiles and populates the
    on-disk cache; a restarted process B serving the same programs
    performs ZERO fresh compiles (every compile request is a persistent
    hit) with bit-identical outputs; tampering the manifest's committed
    fingerprint then forces a clean purge + recompile — classified
    drift, no stale executable served, outputs still bit-identical."""
    cache_dir = tmp_path / "cc"
    a = _run_restart(cache_dir)
    assert a["state"]["dir"] == str(cache_dir)
    assert a["state"]["reused"] is False
    assert a["stats"]["misses"] > 0          # populated the cache
    assert a["stats"]["hits"] == 0

    b = _run_restart(cache_dir)
    assert b["state"]["reused"] is True      # manifest matched the lockfile
    assert b["state"]["invalidated"] is False
    assert b["stats"]["misses"] == 0, b      # zero fresh compiles
    assert b["stats"]["hits"] > 0
    assert b["digest"] == a["digest"]        # bit-identical serving

    manifest = cache_dir / compile_cache.MANIFEST_NAME
    doc = json.loads(manifest.read_text())
    name = sorted(doc["programs"])[0]
    doc["programs"][name]["fingerprint"] = "0" * 64
    manifest.write_text(json.dumps(doc))
    c = _run_restart(cache_dir)
    assert c["state"]["invalidated"] is True
    assert c["state"]["drift_rules"] == ["GC000"]  # fingerprint-only drift
    assert c["state"]["purged_entries"] > 0
    assert c["stats"]["hits"] == 0           # nothing stale was served
    assert c["stats"]["misses"] > 0          # clean recompile
    assert c["digest"] == a["digest"]


def test_compile_cache_env_grammar(monkeypatch):
    """``(directory, placed)`` from the two variables, read in ONE
    place."""
    resolve = compile_cache._resolve_env
    monkeypatch.delenv(compile_cache.PLACED_DIR_ENV, raising=False)
    monkeypatch.delenv("SPARKDL_COMPILE_CACHE", raising=False)
    assert resolve() == (None, False)
    for off in ("0", "false", "off", "no"):
        monkeypatch.setenv("SPARKDL_COMPILE_CACHE", off)
        assert resolve() == (None, False)
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "1")
    assert resolve() == (compile_cache.DEFAULT_DIR, False)
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "/somewhere/else")
    assert resolve() == ("/somewhere/else", False)
    # a placed directory wins over the module's own knob, on or off
    monkeypatch.setenv(compile_cache.PLACED_DIR_ENV, "/placed")
    assert resolve() == ("/placed", True)
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "0")
    assert resolve() == ("/placed", True)


def test_default_dir_is_fixed_inside_the_checkout():
    """The fallback directory the entry points share: inside the
    checkout, git-ignored, and a constant — two runs of one checkout
    meet in it (a name made from a pid, the time or mkdtemp never
    hits)."""
    assert compile_cache.DEFAULT_DIR == os.path.join(_REPO,
                                                     ".compile_cache")
    ignored = open(os.path.join(_REPO, ".gitignore")).read().split()
    assert ".compile_cache/" in ignored


def test_configure_default_uses_the_fixed_dir_when_nothing_is_placed(
        monkeypatch, tmp_path, _fresh_compile_cache_state):
    import jax

    monkeypatch.delenv("SPARKDL_COMPILE_CACHE", raising=False)
    fixed = str(tmp_path / "fixed")
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", fixed)
    st = compile_cache.configure_default()
    assert st["dir"] == fixed and st["placed"] is False
    assert jax.config.jax_compilation_cache_dir == fixed
    # the module's own knob still names a directory for entry points
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", str(tmp_path / "knob"))
    assert compile_cache.configure_default()["dir"] == str(
        tmp_path / "knob")


def test_placed_dir_is_the_cache_and_jax_is_pointed_nowhere_else(
        monkeypatch, tmp_path, _fresh_compile_cache_state):
    """``JAX_COMPILATION_CACHE_DIR`` set: both ways in that read the
    environment — an entry point, the engine probe — resolve to that
    directory, whatever the module's own knob says, and no
    ``jax.config.update`` names a cache directory at all (JAX read the
    variable itself)."""
    import jax

    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.PLACED_DIR_ENV, placed)
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", str(tmp_path / "knob"))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    st = compile_cache.configure_default()
    assert st["dir"] == placed and st["placed"] is True
    compile_cache._reset_for_tests()
    st = compile_cache.ensure_from_env(policy="mesh=1x1")
    assert st["dir"] == placed and st["placed"] is True
    compile_cache._reset_for_tests()
    assert "jax_enable_compilation_cache" in updates  # the spy works
    assert "jax_compilation_cache_dir" not in updates
    assert not (tmp_path / "knob").exists()


def test_explicit_configure_means_that_directory(
        monkeypatch, tmp_path, _fresh_compile_cache_state):
    """The environment is resolved in one place, by the two callers
    that ask it: ``configure(dir)`` is ``dir``, owned by the module,
    even where the installation placed another — and forgetting it puts
    JAX back where it was."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.PLACED_DIR_ENV, str(tmp_path / "placed"))
    st = compile_cache.configure(str(tmp_path / "mine"))
    assert st["dir"] == str(tmp_path / "mine") and st["placed"] is False
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "mine")
    assert (tmp_path / "mine" / compile_cache.MANIFEST_NAME).is_file()
    assert not (tmp_path / "placed").exists()
    compile_cache._reset_for_tests()
    assert jax.config.jax_compilation_cache_dir == before


def test_placed_dir_holds_no_manifest_and_is_never_purged(
        monkeypatch, tmp_path, _fresh_compile_cache_state):
    """A placed directory may be shared between the parent commit and
    the change, so the module owns nothing in it: no manifest is
    written (two commits would overwrite each other's on every start
    and each report the other as an invalidation), whatever is there
    stays — even a manifest that has drifted from the lockfile, which
    in a directory the module chose purges — and no
    ``compile.invalidate`` event tells an operator of a cold start
    that is not one."""
    def drifted(d):
        st = compile_cache.configure(str(d), policy="mesh=1x1")
        assert st["invalidated"] is False
        (d / "jit_other_checkout-entry").write_bytes(b"executable")
        manifest = d / compile_cache.MANIFEST_NAME
        doc = json.loads(manifest.read_text())
        name = sorted(doc["programs"])[0]
        doc["programs"][name]["fingerprint"] = "0" * 64
        manifest.write_text(json.dumps(doc))
        return manifest.read_text()

    chosen = tmp_path / "chosen"
    drifted(chosen)
    st = compile_cache.configure(str(chosen), policy="mesh=1x1")
    assert st["invalidated"] and st["purged_entries"] == 1
    assert not (chosen / "jit_other_checkout-entry").exists()

    placed = tmp_path / "placed"
    foreign = drifted(placed)  # as another checkout might have left it
    monkeypatch.setenv(compile_cache.PLACED_DIR_ENV, str(placed))
    events = []
    monkeypatch.setattr(compile_cache, "flight_emit",
                        lambda name, **attrs: events.append(name))
    for policy in ("mesh=1x1", "mesh=2x2|params=abc"):
        compile_cache._reset_for_tests()
        st = compile_cache.ensure_from_env(policy=policy)
        assert st["placed"] and st["reused"] is None
        assert st["invalidated"] is False
        assert (placed / "jit_other_checkout-entry").exists()
        assert (placed / compile_cache.MANIFEST_NAME).read_text() == foreign
    assert events == ["compile.persist"] * 2
    empty = tmp_path / "empty"
    monkeypatch.setenv(compile_cache.PLACED_DIR_ENV, str(empty))
    compile_cache._reset_for_tests()
    assert compile_cache.configure_default()["dir"] == str(empty)
    assert os.listdir(empty) == []


def test_configuring_the_cache_initialises_no_backend(tmp_path):
    """``bench.py`` configures the cache and THEN starts its chip-free
    children, which it may only do while it does not hold the chip: a
    configure that asks JAX for its platform takes the chip first.  A
    platform JAX cannot initialise proves it — any backend access in
    this child raises — for a directory the module owns (manifest
    written) and for a placed one."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {_REPO!r})\n"
        "from sparkdl_tpu.parallel import compile_cache\n"
        "st = compile_cache.configure_default()\n"
        "assert st is not None, 'configure degraded: it touched JAX'\n"
        "import jax\n"
        "try:\n"
        "    jax.devices()\n"
        "except RuntimeError:\n"
        "    print(json.dumps(st))\n"
        "else:\n"
        "    raise SystemExit('the bogus platform initialised?')\n")
    for var in ("SPARKDL_COMPILE_CACHE", compile_cache.PLACED_DIR_ENV):
        env = {k: v for k, v in os.environ.items()
               if k not in ("SPARKDL_COMPILE_CACHE",
                            compile_cache.PLACED_DIR_ENV, "SPARKDL_FAULTS")}
        env["JAX_PLATFORMS"] = "no_such_platform"
        env[var] = str(tmp_path / var)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120,
                           env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        st = json.loads(r.stdout.strip().splitlines()[-1])
        assert st["dir"] == str(tmp_path / var)
        assert st["placed"] is (var == compile_cache.PLACED_DIR_ENV)


def test_placed_restart_hits_without_fresh_compiles(tmp_path):
    """The cross-process proof again, through the placed variable
    alone: a second process finds what the first compiled."""
    placed = tmp_path / "placed"
    a = _run_restart(placed, placed=True)
    assert a["state"]["dir"] == str(placed) and a["state"]["placed"]
    assert a["stats"]["misses"] > 0 and a["stats"]["hits"] == 0
    b = _run_restart(placed, placed=True)
    assert b["stats"]["misses"] == 0 and b["stats"]["hits"] > 0, b
    assert b["digest"] == a["digest"]


def test_compile_cache_disabled_by_default(monkeypatch,
                                           _fresh_compile_cache_state):
    monkeypatch.delenv("SPARKDL_COMPILE_CACHE", raising=False)
    compile_cache._reset_for_tests()
    assert compile_cache.ensure_from_env() is None
    assert compile_cache.state() is None
    assert compile_cache.enabled() is False


def test_compile_cache_drift_classified_to_gc_rule(
        tmp_path, _fresh_compile_cache_state):
    """A manifest whose stored program records drifted in a TRACKED
    field classifies back to the rule whose invariant moved (GC002
    here: a dtype-mix change), not just generic fingerprint drift."""
    st = compile_cache.configure(str(tmp_path / "cc"))
    assert st is not None and st["invalidated"] is False
    manifest = tmp_path / "cc" / compile_cache.MANIFEST_NAME
    doc = json.loads(manifest.read_text())
    name = sorted(doc["programs"])[0]
    doc["programs"][name]["dtype_counts"] = {"conv_f32": 999}
    manifest.write_text(json.dumps(doc))
    st2 = compile_cache.configure(str(tmp_path / "cc"))
    assert st2["invalidated"] is True
    assert st2["drift_rules"] == ["GC002"]


def test_compile_cache_injected_fault_degrades_to_fresh_compiles(
        tmp_path, _fresh_compile_cache_state):
    """The ``compile.cache`` chaos contract: a corrupt cache dir (an
    injected configure-time error) disables the cache — serving
    continues on fresh compiles, nothing raises."""
    from sparkdl_tpu import faults

    with faults.active(faults.FaultPlan.parse(
            "seed=9;compile.cache:error:times=1")):
        assert compile_cache.configure(str(tmp_path / "cc")) is None
    assert compile_cache.state() is None
    # the same dir configures fine once the fault is gone
    assert compile_cache.configure(str(tmp_path / "cc")) is not None


def test_engines_share_compiled_program_across_weight_sets():
    def fn(v, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ v["w"])

    rng = np.random.default_rng(1)
    v1 = {"w": rng.normal(size=(4, 3)).astype(np.float32)}
    v2 = {"w": rng.normal(size=(4, 3)).astype(np.float32)}
    e1 = InferenceEngine(fn, v1, device_batch_size=8)
    e2 = InferenceEngine(fn, v2, device_batch_size=8)
    assert e1._compiled is e2._compiled  # one program, two weight sets
    x = rng.normal(size=(8, 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(e1(x)), np.tanh(x @ v1["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(e2(x)), np.tanh(x @ v2["w"]),
                               rtol=1e-5, atol=1e-6)
