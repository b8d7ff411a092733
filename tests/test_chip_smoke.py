"""chip_smoke.py off the chip: it refuses to run here, and every phase it
would run there passes at toy size once the test steers the platform
check.

What is steered, and from here only (the script has no option for it):
``chip_smoke.PLATFORM`` (the platform a run must find) and
``chip_smoke.seed_zoo_weights`` (a two-layer stand-in for the zoo models,
so a CPU compiles it in milliseconds).  Everything else — readImages,
the transformers, the server, the estimators, the engines, the train
step — is the real code.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(models=("ResNet50",), n_images=31,
                       serve_model="ResNet50", serve_batch=8,
                       serve_requests=14, fit_epochs=20, cnn_epochs=3,
                       chips_batch=8, chips_train_rows=128)


class _TinyZoo:
    """pooled pixels -> dense -> tanh, at the real feature width."""

    def apply(self, v, x, train=False, features=False):
        import jax.numpy as jnp

        head = v["params"]["head"]
        return jnp.tanh(jnp.mean(x, axis=(1, 2)) @ head["kernel"]
                        + head["bias"])


def _seed_tiny(name):
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.transformers.named_image import set_zoo_model

    spec = get_model_spec(name)
    rng = np.random.default_rng(0)
    variables = {"params": {"head": {
        "kernel": rng.normal(size=(3, spec.feature_size)).astype(np.float32),
        "bias": np.zeros((spec.feature_size,), np.float32)}}}
    set_zoo_model(name, _TinyZoo(), variables)


@pytest.fixture()
def steered(monkeypatch):
    from sparkdl_tpu.parallel.engine import clear_engine_jit_cache
    from sparkdl_tpu.transformers import named_image

    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "seed_zoo_weights", _seed_tiny)
    named_image.clear_model_caches()
    yield
    named_image.clear_model_caches()
    clear_engine_jit_cache()


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory):
    return chip_smoke.make_images(str(tmp_path_factory.mktemp("smoke")),
                                  224, TOY.n_images)


def _run_script(*argv, env=None, cwd=REPO, script=None):
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         *argv], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


# -- here it must fail ---------------------------------------------------------

def test_exits_nonzero_on_the_cpu_and_says_why():
    """No accelerator: a non-zero exit, the reason on stderr, and NO
    result line on stdout — the driver runs it here first, expecting
    exactly that."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_script(env=env)
    assert proc.returncode == 2
    assert "platform 'cpu', not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_four_chip_option_exits_nonzero_on_the_cpu_too(capsys):
    assert chip_smoke.main(["--chips", "4"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "platform 'cpu', not 'tpu'" in err


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script without the program is not a proof of anything."""
    import shutil

    alone = shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run_script(env=env, cwd=str(tmp_path), script=alone)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "sparkdl_tpu" in proc.stderr


def test_wrong_device_count_is_refused(steered):
    with pytest.raises(chip_smoke.CheckFailed, match="expected 1 device"):
        chip_smoke.phase_environment(1)  # tier-1 has 8 virtual devices


# -- there, every phase: toy size, platform steered ----------------------------

def test_phase_decoder(image_dir):
    obs = chip_smoke.phase_decoder(image_dir)
    assert obs["decoder"] == "native"
    assert obs["source_sha256"][:16] in obs["path"]


def test_phase_decoder_fails_when_the_native_core_did_not_build(
        image_dir, monkeypatch):
    import sparkdl_tpu.native as native

    monkeypatch.setattr(native, "library_info", lambda: {"decoder": "pil"})
    with pytest.raises(chip_smoke.CheckFailed, match="did not build"):
        chip_smoke.phase_decoder(image_dir)


def test_phase_compile_cache_fails_when_the_cache_does_not_come_up(
        monkeypatch, tmp_path):
    """What a server shrugs off (a cache that degrades to off) is a
    failed phase here."""
    from sparkdl_tpu import faults
    from sparkdl_tpu.parallel import compile_cache

    monkeypatch.delenv(compile_cache.PLACED_DIR_ENV, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", str(tmp_path / "cc"))
    try:
        with faults.active(faults.FaultPlan.parse(
                "seed=1;compile.cache:error:times=1")):
            with pytest.raises(chip_smoke.CheckFailed,
                               match="did not come up"):
                chip_smoke.phase_compile_cache()
        obs = chip_smoke.phase_compile_cache()
        assert obs["dir"] == str(tmp_path / "cc") and not obs["placed"]
    finally:
        compile_cache._reset_for_tests()


@pytest.fixture()
def batch_features(steered, image_dir):
    chip_smoke.seed_zoo_weights("ResNet50")
    obs, feats = chip_smoke.phase_featurize("ResNet50", "float32",
                                            image_dir)
    return obs, feats


def test_phase_featurize_both_dtypes(batch_features, image_dir):
    obs, feats = batch_features
    assert obs["batch"] == 64  # the transformer's default, not a toy one
    assert feats.shape == (TOY.n_images, 2048)
    assert obs["pipeline_stages"]["pipeline.dispatches"] >= 1
    obs16, feats16 = chip_smoke.phase_featurize(
        "ResNet50", "bfloat16", image_dir, reference=feats)
    assert obs16["dtype"] == "bfloat16" and "rel_err_vs_float32" in obs16
    assert feats16.shape == feats.shape


def test_phase_featurize_fails_on_a_lost_null_row(steered, tmp_path):
    """Every file decodes, so no row is null: the check that the
    undecodable file stays a null row must notice."""
    d = chip_smoke.make_images(str(tmp_path), 224, 3)
    os.remove(os.path.join(d, "zz_not_an_image.jpg"))
    chip_smoke.seed_zoo_weights("ResNet50")
    with pytest.raises(chip_smoke.CheckFailed, match="null row"):
        chip_smoke.phase_featurize("ResNet50", "float32", d)


def test_phase_serving_reaches_two_buckets_and_matches_the_batch_path(
        batch_features, image_dir):
    import dataclasses

    # this process has 8 virtual devices, which round every bucket up to
    # a multiple of 8: the real plan (8/16/32) is the smallest with three
    sizes = dataclasses.replace(TOY, serve_batch=32, serve_requests=30)
    _, feats = batch_features
    obs = chip_smoke.phase_serving(sizes, image_dir, feats)
    assert obs["bucket_plan"] == [8, 16, 32]
    assert len(obs["buckets_served"]) >= 2
    assert obs["requests"] == 30
    # and a wrong answer is caught: serve against shifted features
    with pytest.raises(chip_smoke.CheckFailed, match="differ from the batch"):
        chip_smoke.phase_serving(sizes, image_dir, feats + 1.0)


def test_phase_fit_pipeline_and_estimator(steered, image_dir):
    chip_smoke.seed_zoo_weights("ResNet50")
    obs = chip_smoke.phase_fit_pipeline(TOY, image_dir)
    assert obs["rows"] == TOY.n_images
    assert obs["loss_final"] < obs["loss_initial"]
    obs = chip_smoke.phase_fit_cnn(TOY, image_dir)
    assert obs["loss_final"] < obs["loss_initial"]


def test_xception_kernel_phase_fails_where_the_reference_path_ran(
        steered, monkeypatch):
    """On the CPU the kernels give way to the reference path, which is
    right for tests and exactly what must not pass for a chip run: a
    compiled program with no custom call fails the phase."""
    import jax

    from sparkdl_tpu.parallel.engine import InferenceEngine

    eng = InferenceEngine(lambda v, x: x.astype("float32").mean(axis=(1, 2)),
                          {}, mesh=None, device_batch_size=8)
    monkeypatch.setattr(chip_smoke, "_engine_that_ran",
                        lambda name, dtype: eng)
    assert jax.default_backend() == "cpu"
    with pytest.raises(chip_smoke.CheckFailed, match="no Pallas kernel"):
        chip_smoke.phase_xception_kernel("unused", None)


# -- the driver: lines, exit codes, the four-chip switch ------------------------

_STEER = """
import sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import chip_smoke
import test_chip_smoke as t
chip_smoke.PLATFORM = "cpu"
chip_smoke.seed_zoo_weights = t._seed_tiny
sys.exit(chip_smoke.main({argv!r}, sizes=t.TOY))
"""


def _run_steered(argv, n_devices, tmp_path, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}",
               SPARKDL_COMPILE_CACHE=str(tmp_path / "cc"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-c", _STEER.format(
            repo=REPO, tests=os.path.join(REPO, "tests"), argv=list(argv))],
        env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


def test_whole_run_one_device_line_shapes_and_second_run_hits_the_cache(
        tmp_path):
    """The run the driver makes, end to end at toy size: one JSON object
    per phase, every phase ok, and the LAST line exactly the contract's.
    Run again over the same cache directory, the programs the first run
    built are hits, not misses."""
    proc, lines = _run_steered([], 1, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert proc.stdout.rstrip().splitlines()[-1] == json.dumps(lines[-1])
    phases = [ln["phase"] for ln in lines[:-1]]
    assert phases == [
        "environment", "compile_cache", "decoder",
        "featurize/ResNet50/float32", "featurize/ResNet50/bfloat16",
        "serving", "fit/pipeline", "fit/image_file_estimator", "summary"]
    assert all(ln["ok"] for ln in lines)
    first = lines[-2]["compile_cache"]
    assert first["dir"] == str(tmp_path / "cc") and first["misses"] > 0

    proc, lines = _run_steered([], 1, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    second = lines[-2]["compile_cache"]
    assert second["hits"] >= first["misses"] - first["hits"] > 0
    assert second["misses"] == 0, second
    assert lines[1]["phase"] == "compile_cache" and lines[1]["reused"]


def test_four_chip_option_runs_only_the_cross_chip_phases(tmp_path):
    """``--chips 4`` on four (virtual) devices: the cross-chip phases and
    nothing else, placement asserted, ``count`` 4 in the last line."""
    proc, lines = _run_steered(["--chips", "4"], 4, tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert [ln["phase"] for ln in lines[:-1]] == [
        "environment", "compile_cache", "chips/featurize",
        "chips/xception_program", "chips/train", "summary"]
    feat = lines[2]
    assert feat["dp4"]["mesh"] == {"data": 4, "model": 1}
    assert feat["dp4"]["output_shard"] == [2, 2048]
    assert feat["dp2xtp2"]["mesh"] == {"data": 2, "model": 2}
    assert feat["dp2xtp2"]["kernel_shard"] == [3, 1024]
    assert lines[3]["program"] == "xla lowering"
    assert lines[4]["batch_shard"] == [16, 2048]


def test_four_chip_option_on_one_device_is_refused(tmp_path):
    proc, lines = _run_steered(["--chips", "4"], 1, tmp_path)
    assert proc.returncode == 2 and lines == []
    assert "expected 4 device(s)" in proc.stderr


def test_a_failed_phase_fails_the_run_and_the_rest_still_report(tmp_path):
    """No phase's failure is swallowed into an exit 0: break one (the
    native core "did not build") and the run exits non-zero with that
    phase's line ``ok: false``, the last line ``ok: false`` — and the
    phases after it still ran."""
    proc, lines = _run_steered(
        [], 1, tmp_path, extra_env={"SPARKDL_TPU_DISABLE_NATIVE": "1"})
    assert proc.returncode == 1
    by_phase = {ln.get("phase"): ln for ln in lines[:-1]}
    assert by_phase["decoder"]["ok"] is False
    assert "did not build" in by_phase["decoder"]["error"]
    assert by_phase["fit/pipeline"]["ok"] is True
    assert lines[-1]["ok"] is False and lines[-1]["device"]["count"] == 1
    assert "Traceback" in proc.stderr
