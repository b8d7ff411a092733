"""The harness is driven by data: it finds cells, configurations,
traffic mixes and per-layer metrics by name, refuses a name it cannot
find, builds its traffic from the seed alone, and does not measure
without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests import toy


@pytest.fixture(autouse=True)
def _restore_environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


def _bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_every_name_in_benchmark_json_has_its_file():
    bench = _bench()
    for w in bench["workloads"]:
        cell = harness.load_cell(harness.ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.config["reduced"] == []
        harness.find_generator(cell.traffic["generator"])
        assert {m["name"] for m in cell.end_to_end} == {"images_per_s",
                                                        "setup_s"}
    for m in bench["per_layer"]:
        assert callable(harness.find_reader(m["name"]).read)
    for m in bench["end_to_end"]:
        assert callable(harness.find_end_to_end(m["name"]).read)


def test_a_cell_reports_the_metrics_that_list_it(tmp_path):
    names = lambda cell: {m["name"] for m in cell.per_layer}  # noqa: E731
    root, _ = toy.make_root(tmp_path)
    jpeg = harness.load_cell(root, "inceptionv3.jpeg")
    structs = harness.load_cell(root, "inceptionv3.f32.structs")
    assert names(jpeg) - names(structs) == {"decode_ms_per_image"}
    assert names(jpeg) == {m["name"] for m in _bench()["per_layer"]}


@pytest.mark.parametrize("call,arg", [
    (lambda a: harness.load_cell(harness.ROOT, a), "no.such.cell"),
    (harness.find_generator, "no_such_generator"),
    (harness.find_reader, "no_such_metric"),
    (harness.find_reader, "../run"),
    (harness.find_end_to_end, "no_such_metric"),
    (harness.load_peak, "TPU v9 imaginary"),
])
def test_an_unknown_name_is_refused(call, arg):
    with pytest.raises(harness.BenchmarkError):
        call(arg)


def test_the_program_runs_at_the_precision_the_configuration_states():
    """The environment is derived from ``compute_dtype``; the program's
    own reading of it has to agree, except in a control that switches
    the program's lower precision on."""
    bf16 = {"name": "c", "compute_dtype": "bfloat16",
            "matmul_precision": "default"}
    f32 = {**bf16, "compute_dtype": "float32"}
    assert harness.program_environment(f32) == {}
    assert harness.program_environment(bf16) == {
        "SPARKDL_ZOO_COMPUTE_DTYPE": "bfloat16"}
    with pytest.raises(harness.BenchmarkError, match="matmul_precision"):
        harness.program_environment({**f32, "matmul_precision": "highest"})
    for states, other in ((f32, bf16), (bf16, f32)):
        harness.scrub_environment(harness.program_environment(states))
        harness.check_stated_precision(states, control=False)
        harness.check_stated_precision(other, control=True)
        with pytest.raises(harness.BenchmarkError, match="states"):
            harness.check_stated_precision(other, control=False)
        with pytest.raises(harness.BenchmarkError, match="control"):
            harness.check_stated_precision(states, control=True)


def test_the_stage_is_the_one_the_configuration_names():
    from benchmark import traffic
    from sparkdl_tpu import DeepImageFeaturizer, DeepImagePredictor

    config = {"stage": "DeepImageFeaturizer", "model_name": "ResNet50"}
    stage = traffic.make_stage(config, 8)
    assert type(stage) is DeepImageFeaturizer
    assert stage.getBatchSize() == 8
    assert stage.getOutputCol() == traffic.OUTPUT_COL
    assert type(traffic.make_stage(
        {**config, "stage": "DeepImagePredictor"}, 8)) is DeepImagePredictor


def test_set_up_warms_with_a_small_input_of_the_same_kind(tmp_path):
    for name in ("inceptionv3.jpeg", "inceptionv3.f32.structs"):
        t = _build(name, 3, tmp_path / name)
        assert 0 < t.warm_images < t.job_images


def test_an_unknown_traffic_mix_or_configuration_is_refused(tmp_path):
    root, _ = toy.make_root(tmp_path, extra_workloads=[
        {"name": "x.y", "config": "inceptionv3", "traffic": "absent",
         "chips": 1, "why": "-"},
        {"name": "x.z", "config": "absent", "traffic": "image_structs",
         "chips": 1, "why": "-"}])
    for name in ("x.y", "x.z"):
        with pytest.raises(harness.BenchmarkError, match="absent"):
            harness.load_cell(root, name)


def _build(name, seed, workdir):
    root, _ = toy.make_root(workdir / "data")
    cell = harness.load_cell(root, name)
    mix = toy.TOY_TRAFFIC[cell.traffic_name]
    return harness.find_generator(mix["generator"]).build(
        mix, cell.config, seed, str(workdir))


def _jpeg_bytes(traffic):
    return [open(os.path.join(d, f), "rb").read()
            for d in traffic.inputs for f in sorted(os.listdir(d))]


def _struct_bytes(traffic):
    return [frame.table.column("image").combine_chunks().field("data")
            .to_pylist() for frame in traffic.inputs]


@pytest.mark.parametrize("name,content", [
    ("inceptionv3.jpeg", _jpeg_bytes),
    ("inceptionv3.f32.structs", _struct_bytes)])
def test_traffic_is_made_from_the_seed_alone(tmp_path, name, content):
    big = 2**31 + 977          # more than 32 signed bits hold
    a = _build(name, big, tmp_path / "a")
    bytes_a, sources_a = content(a), [s.tolist() for s in a.row_sources]
    b = _build(name, big, tmp_path / "b")
    assert content(b) == bytes_a
    assert [s.tolist() for s in b.row_sources] == sources_a
    assert np.array_equal(a.reference_images(), b.reference_images())
    other = _build(name, big + 1, tmp_path / "c")
    assert content(other) != bytes_a
    # every job input shows every distinct image, in an order of its own
    assert sources_a[0] != sources_a[1]
    assert all(len(s) == a.job_images for s in a.row_sources)


def test_weights_are_made_from_the_seed_alone():
    from benchmark.flops import reference_module
    from benchmark.reference import net

    ref = reference_module("inceptionv3")
    params = {k: v for k, v in net.declare(
        ref.forward, (1, 299, 299, 3)).params.items()
        if k.startswith("stem_conv1")}
    draw = lambda seed: {k: np.asarray(v) for k, v in  # noqa: E731
                         net.draw_weights(params, seed).items()}
    a, b, c = draw(2**31 + 5), draw(2**31 + 5), draw(5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not any(np.array_equal(a[k], c[k]) for k in a)


def test_a_later_cell_is_one_data_file_and_one_entry(tmp_path):
    """PERF.md's first open question, ``inceptionv3.structs``: a traffic
    mix file and a ``BENCHMARK.json`` entry, and no edit under
    ``benchmark/`` — the harness here is the committed one."""
    root, peaks = toy.make_root(
        tmp_path,
        extra_traffic={"image_structs_b4": {
            "generator": "image_structs", "distinct_images": 4,
            "batch_size": 4, "job_batches": 3.5, "frames": 2,
            "warm_rows": 2}},
        extra_workloads=[{"name": "inceptionv3.structs",
                          "config": "inceptionv3",
                          "traffic": "image_structs_b4", "chips": 1,
                          "why": "the north-star program without decode"}])
    result = toy.run(root, peaks, "inceptionv3.structs")
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == result["jobs"] * 14 and not result["failed"]
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}
    assert list(result)[-1] == "checks"


def _run_entry(cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "inceptionv3.jpeg", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_entry_does_not_measure_without_a_tpu():
    done = _run_entry(harness.ROOT)
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]


def test_the_entry_refuses_a_directory_with_the_benchmark_alone(tmp_path):
    alone = tmp_path / "alone"
    shutil.copytree(harness.BENCH_DIR, alone / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), alone)
    # even where another checkout's package can be imported
    done = _run_entry(str(alone), {"PYTHONPATH": harness.ROOT})
    assert done.returncode != 0
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]
