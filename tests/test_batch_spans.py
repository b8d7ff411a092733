"""Spans inside the batch path (ISSUE 27): ``readImages`` +
``DeepImageFeaturizer.transform`` over a dozen tiny JPEGs leave one span
tree per call — ``io.*`` under ``io.read_images``; packing, pad, H2D and
gather under ``transform.run`` — whose attrs add up, which costs nothing
and changes nothing when tracing is off, and which no generator leaves
open across a ``yield``."""

import importlib.util
import os
import threading

import numpy as np
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.image import io as image_io
from sparkdl_tpu.transformers import (DeepImageFeaturizer,
                                      DeepImagePredictor)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES, BATCH = 12, 8          # one full dispatch and one of 4 rows + 4 pad


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    obs.configure_from_env()


@pytest.fixture(scope="module")
def jpeg_dir(tmp_path_factory):
    from PIL import Image

    rng = np.random.default_rng(27)
    d = tmp_path_factory.mktemp("jpegs")
    for i in range(FILES):
        arr = (rng.random((20, 24, 3)) * 255).astype("uint8")
        Image.fromarray(arr).save(d / f"img_{i:02d}.jpg", quality=90)
    return str(d)


def _featurizer():
    return DeepImageFeaturizer(inputCol="image", outputCol="features",
                               modelName="ResNet50", batchSize=BATCH)


def _features(df):
    return [r["features"] for r in df.collect()]


def _chain(spans, span):
    """Names from ``span`` up to its root."""
    by_id = {s["span_id"]: s for s in spans}
    path = []
    while span is not None:
        path.append(span["name"])
        span = by_id.get(span["parent_id"])
    return tuple(path)


def _chains(spans, name):
    return [_chain(spans, s) for s in spans if s["name"] == name]


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _one_job(jpeg_dir):
    """``(read spans, transform spans, features, pad counter delta)`` of
    one ``readImages`` and one ``transform`` with tracing on."""
    tracer = obs.configure(enabled=True)
    df = image_io.readImages(jpeg_dir, numPartitions=1)
    read = tracer.snapshot()
    stage = _featurizer()
    # built here, outside any call: ``engine.build`` is a root of its own
    pad0 = stage.engine().metrics.counters.get("engine.pad_rows", 0.0)
    tracer.clear()
    out = stage.transform(df)
    pad = stage.engine().metrics.counters["engine.pad_rows"] - pad0
    return read, tracer.snapshot(), _features(out), pad


@pytest.fixture()
def job(jpeg_dir, tiny_resnet):
    return _one_job(jpeg_dir)


UNDER_READ = ("io.read_images",)
UNDER_RUN = ("pipeline.run", "transform.run")
UNDER_PREPARE = ("pipeline.prepare",) + UNDER_RUN

#: span, how many a job of 12 files at batch 8 leaves, the chain above
#: it, the attrs it carries
TABLE = [
    ("io.read_images", 1, (), {"files", "rows", "null_rows", "partitions"}),
    ("io.read", 1, UNDER_READ, {"files", "bytes"}),
    ("io.decode", 1, UNDER_READ, {"rows", "failed", "workers"}),
    ("io.to_arrow", 1, UNDER_READ, {"rows", "bytes", "direct_rows"}),
    ("io.repartition", 1, UNDER_READ,
     {"rows", "partitions", "copied_bytes"}),
    ("transform.run", 1, (),
     {"rows", "valid_rows", "model", "batch_size"}),
    ("engine.pad", 1, UNDER_PREPARE, {"rows", "pad_rows"}),
    ("engine.h2d", 2,
     ("engine.dispatch", "pipeline.dispatch") + UNDER_RUN, {"bytes"}),
    ("pipeline.gather", 2, UNDER_RUN, {"rows", "bytes"}),
    ("transform.pack_out", 1, ("transform.run",),
     {"rows", "values", "bytes", "null_rows", "py_values"}),
]


@pytest.mark.parametrize("name,count,above,attrs", TABLE,
                         ids=[row[0] for row in TABLE])
def test_every_span_of_the_table_in_its_place(job, name, count, above,
                                              attrs):
    read, run, _, _ = job
    spans = read if name.startswith("io.") else run
    found = _named(spans, name)
    assert len(found) == count
    assert _chains(spans, name) == [(name,) + above] * count
    assert all(set(s["attrs"]) == attrs for s in found)
    assert all(s["status"] == "ok" for s in found)


def test_first_chunk_packs_on_the_callers_thread_the_rest_in_prepare(job):
    """The first chunk is pulled before the engine is built, so its
    ``transform.pack_in`` hangs under ``transform.run`` itself; every
    later one under ``pipeline.prepare``, on the prepare thread."""
    _, run, _, _ = job
    packs = sorted(_named(run, "transform.pack_in"),
                   key=lambda s: s["ts_us"])
    assert [_chain(run, s) for s in packs] == [
        ("transform.pack_in", "transform.run"),
        ("transform.pack_in",) + UNDER_PREPARE]
    assert packs[0]["thread"] == threading.current_thread().name
    assert packs[1]["thread"] == "sparkdl-pipeline-prepare"
    # JPEG-born structs are 8-bit BGR: every row takes the raw route, in
    # pool tasks whose size follows from the host's cores
    tasks = [s["attrs"].pop("tasks") for s in packs]
    assert 1 <= tasks[0] <= BATCH and 1 <= tasks[1] <= FILES - BATCH
    assert [s["attrs"] for s in packs] == [
        {"rows": BATCH, "valid": BATCH, "raw_rows": BATCH},
        {"rows": FILES - BATCH, "valid": FILES - BATCH,
         "raw_rows": FILES - BATCH}]


def test_one_trace_id_a_call_and_children_inside_parents(job):
    read, run, _, _ = job
    for spans, root in ((read, "io.read_images"), (run, "transform.run")):
        assert len({s["trace_id"] for s in spans}) == 1
        assert [s["name"] for s in spans if s["parent_id"] is None] == [root]
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            p = by_id.get(s["parent_id"])
            if p is not None:
                assert p["ts_us"] - 1 <= s["ts_us"]
                assert (s["ts_us"] + s["dur_us"]
                        <= p["ts_us"] + p["dur_us"] + 1)
    assert read[0]["trace_id"] != run[0]["trace_id"]


def test_attrs_add_up(job, jpeg_dir, tiny_resnet):
    read, run, features, pad_delta = job
    attrs = lambda spans, name: [s["attrs"] for s in _named(spans, name)]  # noqa: E731
    on_disk = sum(os.path.getsize(os.path.join(jpeg_dir, f))
                  for f in os.listdir(jpeg_dir))
    assert attrs(read, "io.read") == [{"files": FILES, "bytes": on_disk}]
    (decode,) = _named(read, "io.decode")
    # the package's decoder, 12 files: on the io pool, the caller waiting
    assert 1 <= decode["attrs"].pop("workers") \
        <= image_io._io_executor()._max_workers
    assert decode["attrs"] == {"rows": FILES, "failed": 0}
    assert decode["thread"] == threading.current_thread().name
    assert attrs(read, "io.read_images") == [
        {"files": FILES, "rows": FILES, "null_rows": 0, "partitions": 1}]
    (to_arrow,) = attrs(read, "io.to_arrow")
    # every row's flip wrote into the frame's own buffer; at
    # numPartitions=1 that buffer is the partition: nothing copied after
    assert (to_arrow["rows"], to_arrow["direct_rows"]) == (FILES, FILES)
    assert to_arrow["bytes"] >= FILES * 20 * 24 * 3
    assert attrs(read, "io.repartition") == [
        {"rows": FILES, "partitions": 1, "copied_bytes": 0}]
    assert attrs(run, "engine.pad") == [
        {"rows": FILES - BATCH, "pad_rows": 2 * BATCH - FILES}]
    assert pad_delta == 2 * BATCH - FILES
    h, w = tiny_resnet.input_size
    assert attrs(run, "engine.h2d") == [{"bytes": BATCH * h * w * 3}] * 2
    gathers = attrs(run, "pipeline.gather")
    assert [g["rows"] for g in gathers] == [BATCH, FILES - BATCH]
    assert sum(g["bytes"] for g in gathers) == FILES * 2048 * 4
    # the column's values buffer is the features as float32, and none of
    # them was a Python object on the way
    assert attrs(run, "transform.pack_out") == [
        {"rows": FILES, "values": FILES * tiny_resnet.feature_size,
         "bytes": FILES * tiny_resnet.feature_size * 4, "null_rows": 0,
         "py_values": 0}]
    assert attrs(run, "transform.run") == [
        {"rows": FILES, "valid_rows": FILES, "model": "ResNet50",
         "batch_size": BATCH}]
    assert len(features) == FILES


def test_a_file_that_does_not_decode_is_counted_not_dropped(
        jpeg_dir, tiny_resnet, tmp_path):
    for f in os.listdir(jpeg_dir)[:3]:
        os.link(os.path.join(jpeg_dir, f), tmp_path / f)
    (tmp_path / "broken.jpg").write_bytes(b"no jpeg")
    tracer = obs.configure(enabled=True)
    out = _featurizer().transform(image_io.readImages(str(tmp_path)))
    spans = tracer.snapshot()
    (decode,), (root,) = _named(spans, "io.decode"), \
        _named(spans, "io.read_images")
    assert 1 <= decode["attrs"].pop("workers") <= 4
    assert decode["attrs"] == {"rows": 4, "failed": 1}
    assert root["attrs"]["null_rows"] == 1 and root["attrs"]["rows"] == 4
    (pack,) = _named(spans, "transform.pack_in")
    # three valid rows are packed on the caller's thread: no pool task
    assert pack["attrs"] == {"rows": 4, "valid": 3, "raw_rows": 3,
                             "tasks": 0}
    (run,) = _named(spans, "transform.run")
    assert (run["attrs"]["rows"], run["attrs"]["valid_rows"]) == (4, 3)
    assert sum(f is None for f in _features(out)) == 1


def test_no_span_stays_open_across_a_yield(jpeg_dir, tiny_resnet):
    """An abandoned generator leaves the thread's span stack clean."""
    tracer = obs.configure(enabled=True)
    batches = image_io.iterImageBatches(jpeg_dir, batch_size=5)
    first = next(batches)
    assert tracer.current() is None
    assert {s["name"] for s in tracer.snapshot()} == {
        "io.read", "io.decode", "io.to_arrow"}
    batches.close()
    from sparkdl_tpu.frame import DataFrame

    chunks = _featurizer()._decoded_chunks(DataFrame(first), 8, 8, 2, [])
    next(chunks)
    assert tracer.current() is None
    assert len(_named(tracer.snapshot(), "transform.pack_in")) == 1


def test_tracing_off_records_nothing_and_never_blocks(jpeg_dir,
                                                      tiny_resnet,
                                                      monkeypatch):
    """Off: an empty ring, no id issued, no ``block_until_ready``
    anywhere.  On: only the gather thread blocks — never the dispatch
    path, which stays asynchronous."""
    import jax

    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: blocked.append(threading.current_thread().name)
        or real(x))
    tracer = obs.configure(enabled=False)
    _featurizer().transform(image_io.readImages(jpeg_dir, numPartitions=1))
    assert len(tracer) == 0 and tracer.dropped == 0
    assert next(tracer._ids) == 1
    assert blocked == []
    obs.configure(enabled=True)
    _featurizer().transform(image_io.readImages(jpeg_dir, numPartitions=1))
    assert set(blocked) == {"sparkdl-pipeline-gather"}


def test_results_identical_with_tracing_on_and_off(jpeg_dir, tiny_resnet):
    obs.configure(enabled=False)
    df = image_io.readImages(jpeg_dir, numPartitions=1)
    plain = _features(_featurizer().transform(df))
    _, _, traced, _ = _one_job(jpeg_dir)
    assert plain == traced
    assert df.table.equals(
        image_io.readImages(jpeg_dir, numPartitions=1).table)


def test_dropped_counts_evictions_and_clear_resets_it():
    tracer = obs.configure(enabled=True, capacity=4)
    for i in range(4):
        tracer.span("x.y", i=i).finish()
    assert (len(tracer), tracer.dropped) == (4, 0)
    for i in range(3):
        tracer.span("x.y", i=i).finish()
    assert (len(tracer), tracer.dropped) == (4, 3)
    tracer.clear()
    assert (len(tracer), tracer.dropped) == (0, 0)


def test_serial_path_yields_the_same_names_without_pipeline(
        jpeg_dir, tiny_resnet, monkeypatch):
    from sparkdl_tpu.parallel.engine import InferenceEngine

    _, piped, features, _ = _one_job(jpeg_dir)
    map_batches = InferenceEngine.map_batches
    monkeypatch.setattr(
        InferenceEngine, "map_batches",
        lambda self, batches: map_batches(self, batches, pipeline=False))
    _, serial, serial_features, _ = _one_job(jpeg_dir)
    # the compile.* spans are the job's that met the program first
    names = lambda spans: {  # noqa: E731
        s["name"] for s in spans if not s["name"].startswith("compile.")}
    assert names(serial) == {n for n in names(piped)
                             if not n.startswith("pipeline.")}
    assert len({s["trace_id"] for s in serial}) == 1
    assert set(_chains(serial, "transform.pack_in")) == {
        ("transform.pack_in", "transform.run")}
    assert serial_features == features


def test_predictor_tail_is_under_pack_out(jpeg_dir, tiny_resnet):
    tracer = obs.configure(enabled=True)
    df = image_io.readImages(jpeg_dir, numPartitions=1)
    tracer.clear()
    rows = DeepImagePredictor(
        inputCol="image", outputCol="preds", modelName="ResNet50",
        decodePredictions=True, topK=3, batchSize=BATCH
    ).transform(df).collect()
    assert all(len(r["preds"]) == 3 for r in rows)
    spans = tracer.snapshot()
    (pack,) = _named(spans, "transform.pack_out")
    # the decoded tail is the one output that crosses Python objects:
    # three probabilities a row
    assert pack["attrs"].pop("bytes") > 0
    assert pack["attrs"] == {"rows": FILES, "values": FILES * 1000,
                             "null_rows": 0, "py_values": FILES * 3}
    assert _chain(spans, pack) == ("transform.pack_out", "transform.run")


def test_trace_summary_folds_the_new_names(job):
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(REPO, "tools", "trace_summary.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    read, run, _, _ = job
    summary = tool.summarize(read + run)
    assert {row[0] for row in TABLE} | {"transform.pack_in"} \
        <= set(summary["stages"])
    table = tool.render(summary)
    assert "| engine.h2d | 2 |" in table
    assert "| io.decode | 1 |" in table
    # the compiles fold by program: one row a program, a column a phase
    programs = tool.summarize_compiles(run)
    assert programs and all(p["count"] >= 1 for p in programs.values())
    assert all(p["off"] == p["count"] and not p["hit"] + p["miss"]
               for p in programs.values())       # no persistent cache here
    mine = max(programs.values(), key=lambda p: p["trace_us"])
    assert min(mine["trace_us"], mine["lower_us"], mine["backend_us"]) > 0
    by_hand = [
        {"name": "compile.trace", "dur_us": 2e6, "attrs": {"program": "f"}},
        {"name": "compile.lower", "dur_us": 1e6, "attrs": {"program": "f"}},
        {"name": "compile.backend", "dur_us": 5e5,
         "attrs": {"program": "f", "cache": "hit", "load_s": 0.25,
                   "saved_s": 30.0}},
        {"name": "compile.backend", "dur_us": 4e6,
         "attrs": {"program": "g", "cache": "miss"}},
        {"name": "engine.build", "dur_us": 1e6,
         "attrs": {"device_batch_size": 8, "param_bytes": 24,
                   "jit_cached": False}},
        {"name": "engine.build", "dur_us": 5e5,
         "attrs": {"device_batch_size": 8, "param_bytes": 24,
                   "jit_cached": True}},
    ]
    folded = tool.summarize_compiles(by_hand)
    assert folded["f"] == {"trace_us": 2e6, "lower_us": 1e6,
                           "backend_us": 5e5, "count": 1, "hit": 1,
                           "miss": 0, "off": 0, "load_us": 2.5e5,
                           "saved_us": 3e7}
    assert (folded["g"]["miss"], folded["g"]["backend_us"]) == (1, 4e6)
    lines = tool.render_compiles(folded).splitlines()
    assert lines[2] == "| g | 1 | 0.0 | 0.0 | 4000.0 | 0/1/0 | 0.0 | 0.0 |"
    assert lines[3] == ("| f | 1 | 2000.0 | 1000.0 | 500.0 | 1/0/0 | 250.0 "
                        "| 30000.0 |")
    assert "7.500 s in 2 programs" in lines[-1]
    assert tool.summarize_builds(by_hand) == {
        "count": 2, "total_us": 1.5e6, "jit_cached": 1, "param_bytes": 48,
        "device_batch_sizes": [8]}
    assert tool.summarize_builds(run)["count"] == 0    # built before it
