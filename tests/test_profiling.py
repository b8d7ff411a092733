"""Throughput logging and the ``Metrics`` registry's timers, percentiles
and histograms."""

from sparkdl_tpu.utils.metrics import Metrics, StepTimer, throughput_counter


def test_transformer_logs_throughput(fixture_images):
    # The sparkdl_tpu logger sets propagate=False, so pytest's caplog (which
    # captures via the root logger) never sees its records; attach a handler
    # directly to the framework logger instead.
    import logging

    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image.io import readImages
    from sparkdl_tpu.transformers import TFImageTransformer

    df = readImages(fixture_images["dir"])
    mf = ModelFunction(fn=lambda v, x: x.astype("float32").mean(axis=(1, 2)),
                       variables={})
    t = TFImageTransformer(inputCol="image", outputCol="o",
                           modelFunction=mf, inputSize=[8, 8], batchSize=8)

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger = logging.getLogger("sparkdl_tpu")
    handler = _Capture(level=logging.INFO)
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        t.transform(df)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert any("img/s/chip" in msg for msg in records), records


def test_metrics_summary_and_timer():
    m = Metrics()
    m.incr("items", 5)
    m.gauge("depth", 2.0)
    timer = StepTimer(m, name="step")
    with timer.time():
        pass
    s = m.summary()
    assert s["items"] == 5 and s["depth"] == 2.0
    assert s["step.count"] == 1
    tc = throughput_counter(100, 2.0, num_devices=4)
    assert tc["items_per_sec"] == 50.0
    assert tc["items_per_sec_per_chip"] == 12.5


def test_metrics_percentiles_and_histograms():
    """The serving layer's latency surface: nearest-rank percentiles over
    timing AND unitless histogram series, with p50/p99 in summary."""
    m = Metrics()
    for v in range(1, 101):  # 0.01s .. 1.00s
        m.record_time("lat", v / 100.0)
    assert m.percentile("lat", 50) == 0.50
    assert m.percentile("lat", 99) == 0.99
    assert m.percentile("lat", 100) == 1.00
    assert m.percentile("absent", 50) is None
    m.observe("fill", 0.25)
    m.observe("fill", 0.75)
    s = m.summary()
    assert s["lat.p50_s"] == 0.50 and s["lat.p99_s"] == 0.99
    assert s["fill.mean"] == 0.5 and s["fill.count"] == 2
    assert s["fill.p50"] == 0.25 and s["fill.p99"] == 0.75


def test_metrics_series_are_bounded():
    """Per-request serving series must not grow without limit: on
    overflow the oldest half drops, recent samples survive."""
    m = Metrics(max_samples=8)
    for v in range(20):
        m.record_time("lat", float(v))
        m.observe("h", float(v))
    assert len(m.timings_s["lat"]) <= 8
    assert len(m.histograms["h"]) <= 8
    assert m.timings_s["lat"][-1] == 19.0  # newest retained
    assert m.percentile("lat", 100) == 19.0
