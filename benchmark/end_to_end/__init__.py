"""End-to-end metrics: one reader per file, ``end_to_end/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.  A reader is one
function ``read(obs) -> float | None`` over the run's ``Observations``
(``layer_metrics/__init__.py``); all of them are taken on the
benchmark's own clock, none from the program."""
