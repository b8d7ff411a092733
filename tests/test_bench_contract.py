"""bench.py output-contract tests (hardware-free).

The driver parses bench stdout line by line and keeps the FINAL line as
the tracked metric, so the JSON-line contract — self-describing
denominators, the two-sided baseline fields, the device stamp on every
line — and the run's failure policy (no accelerator or a raising config
fails the run; no CPU number is printed under a device metric's name;
one process per chip) are product surface and get pinned here.  The
throughput numbers themselves need the chip.
"""

import json
import subprocess

import pytest

import bench

CPU_STAMP = {"platform": "cpu", "kind": "cpu"}


@pytest.fixture(autouse=True)
def _no_chip_config_has_started(monkeypatch):
    """The flag is the process's, and the real gate sets it for good:
    each test starts as a fresh bench process does."""
    monkeypatch.setattr(bench, "_CHIP_CONFIGS_STARTED", [False])


@pytest.fixture()
def captured(monkeypatch, tmp_path):
    from sparkdl_tpu.utils.jsonl import CrashSafeJsonlWriter

    lines = []
    monkeypatch.setattr(bench, "_print_line",
                        lambda s: lines.append(json.loads(s)))
    monkeypatch.setattr(bench, "_LINES", {})
    # in-process main() calls reset() on the crash-safe artifact rider:
    # point it at a scratch path so contract tests never truncate the
    # repo's real artifacts/bench_lines.jsonl forensics record
    monkeypatch.setattr(bench, "_ARTIFACT",
                        CrashSafeJsonlWriter(str(tmp_path / "lines.jsonl")))
    monkeypatch.setattr(bench, "BENCH_TRACE", False)
    return lines


@pytest.fixture()
def on_chip(monkeypatch):
    """Steer the accelerator gate: tier-1 has no chip, and these tests
    are about what main() does around the configs, not the configs."""
    monkeypatch.setattr(bench, "require_accelerator", bench.device_stamp)


def test_emit_two_sided_baseline_fields(captured):
    """FLOP-scaled lines carry BOTH vs_baseline (per-model denominator)
    and vs_sourced_anchor (value / the single sourced 875) so the
    denominator-method sensitivity is visible in the JSON itself
    (VERDICT r4 #4)."""
    bench.emit("2-Xception", "m", 3184.0, "images/sec/chip",
               baseline_model="Xception")
    rec = captured[-1]
    assert rec["vs_baseline"] == pytest.approx(3184 / 573, rel=0.01)
    assert rec["vs_sourced_anchor"] == pytest.approx(3184 / 875, rel=0.01)
    # the sourced anchor itself carries only vs_baseline (same number)
    bench.emit("1", "m", 6500.0, "images/sec/chip",
               baseline_model="InceptionV3")
    rec = captured[-1]
    assert rec["vs_baseline"] == pytest.approx(6500 / 875, rel=0.01)
    assert "vs_sourced_anchor" not in rec


def test_denominators_cover_reference_zoo():
    """Every reference SUPPORTED_MODELS member has a defensible
    denominator; beyond-reference models report null."""
    for name in ("InceptionV3", "ResNet50", "VGG16", "VGG19", "Xception"):
        ips, basis = bench.v100_baseline(name)
        assert ips and basis, name
    for name in ("MobileNetV2", "EfficientNetB0", "ResNet101", "ResNet152"):
        assert bench.v100_baseline(name) == (None, None), name


def test_emit_extra_fields_merge_without_touching_core_keys(captured):
    """The serving line carries p50/p99 next to the core contract keys;
    ``extra`` must merge, never shadow, the core fields."""
    bench.emit("serving", "m", 1234.5, "images/sec",
               extra={"p50_ms": 4.2, "p99_ms": 9.9, "num_requests": 64})
    rec = captured[-1]
    assert rec["value"] == 1234.5 and rec["unit"] == "images/sec"
    assert rec["p50_ms"] == 4.2 and rec["p99_ms"] == 9.9
    assert rec["vs_baseline"] is None and rec["baseline"] is None
    # a colliding key is a loud error, never a silent overwrite
    with pytest.raises(ValueError, match="collides"):
        bench.emit("serving", "m", 1.0, "images/sec",
                   extra={"value": 2.0})


@pytest.mark.slow
def test_pipeline_bench_line_contract(captured):
    """The real synthetic-device child emits a line with the overlap
    speedup and the per-stage stall ledger under the core contract keys
    (slow: spawns a python child that imports jax + runs ~2.5s of
    sleep-clocked batches)."""
    bench.bench_pipeline()
    rec = captured[-1]
    assert rec["config"] == "pipeline"
    assert rec["unit"] == "x vs serial path"
    assert rec["value"] >= 1.5
    assert rec["pipelined_s"] < rec["serial_s"]
    assert rec["pipeline_stages"]["pipeline.dispatches"] == rec["n_batches"]
    for key in ("config", "metric", "value", "unit", "vs_baseline",
                "baseline", "env_bound"):
        assert key in rec


def test_pad_overhead_rider_on_every_line(captured):
    """Every per-config line carries the ``pad_overhead`` rider (ISSUE
    11, the prep step for ROADMAP item 2's ragged batching): the GC004
    analytic bounds from the committed PROGRAMS.lock.json, plus the
    measured pad-row fraction whenever the line's metrics snapshot
    recorded the engine's rows/pad_rows ledger."""
    bench.emit("2-Xception", "m", 3184.0, "images/sec/chip",
               baseline_model="Xception")
    rec = captured[-1]
    lock = rec["pad_overhead"]["lockfile"]
    assert "MobileNetV2" in lock and "InceptionV3" in lock
    for model, b in lock.items():
        assert b["buckets"] == sorted(b["buckets"])
        # the analytic worst cases sit inside graftcheck's GC004
        # budgets (interior 55% / floor 95%) — the committed bucket
        # plan cannot quietly drift past what the auditor allows
        assert 0.0 <= b["interior_worst_frac"] <= 0.55
        assert 0.0 <= b["floor_frac"] <= 0.95
    # a line whose snapshot carries the engine ledger gets the
    # measured half stamped next to the analytic one
    snap = {"counters": {"engine.rows": 30.0, "engine.pad_rows": 10.0},
            "gauges": {}, "timings_s": {},
            "histograms": {"serving.batch_fill_ratio":
                           {"count": 4, "mean": 0.75,
                            "p50": 0.75, "p99": 1.0}}}
    bench.emit("serving", "m", 100.0, "images/sec",
               extra={"metrics_snapshot": snap})
    measured = captured[-1]["pad_overhead"]["measured"]
    assert measured["pad_row_frac"] == pytest.approx(0.25)
    assert measured["serving_pad_frac"] == pytest.approx(0.25)


def test_cache_config_is_chipless_and_line_contract(captured, monkeypatch):
    """The ``cache`` config is chipless by design (synthetic sleep
    device): it runs in a CPU-pinned child — never falls back from a
    chip — and its line is self-auditing: measured hit rate pinned next
    to the analytic floor, dispatch counts for both passes, the
    bit-identical verdict, and the CHILD's device stamp (small replay
    via the env knobs to keep this tier-1-cheap)."""
    assert "cache" in bench._CHIPLESS_CONFIGS
    monkeypatch.setenv("SPARKDL_BENCH_CACHE_REQUESTS", "24")
    monkeypatch.setenv("SPARKDL_BENCH_CACHE_UNIVERSE", "6")
    monkeypatch.setenv("SPARKDL_BENCH_CACHE_DISPATCH_MS", "5.0")
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "cache")
    assert bench.main() == 0  # needs no accelerator gate: it is chip-free
    rec = captured[-1]
    assert rec["config"] == "cache"
    assert rec["unit"] == "x vs uncached serving path"
    assert rec["value"] >= 1.5
    assert rec["bit_identical"] is True
    assert rec["hit_rate"] >= rec["analytic_hit_rate"]
    assert rec["uncached_dispatches"] == rec["n_requests"] == 24
    assert rec["cached_dispatches"] < rec["uncached_dispatches"]
    assert rec["faults"] == "none"
    assert rec["device"]["platform"] == "cpu"
    assert "synthetic" in rec["env_bound"]
    for key in ("config", "metric", "value", "unit", "vs_baseline",
                "baseline", "env_bound", "pad_overhead"):
        assert key in rec


# -- the failure policy -----------------------------------------------------

def test_no_accelerator_fails_the_run_and_prints_no_device_metric(
        captured, monkeypatch):
    """A measurement path that finds no chip FAILS: on the CPU backend
    the chip configs are never called, one stamped error line says why,
    and main() returns non-zero."""
    ran = []
    monkeypatch.setitem(bench.BENCHES, "1", lambda: ran.append("1"))
    monkeypatch.setitem(bench.BENCHES, "3", lambda: ran.append("3"))
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "1,3")
    assert bench.main() != 0
    assert ran == []
    assert len(captured) == 1 and captured[0]["config"] == "1"
    assert "no accelerator" in captured[0]["error"]
    assert captured[0]["device"]["platform"] == "cpu"
    assert not any("value" in r for r in captured)


def test_real_chip_config_on_cpu_raises_before_measuring():
    """The gate itself, unsteered: it names the platform JAX reports."""
    with pytest.raises(bench.NoAcceleratorError, match="platform 'cpu'"):
        bench.require_accelerator()


def test_raising_chip_config_fails_the_run_but_the_rest_report(
        captured, monkeypatch, on_chip, capsys):
    """A config that raises is a stamped error line plus a traceback on
    stderr and a non-zero exit — not an exit-0 run with an ``error``
    key buried in it — and the configs after it still report."""
    def boom():
        raise ValueError("kernel refused")

    monkeypatch.setitem(bench.BENCHES, "1", boom)
    monkeypatch.setitem(bench.BENCHES, "3",
                        lambda: bench.emit("3", "m", 2.0, "rows/sec"))
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "1,3")
    assert bench.main() == 1
    assert [r["config"] for r in captured] == ["1", "3"]
    assert "kernel refused" in captured[0]["error"]
    assert captured[0]["device"]["platform"] == "cpu"
    assert captured[1]["value"] == 2.0
    err = capsys.readouterr().err
    assert "Traceback" in err and "FAILED configs: 1" in err


def test_clean_run_returns_zero_and_reemits_the_headline_last(
        captured, monkeypatch, on_chip):
    monkeypatch.setitem(bench.BENCHES, "1",
                        lambda: bench.emit("1", "m", 1.0, "images/sec/chip"))
    monkeypatch.setitem(bench.BENCHES, "3",
                        lambda: bench.emit("3", "m", 2.0, "rows/sec"))
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "1,3,none-such")
    assert bench.main() == 0
    assert [r["config"] for r in captured] == ["1", "3", "1"]


# -- the device stamp -------------------------------------------------------

def test_every_line_carries_the_device_stamp(captured, monkeypatch,
                                             on_chip):
    """Measured in this process: this process's device.  Measured in a
    child: the child's, passed through ``extra`` and never re-read
    here.  Error lines are stamped too."""
    import jax

    bench.emit("x", "m", 1.0, "u")
    assert captured[-1]["device"] == {
        **CPU_STAMP, "count": len(jax.devices())}
    child = {"platform": "cpu", "kind": "cpu", "count": 1}
    bench.emit("y", "m", 1.0, "u", extra={"device": child})
    assert captured[-1]["device"] == child
    monkeypatch.setitem(bench.BENCHES, "1", lambda: 1 / 0)
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "1")
    assert bench.main() == 1
    assert all("device" in r for r in captured)


# -- one process per chip ---------------------------------------------------

def test_no_child_is_started_once_a_chip_config_has_started(monkeypatch):
    """A chip belongs to one process: the gate every chip config passes
    closes the door on children BEFORE it asks JAX for the device (which
    is what takes the chip), whatever the answer; from then on the child
    runner refuses before it spawns."""
    assert bench._CHIP_CONFIGS_STARTED == [False]
    with pytest.raises(bench.NoAcceleratorError):
        bench.require_accelerator()
    assert bench._CHIP_CONFIGS_STARTED == [True]
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda *a, **kw: pytest.fail("a child was started"))
    with pytest.raises(RuntimeError, match="refused"):
        bench._run_json_subprocess("out = {}", timeout_s=5)


def test_bench_entry_touches_no_backend_through_its_chipfree_children(
        tmp_path):
    """``python bench.py`` as the driver runs it, on a platform JAX
    cannot initialise: everything the parent does before and during its
    chip-free children — configuring the compile cache, provisioning
    traces, stamping and printing the child's line — must leave JAX's
    backend alone, or on the chip machine the parent would hold the
    chip before its first child and (rightly) refuse every one of them.
    Here any backend access in the parent raises, so exit 0 with the
    child's stamped line is the proof."""
    import os
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("SPARKDL_FAULTS", "SPARKDL_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="no_such_platform",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               SPARKDL_BENCH_CONFIGS="cache",
               SPARKDL_BENCH_CACHE_REQUESTS="24",
               SPARKDL_BENCH_CACHE_UNIVERSE="6",
               SPARKDL_BENCH_CACHE_DISPATCH_MS="5.0",
               SPARKDL_BENCH_TRACE_DIR=str(tmp_path / "traces"),
               SPARKDL_BENCH_ARTIFACT=str(tmp_path / "lines.jsonl"))
    r = subprocess.run([sys.executable, bench.__file__],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["config"] == "cache" and "error" not in rec
    assert rec["device"]["platform"] == "cpu"  # the child's, not ours


def test_children_are_cpu_pinned_and_stamped(monkeypatch):
    """Whatever platform the parent's environment names, the child gets
    ``JAX_PLATFORMS=cpu`` and reports the device it really had."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    out = bench._run_json_subprocess("import json\nout = {'x': 1}",
                                     timeout_s=120)
    assert out["x"] == 1 and out["device"]["platform"] == "cpu"


def test_chipless_children_all_run_before_the_first_chip_config(
        captured, monkeypatch, on_chip):
    """main() orders every chip-free child ahead of the in-process chip
    configs (which keep their own order, headline first): the parent
    initialises its backend only after the last child has gone."""
    order = []
    for key in ("1", "3", "serving", "fleet", "pipeline", "cache", "twin"):
        monkeypatch.setitem(bench.BENCHES, key,
                            lambda key=key: order.append(key))
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS",
                       "1,serving,pipeline,3,cache,fleet,twin")
    assert bench.main() == 0
    assert order == ["pipeline", "cache", "twin", "1", "serving", "3",
                     "fleet"]


@pytest.mark.parametrize("config", ["serving", "fleet"])
def test_serving_and_fleet_measure_in_this_process(config, captured,
                                                   monkeypatch):
    """The two configs that used to re-run in a child (and on the CPU
    when the chip was away) now run where the chip is held: in-process,
    no child, and with no claim about the platform beyond the stamp."""
    assert config not in bench._CHIPLESS_CONFIGS
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda *a, **kw: pytest.fail(f"{config} started a child"))
    monkeypatch.setenv("SPARKDL_BENCH_SERVING_REQUESTS", "32")
    monkeypatch.setenv("SPARKDL_BENCH_FLEET_REQUESTS", "48")
    bench.BENCHES[config]()
    rec = captured[-1]
    assert rec["config"] == config and "error" not in rec
    assert rec["unit"] == "images/sec" and rec["value"] > 0
    assert rec["p50_ms"] > 0 and rec["p99_ms"] >= rec["p50_ms"]
    assert rec["env_bound"] is None
    assert rec["device"]["platform"] == "cpu"
    if config == "fleet":
        assert rec["swap_no_recompile"] is True
        assert rec["final_version"] == 2
