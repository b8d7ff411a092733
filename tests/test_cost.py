"""Cost ledger + perf-regression sentinel tests (ISSUE 18).

Tier-1, CPU-only, seconds-scale: the headline chip-free conservation
proof (per-tenant attributed device time sums to the engine's metered
total, bit-stable across two seeded replays, pad tax and cache hits
itemized), the sentinel end-to-end (injected slowdown flips
``cost.regression`` + a degraded ``health()``, recovery clears both,
``tools/costreport.py`` exits 1 while open), the 10k-tenant
cardinality storm staying bounded at top-K + ``__overflow__``, the
``cost.attr`` degrade-not-fail fault site, the varz/cache schema
contract across ``Server`` and ``HeadFanoutServer``, the
``SPARKDL_COST`` gate grammar, and the twin policy's cost-share cap.
"""

import json
import os
import sys

import numpy as np
import pytest

from sparkdl_tpu import faults
from sparkdl_tpu.faults.plan import FaultPlan
from sparkdl_tpu.obs import flight
from sparkdl_tpu.obs.cost import (DEFAULT_MAX_TENANTS, OVERFLOW_TENANT,
                                  PAD_TENANT, CostLedger, CostRegression,
                                  cost_from_env, cost_rider, resolve_cost)
from sparkdl_tpu.obs import cost as cost_module
from sparkdl_tpu.serving import InferenceCache, Server
from sparkdl_tpu.utils.health import HealthTracker

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"] + variables["b"])


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(11)
    variables = {
        "w": rng.normal(size=(12, 5)).astype(np.float32),
        "b": rng.normal(size=(5,)).astype(np.float32),
    }
    x = rng.normal(size=(64, 12)).astype(np.float32)
    return variables, x


@pytest.fixture(autouse=True)
def _restore_obs():
    """Tests flip the flight recorder and the process-default ledger;
    hand both back exactly as the environment would configure them."""
    saved = cost_module._default
    yield
    cost_module._default = saved
    flight.configure_from_env()


def _fake_lockfile(tmp_path, model="m", rows=8, flops_per_row=100.0,
                   bytes_accessed=64.0, name="m/fused/b8"):
    doc = {
        "schema_version": 1,
        "programs": {
            name: {
                "kind": "dispatch", "model": model, "rows": rows,
                "fingerprint": "abc123", "flops_per_row": flops_per_row,
                "bytes_accessed": bytes_accessed,
            },
        },
    }
    p = tmp_path / "lock.json"
    p.write_text(json.dumps(doc))
    return str(p), name


# -- the headline conservation proof ---------------------------------------

def _seeded_replay(seed):
    """A deterministic mixed-tenant replay into a fresh ledger: 60
    batches over 12 tenants with pad, queue wait, and cache hits."""
    ledger = CostLedger(max_tenants=8, window=6,
                        lockfile_path="/nonexistent/lock.json")
    rng = np.random.default_rng(seed)
    total_device = 0.0
    for _ in range(60):
        k = int(rng.integers(1, 4))
        tenants = rng.choice(12, size=k, replace=False)
        tenant_rows = {f"t{int(t)}": int(rng.integers(1, 5))
                       for t in tenants}
        pad = int(rng.integers(0, 4))
        device_s = float(rng.uniform(1e-4, 5e-3))
        total_device += device_s
        ledger.record_batch(
            model="m", bucket=8, tenant_rows=tenant_rows,
            device_s=device_s,
            queue_s_by_tenant={t: float(rng.uniform(0, 1e-3))
                               for t in tenant_rows},
            pad_rows=pad, hbm_bytes=1024.0)
        if rng.uniform() < 0.3:
            ledger.record_hit(tenant=f"t{int(tenants[0])}", model="m",
                              kind=("hit" if rng.uniform() < 0.5
                                    else "coalesced"))
    return ledger, total_device


def test_conservation_seeded_replay_bit_stable():
    """ISSUE 18 acceptance: attributed device time (tenants + pad)
    equals the metered total within 1e-6 relative, the snapshot is
    IDENTICAL across two seeded runs, and the pad tax and cache hits
    appear as their own itemized lines."""
    faults.clear()  # the cost stage re-runs this file with
    # SPARKDL_FAULTS exported; conservation is only defined without
    # attribution chaos (the degrade path has its own test below)
    ledger_a, device_a = _seeded_replay(7)
    ledger_b, device_b = _seeded_replay(7)
    snap_a, snap_b = ledger_a.snapshot(), ledger_b.snapshot()
    assert device_a == device_b
    assert json.dumps(snap_a, sort_keys=True) == \
        json.dumps(snap_b, sort_keys=True)

    tot = snap_a["totals"]
    assert tot["device_s"] == pytest.approx(device_a, rel=1e-12)
    # conservation: tenant shares + pad residual == metered total
    assert abs(tot["attributed_device_s"] - tot["device_s"]) <= \
        1e-6 * tot["device_s"]
    # the pad tax is itemized on its own shared line, never a tenant
    assert snap_a["pad"]["device_s"] > 0.0
    assert snap_a["pad"]["rows"] == tot["pad_rows"] > 0
    assert PAD_TENANT not in snap_a["tenants"]
    # cache hits itemized at zero device cost
    assert tot["hits"] + tot["coalesced"] > 0
    hit_tenants = [t for t, v in snap_a["tenants"].items()
                   if v["hits"] + v["coalesced"] > 0]
    assert hit_tenants
    # per-tenant sums re-derive the totals
    assert sum(v["device_s"] for v in snap_a["tenants"].values()) + \
        snap_a["pad"]["device_s"] == pytest.approx(tot["device_s"],
                                                   rel=1e-9)
    assert sum(v["rows"] for v in snap_a["tenants"].values()) == \
        tot["rows"]


def test_server_e2e_conservation_vs_engine_counter(setup):
    """End to end through the real batcher + engine: the ledger's
    metered total equals the ``engine.call_wall_s`` counter, and the
    attributed split (tenants + pad) conserves it within 1e-6."""
    faults.clear()  # conservation needs every batch attributed — see
    # test_conservation_seeded_replay_bit_stable
    variables, x = setup
    ledger = CostLedger(max_tenants=16)
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=5,
                bucket_sizes=[8], max_queue=256, cache=False,
                cost=ledger, model_desc="m") as srv:
        futs = [srv.submit(x[i], tenant=f"t{i % 5}") for i in range(43)]
        for f in futs:
            np.asarray(f.result(timeout=60))
        metered = srv.metrics.counters["engine.call_wall_s"]
        snap = ledger.snapshot()
    tot = snap["totals"]
    assert metered > 0.0
    assert tot["device_s"] == pytest.approx(metered, rel=1e-9)
    assert abs(tot["attributed_device_s"] - tot["device_s"]) <= \
        1e-6 * tot["device_s"]
    assert set(snap["tenants"]) == {f"t{i}" for i in range(5)}
    assert tot["rows"] == 43
    # 43 rows over bucket-8 batches -> at least one padded dispatch
    assert tot["pad_rows"] > 0 and snap["pad"]["device_s"] > 0.0
    assert tot["queue_s"] > 0.0
    # varz carries the section, JSON-clean
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=5,
                bucket_sizes=[8], cache=False, cost=ledger) as srv2:
        doc = srv2.varz()
        json.dumps(doc)
        assert doc["cost"]["totals"]["rows"] == 43


# -- lockfile-analytic FLOPs / HBM ----------------------------------------

def test_lockfile_flops_and_hbm_attribution(tmp_path):
    """A covered (model, bucket) resolves its lockfile program name and
    charges rows x ``flops_per_row``; HBM byte-seconds scale with each
    attributed second; uncovered programs degrade to rows-only."""
    path, prog = _fake_lockfile(tmp_path, model="m", rows=8,
                                flops_per_row=100.0)
    ledger = CostLedger(lockfile_path=path)
    ledger.record_batch(model="m", bucket=8,
                        tenant_rows={"a": 3, "b": 1}, device_s=0.008,
                        pad_rows=4, hbm_bytes=1000.0)
    snap = ledger.snapshot()
    assert snap["tenants"]["a"]["flops"] == 300.0
    assert snap["tenants"]["b"]["flops"] == 100.0
    assert snap["pad"]["flops"] == 400.0
    # shares: 3/8 and 1/8 of 8ms; hbm_bytes_s = bytes * share
    assert snap["tenants"]["a"]["device_s"] == pytest.approx(0.003)
    assert snap["tenants"]["a"]["hbm_bytes_s"] == pytest.approx(3.0)
    assert prog in snap["programs"]
    # uncovered model: synthetic program name, rows-only
    ledger.record_batch(model="other", bucket=4,
                        tenant_rows={"a": 4}, device_s=0.001)
    snap = ledger.snapshot()
    assert "other/b4" in snap["programs"]
    assert snap["tenants"]["a"]["flops"] == 300.0  # unchanged


# -- bounded cardinality ---------------------------------------------------

def test_cardinality_bound_survives_10k_tenant_storm():
    """An adversarial 10k-distinct-tenant storm stays bounded at
    top-``max_tenants`` + ``__overflow__`` — and conservation still
    holds because folding merges lines instead of dropping them."""
    ledger = CostLedger(max_tenants=16,
                        lockfile_path="/nonexistent/lock.json")
    total = 0.0
    for i in range(10_000):
        d = 1e-5 * (1 + (i % 7))
        total += d
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={f"storm-{i}": 1},
                            device_s=d, pad_rows=7)
    # a few repeat big spenders must keep their own lines
    for i in range(4):
        total += 0.01
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={f"whale-{i}": 8},
                            device_s=0.01)
    snap = ledger.snapshot()
    assert snap["tracked_tenants"] <= 16
    assert snap["overflow"] is True
    assert len(snap["tenants"]) <= 17  # top-K + __overflow__
    assert OVERFLOW_TENANT in snap["tenants"]
    for i in range(4):
        assert f"whale-{i}" in snap["tenants"]
    tot = snap["totals"]
    assert tot["rows"] == 10_000 + 32
    assert tot["device_s"] == pytest.approx(total, rel=1e-9)
    assert abs(tot["attributed_device_s"] - tot["device_s"]) <= \
        1e-6 * tot["device_s"]
    # the export surfaces stay bounded too
    text = ledger.prometheus_text()
    assert text.count("\n") < 400
    json.dumps(snap)


# -- the regression sentinel ----------------------------------------------

def test_sentinel_regression_degrades_health_then_recovers(tmp_path):
    """The e2e sentinel story: a sustained slowdown past
    ``regress_factor`` opens a ``cost.regression`` flight event and
    degrades the bound ``health()`` with a ``CostRegression``; dropping
    back under ``recover_factor`` emits ``cost.recovered`` and clears
    the degradation; ``tools/costreport.py`` exits 1 exactly while the
    regression is open."""
    from costreport import main as costreport_main

    tracker = HealthTracker("test.cost.sentinel")
    ledger = CostLedger(window=4, min_batches=4, regress_factor=2.0,
                        recover_factor=1.5, health=tracker,
                        lockfile_path="/nonexistent/lock.json")
    rec = flight.configure(enabled=True)

    def batch(device_s):
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={"a": 8}, device_s=device_s)

    for _ in range(6):          # pin the baseline at 1ms / 8 rows
        batch(0.001)
    assert ledger.regressions() == {}
    assert tracker.snapshot()["state"] == "ready"

    for _ in range(4):          # 10x slowdown fills the window
        batch(0.010)
    open_now = ledger.regressions()
    assert set(open_now) == {"m/b8"}
    assert open_now["m/b8"]["factor"] >= 2.0
    assert open_now["m/b8"]["reason"] == "baseline"
    health = tracker.snapshot()
    assert health["state"] == "degraded"
    assert health["last_error"]["type"] == CostRegression.__name__

    # costreport: exit 1 while open, table render does not crash
    dump = tmp_path / "varz.json"
    dump.write_text(json.dumps({"cost": ledger.snapshot()}))
    assert costreport_main([str(dump)]) == 1
    assert costreport_main([str(dump), "--json", "--tenant", "a"]) == 1

    for _ in range(4):          # recovery: back to the pinned rate
        batch(0.001)
    assert ledger.regressions() == {}
    assert tracker.snapshot()["state"] == "ready"
    dump.write_text(json.dumps({"cost": ledger.snapshot()}))
    assert costreport_main([str(dump)]) == 0

    names = [e["event"] for e in rec.snapshot()]
    assert "cost.regression" in names
    assert "cost.recovered" in names
    assert names.index("cost.regression") < names.index("cost.recovered")
    # and the health transitions rode the same recorder
    assert "health.degraded" in names and "health.ready" in names


def test_sentinel_recovery_guard_preserves_foreign_degradation():
    """The SLOEngine recovery guard: the sentinel only clears a
    degradation IT caused — a foreign failure recorded after the
    regression opened survives the cost recovery."""
    tracker = HealthTracker("test.cost.guard")
    ledger = CostLedger(window=4, min_batches=4, regress_factor=2.0,
                        recover_factor=1.5, health=tracker,
                        lockfile_path="/nonexistent/lock.json")

    def batch(device_s):
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={"a": 8}, device_s=device_s)

    for _ in range(6):
        batch(0.001)
    for _ in range(4):
        batch(0.010)
    assert tracker.snapshot()["state"] == "degraded"
    tracker.note_failure(RuntimeError("unrelated outage"))
    for _ in range(4):
        batch(0.001)
    assert ledger.regressions() == {}
    # the foreign degradation must NOT have been cleared
    snap = tracker.snapshot()
    assert snap["state"] == "degraded"
    assert snap["last_error"]["type"] == "RuntimeError"


def test_sentinel_analytic_check_catches_slow_pinned_baseline(tmp_path):
    """A program whose baseline was pinned while ALREADY slow is still
    caught by the lockfile-analytic cross-check: measured device-time/
    row beyond ``analytic_slack`` x the calibrated expectation opens
    with reason ``analytic`` even at factor 1.0."""
    doc = {
        "schema_version": 1,
        "programs": {
            "fast/b8": {"kind": "dispatch", "model": "fast", "rows": 8,
                        "fingerprint": "f", "flops_per_row": 100.0,
                        "bytes_accessed": 1.0},
            "slow/b8": {"kind": "dispatch", "model": "slow", "rows": 8,
                        "fingerprint": "s", "flops_per_row": 100.0,
                        "bytes_accessed": 1.0},
        },
    }
    path = str(tmp_path / "lock.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    ledger = CostLedger(window=4, min_batches=4, regress_factor=2.0,
                        analytic_slack=4.0, lockfile_path=path)
    # the fast program calibrates s_per_flop from its pinned window
    for _ in range(4):
        ledger.record_batch(model="fast", bucket=8,
                            tenant_rows={"a": 8}, device_s=0.0008)
    # same analytic FLOPs, but 100x slower from the very first batch:
    # its own baseline is flat (factor 1.0) yet the analytic check trips
    for _ in range(5):
        ledger.record_batch(model="slow", bucket=8,
                            tenant_rows={"a": 8}, device_s=0.08)
    open_now = ledger.regressions()
    assert "slow/b8" in open_now
    assert open_now["slow/b8"]["reason"] == "analytic"
    assert "fast/b8" not in open_now


def test_pin_baseline_explicit_and_from_window():
    ledger = CostLedger(window=4, min_batches=4,
                        lockfile_path="/nonexistent/lock.json")
    with pytest.raises(ValueError):
        ledger.pin_baseline("never-seen")
    pinned = ledger.pin_baseline("m/b8", s_per_row=1e-4)
    assert pinned == {"m/b8": 1e-4}
    for _ in range(3):
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={"a": 8}, device_s=8e-4)
    # pin-all re-derives from the rolling windows
    pinned = ledger.pin_baseline()
    assert pinned["m/b8"] == pytest.approx(1e-4)
    snap = ledger.snapshot()
    assert snap["programs"]["m/b8"]["baseline_s_per_row"] == \
        pytest.approx(1e-4)


# -- the cost.attr fault site (degrade, never fail) ------------------------

def test_cost_attr_fault_never_fails_a_request(setup):
    """An injected ``cost.attr`` failure degrades to the
    ``serving.cost_attr_errors`` counter + the ledger's own
    ``attr_errors`` — the request itself still settles with its
    result."""
    variables, x = setup
    ledger = CostLedger()
    plan = FaultPlan.parse("seed=9;cost.attr:error:at=1")
    with faults.active(plan):
        with Server(_fn, variables, max_batch_size=8, max_wait_ms=5,
                    bucket_sizes=[8], cache=False, cost=ledger,
                    model_desc="m") as srv:
            out = np.asarray(srv.submit(x[0], tenant="t0")
                             .result(timeout=60))
            assert out.shape == (5,)
            assert plan.fired("cost.attr") == 1
            assert srv.metrics.counters["serving.cost_attr_errors"] >= 1
    snap = ledger.snapshot()
    assert snap["totals"]["attr_errors"] >= 1
    # the poisoned batch was skipped, not half-charged
    assert snap["totals"]["batches"] == 0


def test_disabled_ledger_is_inert_even_under_fault():
    """``enabled=False`` short-circuits BEFORE the fault site — the
    disabled path is one attribute read, never an injection probe."""
    ledger = CostLedger(enabled=False)
    plan = FaultPlan.parse("seed=9;cost.attr:error:at=1")
    with faults.active(plan):
        ledger.record_batch(model="m", bucket=8,
                            tenant_rows={"a": 8}, device_s=1.0)
        ledger.record_hit(tenant="a", model="m")
    assert plan.fired("cost.attr") == 0
    snap = ledger.snapshot()
    assert snap["totals"]["batches"] == 0
    assert snap["totals"]["hits"] == 0


# -- cache / hit charging --------------------------------------------------

def test_record_hit_kinds_and_unknown_kind():
    ledger = CostLedger(lockfile_path="/nonexistent/lock.json")
    ledger.record_hit(tenant="a", model="m", kind="hit")
    ledger.record_hit(tenant="a", model="m", kind="coalesced")
    ledger.record_hit(tenant="b", model="m", kind="feature_hit")
    with pytest.raises(ValueError):
        ledger.record_hit(tenant="a", model="m", kind="warm")
    snap = ledger.snapshot()
    assert snap["tenants"]["a"]["hits"] == 1
    assert snap["tenants"]["a"]["coalesced"] == 1
    assert snap["tenants"]["b"]["feature_hits"] == 1
    # hits charge ZERO device seconds — that is the cache's point
    assert snap["totals"]["device_s"] == 0.0
    assert snap["tenants"]["a"]["device_s"] == 0.0


def test_server_cache_hit_charged_to_tenant(setup):
    """A result-cache absorption lands on the riding tenant's ledger
    line (zero device seconds) instead of vanishing from showback."""
    variables, x = setup
    ledger = CostLedger()
    cache = InferenceCache()
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=5,
                bucket_sizes=[8], cache=cache, cost=ledger,
                model_desc="m") as srv:
        a = np.asarray(srv.submit(x[0], tenant="t0").result(timeout=60))
        b = np.asarray(srv.submit(x[0], tenant="t1").result(timeout=60))
        assert a.tobytes() == b.tobytes()
        assert cache.metrics.counters.get("cache.hits", 0) >= 1
    snap = ledger.snapshot()
    assert snap["tenants"]["t1"]["hits"] >= 1
    assert snap["tenants"]["t1"]["device_s"] == 0.0
    assert snap["tenants"]["t0"]["device_s"] > 0.0


# -- varz contract: Server and HeadFanoutServer agree ----------------------

def test_varz_cache_and_cost_schema_unified_across_server_types(setup):
    """Satellite 2: both server classes expose the SAME cache-counter
    key schema (``cache.feature_hits``/``cache.feature_requests``
    present even when zero) and a JSON-clean ``cost`` section."""
    from sparkdl_tpu.parallel.engine import head_fanout_backbone_fn
    from sparkdl_tpu.serving.server import HeadFanoutServer

    variables, x = setup
    ledger = CostLedger()
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=5,
                bucket_sizes=[8], cache=InferenceCache(), cost=ledger,
                model_desc="m") as srv:
        np.asarray(srv.submit(x[0], tenant="t0").result(timeout=60))
        doc_plain = srv.varz()
    json.dumps(doc_plain)
    plain_keys = set(doc_plain["cache"]["counters"])
    assert {"cache.feature_hits", "cache.feature_requests"} <= plain_keys
    assert doc_plain["cost"]["totals"]["rows"] >= 1

    rng = np.random.default_rng(0)
    hf_vars = {"backbone": rng.normal(size=(12, 16)).astype(np.float32)}
    head = {"kernel": rng.normal(size=(16, 4)).astype(np.float32),
            "bias": rng.normal(size=(4,)).astype(np.float32)}
    hf_ledger = CostLedger()
    with HeadFanoutServer(head_fanout_backbone_fn, hf_vars,
                          model_desc="headfanout",
                          cache=InferenceCache(),
                          cost=hf_ledger, max_batch_size=8,
                          max_wait_ms=0.5) as hsrv:
        hsrv.add_head("t0", head)
        hsrv.submit(x[0][:12], "t0").result(timeout=60)
        hsrv.submit(x[0][:12], "t0").result(timeout=60)  # feature hit
        doc_hf = hsrv.varz()
    json.dumps(doc_hf)
    hf_keys = set(doc_hf["cache"]["counters"])
    assert {"cache.feature_hits", "cache.feature_requests"} <= hf_keys
    assert doc_hf["cache"]["counters"]["cache.feature_hits"] >= 1
    # the feature hit rode the warm entry onto t0's ledger line
    assert doc_hf["cost"]["tenants"]["t0"]["feature_hits"] >= 1
    # the two classes agree on the unified counter keys
    assert {"cache.feature_hits", "cache.feature_requests"} <= \
        (plain_keys & hf_keys)


# -- env gate + constructor resolution -------------------------------------

def test_sparkdl_cost_env_grammar(monkeypatch):
    monkeypatch.setenv("SPARKDL_COST", "")
    assert cost_from_env() is None
    monkeypatch.setenv("SPARKDL_COST", "off")
    assert cost_from_env() is None
    monkeypatch.setenv("SPARKDL_COST", "1")
    ledger = cost_from_env()
    assert isinstance(ledger, CostLedger)
    assert ledger.max_tenants == DEFAULT_MAX_TENANTS
    monkeypatch.setenv("SPARKDL_COST", "tenants=4,window=8,factor=3.5")
    ledger = cost_from_env()
    assert (ledger.max_tenants, ledger.window,
            ledger.regress_factor) == (4, 8, 3.5)
    for bad in ("bogus", "tenants=x", "volume=11"):
        monkeypatch.setenv("SPARKDL_COST", bad)
        with pytest.raises(ValueError):
            cost_from_env()


def test_resolve_cost_rules():
    ledger = CostLedger()
    assert resolve_cost(False) is None
    assert resolve_cost(ledger) is ledger
    with pytest.raises(TypeError):
        resolve_cost(42)
    cost_module.configure(ledger)
    assert resolve_cost(None) is ledger
    cost_module.configure(None)
    assert resolve_cost(None) is None


# -- export surfaces -------------------------------------------------------

def test_prometheus_text_deterministic_and_escaped():
    ledger = CostLedger(window=2, min_batches=2, regress_factor=2.0,
                        lockfile_path="/nonexistent/lock.json")
    ledger.record_batch(model='mo"del\\x', bucket=8,
                        tenant_rows={'te"nant\nz': 4}, device_s=0.004,
                        pad_rows=4)
    ledger.record_hit(tenant='te"nant\nz', model='mo"del\\x')
    assert ledger.prometheus_text() == ledger.prometheus_text()
    text = ledger.prometheus_text()
    assert r'te\"nant\nz' in text
    assert "\n" + "sparkdl_cost_device_seconds_total{" in text
    assert 'bucket="8"' in text
    # zero-valued fields are elided, the regression gauge absent
    assert "sparkdl_cost_regression_open{" not in text
    # force a regression open -> the gauge line appears
    ledger.pin_baseline('mo"del\\x/b8', s_per_row=1e-9)
    for _ in range(2):
        ledger.record_batch(model='mo"del\\x', bucket=8,
                            tenant_rows={"a": 8}, device_s=0.01)
    assert "sparkdl_cost_regression_open{" in ledger.prometheus_text()


def test_cost_rider_shape():
    assert cost_rider(None) is None
    ledger = CostLedger(lockfile_path="/nonexistent/lock.json")
    ledger.record_batch(model="m", bucket=8, tenant_rows={"a": 6},
                        device_s=0.006, pad_rows=2)
    ledger.record_hit(tenant="a", model="m")
    rider = cost_rider(ledger)
    assert rider["sentinel"] == "ok"
    assert rider["open_regressions"] == []
    assert rider["tenants"]["a"]["rows"] == 6
    assert rider["tenants"]["a"]["hits"] == 1
    assert rider["pad_device_s"] == pytest.approx(0.0015, rel=1e-6)
    json.dumps(rider)


def test_costreport_cli_edge_cases(tmp_path, capsys):
    from costreport import main as costreport_main

    # cost attribution off (varz "cost": null) -> informative exit 0
    off = tmp_path / "off.json"
    off.write_text(json.dumps({"cost": None}))
    assert costreport_main([str(off)]) == 0
    # corrupt input -> exit 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert costreport_main([str(bad)]) == 2
    assert costreport_main([str(tmp_path / "missing.json")]) == 2
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"cost": {"nope": 1}}))
    assert costreport_main([str(wrong)]) == 2
    capsys.readouterr()


# -- twin policy: cost-aware grants ----------------------------------------

def test_quota_autoscaler_cost_share_cap():
    """A tenant holding more than ``cost_share_cap`` of the measured
    cost is denied its burn-driven scale-up (recorded as a
    ``quota_denied`` adjustment); under-cap tenants still scale."""
    from sparkdl_tpu.serving.fleet.admission import TenantQuota
    from sparkdl_tpu.twin.policy import QuotaAutoscaler, TickObservation

    def obs(cost_by_tenant):
        return TickObservation(
            tick=3, vt=3.0, arrivals=40, admitted=30, completed=28,
            shed_total=10, shed_by_reason={"quota": 10},
            shed_by_tenant={"whale": 6, "minnow": 4},
            slo_state="breach", burn_short=20.0, burn_long=2.0,
            cost_by_tenant=cost_by_tenant)

    base = TenantQuota(rate_per_s=0.2, burst=60)
    pol = QuotaAutoscaler(base, cost_share_cap=0.5)
    d = pol.decide(obs({"whale": 90.0, "minnow": 10.0}))
    by_lever = {}
    for adj in d.adjustments:
        by_lever.setdefault(adj["lever"], []).append(adj)
    denied = {a["tenant"] for a in by_lever.get("quota_denied", [])}
    assert denied == {"whale"}
    scaled = {a.get("tenant") for a in by_lever.get("quota", [])}
    assert "minnow" in scaled and "whale" not in scaled
    # without the cap (default None) both scale — the pre-cost law
    pol_uncapped = QuotaAutoscaler(base)
    d2 = pol_uncapped.decide(obs({"whale": 90.0, "minnow": 10.0}))
    assert not any(a["lever"] == "quota_denied" for a in d2.adjustments)


@pytest.mark.slow
def test_twin_day_cost_fairness_deterministic():
    """The twin reads the LIVE ledger each tick (deterministic cost
    units: lockfile FLOPs or rows, never wall seconds) — two identical
    virtual days agree byte-for-byte including the new
    ``cost_by_tenant`` stream field and the ``cost_fairness`` score."""
    from sparkdl_tpu.serving import TenantQuota
    from sparkdl_tpu.twin import QuotaAutoscaler, ScenarioConfig, run_day

    def run():
        cfg = ScenarioConfig(seed=5, ticks=12, tenants=16,
                             mean_arrivals_per_tick=60.0, flash_start=4,
                             flash_end=8, flash_tenants=4,
                             canary_tick=2, stream_every=5,
                             digest_universe=64)
        quota = TenantQuota(rate_per_s=0.15, burst=60)
        pol = QuotaAutoscaler(quota, cost_share_cap=0.5)
        return run_day(cfg, policy=pol, default_quota=quota)

    a, b = run(), run()
    assert a.event_digest == b.event_digest
    assert a.scores["cost_fairness"] == b.scores["cost_fairness"]
    assert 0.0 < a.scores["cost_fairness"] <= 1.0
    assert '"cost_by_tenant"' in a.event_lines[-1]
