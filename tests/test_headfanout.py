"""Shared-backbone head fan-out tier (ISSUE 17).

Tier-1, CPU-only, seconds-scale: the headline seeded-Zipf 64-tenant
replay (backbone dispatches == distinct content digests, warm-path
latency under the full-model baseline, every row bit-identical to an
INDEPENDENT per-tenant full-model oracle), head hot-swap under load
with the three-witness no-backbone-recompile proof, feature-cache
survival across head churn vs rotation on backbone weight change,
stacked-bank eviction, the indivisible/oversized fallback modes, the
``head.dispatch``/``head.swap`` fault sites, the flight events on the
blackbox timeline, the lockfile-pinned program pair, and the fleet's
``add_fanout_model``/``add_head``/``swap_head`` surface.
"""

import threading
import time

import numpy as np
import pytest

from sparkdl_tpu import faults
from sparkdl_tpu.parallel.engine import (HeadBank, dense_head_row,
                                         head_fanout_backbone_fn,
                                         head_fanout_oracle_fn)
from sparkdl_tpu.serving import InferenceCache
from sparkdl_tpu.serving.cache import (feature_namespace,
                                       head_fanout_benchmark,
                                       lockfile_model_fingerprint)
from sparkdl_tpu.serving.server import HeadFanoutServer

D_IN, D_FEAT, CLASSES = 12, 16, 4


def _variables(seed=0):
    rng = np.random.default_rng(seed)
    return {"backbone": rng.normal(size=(D_IN, D_FEAT)).astype(np.float32)}


def _head(seed):
    rng = np.random.default_rng(100 + seed)
    return {"kernel": rng.normal(size=(D_FEAT, CLASSES)).astype(np.float32),
            "bias": rng.normal(size=(CLASSES,)).astype(np.float32)}


def _payload(seed):
    return np.random.default_rng(200 + seed).normal(
        size=(D_IN,)).astype(np.float32)


def _server(cache=False, variables=None, **kw):
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("max_wait_ms", 0.5)
    return HeadFanoutServer(
        head_fanout_backbone_fn,
        variables if variables is not None else _variables(),
        model_desc="headfanout", cache=cache, **kw)


_oracle_jit = None


def _oracle(variables, head, x):
    """The independent full-model oracle: ONE unbatched row through its
    own jit of ``head_fanout_oracle_fn`` — never the fan-out pipeline."""
    global _oracle_jit
    import jax
    import jax.numpy as jnp

    if _oracle_jit is None:
        _oracle_jit = jax.jit(head_fanout_oracle_fn)
    return np.asarray(_oracle_jit(
        {"backbone": variables["backbone"], **head}, jnp.asarray(x)))


def _wrap_slow(srv, sleep_s=0.0):
    """Count (and optionally slow) the BACKBONE's dispatches."""
    calls = [0]
    for b in srv.bucket_sizes:
        eng = srv.backbone._engine_for(b)
        real = eng.run_padded

        def slow(batch, _real=real):
            calls[0] += 1
            if sleep_s:
                time.sleep(sleep_s)
            return _real(batch)

        eng.run_padded = slow
    return calls


# -- the headline replay ----------------------------------------------------
def test_headline_zipf_64_tenant_replay():
    """ISSUE 17 acceptance: a seeded Zipf-content replay over 64
    tenants and a sleep-wrapped backbone — backbone dispatches equal
    distinct content digests (featurize ONCE), per-tenant outputs are
    bit-identical to independent full-model oracles, and the warm
    per-request latency sits well under the full-model baseline."""
    out = head_fanout_benchmark(n_requests=96, universe=12, tenants=64,
                                dispatch_ms=5.0, seed=0)
    assert out["bit_identical"] is True
    assert out["backbone_dispatches"] == out["distinct"]
    assert out["dispatch_ratio"] == 1.0
    assert out["baseline_dispatches"] == out["n_requests"]
    assert out["warm_p50_ms"] < out["baseline_p50_ms"]
    assert out["feature_hits"] > 0
    assert out["bank_mode"] == "stacked"
    assert out["bank_capacity"] == 64
    assert out["bank_param_bytes_per_chip"] > 0


def test_mixed_tenant_batch_one_head_pass_bit_identical():
    """K tenants' rows in one predict_batch cost ONE head pass, and
    every row matches its tenant's own oracle bitwise."""
    variables = _variables()
    with _server(variables=variables) as srv:
        heads = {f"t{i}": _head(i) for i in range(5)}
        for t, h in heads.items():
            srv.add_head(t, h)
        srv.warmup(_payload(0))
        xs = [_payload(i % 3) for i in range(7)]
        ts = [f"t{i % 5}" for i in range(7)]
        before = srv.metrics.snapshot_raw()["counters"].get(
            "headfanout.head_passes", 0)
        rows = srv.predict_batch(xs, ts)
        after = srv.metrics.snapshot_raw()["counters"].get(
            "headfanout.head_passes", 0)
        assert after - before == 1
        for x, t, y in zip(xs, ts, rows):
            ref = _oracle(variables, heads[t], x)
            assert np.asarray(y).tobytes() == ref.tobytes()


# -- head hot-swap ----------------------------------------------------------
def test_head_hot_swap_under_load_proof_and_bit_correctness():
    """Swap a tenant's head mid-load: zero failed futures, every output
    bitwise equal to the OLD or NEW oracle (never a torn head), the
    swapped tenant serves the new head afterwards, and the swap report
    carries all three no-backbone-recompile witnesses."""
    variables = _variables()
    old, new = _head(1), _head(99)
    with _server(variables=variables, cache=InferenceCache()) as srv:
        srv.add_head("a", old)
        srv.add_head("b", _head(2))
        srv.warmup(_payload(0))
        srv.warm_head(np.zeros(D_FEAT, np.float32))
        x = _payload(0)
        srv.predict(x, "a")  # warm the feature cache for this digest

        results, errors = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    results.append(np.asarray(srv.predict(x, "a")))
                # graftlint: allow=SDL003 reason=collected and asserted empty below
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        report = srv.swap_head("a", new)
        time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join()

        assert not errors, errors
        assert len(results) > 0
        ref_old = _oracle(variables, old, x)
        ref_new = _oracle(variables, new, x)
        for y in results:
            assert (y.tobytes() == ref_old.tobytes()
                    or y.tobytes() == ref_new.tobytes())
        # post-swap requests serve the NEW head exactly
        got = np.asarray(srv.predict(x, "a"))
        assert got.tobytes() == ref_new.tobytes()
        # the three-witness proof
        assert report["no_backbone_recompile"] is True
        assert report["head_jit_shared"] is True
        assert report["fingerprint_pinned"] is True
        assert all(b["shared_jit"] for b in report["buckets"].values())


def test_feature_cache_survives_head_swap():
    """The feature-cut namespace is backbone identity: a head swap must
    keep warm feature entries serving (zero new backbone dispatches),
    with the post-swap output already on the NEW head."""
    variables = _variables()
    cache = InferenceCache()
    with _server(variables=variables, cache=cache) as srv:
        srv.add_head("a", _head(1))
        srv.warmup(_payload(0))
        calls = _wrap_slow(srv)
        x = _payload(5)
        srv.predict(x, "a")
        assert calls[0] == 1
        entries_before = len(cache)
        srv.swap_head("a", _head(7))
        assert len(cache) == entries_before  # nothing invalidated
        got = np.asarray(srv.predict(x, "a"))
        assert calls[0] == 1, "feature hit must skip the backbone"
        ref = _oracle(variables, _head(7), x)
        assert got.tobytes() == ref.tobytes()


def test_backbone_weight_change_rotates_feature_namespace():
    """Different backbone weights → different weight digest → a
    DIFFERENT feature namespace: the old entries are unreachable, so a
    stale featurization can never reach the new backbone's tenants."""
    cache = InferenceCache()
    with _server(variables=_variables(0), cache=cache) as srv1:
        srv1.add_head("a", _head(1))
        srv1.warmup(_payload(0))
        srv1.predict(_payload(5), "a")
        ns1 = srv1.feature_namespace
    # close() must NOT reclaim the namespace (backbone identity, not
    # server identity): a restarted server over the SAME backbone
    # serves the entries warm
    with _server(variables=_variables(0), cache=cache) as srv2:
        srv2.add_head("a", _head(1))
        srv2.warmup(_payload(0))
        calls = _wrap_slow(srv2)
        srv2.predict(_payload(5), "a")
        assert srv2.feature_namespace == ns1
        assert calls[0] == 0, "same backbone must inherit warm entries"
    with _server(variables=_variables(3), cache=cache) as srv3:
        srv3.add_head("a", _head(1))
        srv3.warmup(_payload(0))
        calls = _wrap_slow(srv3)
        assert srv3.feature_namespace != ns1
        srv3.predict(_payload(5), "a")
        assert calls[0] == 1, "new backbone weights must re-featurize"
    # and the schema itself: head churn appears NOWHERE in the key
    ns = feature_namespace("headfanout", "fp", "digest")
    assert ns == ("features", "headfanout", "fp", "digest")
    assert feature_namespace("headfanout", None, "d") == (
        "features", "headfanout", "unpinned", "d")


def test_stacked_bank_evicts_departed_tenant():
    """Eviction re-stacks the survivors; the departed tenant fails
    loudly (KeyError) instead of serving a stale row."""
    variables = _variables()
    with _server(variables=variables) as srv:
        heads = {f"t{i}": _head(i) for i in range(3)}
        for t, h in heads.items():
            srv.add_head(t, h)
        srv.warmup(_payload(0))
        report = srv.remove_head("t1")
        assert report["op"] == "remove"
        assert srv.tenants() == ["t0", "t2"]
        with pytest.raises(KeyError):
            srv.predict(_payload(0), "t1")
        for t in ("t0", "t2"):
            got = np.asarray(srv.predict(_payload(1), t))
            ref = _oracle(variables, heads[t], _payload(1))
            assert got.tobytes() == ref.tobytes()


# -- degraded modes ---------------------------------------------------------
def _head_oracle(head, feats):
    """The per-tenant oracle for one head: ``dense_head_row`` on ONE
    unbatched row through its own jit — like :func:`_oracle`, never the
    fan-out pipeline.  Compiled, not eager, and that is the contract:
    the bit-identity ``dense_head_row`` promises is between COMPILED
    programs (vmapped bank vs unbatched oracle share one reduction
    order).  Evaluated eagerly, op by op, each of the K=16 products is
    rounded to f32 before it is summed, while under the installed XLA
    every compiled form fuses the multiply into the accumulation and
    keeps it unrounded — the two differ in the last bit (one f32 ulp on
    two of four outputs here), in the oracle, not in the bank."""
    import jax

    # no donation: the caller's head and feature row are reused
    return np.asarray(jax.jit(dense_head_row, donate_argnums=())(head, feats))


def test_indivisible_head_falls_back_per_tenant_not_crash():
    """A head whose pytree cannot stack with the bank flips the bank to
    per-tenant fallback: every tenant (old shape and new) keeps serving
    bit-identically through the SAME fan-out jit as a bank of one."""
    bank = HeadBank()
    h0 = _head(0)
    bank.add_head("a", h0)
    jit_before = bank.jit_info()["jit_id"]
    odd = {"kernel": np.random.default_rng(9).normal(
        size=(D_FEAT, CLASSES + 3)).astype(np.float32),
        "bias": np.zeros(CLASSES + 3, np.float32)}
    bank.add_head("weird", odd)  # must degrade, not raise
    assert bank.mode == "fallback"
    assert bank.jit_info()["jit_id"] == jit_before
    assert "mismatch" in bank.stats()["fallback_reason"]
    feats = np.random.default_rng(3).normal(
        size=(D_FEAT,)).astype(np.float32)
    got_a = np.asarray(bank.dispatch(feats[None], ["a"]))[0]
    ref_a = _head_oracle(h0, feats)
    assert got_a.tobytes() == ref_a.tobytes()
    got_w = np.asarray(bank.dispatch(feats[None], ["weird"]))[0]
    ref_w = _head_oracle(odd, feats)
    assert got_w.shape == (CLASSES + 3,)
    assert got_w.tobytes() == ref_w.tobytes()


def test_oversized_bank_falls_back_within_budget():
    """A bank whose stacked bytes would bust ``hbm_budget_bytes``
    degrades to per-tenant dispatch instead of crashing, and the
    budget check uses the same ``param_sharding_stats`` ledger GC005
    audits."""
    one_head_bytes = (D_FEAT * CLASSES + CLASSES) * 4
    bank = HeadBank(hbm_budget_bytes=3 * one_head_bytes)
    bank.add_head("a", _head(1))
    bank.add_head("b", _head(2))
    assert bank.mode == "stacked"  # capacity 2 fits
    bank.add_head("c", _head(3))   # capacity 4 would bust the budget
    assert bank.mode == "fallback"
    assert "hbm_budget_bytes" in bank.stats()["fallback_reason"]
    feats = np.random.default_rng(4).normal(
        size=(2, D_FEAT)).astype(np.float32)
    out = bank.dispatch(feats, ["a", "c"])
    for i, t in enumerate(("a", "c")):
        ref = _head_oracle(_head({"a": 1, "c": 3}[t]), feats[i])
        assert np.asarray(out[i]).tobytes() == ref.tobytes()


# -- fault sites + flight events (SDL008) -----------------------------------
def test_head_fault_sites_registered_and_abort_cleanly():
    from sparkdl_tpu.faults.sites import SITE_HELP, validate_site

    for site in ("head.dispatch", "head.swap"):
        assert site in SITE_HELP
        validate_site(site)
    plan = faults.FaultPlan.parse(
        "seed=8;head.dispatch:error:times=1;head.swap:error:times=1")
    assert plan.has_rules("head.dispatch") and plan.has_rules("head.swap")

    variables = _variables()
    old = _head(1)
    with _server(variables=variables) as srv:
        srv.add_head("a", old)
        srv.warmup(_payload(0))
        x = _payload(0)
        # head.swap fires BEFORE state changes: the bank is unchanged
        # and the OLD head keeps serving
        with faults.active(faults.FaultPlan.parse(
                "seed=8;head.swap:error:exc=fatal,times=1")):
            with pytest.raises(faults.InjectedFault):
                srv.swap_head("a", _head(9))
        got = np.asarray(srv.predict(x, "a"))
        assert got.tobytes() == _oracle(variables, old, x).tobytes()
        # head.dispatch fails that head pass only; the next one serves
        with faults.active(faults.FaultPlan.parse(
                "seed=8;head.dispatch:error:exc=fatal,times=1")):
            with pytest.raises(faults.InjectedFault):
                srv.predict_batch([x], ["a"])
        got = np.asarray(srv.predict(x, "a"))
        assert got.tobytes() == _oracle(variables, old, x).tobytes()


def test_head_events_cataloged_and_on_blackbox_timeline(tmp_path):
    from sparkdl_tpu.obs import flight
    from tools.blackbox import build_timeline

    for name in ("head.swap", "cache.feature_hit"):
        assert name in flight.EVENT_HELP
        flight.validate_event(name)
    rec = flight.configure(enabled=True, out_dir=str(tmp_path))
    try:
        with _server(cache=InferenceCache()) as srv:
            srv.add_head("a", _head(1))      # head.swap (op=add)
            srv.warmup(_payload(0))
            x = _payload(0)
            srv.predict(x, "a")              # cache.miss on features
            srv.predict(x, "a")              # cache.feature_hit
            srv.swap_head("a", _head(2))     # head.swap (op=swap)
        path = rec.dump()
    finally:
        flight.configure_from_env()
    doc = build_timeline(path)
    chain = doc["chain"]
    for name in ("head.swap", "cache.feature_hit"):
        assert name in chain, f"{name} missing from blackbox timeline"
    assert doc["counts"]["head.swap"] >= 2


# -- the lockfile pin -------------------------------------------------------
def test_lockfile_pins_headfanout_program_pair():
    """The backbone-cut and stacked-head programs are in the committed
    PROGRAMS.lock.json with byte-stable fingerprints, the backbone
    record resolves through ``lockfile_model_fingerprint`` (what the
    feature namespace and the swap proof key on), and the head record
    deliberately does NOT carry the model tag."""
    from sparkdl_tpu.analysis.program import (DEFAULT_LOCKFILE,
                                              audit_program,
                                              headfanout_dispatch_specs,
                                              read_lockfile)

    committed = read_lockfile(DEFAULT_LOCKFILE)["programs"]
    specs = headfanout_dispatch_specs()
    assert len(specs) == 2
    for spec in specs:
        assert spec.name in committed, spec.name
        rec = audit_program(spec)["record"]
        assert rec["fingerprint"] == committed[spec.name]["fingerprint"]
    backbone, heads = specs
    assert backbone.model == "headfanout" and heads.model is None
    fp = lockfile_model_fingerprint("headfanout")
    assert fp is not None
    # a fresh server over the canonical backbone pins that fingerprint
    with _server() as srv:
        assert srv.feature_namespace[2] == fp


# -- fleet surface ----------------------------------------------------------
def test_fleet_fanout_deploy_swap_and_guards():
    from sparkdl_tpu.serving.fleet import Fleet

    variables = _variables()
    with Fleet(max_batch_size=8, max_wait_ms=0.5) as fleet:
        fleet.add_fanout_model("multi", head_fanout_backbone_fn, variables,
                               model_desc="headfanout")
        r1 = fleet.add_head("multi", "a", _head(1))
        assert r1["head_version"] == 1
        srv = fleet._state("multi").server
        srv.warmup(_payload(0))
        srv.warm_head(np.zeros(D_FEAT, np.float32))
        x = _payload(0)
        got = np.asarray(fleet.predict("multi", x, tenant="a"))
        assert got.tobytes() == _oracle(variables, _head(1), x).tobytes()
        rep = fleet.swap_head("multi", "a", _head(5))
        assert rep["no_backbone_recompile"] is True
        assert rep["head_version"] == 2
        assert fleet.registry.head_versions("multi", "a") == [1, 2]
        got = np.asarray(fleet.predict("multi", x, tenant="a"))
        assert got.tobytes() == _oracle(variables, _head(5), x).tobytes()
        # backbone versioning is refused for fan-out entries
        fleet.add_version("multi", variables)
        with pytest.raises(RuntimeError, match="fan-out"):
            fleet.start_rollout("multi")
        # head ops are refused for plain entries
        fleet.add_model("plain", head_fanout_backbone_fn, variables)
        with pytest.raises(TypeError, match="not a head fan-out"):
            fleet.add_head("plain", "t", _head(1))
        # varz carries the fan-out section, JSON-clean
        import json

        v = fleet.varz()
        section = v["fleet"]["models"]["multi"]["headfanout"]
        assert section["tenants"] == ["a"]
        assert section["bank"]["mode"] == "stacked"
        json.dumps(v, default=str)
        assert v["fleet"]["registry"]["multi"]["heads"] == {"a": 2}
