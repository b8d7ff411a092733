"""Mesh-sharded inference core (ISSUE 14): tensor-parallel weight
sharding via partition rules, on the 8-virtual-device CPU topology.

The contract under test, end to end:

* ``mesh.match_partition_rules`` — regex over ``/``-joined param paths
  to ``PartitionSpec``s (scalars replicated, no-match is a loud error);
* the default rule set splits dense/conv kernels on the ``model`` axis
  iff the axis is >1 and the dim divides (the divisibility fallback),
  collapsing to the classic replicate-everything layout otherwise —
  byte-identical programs on every model-axis-1 mesh;
* sharded outputs equal the replicated oracle on the same mesh up to
  the order in which one output element's products are summed (the
  split rides output dims, so no reduction crosses shards; XLA still
  picks its dot emitter by the per-shard shape — bit-identical where
  the emitters coincide, a stated K*eps bound where they do not);
* graftcheck GC005 proves the HBM claim chip-free: a synthetic
  wide-dense model whose 64 MB kernel busts the 32 MB replicated-param
  budget on a model-axis mesh audits CLEAN once sharded by the default
  rules, and the sharded programs are pinned in PROGRAMS.lock.json with
  drift classified back to GC005;
* ragged batching cuts stay multiples of the mesh data-axis size;
* the persistent compile-cache manifest carries the mesh/partition
  policy, so a restarted process under a different policy purges.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from sparkdl_tpu.parallel import mesh as mesh_lib
from sparkdl_tpu.parallel.engine import (InferenceEngine,
                                         clear_engine_jit_cache)


@pytest.fixture(autouse=True)
def _fresh_jit_cache():
    clear_engine_jit_cache()
    yield
    clear_engine_jit_cache()


def _wide_fn(v, x):
    return jnp.tanh(x @ v["dense"]["kernel"] + v["dense"]["bias"])


def _variables(rng, d=16):
    return {"dense": {
        "kernel": rng.normal(size=(d, d)).astype(np.float32),
        "bias": rng.normal(size=(d,)).astype(np.float32),
    }}


# ---------------------------------------------------------------------------
# rule matching
# ---------------------------------------------------------------------------

def test_match_partition_rules_regex_scalar_and_order():
    params = {"dense": {"kernel": np.zeros((8, 8), np.float32),
                        "bias": np.zeros((8,), np.float32)},
              "scale": np.float32(2.0),
              "one_elem": np.zeros((1,), np.float32)}
    specs = mesh_lib.match_partition_rules(
        [(r"(^|/)kernel$", P(None, "model")),
         (r".*", P())], params)
    assert tuple(specs["dense"]["kernel"]) == (None, "model")
    assert tuple(specs["dense"]["bias"]) == ()
    # scalars and one-element leaves are never partitioned, even if a
    # rule would match them
    assert tuple(specs["scale"]) == ()
    assert tuple(specs["one_elem"]) == ()
    # FIRST matching rule wins
    ordered = mesh_lib.match_partition_rules(
        [(r"dense/kernel", P("model")), (r"kernel", P(None, "model")),
         (r".*", P())], params)
    assert tuple(ordered["dense"]["kernel"]) == ("model",)


def test_match_partition_rules_no_match_raises():
    with pytest.raises(ValueError, match="Partition rule not found.*bias"):
        mesh_lib.match_partition_rules(
            [(r"kernel$", P(None, "model"))],
            {"kernel": np.zeros((4, 4), np.float32),
             "bias": np.zeros((4,), np.float32)})


def test_default_rules_divisibility_fallback():
    mesh = mesh_lib.get_mesh(model_parallel=8)
    params = {"a": {"kernel": np.zeros((4, 16), np.float32)},
              "b": {"kernel": np.zeros((4, 12), np.float32)},  # 12 % 8
              "c": {"bias": np.zeros((16,), np.float32)}}
    _, specs = mesh_lib.resolve_param_shardings(params, mesh)
    assert tuple(specs["a"]["kernel"]) == (None, mesh_lib.MODEL_AXIS)
    assert tuple(specs["b"]["kernel"]) == ()   # indivisible -> replicated
    assert tuple(specs["c"]["bias"]) == ()


def test_resolve_collapses_replicated_on_model_axis_1():
    """Model-axis-1 meshes must keep the pre-ISSUE-14 layout exactly:
    the resolved policy is all-replicated, the digest is the canonical
    "replicated", and an engine built with the default rules shares the
    SAME compiled jit object (same cache key) as one built without."""
    rng = np.random.default_rng(0)
    v = _variables(rng)
    _, specs = mesh_lib.resolve_param_shardings(v, mesh_lib.get_mesh())
    assert mesh_lib.specs_all_replicated(specs)
    assert mesh_lib.partition_digest(specs) == "replicated"
    e_plain = InferenceEngine(_wide_fn, v, device_batch_size=8)
    e_rules = InferenceEngine(_wide_fn, v, device_batch_size=8,
                              partition_rules=mesh_lib.
                              default_partition_rules)
    assert e_rules.param_shardings is None
    assert e_rules._compiled is e_plain._compiled


# ---------------------------------------------------------------------------
# engine parity: sharded == replicated (bit for bit at these shapes)
# ---------------------------------------------------------------------------

def test_engine_sharded_vs_replicated_bit_identical_tp8():
    rng = np.random.default_rng(1)
    v = _variables(rng)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    mesh = mesh_lib.get_mesh(model_parallel=8)
    e_rep = InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=16)
    e_tp = InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=16,
                           partition_rules=mesh_lib.
                           default_partition_rules)
    # the kernel really is split: each chip holds a (16, 2) column slice
    kernel = e_tp.variables["dense"]["kernel"]
    assert tuple(kernel.sharding.spec) == (None, mesh_lib.MODEL_AXIS)
    assert kernel.addressable_shards[0].data.shape == (16, 2)
    # distinct compiled programs (the policy is part of the cache key)…
    assert e_tp._compiled is not e_rep._compiled
    assert e_tp.sharding_digest != e_rep.sharding_digest
    # …but bit-identical outputs: the split rides the kernel's OUTPUT
    # dim, so no cross-shard reduction enters the math
    assert np.array_equal(np.asarray(e_tp(x)), np.asarray(e_rep(x)))
    info = e_tp.sharding_info()
    assert info["sharded"] and info["sharded_leaves"] == 1
    assert info["mesh_shape"] == {"data": 1, "model": 8}
    total, per_chip = (info["param_bytes_total"],
                       info["param_bytes_per_chip"])
    # kernel bytes / 8 + replicated bias
    assert per_chip == total - (16 * 16 * 4) + (16 * 16 * 4) // 8
    json.dumps(info)  # varz-embeddable


def test_engine_explicit_param_shardings_and_default_rules():
    rng = np.random.default_rng(2)
    v = _variables(rng)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    mesh = mesh_lib.get_mesh(model_parallel=4)  # dp2 x tp4
    ref = np.asarray(InferenceEngine(_wide_fn, v, mesh=mesh,
                                     device_batch_size=16)(x))
    e_exp = InferenceEngine(
        _wide_fn, v, mesh=mesh, device_batch_size=16,
        param_shardings={"dense": {"kernel": P(None, "model"),
                                   "bias": P()}})
    assert np.array_equal(np.asarray(e_exp(x)), ref)
    # the default rules split the same kernel the same way
    e_rules = InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=16,
                              partition_rules=mesh_lib.
                              default_partition_rules)
    got = np.concatenate(
        list(e_rules.map_batches([x], pipeline=False)), axis=0)
    assert np.array_equal(got, ref)


def test_server_sharded_parity_dp2tp4():
    """The serving path end to end on a mixed dp2 x tp4 mesh: sharded
    vs replicated servers on the SAME mesh serve the same rows up to
    summation order (tolerance and its reason below), and varz reports
    the layout."""
    from sparkdl_tpu.serving.server import Server

    rng = np.random.default_rng(3)
    v = _variables(rng, d=8)
    rows = [rng.normal(size=(8,)).astype(np.float32) for _ in range(12)]
    mesh = mesh_lib.get_mesh(model_parallel=4)

    def run(rules):
        with Server(_wide_fn, v, mesh=mesh, max_batch_size=8,
                    max_wait_ms=2, bucket_sizes=[4, 8], cache=False,
                    partition_rules=rules) as srv:
            srv.warmup(rows[0])
            outs = [np.asarray(srv.predict(r)) for r in rows]
            return outs, srv.varz()["sharding"]

    tp_outs, tp_info = run(mesh_lib.default_partition_rules)
    rep_outs, rep_info = run(None)
    # Equal up to summation order, not bit for bit.  The split rides the
    # kernel's OUTPUT dim, so every output element is still ONE K=8-term
    # dot product and no reduction crosses shards; but the per-shard dot
    # is (rows, 8) x (8, 2) where the replicated one is (rows, 8) x
    # (8, 8), and XLA picks its dot emitter — and with it the order in
    # which the K products are summed and fused — by shape.  On this
    # backend the 4-row bucket (2 rows per data shard) differs in the
    # last bits (<= 6e-7 on the dot, <= 3.3e-7 after tanh, values O(1));
    # the 8-row bucket coincides.  Two summation orders of a K-term f32
    # dot differ by at most 2*gamma_K*sum|x_i*w_i| ~= K*eps*sum|x_i*w_i|;
    # the bias add and the 1-Lipschitz tanh add a few ulp at |y| <= 1.
    # A real precision drop (a bf16 operand) is ~1e-2, four orders above.
    eps = np.finfo(np.float32).eps
    k_terms = v["dense"]["kernel"].shape[0]
    s_max = max(float((np.abs(r) @ np.abs(v["dense"]["kernel"])).max())
                for r in rows)
    atol = k_terms * eps * s_max + 4 * eps
    for a, b in zip(tp_outs, rep_outs):
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)
    assert tp_info["sharded"] and not rep_info["sharded"]
    assert tp_info["mesh_shape"] == {"data": 2, "model": 4}
    assert (tp_info["param_bytes_per_chip"]
            < rep_info["param_bytes_per_chip"])


def test_fleet_exposes_partition_rules_knob():
    from sparkdl_tpu.serving.fleet import Fleet

    rng = np.random.default_rng(4)
    v = _variables(rng, d=8)
    mesh = mesh_lib.get_mesh(model_parallel=8)
    fleet = Fleet(cache=False)
    try:
        fleet.add_model("wide", _wide_fn, v, mesh=mesh,
                        max_batch_size=8, bucket_sizes=[8], max_wait_ms=2,
                        partition_rules=mesh_lib.default_partition_rules,
                        warm_example=np.zeros((8,), np.float32))
        out = np.asarray(fleet.submit("wide", rng.normal(size=(8,)).astype(
            np.float32), tenant="t").result(timeout=30))
        assert out.shape == (8,)
        info = fleet._state("wide").server.sharding_info()
        assert info["sharded"] and info["mesh_shape"]["model"] == 8
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# the chip-free HBM proof (graftcheck GC005 + lockfile)
# ---------------------------------------------------------------------------

def _wide_dense_spec(sharded: bool, model_parallel: int = 8):
    from sparkdl_tpu.analysis.program.audit import ProgramSpec
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    mesh = mesh_lib.get_mesh(model_parallel=model_parallel)
    d = 4096  # 64 MB f32 kernel: busts the 32 MB replicated budget

    def build():
        variables = {"dense": {
            "kernel": jax.ShapeDtypeStruct((d, d), np.float32),
            "bias": jax.ShapeDtypeStruct((d,), np.float32)}}
        shardings = None
        if sharded:
            shardings, _ = mesh_lib.resolve_param_shardings(variables,
                                                            mesh)
        jitted = build_dispatch_jit(_wide_fn, mesh, donate_batch=True,
                                    param_shardings=shardings)
        batch = jax.ShapeDtypeStruct((32, d), np.float32)
        return jitted, (variables, batch)

    axes = {str(n): int(mesh.shape[n]) for n in mesh.axis_names}
    if sharded:
        kw = dict(shardings=("params", "batch"),
                  param_partition=(("dense/bias", []),
                                   ("dense/kernel", [None, "model"])))
    else:
        kw = dict(shardings=("replicated", "batch"))
    return ProgramSpec(name="synth/wide_dense", kind="dispatch",
                       build=build, donate=(1,), batch_rows=32,
                       mesh_axes=axes, group="synth/wide_dense", **kw)


def test_gc005_budget_buster_goes_clean_when_sharded():
    """THE acceptance gate: replicated, the wide-dense model's 64 MB
    kernel fires GC005 on a model-axis mesh; under the default
    partition rules the SAME program audits clean — per-chip kernel
    bytes are bytes/8, below budget — with the donation still consumed
    and sharding annotations present."""
    from sparkdl_tpu.analysis.program.audit import audit_program

    busted = audit_program(_wide_dense_spec(sharded=False))
    assert any(f.code == "GC005" and "replicated" in f.message
               for f in busted["findings"])
    clean = audit_program(_wide_dense_spec(sharded=True))
    assert clean["findings"] == []
    summary = clean["record"]["sharding_summary"]
    assert summary["largest_replicated_leaf_bytes"] == 4096 * 4  # bias
    shards = summary["param_shards"]
    assert shards["sharded_leaves"] == 1
    assert shards["sharded_bytes_per_chip"] == 4096 * 4096 * 4 // 8
    assert shards["indivisible"] == []
    assert summary["annotated"] > 0
    # donation consumed under the sharded layout too (GC001's criterion)
    assert clean["record"]["donation"]["aliased"] >= 1


def test_budget_buster_serves_bit_identical_to_single_device_oracle():
    """The acceptance criterion, runtime half: the EXACT wide-dense
    model the lockfile pins (``inventory.wide_dense_fn`` at the
    committed 128 x 131072 shape — its 64 MB kernel busts the GC005
    per-chip budget) runs tensor-parallel on the 8-virtual-device
    model-axis mesh with outputs BIT-IDENTICAL to a single-device
    replicated oracle: the split rides output columns, so no output
    element's accumulation order changes."""
    from sparkdl_tpu.analysis.program.inventory import (WIDE_DENSE_IN,
                                                        WIDE_DENSE_OUT,
                                                        wide_dense_fn)

    rng = np.random.default_rng(8)
    v = {"dense": {"kernel": rng.normal(
        scale=0.05, size=(WIDE_DENSE_IN, WIDE_DENSE_OUT)).astype(
            np.float32),
        "bias": rng.normal(size=(WIDE_DENSE_OUT,)).astype(np.float32)}}
    x = rng.normal(size=(32, WIDE_DENSE_IN)).astype(np.float32)
    oracle = InferenceEngine(wide_dense_fn, v,
                             mesh=mesh_lib.get_mesh(num_devices=1),
                             device_batch_size=32)
    tp = InferenceEngine(wide_dense_fn, v,
                         mesh=mesh_lib.get_mesh(model_parallel=8),
                         device_batch_size=32,
                         partition_rules=mesh_lib.
                         default_partition_rules)
    # per-chip HBM really dropped below the 32 MB budget
    from sparkdl_tpu.analysis.program.audit import (
        REPLICATED_PARAM_BUDGET_BYTES)

    info = tp.sharding_info()
    assert info["param_bytes_total"] > REPLICATED_PARAM_BUDGET_BYTES
    assert info["param_bytes_per_chip"] < REPLICATED_PARAM_BUDGET_BYTES
    assert np.array_equal(np.asarray(tp(x)), np.asarray(oracle(x)))


def test_gc005_indivisible_declared_split_fires():
    from sparkdl_tpu.analysis.program.audit import audit_program

    spec = _wide_dense_spec(sharded=True)
    # declare a split the leaf cannot honor: bias (4096,) "split" on a
    # dim it does not have
    spec.param_partition = (("dense/bias", [None, "model"]),
                            ("dense/kernel", [None, "model"]))
    out = audit_program(spec)
    assert any(f.code == "GC005" and "not divisible" in f.message
               for f in out["findings"])


def test_sharded_programs_pinned_in_lockfile():
    """The committed PROGRAMS.lock.json carries the tensor-parallel
    wide-dense programs with fingerprints matching a fresh abstract
    lowering — the mesh-sharded core regenerated the lockfile exactly
    once and the sharded variants are now part of the audited
    surface."""
    from sparkdl_tpu.analysis.program.audit import audit_program
    from sparkdl_tpu.analysis.program.inventory import (
        sharded_dispatch_specs)
    from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                       read_lockfile)

    committed = read_lockfile(DEFAULT_LOCKFILE)["programs"]
    specs = sharded_dispatch_specs()
    assert {s.name for s in specs} == {
        "serving/wide_dense/f32/b32/dp1tp8",
        "serving/wide_dense/f32/b32/dp2tp4"}
    for spec in specs:
        out = audit_program(spec)
        assert out["findings"] == []
        base = committed[spec.name]
        assert out["record"]["fingerprint"] == base["fingerprint"]
        fresh_summary = json.loads(  # JSON-normalize tuples vs lists
            json.dumps(out["record"]["sharding_summary"]))
        assert fresh_summary == base["sharding_summary"]


def test_lockfile_sharding_drift_classified_gc005():
    from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                       diff_records,
                                                       read_lockfile)

    committed = read_lockfile(DEFAULT_LOCKFILE)
    name = "serving/wide_dense/f32/b32/dp1tp8"
    rec = dict(committed["programs"][name], name=name)
    summary = json.loads(json.dumps(rec["sharding_summary"]))
    summary["param_shards"]["sharded_leaves"] = 0  # layout "un-sharded"
    rec["sharding_summary"] = summary
    findings = diff_records(committed, [rec], subset=True)
    assert [f.code for f in findings] == ["GC005"]
    assert "sharding" in findings[0].message


# ---------------------------------------------------------------------------
# ragged batching x mesh alignment (dp=4)
# ---------------------------------------------------------------------------

def test_batcher_rounds_raw_bucket_plan_to_mesh_multiple():
    from sparkdl_tpu.serving.batcher import DynamicBatcher

    b = DynamicBatcher(max_batch_size=30, bucket_plan=[6, 12, 30],
                       align=4)
    assert b.bucket_plan == [8, 12, 32]  # effective_device_batch rounding
    assert all(x % 4 == 0 for x in b.bucket_plan)
    # align=1 keeps raw plans untouched
    assert DynamicBatcher(max_batch_size=30,
                          bucket_plan=[6, 12, 30]).bucket_plan == [6, 12, 30]


def test_ragged_cuts_stay_mesh_aligned_dp4():
    """Regression gate for the ragged/mesh interplay: on a dp=4 mesh
    every ragged CUT lands on a mesh-rounded bucket boundary, so a
    20-deep queue dispatches as 12 + 8 with ZERO pad rows, and a
    5-deep residual pads to the 8 bucket — all device batches
    multiples of the data-axis size."""
    from sparkdl_tpu.serving.server import Server

    rng = np.random.default_rng(5)
    v = _variables(rng, d=8)
    mesh = mesh_lib.get_mesh(num_devices=4)  # dp4 x tp1
    rows = [rng.normal(size=(8,)).astype(np.float32) for _ in range(25)]
    from sparkdl_tpu.utils.metrics import Metrics
    metrics = Metrics()
    with Server(_wide_fn, v, mesh=mesh, max_batch_size=24,
                max_wait_ms=25, bucket_sizes=[6, 12, 24], ragged=True,
                cache=False, max_inflight_batches=1,
                metrics=metrics) as srv:
        assert srv.bucket_sizes == [8, 12, 24]  # mesh-rounded
        assert srv._batcher.bucket_plan == [8, 12, 24]
        assert srv._batcher.align == 4
        srv.warmup(rows[0])
        warm = dict(metrics.snapshot_raw()["counters"])
        futs = [srv.submit(r) for r in rows[:20]]
        outs = [np.asarray(f.result(timeout=30)) for f in futs]
        counters = metrics.snapshot_raw()["counters"]
        # 20 queued -> cut 12 + cut 8: zero pad rows for the burst
        assert counters.get("engine.pad_rows", 0) == warm.get(
            "engine.pad_rows", 0)
        # a 5-deep residual pads to the smallest (8) bucket
        futs = [srv.submit(r) for r in rows[20:]]
        outs += [np.asarray(f.result(timeout=30)) for f in futs]
        counters = metrics.snapshot_raw()["counters"]
        assert (counters.get("engine.pad_rows", 0)
                - warm.get("engine.pad_rows", 0)) == 3
    ref = np.tanh(np.stack(rows) @ v["dense"]["kernel"]
                  + v["dense"]["bias"]).astype(np.float32)
    assert all(np.allclose(o, r, rtol=1e-6, atol=1e-6)
               for o, r in zip(outs, ref))


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------

def test_engine_indivisible_explicit_spec_falls_back_to_replicated():
    """An explicit param_shardings spec that does not divide its leaf
    gets the SAME per-leaf replicate fallback the rules path promises
    (resolve_param_shardings' contract) instead of crashing
    device_put/jit."""
    rng = np.random.default_rng(9)
    v = {"dense": {"kernel": rng.normal(size=(16, 12)).astype(np.float32),
                   "bias": rng.normal(size=(12,)).astype(np.float32)}}

    def fn(vv, x):
        return jnp.tanh(x @ vv["dense"]["kernel"])

    mesh = mesh_lib.get_mesh(model_parallel=8)  # 12 % 8 != 0
    eng = InferenceEngine(fn, v, mesh=mesh, device_batch_size=8,
                          param_shardings={"dense": {
                              "kernel": P(None, "model"), "bias": P()}})
    assert eng.param_shardings is None  # both leaves fell back -> collapse
    x = rng.normal(size=(8, 16)).astype(np.float32)
    ref = np.tanh(x @ v["dense"]["kernel"])
    np.testing.assert_allclose(np.asarray(eng(x)), ref, rtol=1e-5,
                               atol=1e-6)
    # a spec pytree that does NOT mirror the params structure raises
    # instead of silently pairing specs with the wrong leaves
    with pytest.raises(ValueError, match="mirror the params"):
        InferenceEngine(fn, v, mesh=mesh, device_batch_size=8,
                        param_shardings=[P(None, "model"), P()])


def test_none_only_specs_collapse_like_empty():
    """``P(None, None)`` names no axis: it must collapse exactly like
    ``P()`` — same digest ("replicated"), same compiled program — or a
    spelling difference would fork a second compile of a byte-identical
    program and purge the compile cache across restarts."""
    assert mesh_lib.spec_is_replicated(P(None, None))
    assert mesh_lib.specs_all_replicated({"a": P(None, None), "b": P()})
    assert mesh_lib.partition_digest(
        {"a": P(None, None), "b": P()}) == "replicated"
    rng = np.random.default_rng(10)
    v = _variables(rng)
    mesh = mesh_lib.get_mesh(model_parallel=8)
    e_spelled = InferenceEngine(
        _wide_fn, v, mesh=mesh, device_batch_size=8,
        param_shardings={"dense": {"kernel": P(None, None),
                                   "bias": P()}})
    e_plain = InferenceEngine(_wide_fn, v, mesh=mesh,
                              device_batch_size=8)
    assert e_spelled.sharding_digest == "replicated"
    assert e_spelled._compiled is e_plain._compiled


def test_fleet_zoo_overrides_survive_explicit_dtype():
    """A caller pinning compute_dtype must not silently drop the
    entry's NON-dtype overrides (partition_rules, the donate_batch
    GC001 exemption) — only the dtype contract yields to the caller."""
    from types import SimpleNamespace

    from sparkdl_tpu.serving.fleet import Fleet

    rng = np.random.default_rng(11)
    v = _variables(rng, d=8)
    mesh = mesh_lib.get_mesh(model_parallel=8)
    entry = SimpleNamespace(
        fn=_wide_fn,
        engine_overrides={"donate_batch": False,
                          "partition_rules":
                          mesh_lib.default_partition_rules,
                          "compute_dtype": jnp.bfloat16,
                          "output_host_dtype": np.float32})
    mv = SimpleNamespace(version=1, variables=v)
    # donate_batch=True as a FLEET-WIDE default: the entry's recorded
    # exemption (False) must still win — entry overrides beat fleet
    # defaults, explicit per-entry server_kwargs beat both
    fleet = Fleet(cache=False, donate_batch=True)
    try:
        srv = fleet._build_server(
            entry, mv, {"compute_dtype": None, "mesh": mesh,
                        "max_batch_size": 8, "bucket_sizes": [8]})
        try:
            # caller's dtype choice won; the sharding + donation
            # overrides still applied
            assert srv._compute_dtype is None
            assert (srv._partition_rules
                    is mesh_lib.default_partition_rules)
            assert srv._donate_batch is False
        finally:
            srv.close(drain=False)
    finally:
        fleet.close()


def test_gc005_unknown_axis_in_declaration_fires():
    from sparkdl_tpu.analysis.program.audit import audit_program

    spec = _wide_dense_spec(sharded=True)
    spec.param_partition = (("dense/bias", []),
                            ("dense/kernel", [None, "modle"]))  # typo
    out = audit_program(spec)
    assert any(f.code == "GC005" and "unknown mesh axis" in f.message
               for f in out["findings"])


# ---------------------------------------------------------------------------
# compile-cache manifest carries the sharding policy
# ---------------------------------------------------------------------------

def test_compile_cache_policy_flip_purges_classified_gc005(tmp_path):
    from sparkdl_tpu.parallel import compile_cache

    d = str(tmp_path / "cc")
    rng = np.random.default_rng(6)
    v = _variables(rng)
    mesh = mesh_lib.get_mesh(model_parallel=8)
    e_rep = InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=8)
    e_tp = InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=8,
                           partition_rules=mesh_lib.
                           default_partition_rules)
    assert e_rep.compile_policy() != e_tp.compile_policy()
    assert e_rep.compile_policy().endswith("params=replicated")
    try:
        st = compile_cache.configure(d, policy=e_rep.compile_policy())
        assert st["invalidated"] is False
        assert st["sharding_policy"] == e_rep.compile_policy()
        manifest = json.loads(
            (tmp_path / "cc" / compile_cache.MANIFEST_NAME).read_text())
        assert manifest["sharding_policies"] == [e_rep.compile_policy()]
        # same policy on "restart": reused, nothing purged
        st = compile_cache.configure(d, policy=e_rep.compile_policy())
        assert st["reused"] is True and st["invalidated"] is False
        # a policy the deployment never used purges, classified GC005
        st = compile_cache.configure(d, policy=e_tp.compile_policy())
        assert st["invalidated"] is True
        assert st["drift_rules"] == ["GC005"]
    finally:
        compile_cache._reset_for_tests()


def test_compile_cache_policy_set_is_order_independent(tmp_path):
    """A deployment whose engines use SEVERAL policies (a fleet mixing
    sharded and replicated entries) must reuse across restarts no
    matter which engine constructs first: every engine's policy joins
    the manifest's set (note_policy), and a restart whose first policy
    is already IN the set reuses; only a policy the deployment never
    used purges."""
    from sparkdl_tpu.parallel import compile_cache

    d = str(tmp_path / "cc")
    a, b, c = ("mesh=1x8|params=aaa", "mesh=8x1|params=replicated",
               "mesh=2x4|params=ccc")
    try:
        st = compile_cache.configure(d, policy=a)
        assert st["invalidated"] is False
        compile_cache.note_policy(b)  # the second engine's layout
        manifest = json.loads(
            (tmp_path / "cc" / compile_cache.MANIFEST_NAME).read_text())
        assert manifest["sharding_policies"] == sorted([a, b])
        # restart constructing the OTHER engine first: reused
        st = compile_cache.configure(d, policy=b)
        assert st["reused"] is True and st["invalidated"] is False
        # a test/CLI configure with no policy is a wildcard: no purge
        st = compile_cache.configure(d)
        assert st["reused"] is True
        # a layout the deployment never used still purges (GC005)
        st = compile_cache.configure(d, policy=c)
        assert st["invalidated"] is True
        assert st["drift_rules"] == ["GC005"]
        assert st["sharding_policies"] == [c]  # fresh set after purge
    finally:
        compile_cache._reset_for_tests()


# ---------------------------------------------------------------------------
# bench HBM rider
# ---------------------------------------------------------------------------

def test_bench_sharding_rider_stamps_mesh_and_bytes():
    import bench

    bench._SHARD_LOCK_CACHE.clear()
    try:
        snapshot = {"gauges": {
            "engine.mesh_data_axis": 1.0, "engine.mesh_model_axis": 8.0,
            "engine.replicated_param_bytes": 800.0,
            "engine.param_bytes_per_chip": 100.0}}
        rider = bench._sharding_rider(snapshot)
        m = rider["measured"]
        assert m["mesh_shape"] == {"data": 1, "model": 8}
        assert m["replicated_param_bytes_per_chip"] == 800
        assert m["sharded_param_bytes_per_chip"] == 100
        assert m["sharded_vs_replicated_ratio"] == 0.125
        lock = rider["lockfile"]
        # the lockfile half: every zoo model's replicated HBM cost and
        # the committed tensor-parallel programs' per-chip ratio
        assert len(lock["zoo"]) >= 9
        tp8 = lock["sharded_programs"][
            "serving/wide_dense/f32/b32/dp1tp8"]
        assert tp8["sharded_vs_replicated_ratio"] < 0.2
        assert (tp8["sharded_param_bytes_per_chip"]
                < tp8["replicated_param_bytes_per_chip"])
        # no gauges -> lockfile half only, never a crash
        assert bench._sharding_rider(None)["measured"] is None
        json.dumps(rider)
    finally:
        bench._SHARD_LOCK_CACHE.clear()


def test_live_engine_gauges_feed_the_rider():
    import bench

    from sparkdl_tpu.obs.export import metrics_snapshot
    from sparkdl_tpu.utils.metrics import Metrics

    rng = np.random.default_rng(7)
    v = _variables(rng)
    metrics = Metrics()
    mesh = mesh_lib.get_mesh(model_parallel=8)
    InferenceEngine(_wide_fn, v, mesh=mesh, device_batch_size=8,
                    partition_rules=mesh_lib.default_partition_rules,
                    metrics=metrics)
    rider = bench._sharding_rider(metrics_snapshot(metrics))
    m = rider["measured"]
    assert m["mesh_shape"] == {"data": 1, "model": 8}
    assert m["sharded_vs_replicated_ratio"] < 1.0
