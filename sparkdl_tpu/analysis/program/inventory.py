"""The stack's program inventory: every jit program the scoring and
training layers construct, as :class:`~sparkdl_tpu.analysis.program.
audit.ProgramSpec`s built from the SAME constructors the runtime uses
(``parallel.engine.build_dispatch_jit``, ``serving.server.bucket_plan``,
``transformers.named_image.zoo_model_fn``, ``parallel.train.
make_train_step``, the ``ops.sepconv`` kernel jits) — so the audited
program set cannot drift from the served one.

Abstract by construction: model variables come from
``ModelSpec.abstract_variables()`` (``jax.eval_shape`` over ``init`` —
shape/dtype only), batches are ``ShapeDtypeStruct``s, and nothing is
ever placed on a device.

Not in the inventory, and so not in ``PROGRAMS.lock.json``: the sequence
trunks' programs (``models/hybrid_trunk``, ``models/expert_trunk``),
which run through ``ModelTransformer`` alone (``ROADMAP.md``, Reach R3).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from sparkdl_tpu.analysis.program.audit import ProgramSpec

#: The donation exemption every zoo dispatch program records (GC001):
#: proved by the audit itself — jax reports the donated uint8 batch
#: unusable because no f32/bf16 output can alias it.
ZOO_DONATE_REASON = (
    "uint8 image batch cannot alias the float feature output (smaller, "
    "different dtype); XLA drops the donation, so the engine leaves "
    "donate_batch off for zoo programs")

SEPCONV_DONATE_REASON = (
    "chained padded-flat activations; callers reuse the input "
    "(residual adds), so donation would corrupt the residual source")

#: Canonical kernel audit shapes: Xception middle flow (sepconv), entry
#: flow block under row tiling, MobileNetV2 inverted-residual tail.
_KERNEL_SHAPES = {
    "sepconv": dict(b=8, h=19, w=19, c=728, f=728),
    "sepconv_tiled": dict(b=8, h=74, w=74, c=256, f=256, th=8),
    "mbconv": dict(b=8, h=28, w=28, c=192, f=32),
}


def _cast_floating_avals(avals, dtype):
    """ShapeDtypeStruct twin of the engine's ``_cast_floating``: the
    audited variables must carry the dtype the engine would actually
    place on device under a compute-dtype knob."""
    import jax
    import jax.numpy as jnp

    def cast(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(leaf.shape, dtype)
        return leaf

    return jax.tree_util.tree_map(cast, avals)


def _mesh_axes(mesh) -> Dict[str, int]:
    return {str(name): int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)}


def zoo_dispatch_specs(max_batch_size: int = 32,
                       models: Optional[Sequence[str]] = None,
                       compute_dtype: str = "bfloat16",
                       mesh=None) -> List[ProgramSpec]:
    """One spec per (zoo model x serving bucket x cut): the engine
    program exactly as ``_zoo_engine`` + ``InferenceEngine`` build it
    (fused preprocess, compute-dtype cast, replicated params, data-axis
    batch sharding) — the featurizer cut at every compiled shape in the
    serving bucket plan, and the predictor cut (``Server(featurize=
    False)``, the serving default) at the largest bucket."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import SUPPORTED_MODELS, get_model_spec
    from sparkdl_tpu.parallel.engine import (effective_device_batch,
                                             resolve_engine_mesh)
    from sparkdl_tpu.serving.server import bucket_plan

    mesh = resolve_engine_mesh(mesh)
    buckets = bucket_plan(max_batch_size, mesh=mesh)
    names = list(models) if models else list(SUPPORTED_MODELS)
    axes = _mesh_axes(mesh)
    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else None
    specs: List[ProgramSpec] = []
    # one abstract-variables eval_shape per model, shared by its buckets
    # and cuts
    memo: Dict[str, Any] = {}

    def avals(name: str):
        mspec = get_model_spec(name)
        if name not in memo:
            av = mspec.abstract_variables()
            if cdt is not None:
                av = _cast_floating_avals(av, cdt)
            memo[name] = av
        return memo[name]

    def build(name: str, bucket: int, featurize: bool):
        def _build():
            import jax
            import numpy as np

            from sparkdl_tpu.parallel.engine import build_dispatch_jit
            from sparkdl_tpu.transformers.named_image import zoo_model_fn

            mspec = get_model_spec(name)
            fn = zoo_model_fn(name, featurize=featurize, compute_dtype=cdt)
            h, w = mspec.input_size
            jitted = build_dispatch_jit(fn, mesh, donate_batch=False)
            batch = jax.ShapeDtypeStruct((bucket, h, w, 3), np.uint8)
            return jitted, (avals(name), batch)

        return _build

    base = dict(kind="dispatch", compute_dtype=compute_dtype, donate=(),
                donate_reason=ZOO_DONATE_REASON, mesh_axes=axes)
    for name in names:
        canonical = get_model_spec(name).name  # registry casing
        for b in buckets:
            specs.append(ProgramSpec(
                name=f"zoo/{canonical}/featurize/{compute_dtype}/b{b}",
                build=build(canonical, b, featurize=True),
                batch_rows=b,
                shardings=("replicated", "batch"),
                group=f"zoo/{canonical}/featurize/{compute_dtype}",
                model=canonical, bucket=b, **base))
        # the predictor cut (serving default) at ONE fixed canonical
        # bucket (b32, mesh-rounded — stable across --max-batch subset
        # audits); no model/bucket tags: GC004's pad accounting is
        # cut-independent and already gated by the featurize set above
        pb = effective_device_batch(32, mesh)
        specs.append(ProgramSpec(
            name=f"zoo/{canonical}/predict/{compute_dtype}/b{pb}",
            build=build(canonical, pb, featurize=False),
            batch_rows=pb,
            shardings=("replicated", "batch"),
            group=f"zoo/{canonical}/predict/{compute_dtype}", **base))
    return specs


def fleet_dispatch_specs(models: Optional[Sequence[str]] = None,
                         max_batch_size: int = 32,
                         compute_dtype: str = "bfloat16",
                         mesh=None) -> List[ProgramSpec]:
    """Every program a ``serving.fleet.Fleet`` can construct for its
    zoo-backed entries — the fleet enumeration hook graftcheck audits.

    BY CONSTRUCTION this is the existing zoo × serving-bucket-plan
    program set, nothing more: a fleet entry resolves its fn exactly
    once through ``named_image.zoo_serving_bundle`` (→ ``zoo_model_fn``,
    the same constructor :func:`zoo_dispatch_specs` lowers), every
    version of the entry reuses that one fn object with new WEIGHTS
    only, and each version's ``Server`` compiles through the same
    ``bucket_plan`` × ``build_dispatch_jit`` path.  New versions and
    hot-swaps therefore add NO programs to the inventory —
    ``PROGRAMS.lock.json`` regenerates only if the underlying zoo ×
    bucket set itself changes (tests pin the set equality and match the
    audited executable keys/fingerprints against the committed
    lockfile).

    The head fan-out tier (``Fleet.add_fanout_model``) keeps the same
    property by a different split: its backbone is one ordinary
    dispatch program and ALL tenant heads share one vmapped gather
    program, audited separately by :func:`headfanout_dispatch_specs` —
    head add/swap/evict changes weights and bank capacity, never the
    program set."""
    return zoo_dispatch_specs(max_batch_size=max_batch_size,
                              models=models, compute_dtype=compute_dtype,
                              mesh=mesh)


#: The head fan-out proof model's shape (ISSUE 17): a 12 → 16 feature
#: backbone (output WIDER than the input row, so the batch donation can
#: never alias — the recorded GC001 exemption below) in front of 64
#: stacked per-tenant 16 → 4 heads — the smallest program pair that
#: pins the tier's two claims chip-free: the backbone-cut program's
#: StableHLO fingerprint is what ``serving.cache.
#: lockfile_model_fingerprint("headfanout")`` resolves (the feature-cut
#: cache namespace and the head-swap proof both key on it), and the ONE
#: vmapped gather program serves every tenant's head.
HEADFANOUT_DIM_IN = 12
HEADFANOUT_DIM_FEAT = 16
HEADFANOUT_CLASSES = 4
HEADFANOUT_TENANTS = 64

HEADFANOUT_DONATE_REASON = (
    "the (b, 12) f32 row batch cannot alias the (b, 16) feature output "
    "(the feature cut widens it), and the fan-out program's gathered "
    "head inputs are read by every padded row — XLA would drop either "
    "donation, so the serving tier leaves both off")


def headfanout_dispatch_specs(batch_rows: int = 32,
                              tenants: int = HEADFANOUT_TENANTS,
                              mesh=None) -> List[ProgramSpec]:
    """The shared-backbone head fan-out programs (ISSUE 17), built
    through the EXACT runtime constructors: the backbone feature cut
    via ``build_dispatch_jit`` over ``parallel.engine.
    head_fanout_backbone_fn`` (the module-level fn the tests, the bench
    and ``HeadFanoutServer`` smoke paths all serve), and the stacked
    head bank's single vmapped gather program via
    ``build_head_fanout_jit`` over ``parallel.engine.dense_head_row``
    at the canonical 64-tenant capacity.  The backbone record carries
    ``model="headfanout"`` so ``lockfile_model_fingerprint`` resolves
    the tier's committed backbone identity — the fingerprint the
    feature-cut cache namespace and ``head_swap_report``'s
    ``fingerprint_pinned`` witness both pin against; the head program
    deliberately does NOT (head-program evolution must never rotate
    the backbone's feature namespace).  Neither spec records a
    ``bucket``: the fan-out tier reuses the serving bucket plan, whose
    pad accounting GC004 already gates through the zoo set."""
    from sparkdl_tpu.parallel.engine import (effective_device_batch,
                                             resolve_engine_mesh)

    mesh = resolve_engine_mesh(mesh)
    axes = _mesh_axes(mesh)
    b = effective_device_batch(batch_rows, mesh)

    def build_backbone():
        import jax
        import numpy as np

        from sparkdl_tpu.parallel.engine import (build_dispatch_jit,
                                                 head_fanout_backbone_fn)

        jitted = build_dispatch_jit(head_fanout_backbone_fn, mesh,
                                    donate_batch=False)
        variables = {"backbone": jax.ShapeDtypeStruct(
            (HEADFANOUT_DIM_IN, HEADFANOUT_DIM_FEAT), np.float32)}
        batch = jax.ShapeDtypeStruct((b, HEADFANOUT_DIM_IN), np.float32)
        return jitted, (variables, batch)

    def build_heads():
        import jax
        import numpy as np

        from sparkdl_tpu.parallel.engine import (build_head_fanout_jit,
                                                 dense_head_row)

        jitted = build_head_fanout_jit(dense_head_row, mesh)
        stacked = {
            "kernel": jax.ShapeDtypeStruct(
                (tenants, HEADFANOUT_DIM_FEAT, HEADFANOUT_CLASSES),
                np.float32),
            "bias": jax.ShapeDtypeStruct((tenants, HEADFANOUT_CLASSES),
                                         np.float32),
        }
        idx = jax.ShapeDtypeStruct((b,), np.int32)
        feats = jax.ShapeDtypeStruct((b, HEADFANOUT_DIM_FEAT), np.float32)
        return jitted, (stacked, idx, feats)

    base = dict(kind="dispatch", donate=(),
                donate_reason=HEADFANOUT_DONATE_REASON, mesh_axes=axes)
    return [
        ProgramSpec(name=f"headfanout/backbone/f32/b{b}",
                    build=build_backbone, batch_rows=b,
                    shardings=("replicated", "batch"),
                    group="headfanout/backbone/f32",
                    model="headfanout", **base),
        ProgramSpec(name=f"headfanout/heads/k{tenants}/f32/b{b}",
                    build=build_heads, batch_rows=b,
                    shardings=("replicated", "batch", "batch"),
                    group=f"headfanout/heads/k{tenants}/f32", **base),
    ]


def generic_dispatch_specs(feature_dim: int = 16,
                           mesh=None) -> List[ProgramSpec]:
    """The donated GENERIC serving program (ISSUE 13 satellite):
    ``Server`` auto-donates the per-dispatch batch buffer for non-zoo
    float-input models whenever its eval-shape probe proves XLA will
    consume the donation (``Server._probe_donate``), and this spec
    audits that claim — a square float linear head built through the
    SAME ``build_dispatch_jit(donate_batch=True)`` constructor the
    serving path uses, declaring ``donate=(1,)`` with NO recorded
    exemption, so GC001 fails loudly if the donation ever stops
    aliasing.  The zoo programs stay donate-off (their uint8 batch can
    never alias — ``ZOO_DONATE_REASON``); this is the program shape
    where donation is actually consumable, pinned in the lockfile."""
    from sparkdl_tpu.parallel.engine import (effective_device_batch,
                                             resolve_engine_mesh)

    mesh = resolve_engine_mesh(mesh)
    axes = _mesh_axes(mesh)
    b = effective_device_batch(32, mesh)

    def _build():
        import jax
        import numpy as np

        from sparkdl_tpu.parallel.engine import build_dispatch_jit

        def fn(v, x):
            import jax.numpy as jnp

            return jnp.tanh(x @ v["w"])

        jitted = build_dispatch_jit(fn, mesh, donate_batch=True)
        variables = {"w": jax.ShapeDtypeStruct(
            (feature_dim, feature_dim), np.float32)}
        batch = jax.ShapeDtypeStruct((b, feature_dim), np.float32)
        return jitted, (variables, batch)

    return [ProgramSpec(
        name=f"serving/generic/tanh_linear/f32/b{b}",
        kind="dispatch", build=_build, donate=(1,),
        batch_rows=b, mesh_axes=axes,
        shardings=("replicated", "batch"),
        group="serving/generic/tanh_linear/f32")]


#: the wide-dense proof model's shape: a (in, out) f32 kernel of 64 MB
#: — over the 32 MB GC005 replicated budget — with a SMALL contraction
#: dim and a WIDE output dim, so the tensor-parallel split (output
#: columns across the model axis) puts no reduction across shards: each
#: output element stays one 128-term dot product, and at this shape the
#: sharded rows equal the single-device replicated oracle bit for bit
#: (tests pin it at runtime; other shapes may differ in summation order)
WIDE_DENSE_IN = 128
WIDE_DENSE_OUT = 131072


def sharded_dispatch_specs(feature_dim_in: int = WIDE_DENSE_IN,
                           feature_dim_out: int = WIDE_DENSE_OUT,
                           batch_rows: int = 32) -> List[ProgramSpec]:
    """The tensor-parallel dispatch programs (ISSUE 14): a synthetic
    WIDE-DENSE head whose single kernel (128 x 131072 f32 = 64 MB at
    the defaults) busts graftcheck's 32 MB replicated-param budget on
    any model-axis mesh — the smallest model that PROVES the HBM claim
    chip-free.  Each spec builds through the same
    ``build_dispatch_jit(param_shardings=...)`` constructor the engine
    uses, with the layout from ``mesh.resolve_param_shardings`` under
    the default rules (kernel split on its output dim, bias/scalars
    replicated), on the model-axis meshes the 8-virtual-device audit
    topology supports: ``dp1tp8`` (pure tensor parallel) and
    ``dp2tp4`` (mixed).  GC005 then verifies the claim: no replicated
    leaf above budget (the kernel now costs bytes/model_axis per
    chip), every split dim divides, sdy.sharding present — where the
    same program under ``shardings=("replicated", "batch")`` is the
    budget-buster negative fixture the tests pin.  The batch is
    donated (f32 in, f32 out — but note the output is WIDER than the
    batch, so XLA cannot alias it; the recorded reason below is the
    GC001 exemption, symmetric to the zoo's uint8 one)."""
    import jax

    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import effective_device_batch

    n = len(jax.devices())
    layouts = [n]  # pure TP: (1, n)
    if n >= 4 and n % 2 == 0:
        layouts.append(n // 2)  # mixed: (2, n/2)
    specs: List[ProgramSpec] = []
    for model_parallel in layouts:
        if model_parallel < 2 or feature_dim_out % model_parallel:
            continue
        mesh = mesh_lib.get_mesh(model_parallel=model_parallel)
        axes = _mesh_axes(mesh)
        b = effective_device_batch(batch_rows, mesh)
        # the default-rule layout, spelled statically so the declaration
        # cannot drift from what build() resolves
        kernel_spec = mesh_lib.spec_to_json(
            jax.sharding.PartitionSpec(None, mesh_lib.MODEL_AXIS))
        partition = (("dense/bias", []), ("dense/kernel", kernel_spec))

        def build(mesh=mesh, b=b):
            def _build():
                import numpy as np

                from sparkdl_tpu.parallel.engine import build_dispatch_jit

                variables = {"dense": {
                    "kernel": jax.ShapeDtypeStruct(
                        (feature_dim_in, feature_dim_out), np.float32),
                    "bias": jax.ShapeDtypeStruct((feature_dim_out,),
                                                 np.float32),
                }}
                shardings, _ = mesh_lib.resolve_param_shardings(
                    variables, mesh)
                jitted = build_dispatch_jit(wide_dense_fn, mesh,
                                            donate_batch=False,
                                            param_shardings=shardings)
                batch = jax.ShapeDtypeStruct((b, feature_dim_in),
                                             np.float32)
                return jitted, (variables, batch)

            return _build

        name = (f"serving/wide_dense/f32/b{b}/"
                f"dp{axes['data']}tp{axes['model']}")
        specs.append(ProgramSpec(
            name=name, kind="dispatch", build=build(), donate=(),
            donate_reason=WIDE_DENSE_DONATE_REASON,
            batch_rows=b, mesh_axes=axes,
            shardings=("params", "batch"),
            param_partition=partition,
            group=name))
    return specs


def wide_dense_fn(v, x):
    """The wide-dense proof model's fn — module-level so the runtime
    bit-identity test serves the EXACT fn the audited programs lower."""
    import jax.numpy as jnp

    return jnp.tanh(x @ v["dense"]["kernel"] + v["dense"]["bias"])


WIDE_DENSE_DONATE_REASON = (
    "the (b, 128) f32 batch cannot alias the (b, 131072) output — the "
    "whole point of the wide head is an output wider than its input, "
    "so XLA would drop the donation")


def train_step_specs(batch_rows: int = 32, feature_dim: int = 2048,
                     num_classes: int = 10, mesh=None) -> List[ProgramSpec]:
    """The data-parallel train-step programs the estimator layer
    compiles (``parallel.train.make_train_step``): the transfer-learning
    linear head (``estimators.classification``'s fit program) as the
    plain per-step jit and the ``steps_per_execution`` multi-step scan.
    Donation is the whole point here (params/opt_state are donated and
    every leaf must alias), so these are GC001's primary subjects."""
    from sparkdl_tpu.parallel.engine import resolve_engine_mesh

    mesh = resolve_engine_mesh(mesh)
    axes = _mesh_axes(mesh)

    def make(kind_multi: bool):
        def _build():
            import jax
            import numpy as np
            import optax

            from sparkdl_tpu.parallel.train import make_train_step

            def predict_fn(p, xb):
                return xb @ p["w"] + p["b"]  # the linear-head logits

            opt = optax.adam(1e-3)
            step = make_train_step(predict_fn,
                                   "sparse_categorical_crossentropy",
                                   opt, mesh=mesh, cache=False)
            params_av = {
                "w": jax.ShapeDtypeStruct((feature_dim, num_classes),
                                          np.float32),
                "b": jax.ShapeDtypeStruct((num_classes,), np.float32),
            }
            opt_av = jax.eval_shape(opt.init, params_av)
            x = jax.ShapeDtypeStruct((batch_rows, feature_dim), np.float32)
            y = jax.ShapeDtypeStruct((batch_rows,), np.int32)
            if not kind_multi:
                return step.step_fn, (params_av, opt_av, x, y)
            k = 4
            xs = jax.ShapeDtypeStruct((k, batch_rows, feature_dim),
                                      np.float32)
            ys = jax.ShapeDtypeStruct((k, batch_rows), np.int32)
            return step.multi(k), (params_av, opt_av, xs, ys)

        return _build

    return [
        ProgramSpec(name=f"train/linear_head/step/b{batch_rows}",
                    build=make(False), kind="train", donate=(0, 1),
                    batch_rows=batch_rows, mesh_axes=axes,
                    shardings=("replicated", "replicated",
                               "batch", "batch"),
                    group="train/linear_head/step"),
        ProgramSpec(name=f"train/linear_head/multi4/b{batch_rows}",
                    build=make(True), kind="train", donate=(0, 1),
                    batch_rows=batch_rows, mesh_axes=axes,
                    shardings=("replicated", "replicated",
                               "stacked_batch", "stacked_batch"),
                    group="train/linear_head/multi4"),
    ]


def sepconv_kernel_specs() -> List[ProgramSpec]:
    """The fused Pallas kernel jits (``ops/sepconv.py``) at their
    canonical Xception/MobileNetV2 shapes, lowered through the pallas
    INTERPRETER (``interpret=True``) so the fingerprint is chip-free.
    No mesh/sharding (kernels shard through the caller's program) and a
    recorded donation exemption: the flat activations chain."""

    def build_sepconv():
        import jax
        import jax.numpy as jnp

        from sparkdl_tpu.ops.sepconv import _fused_sepconv_tpu, flat_width

        s = _KERNEL_SHAPES["sepconv"]
        lo = (s["h"] + 2) * flat_width(s["w"])
        args = (jax.ShapeDtypeStruct((s["b"], lo, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((3, 3, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["c"], s["f"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["f"],), jnp.float32),
                jax.ShapeDtypeStruct((s["f"],), jnp.float32))
        return _Partial(_fused_sepconv_tpu, h=s["h"], w=s["w"],
                        pre_relu=True, post_relu=False,
                        interpret=True), args

    def build_tiled():
        import jax
        import jax.numpy as jnp

        from sparkdl_tpu.ops.sepconv import (_fused_sepconv_tpu_tiled,
                                             flat_rows, flat_width)

        s = _KERNEL_SHAPES["sepconv_tiled"]
        lo = flat_rows(s["h"], s["th"]) * flat_width(s["w"])
        args = (jax.ShapeDtypeStruct((s["b"], lo, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((3, 3, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["c"], s["f"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["f"],), jnp.float32),
                jax.ShapeDtypeStruct((s["f"],), jnp.float32))
        return _Partial(_fused_sepconv_tpu_tiled, h=s["h"], w=s["w"],
                        th=s["th"], pre_relu=True, post_relu=False,
                        interpret=True), args

    def build_mbconv():
        import jax
        import jax.numpy as jnp

        from sparkdl_tpu.ops.sepconv import _fused_mbconv_tpu, flat_width

        s = _KERNEL_SHAPES["mbconv"]
        lo = (s["h"] + 2) * flat_width(s["w"])
        args = (jax.ShapeDtypeStruct((s["b"], lo, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((3, 3, s["c"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["c"], s["f"]), jnp.bfloat16),
                jax.ShapeDtypeStruct((s["c"],), jnp.float32),
                jax.ShapeDtypeStruct((s["f"],), jnp.float32))
        return _Partial(_fused_mbconv_tpu, h=s["h"], w=s["w"],
                        interpret=True), args

    base = dict(kind="kernel", donate=(),
                donate_reason=SEPCONV_DONATE_REASON,
                compute_dtype="bfloat16")
    s1 = _KERNEL_SHAPES["sepconv"]
    s2 = _KERNEL_SHAPES["sepconv_tiled"]
    s3 = _KERNEL_SHAPES["mbconv"]
    return [
        ProgramSpec(name=f"kernel/sepconv/{s1['h']}x{s1['w']}x{s1['c']}",
                    build=build_sepconv, batch_rows=s1["b"],
                    group="kernel/sepconv", **base),
        ProgramSpec(
            name=f"kernel/sepconv_tiled/{s2['h']}x{s2['w']}x{s2['c']}",
            build=build_tiled, batch_rows=s2["b"],
            group="kernel/sepconv_tiled", **base),
        ProgramSpec(name=f"kernel/mbconv/{s3['h']}x{s3['w']}x{s3['c']}",
                    build=build_mbconv, batch_rows=s3["b"],
                    group="kernel/mbconv", **base),
    ]


class _Partial:
    """A static-kwarg binder exposing the jit object's ``lower``: the
    sepconv jits take their shape parameters as ``static_argnames``, so
    the audit lowers them with those bound."""

    def __init__(self, jitted, **static_kwargs):
        self._jitted = jitted
        self._kw = static_kwargs

    def lower(self, *args):
        return self._jitted.lower(*args, **self._kw)


def stack_programs(max_batch_size: int = 32,
                   models: Optional[Sequence[str]] = None,
                   compute_dtype: str = "bfloat16",
                   include_train: bool = True,
                   include_kernels: bool = True,
                   mesh=None) -> List[ProgramSpec]:
    """The full auditable inventory: zoo x bucket plan (+ the train-step
    and sepconv-kernel programs unless excluded).  ``models`` narrows
    the zoo sweep (the tier-1 acceptance gate audits a small subset;
    ``tools/graftcheck.py`` sweeps everything)."""
    specs = zoo_dispatch_specs(max_batch_size=max_batch_size,
                               models=models, compute_dtype=compute_dtype,
                               mesh=mesh)
    # the donated generic serving program rides every audit (subset
    # ones included): it is model-independent and cheap to lower, and
    # GC001's consumed-donation check is the whole point of it
    specs.extend(generic_dispatch_specs(mesh=mesh))
    # the tensor-parallel wide-dense programs (ISSUE 14) ride every
    # audit the same way: cheap to lower, model-independent, and GC005's
    # sharded-HBM proof (no replicated leaf above budget once the
    # kernel splits) is the whole point of them
    specs.extend(sharded_dispatch_specs())
    # the head fan-out tier's program pair (ISSUE 17): the backbone cut
    # (whose fingerprint keys the feature-cut cache namespace) and the
    # one vmapped gather program every tenant's head shares
    specs.extend(headfanout_dispatch_specs(mesh=mesh))
    if include_train:
        # the train batch is the estimator's default fit batch, NOT a
        # serving bucket — keep it fixed so subset audits (--models /
        # --max-batch) still line up with the committed baseline
        specs.extend(train_step_specs(mesh=mesh))
    if include_kernels:
        specs.extend(sepconv_kernel_specs())
    return specs
