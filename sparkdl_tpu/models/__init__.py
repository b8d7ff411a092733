"""Pretrained-CNN zoo registry.

Replaces the reference's ``SUPPORTED_MODELS`` registry
(``python/sparkdl/transformers/named_image.py — SUPPORTED_MODELS``,
``_buildTFGraphForName``) and the Scala packaged-GraphDef registry
(``src/main/scala/com/databricks/sparkdl/Models.scala``): the same five
named models, but as flax modules compiled by XLA:TPU instead of frozen TF
GraphDefs run in per-executor sessions.

Each ``ModelSpec`` carries what the transformer layer needs: input size,
featurizer cut dimensionality, ImageNet preprocess mode, and a loader that
builds the keras.applications twin for weight import (pretrained weights when
the environment provides them, otherwise architecture-faithful random init).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from sparkdl_tpu.models.preprocess import get_preprocess_fn
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass(frozen=True)
class ModelSpec:
    """One zoo entry: everything needed to featurize/predict with the model."""

    name: str
    module_builder: Callable[[], Any]          # () -> flax module
    input_size: Tuple[int, int]                # (height, width)
    feature_size: int                          # featurizer-cut dimensionality
    preprocess_mode: str                       # see models.preprocess
    keras_app: str                             # keras.applications attr name
    # () -> str tag when module_builder reads process env; caches keyed
    # on the model name fold it in (model_variant_key).
    variant_key_fn: Optional[Callable[[], str]] = None

    @property
    def preprocess(self):
        return get_preprocess_fn(self.preprocess_mode)

    def build(self):
        return self.module_builder()

    def init_variables(self, rng=None, dtype=np.float32) -> dict:
        """Architecture-shaped random variables (for tests / shape checks).

        jit-compiled: eager per-op dispatch of a 94-conv init is ~10x slower
        than one fused XLA program.
        """
        import jax

        module = self.build()
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        h, w = self.input_size
        dummy = np.zeros((1, h, w, 3), dtype=dtype)
        # graftlint: allow=SDL007 reason=one-shot init program; inputs are a PRNG key and a 1-row dummy, nothing worth donating
        init = jax.jit(lambda r, x: module.init(r, x, train=False))
        return jax.tree_util.tree_map(np.asarray, init(rng, dummy))

    def abstract_variables(self, dtype=np.float32) -> dict:
        """Shape/dtype-only variable pytree (``jax.ShapeDtypeStruct`` leaves)
        — free to build, enough for weight import to fill in."""
        import jax

        module = self.build()
        h, w = self.input_size
        dummy = jax.ShapeDtypeStruct((1, h, w, 3), dtype)
        return jax.eval_shape(
            lambda r, x: module.init(r, x, train=False),
            jax.random.PRNGKey(0), dummy)

    def resolve_weights(self, weights: Optional[str] = "imagenet"
                        ) -> Optional[str]:
        """Resolve the ``weights`` argument against the offline bundle.

        ``weights="imagenet"`` checks ``$SPARKDL_WEIGHTS_DIR`` for a local
        file first (air-gapped deployments — the analog of the reference's
        packaged, build-time-fetched GraphDefs in ``Models.scala``); an
        explicit path is returned as-is (and must exist)."""
        import os

        if weights is None:
            return None
        if weights != "imagenet":
            if not os.path.isfile(weights):
                raise FileNotFoundError(
                    f"weights file {weights!r} does not exist")
            return weights
        wdir = os.environ.get("SPARKDL_WEIGHTS_DIR")
        if wdir:
            stems = {self.name, self.name.lower(), self.keras_app,
                     self.keras_app.lower()}
            for stem in sorted(stems):
                for ext in (".weights.h5", ".h5", ".keras"):
                    cand = os.path.join(wdir, stem + ext)
                    if os.path.isfile(cand):
                        logger.info("Using offline weights %s", cand)
                        return cand
        return "imagenet"

    def keras_model(self, weights: Optional[str] = "imagenet"):
        """Build the keras.applications twin (CPU; used for weight import and
        as the parity oracle, mirroring the reference's test strategy).

        ``weights`` may be "imagenet" (keras download cache, with
        ``$SPARKDL_WEIGHTS_DIR`` consulted first), a ``.weights.h5`` file
        (loaded into the twin architecture), a full ``.h5``/``.keras`` model
        file, or None (random init)."""
        import keras

        builder = getattr(keras.applications, self.keras_app)
        resolved = self.resolve_weights(weights)
        if resolved is not None and resolved != "imagenet":
            if resolved.endswith(".weights.h5"):
                model = builder(weights=None)
                model.load_weights(resolved)
                return model
            return keras.saving.load_model(resolved)
        try:
            return builder(weights=resolved)
        except Exception as e:
            # Only the default imagenet download may degrade gracefully (no
            # network / no cache); an explicit user weight path must fail.
            if weights != "imagenet":
                raise
            logger.warning(
                "Could not load %s imagenet weights (%s); falling back to "
                "random initialization. For air-gapped use, point "
                "SPARKDL_WEIGHTS_DIR at a directory holding "
                "<model>.weights.h5 / .h5 / .keras files", self.name, e)
            return builder(weights=None)

class _Registry:
    def __init__(self):
        self._specs: Dict[str, ModelSpec] = {}
        self._auto_orders: Dict[str, Callable] = {}
        self._fixups: Dict[str, Callable] = {}

    def register(self, spec: ModelSpec, auto_order_fn=None,
                 import_fixup=None):
        self._specs[spec.name.lower()] = spec
        if auto_order_fn is not None:
            self._auto_orders[spec.name.lower()] = auto_order_fn
        if import_fixup is not None:
            self._fixups[spec.name.lower()] = import_fixup

    def get(self, name: str) -> ModelSpec:
        spec = self._specs.get(name.lower())
        if spec is None:
            raise ValueError(
                f"Unknown model {name!r}; supported: {self.names()}")
        return spec

    def auto_order_fn(self, name: str):
        return self._auto_orders.get(name.lower())

    def import_fixup(self, name: str):
        return self._fixups.get(name.lower())

    def names(self):
        return sorted(s.name for s in self._specs.values())


_registry = _Registry()


def _populate():
    from sparkdl_tpu.models.efficientnet import EfficientNetB0
    from sparkdl_tpu.models.inception import (InceptionV3,
                                              inception_import_order)
    from sparkdl_tpu.models.mobilenet import MobileNetV2
    from sparkdl_tpu.models.resnet import ResNet50, ResNet101, ResNet152
    from sparkdl_tpu.models.vgg import VGG16, VGG19
    from sparkdl_tpu.models.xception import Xception, xception_auto_order

    _registry.register(ModelSpec(
        name="VGG16", module_builder=VGG16, input_size=(224, 224),
        feature_size=4096, preprocess_mode="caffe", keras_app="VGG16"))
    _registry.register(ModelSpec(
        name="VGG19", module_builder=VGG19, input_size=(224, 224),
        feature_size=4096, preprocess_mode="caffe", keras_app="VGG19"))
    def _resnet_variant():
        # one helper for the whole family: a second ResNet knob must
        # change the tag for ResNet50/101/152 together (the InceptionV3
        # combined-tag lesson)
        return "fsc" if _rn_fused_shortcut_enabled() else ""

    # ResNet50 (reference) + deeper keras siblings (beyond the
    # reference's five): same module, deeper stage tables, same by-name
    # importer and knobs.  SPARKDL_RN_FUSED_SHORTCUT=1 fuses each
    # downsample block's shortcut+reduce 1x1 convs at inference
    # (resnet.py); off until measured on hardware.
    for _depth, _builder in ((50, ResNet50), (101, ResNet101),
                             (152, ResNet152)):
        _registry.register(ModelSpec(
            name=f"ResNet{_depth}",
            module_builder=(lambda b=_builder:
                            b(fused_shortcut=_rn_fused_shortcut_enabled())),
            input_size=(224, 224), feature_size=2048,
            preprocess_mode="caffe", keras_app=f"ResNet{_depth}",
            variant_key_fn=_resnet_variant))
    def _xception_builder():
        # SPARKDL_XC_TILED=1 routes entry blocks 2-3 through the
        # row-tiled pallas kernel — measured -24% whole-model, so the
        # default keeps them on XLA (xception.py tiled_entry / PERF.md)
        return Xception(tiled_entry=_xc_tiled_enabled())

    _registry.register(ModelSpec(
        name="Xception", module_builder=_xception_builder,
        input_size=(299, 299),
        feature_size=2048, preprocess_mode="tf", keras_app="Xception",
        variant_key_fn=lambda: "tiled" if _xc_tiled_enabled() else ""),
        xception_auto_order)
    _registry.register(ModelSpec(
        name="InceptionV3", module_builder=InceptionV3,
        input_size=(299, 299),
        feature_size=2048, preprocess_mode="tf", keras_app="InceptionV3"),
        inception_import_order)
    # Beyond the reference's five: edge/efficiency-class backbones (see
    # mobilenet.py / efficientnet.py).
    def _mobilenet_builder():
        # SPARKDL_MNV2_FUSED=1 routes stride-1 inverted-residual tails
        # through the fused pallas kernel (mobilenet.py); off until
        # measured on hardware
        return MobileNetV2(fused_inference=_mnv2_fused_enabled())

    _registry.register(ModelSpec(
        name="MobileNetV2", module_builder=_mobilenet_builder,
        input_size=(224, 224), feature_size=1280, preprocess_mode="tf",
        keras_app="MobileNetV2",
        variant_key_fn=lambda: "fused" if _mnv2_fused_enabled() else ""))
    # The input Normalization layer is auto-named by keras ("normalization",
    # "normalization_1", ... per session build count), so it imports by
    # creation order as a fallback when the by-name match misses.
    from sparkdl_tpu.models.efficientnet import efficientnet_import_fixup

    _registry.register(ModelSpec(
        name="EfficientNetB0", module_builder=EfficientNetB0,
        input_size=(224, 224), feature_size=1280, preprocess_mode="none",
        keras_app="EfficientNetB0"),
        lambda: [("norm", ("normalization",))],
        import_fixup=efficientnet_import_fixup)


_populate()

SUPPORTED_MODELS = _registry.names()


def get_model_spec(name: str) -> ModelSpec:
    return _registry.get(name)


def _env_flag(name: str, default: bool) -> bool:
    """Truthy env knob: unset or empty -> ``default``; "0"/"false" (any
    case) -> False; anything else -> True."""
    import os

    raw = os.environ.get(name, "").lower()
    if raw == "":
        return default
    return raw not in ("0", "false")


def _xc_tiled_enabled() -> bool:
    return _env_flag("SPARKDL_XC_TILED", False)


def _rn_fused_shortcut_enabled() -> bool:
    return _env_flag("SPARKDL_RN_FUSED_SHORTCUT", False)


def _mnv2_fused_enabled() -> bool:
    return _env_flag("SPARKDL_MNV2_FUSED", False)


def model_variant_key(name: str) -> str:
    """Environment-dependent build-variant tag for ``name`` (its spec's
    ``variant_key_fn``).  Cache owners fold it into their keys, so that a
    flag flipped mid-process rebuilds instead of serving the old build."""
    spec = _registry.get(name)
    return spec.variant_key_fn() if spec.variant_key_fn is not None else ""


def import_keras_weights(name: str, keras_model, variables: dict) -> dict:
    """Import a keras.applications model's weights into flax variables
    (by-name where upstream names are stable, by-creation-order for
    upstream's auto-named layers)."""
    from sparkdl_tpu.models import keras_import

    _registry.get(name)  # validate
    auto_order_fn = _registry.auto_order_fn(name)
    variables = keras_import.import_weights(
        keras_model, variables,
        auto_order=auto_order_fn() if auto_order_fn else None)
    fixup = _registry.import_fixup(name)
    if fixup is not None:
        # model-specific post-import hook for weightless keras layers the
        # importer cannot see (e.g. EfficientNet's imagenet-only Rescaling)
        variables = fixup(keras_model, variables)
    return variables


def load_model(name: str, weights: Optional[str] = "imagenet"):
    """Build (module, variables) for a zoo model, importing Keras weights.

    The TPU-native analog of the reference's ``_buildTFGraphForName``.
    """
    import jax

    spec = _registry.get(name)
    module = spec.build()
    # Shape-only template: every leaf must be filled by the import (a full
    # random init would be overwritten anyway and costs an XLA compile).
    variables = spec.abstract_variables()
    keras_model = spec.keras_model(weights=weights)
    variables = import_keras_weights(name, keras_model, variables)
    abstract = [
        "/".join(str(k) for k in path)
        for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]
        if isinstance(leaf, jax.ShapeDtypeStruct)]
    if abstract:
        raise ValueError(
            f"Import left {len(abstract)} uninitialized leaves: {abstract[:5]}")
    return module, variables
