"""The batch path has one route, and the environment cannot fork it.

Four switches used to (``SPARKDL_S2D_STEM``, ``SPARKDL_FUSED_HEADS``,
``SPARKDL_PIPELINE``, ``SPARKDL_BATCHES_PER_DISPATCH``): set, they are
now ignored, and the names the package still reads are listed here, so
that the next one is a deliberate edit.
"""

import ast
import pathlib
import re

import numpy as np
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models import get_model_spec, model_variant_key
from sparkdl_tpu.parallel import engine as engine_mod
from sparkdl_tpu.parallel.engine import get_cached_engine
from sparkdl_tpu.transformers.named_image import zoo_model_fn

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "sparkdl_tpu"


def _inception_jaxpr() -> str:
    """The featurizer's program as the zoo engine would compile it, over
    an abstract batch: no weights, no device."""
    import jax

    spec = get_model_spec("InceptionV3")
    h, w = spec.input_size
    fn = zoo_model_fn("InceptionV3", featurize=True)
    return str(jax.make_jaxpr(fn)(
        spec.abstract_variables(),
        jax.ShapeDtypeStruct((2, h, w, 3), np.uint8)))


@pytest.fixture(scope="module")
def default_jaxpr():
    return _inception_jaxpr()


def _model_is_the_defaults(monkeypatch, default_jaxpr):
    assert model_variant_key("InceptionV3") == ""
    assert get_model_spec("InceptionV3").variant_key_fn is None
    assert _inception_jaxpr() == default_jaxpr


def _engine_is_the_defaults(monkeypatch, default_jaxpr):
    """An engine got as the transformers get it, over three batches:
    three dispatches of the one per-batch program, on the runner."""
    rng = np.random.default_rng(0)
    mf = ModelFunction(fn=lambda v, x: x @ v["w"],
                       variables={"w": rng.normal(size=(6, 3))
                                  .astype(np.float32)})

    class Holder:
        pass

    eng = get_cached_engine(Holder(), mf, device_batch_size=8)
    runners, launches = [], []
    runner, program = engine_mod.PipelinedRunner, eng._compiled

    def counted(*args, **kwargs):
        runners.append(runner(*args, **kwargs))
        return runners[-1]

    monkeypatch.setattr(engine_mod, "PipelinedRunner", counted)
    monkeypatch.setattr(eng, "_compiled", lambda v, x: (
        launches.append(x.shape), program(v, x))[1])
    x = rng.normal(size=(24, 6)).astype(np.float32)
    got = np.concatenate(list(eng.map_batches([x])))
    np.testing.assert_allclose(got, x @ mf.variables["w"], rtol=1e-5,
                               atol=1e-6)
    assert len(runners) == 1
    assert eng.metrics.counters["pipeline.dispatches"] == 3
    assert launches == [(8, 6)] * 3


REMOVED = [
    ("SPARKDL_S2D_STEM", "1", _model_is_the_defaults),
    ("SPARKDL_FUSED_HEADS", "0", _model_is_the_defaults),
    ("SPARKDL_PIPELINE", "0", _engine_is_the_defaults),
    ("SPARKDL_BATCHES_PER_DISPATCH", "3", _engine_is_the_defaults),
]


@pytest.mark.parametrize("name,value,check", REMOVED,
                         ids=[row[0] for row in REMOVED])
def test_a_removed_switch_is_ignored(monkeypatch, default_jaxpr, name,
                                     value, check):
    monkeypatch.setenv(name, value)
    check(monkeypatch, default_jaxpr)


#: every ``SPARKDL_*`` name the package reads, and why it is not a switch
#: between two routes through the batch path
ENVIRONMENT = {
    # where a deployment keeps things, and how much room they get
    "SPARKDL_WEIGHTS_DIR": "deployment: the offline weight bundle's path",
    "SPARKDL_CLASS_INDEX": "deployment: the ImageNet class index's path",
    "SPARKDL_COMPILE_CACHE": "deployment: the compile cache's directory",
    "SPARKDL_CACHE": "deployment: the serving result cache's size",
    "SPARKDL_DECODE_CACHE_MB": "deployment: the fit's decode cache's size",
    "SPARKDL_COST": "deployment: tenants and window of the cost ledger",
    "SPARKDL_TPU_LOG_LEVEL": "deployment: the log level",
    "SPARKDL_TPU_DISABLE_NATIVE": "deployment: a host with no compiler "
                                  "for the native decode core",
    # what an engineer turns on to look
    "SPARKDL_TRACE": "diagnostic: spans, and where they are written",
    "SPARKDL_BLACKBOX": "diagnostic: the flight recorder's dump",
    "SPARKDL_FAULTS": "diagnostic: the fault-injection plan",
    "SPARKDL_LOCKCHECK": "diagnostic: the lock-order checker",
    "SPARKDL_DEBUG_NANS": "diagnostic: fail fast on a NaN",
    # two callers that exist need different values
    "SPARKDL_ZOO_COMPUTE_DTYPE": "precision: float32 is the product's "
                                 "contract, bfloat16 the benchmark's cell",
    # debts with a date (ROADMAP.md, Design queue)
    "SPARKDL_XC_TILED": "debt D3: a recorded loss, leaves with its kernel",
    "SPARKDL_MNV2_FUSED": "debt D3: never measured, leaves with its kernel",
    "SPARKDL_RN_FUSED_SHORTCUT": "debt D3: never measured",
    "SPARKDL_RAGGED": "debt D4: waits for the serving cells",
}


def test_the_environment_names_the_package_reads_are_these():
    read = set()
    for path in PACKAGE.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and re.fullmatch(r"SPARKDL_[A-Z0-9_]+", node.value)):
                read.add(node.value)
    assert read == set(ENVIRONMENT)
