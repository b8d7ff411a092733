"""ModelFunction: the composable unit of computation.

The TPU-native successor of the reference's ``GraphFunction``
(``python/sparkdl/graph/builder.py``): where the reference serialized TF
``GraphDef`` fragments and spliced them together by tensor name
(``IsolatedSession.importGraphFunction``), a ModelFunction is a pure
jax-traceable function plus its variable pytree.  Composition is ordinary
function composition — XLA fuses the composed program into one kernel
schedule, which is exactly what the reference's graph-splicing tried to
approximate at the GraphDef level.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence


@dataclass
class ModelFunction:
    """A jit-traceable ``fn(variables, x) -> y`` with bound variables.

    ``input_names``/``output_names`` keep the reference's feed/fetch naming
    contract (``GraphFunction(graph_def, input_names, output_names)``) so
    stages can validate column wiring the way ``validated_input/output`` did.
    """

    fn: Callable[[Any, Any], Any]
    variables: Any = field(default_factory=dict)
    input_names: Sequence[str] = ("input",)
    output_names: Sequence[str] = ("output",)
    # Optional train-mode apply: ``train_fn(variables, x) ->
    # (pred, new_batch_stats)`` — set for models with BatchNorm whose
    # statistics can update during fine-tuning (estimator trainBatchStats).
    train_fn: Optional[Callable[[Any, Any], Any]] = None
    # Outputs that are COUNTERS of the program, not columns: ``fn`` then
    # returns a dict keyed by ``output_names``, every leaf with the
    # batch axis leading (the engine pads and trims a counter's rows as
    # it does a column's), and a stage adds what the real rows counted
    # to its engine's metrics instead of writing it to the frame.
    counter_names: Sequence[str] = ()

    def __call__(self, x):
        return self.fn(self.variables, x)

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_callable(cls, fn: Callable[[Any], Any], *,
                      input_names=("input",), output_names=("output",)):
        """Wrap a variable-free function (e.g. a preprocessing lambda)."""
        return cls(fn=lambda _v, x: fn(x), variables={},
                   input_names=input_names, output_names=output_names)

    @classmethod
    def from_flax(cls, module, variables, *,
                  method_kwargs: Optional[dict] = None,
                  input_names=("input",), output_names=("output",)):
        """Bind a flax module's apply (inference mode by default).  Modules
        carrying ``batch_stats`` also get a train-mode apply so BatchNorm
        statistics can update during estimator fits (trainBatchStats)."""
        kw = dict(method_kwargs or {})

        def fn(v, x):
            return module.apply(v, x, **kw)

        train_fn = None
        if isinstance(variables, dict) and "batch_stats" in variables:
            tkw = {k: v for k, v in kw.items() if k != "train"}

            def train_fn(v, x):
                pred, mutated = module.apply(
                    v, x, train=True, mutable=["batch_stats"], **tkw)
                return pred, mutated["batch_stats"]

        return cls(fn=fn, variables=variables, train_fn=train_fn,
                   input_names=input_names, output_names=output_names)

    @classmethod
    def from_keras(cls, model_or_path, **kwargs):
        """Convert a Keras model (object or saved file) — the successor of
        ``GraphFunction.fromKeras``.  See graph.keras_convert."""
        from sparkdl_tpu.graph.keras_convert import keras_to_model_function

        return keras_to_model_function(model_or_path, **kwargs)

    # -- composition -------------------------------------------------------
    def compose(self, other: "ModelFunction") -> "ModelFunction":
        """``self`` then ``other`` — the successor of the reference's
        GraphDef splicing (``builder.py — importGraphFunction`` chains).
        Variables of both stages ride along as a two-slot pytree."""
        f, g = self, other

        def fn(v, x):
            return g.fn(v["g"], f.fn(v["f"], x))

        return ModelFunction(
            fn=fn, variables={"f": f.variables, "g": g.variables},
            input_names=f.input_names, output_names=g.output_names,
            counter_names=g.counter_names)

    def jit(self):
        """Eagerly jit-compile (otherwise the engine jits with shardings)."""
        import jax

        # graftlint: allow=SDL007 reason=generic API: the caller owns both variables and x across calls; donation is decided at the engine layer
        return jax.jit(self.fn)
