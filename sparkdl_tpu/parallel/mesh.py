"""Mesh construction + sharding helpers.

The TPU analog of the reference's cluster topology: where Spark mapped
DataFrame partitions onto executor JVMs (SURVEY.md §2 "parallelism-strategy
inventory"), we map batch rows onto chips through a ``jax.sharding.Mesh``.
Axis names:

  * ``data``  — batch-parallel axis (inference + gradient data parallelism).
    ICI collectives (psum for gradients) ride this axis.
  * ``model`` — tensor-parallel axis for WEIGHT sharding: dense/conv
    kernels split their output dimension across it (ISSUE 14), so the
    per-chip HBM cost of the params is ``bytes / model_axis`` instead of
    one full copy per chip.  Size 1 keeps everything replicated (the
    zoo's <=25M-param models need no TP on real chips, but the same rules
    scale a head that does not fit one chip).

Weight-sharding policy (ISSUE 14): :func:`match_partition_rules` maps
regex rules over ``/``-joined param paths to ``PartitionSpec``s (the
SNIPPETS [2] shape: scalars always replicated, no-match is a loud
error), :func:`default_partition_rules` is the per-zoo-family default
(kernels/embeddings split their last dim on the ``model`` axis iff the
axis is >1 and the dim divides — the SNIPPETS [3] divisibility
fallback; everything else replicated), and
:func:`resolve_param_shardings` turns either into the per-leaf
``NamedSharding`` pytree the inference engine device_puts weights under
and compiles against.  On a model-axis-1 mesh every rule resolves to
replicated and the engine collapses the policy to the classic
replicate-everything layout — byte-identical programs, same executable
cache keys.

Multi-host note: ``get_mesh`` uses ``jax.devices()`` which spans all hosts
under multi-controller jax.distributed initialization, so the same code
scales from 1 chip to a pod slice; per-host data feeding belongs to the IO
layer (``jax.make_array_from_process_local_data``).
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

DATA_AXIS = "data"
MODEL_AXIS = "model"


def get_mesh(num_devices: Optional[int] = None, model_parallel: int = 1,
             devices: Optional[Sequence] = None):
    """Build a (data, model) mesh over the available chips.

    ``num_devices`` limits the mesh to the first N devices (useful for
    carving a tuning fan-out into independent slices); default = all.
    """
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"Requested {num_devices} devices; only {len(devs)} present")
        devs = devs[:num_devices]
    n = len(devs)
    if n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide {n} devices")
    grid = np.asarray(devs).reshape(n // model_parallel, model_parallel)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def device_stamp() -> dict:
    """The device of THIS process as JAX reports it: ``{"platform",
    "kind", "count"}``.  Every measured line (``bench.py``,
    ``chip_smoke.py``) carries it, so a number can never be read as a
    device metric of a device it did not run on.  Initialises the
    backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def batch_sharding(mesh, ndim: int = 1):
    """NamedSharding that splits axis 0 (the batch) across the data axis and
    replicates everything else."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated_sharding(mesh):
    """NamedSharding that replicates (model params on every chip — the TPU
    replacement for Spark's torrent-broadcast of the model GraphDef)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# tensor-parallel weight sharding: partition rules (ISSUE 14)

def param_path_str(path) -> str:
    """``/``-joined name of one param leaf from a
    ``tree_flatten_with_path`` key path — THE spelling every rule regex
    matches against (shared with ``parallel.train.resolve_param_specs``
    and the program auditor's sharding summary, so a rule written for
    the engine audits identically)."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def match_partition_rules(rules, params):
    """Pytree of ``PartitionSpec`` for ``params`` according to ``rules``
    (the SNIPPETS [2] ``match_partition_rules`` shape).

    ``rules`` is an ordered sequence of ``(regex, spec)`` pairs; the
    FIRST rule whose regex ``re.search``-matches the leaf's ``/``-joined
    path wins.  ``spec`` is a ``PartitionSpec`` or a callable
    ``(leaf) -> PartitionSpec`` (how the default rules make the split
    shape- and divisibility-aware).  Scalars (rank 0 or one element)
    are never partitioned; a leaf no rule matches raises ``ValueError``
    naming it — a silent replicate there would un-shard a param the
    policy meant to split, and the HBM math would quietly break.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def get_spec(path, leaf):
        name = param_path_str(path)
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()  # never partition scalar values
        for pat, spec in compiled:
            if pat.search(name) is not None:
                return spec(leaf) if callable(spec) else spec
        raise ValueError(
            f"Partition rule not found for param: {name!r} "
            f"(shape {shape}); add a rule (a catch-all (r'.*', "
            f"PartitionSpec()) replicates the rest)")

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [get_spec(p, l) for p, l in flat])


def default_partition_rules(mesh) -> List[Tuple[str, Any]]:
    """The per-zoo-family default rule set: dense/conv ``kernel`` (and
    ``embedding``) leaves split their LAST dimension — output features /
    channels, so no cross-shard reduction enters the math and sharded
    outputs match replicated ones up to the order XLA's shape-chosen
    dot/conv emitter sums one element's products — across the mesh's
    ``model`` axis, iff that axis is >1 and the dim divides it (the
    SNIPPETS [3] divisibility fallback); everything else (biases, BN
    scales/stats, scalars) stays replicated."""
    from jax.sharding import PartitionSpec as P

    model = int(mesh.shape[MODEL_AXIS])

    def split_last_dim(leaf):
        shape = tuple(leaf.shape)
        if (model > 1 and len(shape) >= 2 and shape[-1] % model == 0):
            return P(*([None] * (len(shape) - 1)), MODEL_AXIS)
        return P()

    return [
        (r"(^|/)(kernel|embedding)$", split_last_dim),
        (r".*", P()),
    ]


def _axis_shards(mesh, spec) -> int:
    """How many ways ``spec`` splits a leaf on ``mesh`` (product of the
    named axis sizes; 1 = replicated)."""
    shards = 1
    for entry in tuple(spec):
        if entry is None:
            continue
        for axis in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            shards *= int(mesh.shape[axis])
    return shards


def spec_shards_leaf(mesh, spec, shape) -> bool:
    """True iff ``spec`` actually divides a leaf of ``shape`` on
    ``mesh`` — per-dim divisibility, the check behind the resolution
    fallback and GC005's sharded-leaf audit."""
    for dim, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        n = 1
        for axis in axes:
            n *= int(mesh.shape[axis])
        if dim >= len(shape) or shape[dim] % n:
            return False
    return True


def resolve_param_shardings(params, mesh, rules=None, specs=None):
    """``(shardings, specs)`` pytrees for ``params``: per-leaf
    ``NamedSharding`` (what the engine device_puts and compiles against)
    and the matched ``PartitionSpec``s (what digests/audits record).

    ``rules`` — a rule list for :func:`match_partition_rules`, or a
    callable ``mesh -> rule list`` (the :func:`default_partition_rules`
    factory form the zoo serving bundle passes); ``None`` uses the
    default rules.  ``specs`` — an EXPLICIT per-leaf pytree mirroring
    ``params`` (``PartitionSpec`` or ``NamedSharding`` leaves; a
    structure mismatch raises rather than pairing specs with the wrong
    leaves) — takes precedence over ``rules``.  Either way, any spec
    that does NOT divide its leaf on this mesh falls back to
    replicated for that leaf (the SNIPPETS [3] shape, THE one spelling
    of the fallback contract) — a spec never turns into a lowering
    crash."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def _is_spec(s):
        return isinstance(s, (P, NamedSharding))

    if specs is not None:
        params_def = jax.tree_util.tree_structure(params)
        specs_def = jax.tree_util.tree_structure(specs, is_leaf=_is_spec)
        if specs_def != params_def:
            raise ValueError(
                f"param shardings must mirror the params pytree "
                f"structure (specs {specs_def} vs params {params_def}) "
                f"— a flat or reordered spec tree would silently pair "
                f"specs with the wrong leaves")
        flat_s = [s.spec if isinstance(s, NamedSharding) else s
                  for s in jax.tree_util.tree_leaves(specs,
                                                     is_leaf=_is_spec)]
        treedef = params_def
    else:
        if rules is None:
            rules = default_partition_rules(mesh)
        elif callable(rules):
            rules = rules(mesh)
        matched = match_partition_rules(rules, params)
        flat_s, treedef = jax.tree_util.tree_flatten(
            matched, is_leaf=_is_spec)
    flat_p = jax.tree_util.tree_leaves(params)
    resolved = []
    for leaf, spec in zip(flat_p, flat_s):
        shape = tuple(getattr(leaf, "shape", ()))
        if tuple(spec) and not spec_shards_leaf(mesh, spec, shape):
            spec = P()  # indivisible on this mesh: replicate the leaf
        resolved.append(spec)
    out_specs = jax.tree_util.tree_unflatten(treedef, resolved)
    shardings = jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, s) for s in resolved])
    return shardings, out_specs


def spec_is_replicated(spec) -> bool:
    """True iff ``spec`` names no mesh axis — ``P()`` and its
    semantically-identical spellings like ``P(None, None)`` both
    replicate."""
    return all(entry is None for entry in tuple(spec))


def specs_all_replicated(specs) -> bool:
    """True iff every matched spec replicates — the engine then
    collapses the policy to the classic replicate-everything layout,
    keeping the lowered programs and executable cache keys
    byte-identical to the pre-ISSUE-14 stack (the model-axis-1
    compatibility contract).  ``P(None, None)`` counts as replicated:
    it names no axis, so it must not fork a second compilation of the
    byte-identical program."""
    import jax

    return all(spec_is_replicated(s) for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))


def spec_to_json(spec) -> list:
    """A ``PartitionSpec`` as a JSON-able per-dim list (``None`` |
    axis name | list of axis names) — the lockfile/manifest spelling."""
    out: list = []
    for entry in tuple(spec):
        if isinstance(entry, (tuple, list)):
            out.append([str(a) for a in entry])
        else:
            out.append(None if entry is None else str(entry))
    return out


def partition_digest(specs=None) -> str:
    """Canonical digest of a resolved sharding policy: sha256 over the
    sorted ``path=spec`` lines (``"replicated"`` for the no-policy /
    all-replicated case).  Keys the engine's jit cache and the
    persistent compile-cache manifest, so two processes (or two engines)
    agree on "same policy" by content, not object identity."""
    import jax

    if specs is None:
        return "replicated"
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    # canonical per-leaf rendering: every replicated spelling (P(),
    # P(None), P(None, None)) digests identically — two processes whose
    # layouts are semantically equal must agree on "same policy"
    lines = sorted(
        f"{param_path_str(p)}="
        f"{[] if spec_is_replicated(s) else spec_to_json(s)}"
        for p, s in flat)
    if all(line.endswith("=[]") for line in lines):
        return "replicated"
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


def param_sharding_stats(mesh, params, specs=None) -> dict:
    """HBM accounting for a (possibly sharded) param pytree: total
    logical bytes, per-chip bytes under the specs (``None`` = all
    replicated), largest replicated leaf, and the sharded/replicated
    ratio — the numbers the bench rider and ``Server.varz`` stamp."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    if specs is None:
        flat_s = [None] * len(leaves)
    else:
        flat_s = jax.tree_util.tree_leaves(
            specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    total = 0
    per_chip = 0
    largest_replicated = 0
    sharded_leaves = 0
    for leaf, spec in zip(leaves, flat_s):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = np.dtype(getattr(leaf, "dtype", np.float64))
        size = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        total += size
        shards = 1 if spec is None else _axis_shards(mesh, spec)
        if shards > 1:
            sharded_leaves += 1
            per_chip += size // shards
        else:
            per_chip += size
            largest_replicated = max(largest_replicated, size)
    return {
        "mesh_shape": {str(n): int(mesh.shape[n]) for n in mesh.axis_names},
        "param_bytes_total": total,
        "param_bytes_per_chip": per_chip,
        "largest_replicated_leaf_bytes": largest_replicated,
        "sharded_leaves": sharded_leaves,
        "total_leaves": len(leaves),
        "sharded_vs_replicated_ratio": (round(per_chip / total, 4)
                                        if total else 1.0),
    }
