"""graftcheck core: abstract lowering + the GC001–GC005 program rules.

Everything here runs CHIP-FREE: a :class:`ProgramSpec` builds its jit
object and abstract argument avals (``jax.ShapeDtypeStruct`` leaves —
no weights materialized, no device memory touched), ``.lower()``
produces StableHLO on the CPU backend, and the rules read three cheap
artifacts of the lowering:

* the StableHLO text (op dtype mix, ``tf.aliasing_output`` aliases
  and bare ``jax.buffer_donor`` marks, Shardy ``sdy.sharding``
  annotations),
* ``lowered.cost_analysis()`` (FLOPs / bytes accessed on the
  UNOPTIMIZED module — no XLA compile, milliseconds even for the zoo),
* the flat input avals (shape/dtype/weak-type — the executable cache
  key jax would use at runtime).

The audited-configuration contract: rules fire on what the spec
DECLARES (kind, compute dtype, donation expectation, shardings), so the
same engine code audits clean in its f32 parity configuration and is
held to the bf16 contract when the inventory declares it.
"""

from __future__ import annotations

import hashlib
import re
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from sparkdl_tpu.analysis.core import Finding

GC_RULE_HELP = {
    "GC000": "committed program fingerprint matches the audited program",
    "GC001": "dispatch/train jits donate; declared donations are consumed",
    "GC002": "no f32 dot/conv under a declared bf16 compute dtype",
    "GC003": "no weak-type/duplicate/churned executable cache keys",
    "GC004": "pad-to-bucket FLOP waste stays inside budget",
    "GC005": "shardings consistent with the mesh; no large param "
             "replicated past a usable model axis",
}

#: GC004 budgets: worst-case pad fraction between adjacent buckets
#: (request of b_{i-1}+1 rows served by bucket b_i), and the inherent
#: floor of the smallest bucket (a 1-row request padded to b_0).
PAD_INTERIOR_BUDGET = 0.55
PAD_FLOOR_BUDGET = 0.95

#: GC005: a single replicated param leaf larger than this, on a mesh
#: whose model axis could shard it, is flagged.
REPLICATED_PARAM_BUDGET_BYTES = 32 * 1024 * 1024

_F32_RESULT = re.compile(r"->\s*tensor<[^>]*xf32>")
#: the op's OPERAND dtype (first input tensor of the call signature):
#: a bf16 x bf16 -> f32 dot is deliberate f32 ACCUMULATION
#: (preferred_element_type, the sepconv kernels' contract), while an
#: f32-operand dot/conv under bf16 compute is a real upcast leak
_OPERAND_DTYPE = re.compile(
    r":\s*\(tensor<[^>]*?x?(bf16|f16|f32|f64)>")
#: one unusable donation in jax's warning ("... not usable:
#: float32[2,16], int32[8].")
_DROPPED_AVAL = re.compile(r"\b[a-z][a-z0-9_]*\[[\d,]*\]")
#: a sharding that reached the lowered program: an argument/result
#: attribute or a ``with_sharding_constraint`` op (Shardy dialect)
_SHARDING_ANNOTATION = re.compile(r"sdy\.sharding\s*=|sdy\.sharding_constraint")


@dataclass
class ProgramSpec:
    """One auditable program: a zero-argument ``build`` returning
    ``(jitted, args)`` where ``args`` are abstract avals, plus the
    declared contract the rules check the lowering against."""

    name: str                      # e.g. "zoo/InceptionV3/featurize/b32"
    kind: str                      # "dispatch" | "train" | "kernel"
    build: Callable[[], Tuple[Any, tuple]]
    # declared contract ----------------------------------------------------
    compute_dtype: Optional[str] = None   # "bfloat16" activates GC002
    donate: Tuple[int, ...] = ()          # jit-level donated arg indices
    donate_reason: Optional[str] = None   # recorded exemption for GC001
    batch_rows: Optional[int] = None      # padded rows per dispatch
    # per-arg sharding declaration: "replicated" | "batch" (data axis on
    # dim 0) | "stacked_batch" (the grouped/multi-step layout — data
    # axis on dim 1) | "params" (per-leaf partition specs — ISSUE 14
    # tensor-parallel weights; see ``param_partition``) | None
    shardings: Optional[Tuple[Optional[str], ...]] = None
    mesh_axes: Optional[Dict[str, int]] = None   # {"data": 8, "model": 1}
    # the "params" arg's declared layout: ((path, spec_json), ...) where
    # spec_json is the per-dim axis list (mesh.spec_to_json) — leaves
    # with an empty spec count as replicated in the GC005 byte budget,
    # sharded leaves contribute bytes/shards per chip and must divide
    param_partition: Optional[Tuple] = None
    # retrace-audit group: one compiled fn identity (GC003 groups shapes
    # under it the way jax's executable cache would)
    group: Optional[str] = None
    model: Optional[str] = None    # zoo model name (GC004 bucket grouping)
    bucket: Optional[int] = None


def _tree_leaves(x) -> list:
    import jax

    return jax.tree_util.tree_leaves(x)


def _aval_signature(aval) -> List[Any]:
    return [list(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False))]


def _scan_op_dtypes(text: str) -> Dict[str, int]:
    """Operand-dtype mix of the compute-carrying ops, plus upcast count:
    ``{"conv_f32": N, "dot_bf16": N, ..., "convert_to_f32": N}``.
    Keyed on the OPERAND dtype: a bf16-operand dot that accumulates to
    f32 is the kernels' deliberate precision contract, not a leak."""
    counts: Dict[str, int] = {}

    def bump(key):
        counts[key] = counts.get(key, 0) + 1

    for line in text.splitlines():
        if "stablehlo.convolution" in line:
            op = "conv"
        elif "stablehlo.dot_general" in line:
            op = "dot"
        elif "stablehlo.convert" in line:
            if _F32_RESULT.search(line):
                bump("convert_to_f32")
            continue
        else:
            continue
        m = _OPERAND_DTYPE.search(line)
        bump(f"{op}_{m.group(1) if m else 'other'}")
    return counts


def _lower(spec: ProgramSpec):
    """Build + abstractly lower one spec, capturing jax's
    donation-dropped warning (the runtime signal GC001 turns into a
    deterministic finding)."""
    jitted, args = spec.build()
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        lowered = jitted.lower(*args)
    dropped = sum(len(_DROPPED_AVAL.findall(str(w.message)))
                  for w in wlist
                  if "donated buffers were not usable" in str(w.message))
    return lowered, args, dropped


def audit_program(spec: ProgramSpec) -> Dict[str, Any]:
    """Lower one program and produce its lockfile record: fingerprint,
    cost, donation map, dtype mix, cache-key signature, sharding summary,
    and the per-program findings (GC001/GC002/GC005) as rendered dicts."""
    try:
        lowered, args, dropped = _lower(spec)
    except ValueError as e:
        # jax refuses sharding-incompatible programs at lowering (e.g. a
        # batch not divisible by the data axis) — that IS the GC005
        # regression, reported as a finding instead of a crashed audit
        if "shard" not in str(e).lower() and "divisible" not in str(e):
            raise
        finding = Finding(
            "GC005", spec.name, 0,
            f"program failed to lower under its declared shardings: {e}")
        return {"record": {"name": spec.name, "kind": spec.kind,
                           "fingerprint": None, "flops": 0.0,
                           "in_avals": {"n": 0, "weak": 0, "key": "",
                                        "shape_key": ""},
                           "findings": ["GC005"]},
                "findings": [finding]}
    text = lowered.as_text()
    try:
        cost = dict(lowered.cost_analysis() or {})
    except NotImplementedError:
        # some backends ship no HLO cost analysis; the record then keeps
        # fingerprint/donation/dtype checking and GC004 is skipped
        cost = {}
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    aliased = text.count("tf.aliasing_output")
    # a donated arg jax could pair only by element count (same size,
    # other dtype/shape) is lowered as a bare donor with NO warning and
    # left to XLA — not an established alias, so it counts as dropped
    dropped += text.count("jax.buffer_donor")
    donated_leaves = sum(len(_tree_leaves(args[i])) for i in spec.donate)
    dtype_counts = _scan_op_dtypes(text)
    sigs = [_aval_signature(a) for a in _tree_leaves(lowered.in_avals)]
    # compact cache-key digest: the executable key is the full
    # (shape, dtype, weak) tuple list; equality is all GC003 and the
    # lockfile diff need, so only hashes are recorded (a zoo model has
    # hundreds of param leaves — the full list would bloat the lockfile
    # ~20x)
    import json as json_lib

    in_avals = {
        "n": len(sigs),
        "weak": sum(1 for s in sigs if s[2]),
        "key": hashlib.sha256(
            json_lib.dumps(sigs).encode()).hexdigest(),
        "shape_key": hashlib.sha256(
            json_lib.dumps([s[0] for s in sigs]).encode()).hexdigest(),
    }

    record: Dict[str, Any] = {
        "name": spec.name,
        "kind": spec.kind,
        "fingerprint": hashlib.sha256(text.encode()).hexdigest(),
        "flops": flops,
        "bytes_accessed": nbytes,
        "rows": spec.batch_rows,
        "flops_per_row": (flops / spec.batch_rows
                          if spec.batch_rows else None),
        "compute_dtype": spec.compute_dtype,
        "donation": {
            "declared": sorted(spec.donate),
            "donated_leaves": donated_leaves,
            "aliased": aliased,
            "dropped": dropped,
            "reason": spec.donate_reason,
        },
        "dtype_counts": dtype_counts,
        "in_avals": in_avals,
        "group": spec.group,
        "model": spec.model,
        "bucket": spec.bucket,
        "mesh_axes": spec.mesh_axes,
        "sharding_summary": _sharding_summary(spec, args, text),
    }
    findings = (_rule_gc001(spec, record)
                + _rule_gc002(spec, record)
                + _rule_gc005(spec, record, args, text))
    record["findings"] = [f.code for f in findings]
    return {"record": record, "findings": findings}


def _leaf_bytes(leaf) -> int:
    import numpy as np

    return int(np.prod(leaf.shape, dtype=np.int64)
               * np.dtype(leaf.dtype).itemsize)


def _spec_shard_count(spec_json, mesh_axes: Dict[str, int]) -> int:
    """How many ways a spec_json dim list splits its leaf (product of
    the named mesh axis sizes; 1 = replicated)."""
    shards = 1
    for entry in spec_json or ():
        if entry is None:
            continue
        for axis in (entry if isinstance(entry, (list, tuple)) else (entry,)):
            shards *= int(mesh_axes.get(str(axis), 1))
    return shards


def _sharding_summary(spec: ProgramSpec, args: tuple,
                      text: str) -> Optional[Dict[str, Any]]:
    if spec.shardings is None:
        return None

    replicated_bytes = 0
    largest_leaf = 0
    batch_args = []
    param_shards: Optional[Dict[str, Any]] = None
    for i, kind in enumerate(spec.shardings):
        if kind in ("batch", "stacked_batch"):
            batch_args.append((i, 0 if kind == "batch" else 1))
        elif kind == "replicated":
            for leaf in _tree_leaves(args[i]):
                size = _leaf_bytes(leaf)
                replicated_bytes += size
                largest_leaf = max(largest_leaf, size)
        elif kind == "params":
            # tensor-parallel weights (ISSUE 14): replicated leaves
            # (empty spec) join the byte budget above; sharded leaves
            # cost bytes/shards per chip and their split dims must
            # divide (audited by GC005 via "indivisible" below)
            import jax

            from sparkdl_tpu.parallel.mesh import param_path_str

            spec_map = dict(spec.param_partition or ())
            axes = spec.mesh_axes or {}
            sharded_bytes = 0
            sharded_leaves = 0
            indivisible = []
            flat, _ = jax.tree_util.tree_flatten_with_path(args[i])
            for path, leaf in flat:
                name = param_path_str(path)
                sj = spec_map.get(name) or ()
                size = _leaf_bytes(leaf)
                # an axis name absent from the declared mesh is a
                # declaration that matches NO real layout — flagged,
                # and the leaf is EXCLUDED from both byte budgets (its
                # intended layout is unknowable, and folding it into
                # the replicated budget would stack a misleading
                # "shard it with a PartitionSpec" finding on top of
                # the typo finding that already names the fix)
                unknown = False
                for dim, entry in enumerate(sj):
                    if entry is None:
                        continue
                    names = (entry if isinstance(entry, (list, tuple))
                             else (entry,))
                    for axis in names:
                        if str(axis) not in axes:
                            unknown = True
                            indivisible.append(
                                {"param": name, "dim": dim,
                                 "shape": list(leaf.shape), "shards": 0,
                                 "unknown_axis": str(axis)})
                if unknown:
                    continue
                shards = _spec_shard_count(sj, axes)
                if shards <= 1:
                    replicated_bytes += size
                    largest_leaf = max(largest_leaf, size)
                    continue
                sharded_leaves += 1
                sharded_bytes += size // shards
                for dim, entry in enumerate(sj):
                    n = _spec_shard_count([entry], axes)
                    if n > 1 and (dim >= len(leaf.shape)
                                  or leaf.shape[dim] % n):
                        indivisible.append(
                            {"param": name, "dim": dim,
                             "shape": list(leaf.shape), "shards": n})
            # accumulate across MULTIPLE "params" args (e.g. separate
            # frozen/trainable collections): a second arg must add to
            # — never replace — the first's accounting and findings
            if param_shards is None:
                param_shards = {"specs": [], "sharded_leaves": 0,
                                "sharded_bytes_per_chip": 0,
                                "indivisible": []}
            param_shards["specs"] = sorted(
                param_shards["specs"]
                + [(n, list(sj)) for n, sj in spec_map.items()])
            param_shards["sharded_leaves"] += sharded_leaves
            param_shards["sharded_bytes_per_chip"] += sharded_bytes
            param_shards["indivisible"].extend(indivisible)
    summary: Dict[str, Any] = {
        "batch_args": batch_args,
        "replicated_bytes": replicated_bytes,
        "largest_replicated_leaf_bytes": largest_leaf,
        "annotated": len(_SHARDING_ANNOTATION.findall(text)),
    }
    if param_shards is not None:
        summary["param_shards"] = param_shards
    return summary


def _rule_gc001(spec: ProgramSpec, record: Dict[str, Any]) -> List[Finding]:
    if spec.kind == "kernel":
        # kernels declare no jit-level donation; their exemption reason
        # rides in the record (inputs are chained/reused activations)
        return []
    d = record["donation"]
    if not d["declared"]:
        if spec.donate_reason is None:
            return [Finding(
                "GC001", spec.name, 0,
                "dispatch-path jit donates nothing and records no "
                "reason; pass donate_argnums (or record why donation "
                "is unsafe/pointless for this program)")]
        return []
    if d["aliased"] < d["donated_leaves"] and spec.donate_reason is None:
        return [Finding(
            "GC001", spec.name, 0,
            f"donation silently dropped: {d['donated_leaves']} donated "
            f"aval(s) but only {d['aliased']} established an "
            f"input/output alias ({d['dropped']} left without one by "
            f"jax) — a dtype/layout mismatch is eating the donation")]
    return []


def _rule_gc002(spec: ProgramSpec, record: Dict[str, Any]) -> List[Finding]:
    if spec.compute_dtype != "bfloat16":
        return []
    c = record["dtype_counts"]
    leaks = c.get("conv_f32", 0) + c.get("dot_f32", 0)
    if leaks:
        return [Finding(
            "GC002", spec.name, 0,
            f"{leaks} f32 compute op(s) under the declared bf16 compute "
            f"dtype (conv_f32={c.get('conv_f32', 0)}, "
            f"dot_f32={c.get('dot_f32', 0)}) — an upcast is leaking "
            f"into the hot path (see PR 6's avg_pool/rescale fixes)")]
    return []


def _rule_gc005(spec: ProgramSpec, record: Dict[str, Any], args: tuple,
                text: str) -> List[Finding]:
    if spec.shardings is None or spec.mesh_axes is None:
        return []
    findings: List[Finding] = []
    data = int(spec.mesh_axes.get("data", 1))
    model = int(spec.mesh_axes.get("model", 1))
    summary = record["sharding_summary"]
    if summary["annotated"] == 0:
        findings.append(Finding(
            "GC005", spec.name, 0,
            "no sdy.sharding annotation reached the lowered program — "
            "the declared NamedShardings were lost before XLA"))
    for i, dim in summary["batch_args"]:
        for leaf in _tree_leaves(args[i]):
            if len(leaf.shape) > dim and leaf.shape[dim] % data:
                findings.append(Finding(
                    "GC005", spec.name, 0,
                    f"batch aval {tuple(leaf.shape)} dim {dim} not "
                    f"divisible by the {data}-way data axis — uneven "
                    f"shards recompile or fail at dispatch"))
    if (model > 1 and summary["largest_replicated_leaf_bytes"]
            > REPLICATED_PARAM_BUDGET_BYTES):
        mb = summary["largest_replicated_leaf_bytes"] / 1e6
        findings.append(Finding(
            "GC005", spec.name, 0,
            f"param leaf of {mb:.0f} MB fully replicated although the "
            f"mesh has a {model}-way model axis — shard it with a "
            f"PartitionSpec (mesh.match_partition_rules / parallel.train "
            f"param_specs) instead of paying {model}x HBM"))
    shards = summary.get("param_shards")
    if shards:
        for bad in shards["indivisible"]:
            if bad.get("unknown_axis"):
                findings.append(Finding(
                    "GC005", spec.name, 0,
                    f"sharded param {bad['param']!r} dim {bad['dim']} "
                    f"names unknown mesh axis {bad['unknown_axis']!r} "
                    f"(declared axes: {sorted(spec.mesh_axes or {})}) "
                    f"— the declaration matches no real layout"))
                continue
            findings.append(Finding(
                "GC005", spec.name, 0,
                f"sharded param {bad['param']!r} dim {bad['dim']} "
                f"(shape {tuple(bad['shape'])}) not divisible by its "
                f"{bad['shards']}-way split — the layout recompiles or "
                f"fails at device_put (mesh.resolve_param_shardings "
                f"would have replicated this leaf)"))
    return findings


def retrace_audit(records: Sequence[Dict[str, Any]]) -> List[Finding]:
    """GC003 over the WHOLE inventory: the executable cache key jax
    uses is (compiled fn identity, flat aval signatures).  Weak types,
    duplicate keys, and same-shape dtype/weak-type churn inside one
    group each force a recompilation of the "same" program at runtime —
    all three are statically visible here."""
    findings: List[Finding] = []
    seen: Dict[tuple, str] = {}
    by_group: Dict[str, list] = {}
    for rec in records:
        avals = rec["in_avals"]
        if avals["weak"]:
            findings.append(Finding(
                "GC003", rec["name"], 0,
                f"{avals['weak']} weak-typed input aval(s): a python "
                f"scalar is reaching the traced signature and will "
                f"re-specialize on the first strongly-typed call"))
        group = rec.get("group") or rec["name"]
        key = (group, avals["key"])
        if key in seen:
            findings.append(Finding(
                "GC003", rec["name"], 0,
                f"duplicate executable cache key: identical avals "
                f"already enumerated by {seen[key]} — the same program "
                f"would be built/compiled twice"))
        else:
            seen[key] = rec["name"]
        by_group.setdefault(group, []).append(rec)
    for group, recs in by_group.items():
        by_shape: Dict[str, set] = {}
        for rec in recs:
            by_shape.setdefault(rec["in_avals"]["shape_key"], set()).add(
                (rec["in_avals"]["key"], rec["name"]))
        for shape_key, keys in by_shape.items():
            if len({k for k, _ in keys}) > 1:
                names = sorted(n for _, n in keys)
                findings.append(Finding(
                    "GC003", names[0], 0,
                    f"dtype/weak-type churn in group {group!r}: "
                    f"{len(keys)} distinct cache keys share identical "
                    f"shapes ({', '.join(names)}) — each is a separate "
                    f"compilation of the same program"))
    return findings


def pad_waste_audit(records: Sequence[Dict[str, Any]],
                    interior_budget: float = PAD_INTERIOR_BUDGET,
                    floor_budget: float = PAD_FLOOR_BUDGET
                    ) -> List[Finding]:
    """GC004 over each model's bucket set: FLOPs are row-linear (the
    per-row figure must agree across buckets — checked), so the padded
    share of a bucket's FLOPs equals its padded row share.  Worst cases:
    a request of ``prev_bucket + 1`` rows served by bucket ``b`` wastes
    ``(b - prev - 1)/b`` of the program; a 1-row request pays the
    smallest bucket's floor."""
    findings: List[Finding] = []
    by_model: Dict[str, list] = {}
    for rec in records:
        if rec.get("model") and rec.get("bucket") and rec.get("flops"):
            by_model.setdefault(rec["model"], []).append(rec)
    for model, recs in sorted(by_model.items()):
        recs = sorted(recs, key=lambda r: r["bucket"])
        per_row = [r["flops"] / r["bucket"] for r in recs]
        lo, hi = min(per_row), max(per_row)
        if lo > 0 and (hi - lo) / lo > 0.02:
            findings.append(Finding(
                "GC004", f"zoo/{model}", 0,
                f"per-row FLOPs disagree across buckets "
                f"({lo / 1e9:.3f}–{hi / 1e9:.3f} GF/row): the program is "
                f"not row-linear, so pad-to-bucket accounting (and the "
                f"bench's FLOP-scaled baselines) are invalid"))
        buckets = [r["bucket"] for r in recs]
        # the formulas live in lockfile.pad_gap_fracs/pad_worst_fracs,
        # shared with bench's pad_overhead rider (ISSUE 11)
        from sparkdl_tpu.analysis.program.lockfile import (pad_gap_fracs,
                                                           pad_worst_fracs)

        floor = pad_worst_fracs(buckets)[1]
        if floor > floor_budget:
            findings.append(Finding(
                "GC004", f"zoo/{model}", 0,
                f"smallest bucket {buckets[0]} pads a 1-row request to "
                f"{floor:.0%} waste (budget {floor_budget:.0%}); add a "
                f"smaller bucket"))
        for prev, b, waste in pad_gap_fracs(buckets):
            if waste > interior_budget:
                findings.append(Finding(
                    "GC004", f"zoo/{model}", 0,
                    f"bucket gap {prev}->{b}: a {prev + 1}-row request "
                    f"wastes {waste:.0%} of bucket {b}'s FLOPs (budget "
                    f"{interior_budget:.0%}); tighten the bucket "
                    f"spacing"))
    return findings


def audit_inventory(specs: Sequence[ProgramSpec],
                    progress: Optional[Callable[[str], None]] = None
                    ) -> Tuple[List[Dict[str, Any]], List[Finding]]:
    """Audit every spec and run the cross-program rules; returns
    ``(records, findings)`` with findings sorted most-actionable first
    (per-program order, then GC003/GC004)."""
    records: List[Dict[str, Any]] = []
    findings: List[Finding] = []
    for spec in specs:
        out = audit_program(spec)
        records.append(out["record"])
        findings.extend(out["findings"])
        if progress is not None:
            r = out["record"]
            progress(f"{spec.name}: {r['flops'] / 1e9:.2f} GF, "
                     f"{len(out['findings'])} finding(s)")
    findings.extend(retrace_audit(records))
    findings.extend(pad_waste_audit(records))
    return records, findings
