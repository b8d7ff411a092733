"""Batched inference engine: the TPU replacement for the reference's hot loop.

Reference hot loop (SURVEY.md §3.1/§3.2): per-partition TensorFrames
``Session::Run`` on each executor, model GraphDef torrent-broadcast to JVMs.
Here instead: ONE jit-compiled XLA program per (model, batch-shape,
sharding policy), params resident on device (replicated via NamedSharding
— the broadcast analog — or tensor-parallel-sharded across the mesh's
``model`` axis via partition rules, ISSUE 14), batch rows sharded over
the mesh's data axis, and a fixed padded batch shape so XLA never
recompiles (SURVEY.md §7 hard part #4).

Throughput design:
  * fixed ``device_batch_size`` (rounded up to a multiple of the data-axis
    size) — one compile, reused forever;
  * the tail batch is zero-padded and trimmed on the host after gather, so
    ragged input never poisons shapes;
  * dispatch is async with a bounded in-flight window (double buffering):
    the next batch's host->device transfer overlaps the current batch's
    compute, while device residency stays O(window x batch) regardless of
    input size (both ``map_batches`` and ``__call__``);
  * host stages overlap the device by default: prepare (decode/pack/pad),
    H2D+dispatch, and D2H gather run on worker threads with backpressure
    queues (``parallel.pipeline.PipelinedRunner``), bit-identically to
    the calling-thread path (``pipeline=False``).
"""

from __future__ import annotations

import time as time_lib
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

import numpy as np

from sparkdl_tpu.analysis.lockcheck import named_lock
from sparkdl_tpu.faults import inject
from sparkdl_tpu.obs.flight import emit as flight_emit
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.parallel import mesh as mesh_lib
from sparkdl_tpu.parallel.pipeline import PipelinedRunner
from sparkdl_tpu.utils.logging import get_logger
from sparkdl_tpu.utils.metrics import Metrics
from sparkdl_tpu.utils.retry import NON_RETRYABLE, with_retries

logger = get_logger(__name__)


class CircuitOpenError(RuntimeError):
    """The engine's dispatch circuit breaker is OPEN: ``breaker_threshold``
    consecutive device errors tripped it, and dispatches now fail fast
    (with the last device error's text) instead of each paying a full
    retry-with-backoff budget against a dead device.  ``retry_after_s``
    is the remaining cool-down before a half-open trial dispatch is
    allowed."""

    def __init__(self, message: str, retry_after_s: float = 0.0,
                 last_error: Optional[str] = None):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)
        self.last_error = last_error


class DispatchCircuitBreaker:
    """Consecutive-failure circuit breaker for device dispatch.

    closed --(threshold consecutive failures)--> open
    open   --(cooldown elapses)-->                half_open (ONE trial)
    half_open --success--> closed; --failure--> open (fresh cooldown)

    Deterministic errors (``utils.retry.NON_RETRYABLE`` — shape/param
    validation, NaN fail-fast) never count: they indicate a caller bug,
    not a dying device, and must keep failing loudly per call.
    ``threshold <= 0`` disables the breaker entirely (gate/record are
    no-ops without taking the lock — the default-path budget).
    """

    def __init__(self, threshold: int = 8, cooldown_s: float = 30.0):
        self.threshold = int(threshold)
        self.cooldown_s = max(0.0, float(cooldown_s))
        self._lock = named_lock("engine.breaker")
        self._consecutive = 0
        self._open_until = 0.0
        self._open = False
        self._trial_inflight = False
        self._last_error: Optional[str] = None
        self._opened_count = 0

    @property
    def enabled(self) -> bool:
        return self.threshold > 0

    def gate(self) -> None:
        """Fail fast with :class:`CircuitOpenError` while open; admit a
        single trial dispatch once the cool-down elapses (half-open —
        recorded as a ``breaker.half_open`` flight event, outside the
        lock)."""
        if self.threshold <= 0:
            return
        trial = False
        with self._lock:
            if self._open:
                now = time_lib.monotonic()
                remaining = self._open_until - now
                if remaining > 0 or self._trial_inflight:
                    raise CircuitOpenError(
                        f"dispatch circuit breaker open "
                        f"({self._consecutive} consecutive device errors; "
                        f"last: {self._last_error}); failing fast — retry in "
                        f"{max(0.0, remaining):.2f}s",
                        retry_after_s=max(0.0, remaining),
                        last_error=self._last_error)
                self._trial_inflight = True  # half-open: this caller probes
                trial = True
        if trial:
            flight_emit("breaker.half_open")

    def record_success(self) -> None:
        if self.threshold <= 0:
            return
        with self._lock:
            closed_now = self._open
            self._consecutive = 0
            self._open = False
            self._trial_inflight = False
        if closed_now:
            flight_emit("breaker.close")

    def release_trial(self) -> None:
        """Give back a half-open trial slot WITHOUT judging the device
        (the attempt died on a deterministic caller error, which proves
        nothing about device health).  The breaker stays open, but the
        next gate() may admit a fresh trial — without this, a
        NON_RETRYABLE error during the trial would pin ``_trial_inflight``
        and leave the breaker open forever."""
        if self.threshold <= 0:
            return
        with self._lock:
            self._trial_inflight = False

    def record_failure(self, exc: BaseException) -> bool:
        """Count a device error; returns True when this failure OPENED
        (or re-opened) the breaker — recorded as a ``breaker.open``
        flight event outside the lock."""
        if self.threshold <= 0 or isinstance(exc, NON_RETRYABLE):
            return False
        with self._lock:
            self._consecutive += 1
            was_trial = self._trial_inflight
            self._trial_inflight = False
            self._last_error = f"{type(exc).__name__}: {exc}"
            opened = was_trial or (not self._open
                                   and self._consecutive >= self.threshold)
            if opened:
                self._open = True
                self._open_until = time_lib.monotonic() + self.cooldown_s
                self._opened_count += 1
            consecutive = self._consecutive
        if opened:
            flight_emit("breaker.open", consecutive=consecutive,
                        cooldown_s=self.cooldown_s,
                        error=type(exc).__name__)
        return opened

    def open_remaining_s(self) -> Optional[float]:
        """Remaining cool-down if OPEN, else None — the cheap per-submit
        query (one lock, no snapshot dict) the serving admission path
        uses; half-open reports None so trial traffic is admitted."""
        if self.threshold <= 0:
            return None
        with self._lock:
            if not self._open:
                return None
            remaining = self._open_until - time_lib.monotonic()
            if remaining <= 0 and not self._trial_inflight:
                return None  # half-open: let the trial through
            return max(0.0, remaining)

    def state(self) -> Dict[str, Any]:
        """JSON-serializable breaker snapshot (``Server.health`` /
        ``varz`` surface this per bucket engine)."""
        with self._lock:
            now = time_lib.monotonic()
            if not self._open:
                st = "closed"
            elif now < self._open_until or self._trial_inflight:
                st = "open"
            else:
                st = "half_open"
            return {
                "state": st,
                "enabled": self.threshold > 0,
                "consecutive_failures": self._consecutive,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown_s,
                "retry_after_s": (round(max(0.0, self._open_until - now), 3)
                                  if st == "open" else 0.0),
                "opened_count": self._opened_count,
                "last_error": self._last_error,
            }


# Module-level compiled-program cache: engines built around the SAME model
# fn / mesh / donation policy share one jax.jit object (whose executable
# cache then de-duplicates per batch shape).  A tuning grid produces many
# fitted models over one fn with different weights — without this, every
# model.transform() recompiled the identical program.  Keys use id(fn);
# safe because the cached jit closes over fn, keeping the id pinned.
# BoundedCache locks put/evict: fitMultiple's parallel fan-out transforms
# from worker threads.
from sparkdl_tpu.utils.cache import BoundedCache

_JIT_CACHE = BoundedCache(cap=32)


def clear_engine_jit_cache() -> None:
    _JIT_CACHE.clear()


def resolve_engine_mesh(mesh=None):
    """The mesh an :class:`InferenceEngine` actually runs on when the
    caller passes ``mesh`` (possibly None).  Scoring is per-controller by
    design (PERF.md topology envelope): under multi-controller jax the
    default covers LOCAL devices only, and an explicit cross-process mesh
    is refused loudly — device_put of process-local numpy onto a global
    sharding fails confusingly at runtime.  Shared with the serving
    bucket plan and ``analysis.program`` so enumerated programs see the
    same topology the engine compiles for."""
    import jax

    if mesh is None:
        if jax.process_count() > 1:
            mesh = mesh_lib.get_mesh(devices=jax.local_devices())
        else:
            mesh = mesh_lib.get_mesh()
    if jax.process_count() > 1 and any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat):
        raise NotImplementedError(
            "InferenceEngine is single-controller: pass a mesh over "
            "this process's local devices (mesh.get_mesh(devices="
            "jax.local_devices())) and shard input rows per host; "
            "multi-controller collectives belong to the TRAIN path "
            "(parallel.train / parallel.distributed).")
    return mesh


def effective_device_batch(device_batch_size: int, mesh) -> int:
    """The device batch the engine actually compiles for: rounded UP to a
    multiple of the mesh's data-axis size so every chip gets identical
    work.  Single-sourced so the serving bucket plan and the program
    auditor (``analysis.program``) enumerate exactly the shapes
    :class:`InferenceEngine` builds."""
    dp = mesh.shape[mesh_lib.DATA_AXIS]
    b = max(1, int(device_batch_size))
    rem = b % dp
    return b + (dp - rem) if rem else b


def build_dispatch_jit(fn: Callable, mesh, donate_batch: bool,
                       param_shardings=None):
    """THE per-batch dispatch program: ``jit(fn)`` with params placed
    under ``param_shardings`` (a pytree of per-leaf ``NamedSharding`` —
    the tensor-parallel weight layout from ``mesh.
    resolve_param_shardings``; ``None`` = the classic replicate-
    everything layout, byte-identical to the pre-ISSUE-14 program),
    batch sharded on the data axis, and the batch donated when asked.
    :class:`InferenceEngine` compiles through this (via the module jit
    cache) and ``analysis.program`` lowers the same object abstractly —
    one constructor, so the audited program cannot drift from the served
    one."""
    import jax

    params_sh = (param_shardings if param_shardings is not None
                 else mesh_lib.replicated_sharding(mesh))
    return jax.jit(
        fn,
        in_shardings=(params_sh, mesh_lib.batch_sharding(mesh)),
        out_shardings=mesh_lib.batch_sharding(mesh),
        donate_argnums=(1,) if donate_batch else ())


def dense_head_row(head, features):
    """THE canonical per-tenant head: one dense projection applied to ONE
    feature row (no batch axis — :func:`build_head_fanout_jit` vmaps it).
    ``head`` is the per-tenant weight pytree ``{"kernel": (D, C),
    "bias": (C,)}``.  Module-level on purpose: the runtime
    :class:`HeadBank`, the audited program in ``analysis.program.
    inventory``, and the zoo's feature-cut bundle all reference this ONE
    function object, so the lockfile-pinned head program is the program
    served.

    Spelled as an explicit broadcast-multiply-reduce rather than ``@``
    ON PURPOSE: the vmapped form (a per-row head gathered out of the
    bank) and the unbatched form (an independent full-model oracle)
    then lower to the SAME reduction order, so fan-out outputs are
    bit-identical to per-tenant oracles — the headline proof.  With
    ``@``, XLA picks a batched-matmul kernel for the vmapped head and a
    plain gemm for the oracle, whose accumulation orders differ by an
    ulp (measured on CPU XLA), silently breaking the bit-identity
    contract."""
    import jax.numpy as jnp

    return (jnp.sum(features[:, None] * head["kernel"], axis=0)
            + head["bias"])


def head_fanout_backbone_fn(variables, batch):
    """The chip-free backbone stand-in for the head fan-out tier's
    deterministic proofs (tests/bench/inventory): a dense tanh
    featurizer.  Module-level for the same reason as
    :func:`dense_head_row` — the audited backbone-cut program and the
    sleep-wrapped backbone the replay tests serve are the SAME fn, so
    jit-object identity is meaningful evidence."""
    import jax.numpy as jnp

    return jnp.tanh(batch @ variables["backbone"])


def head_fanout_oracle_fn(variables, row):
    """The INDEPENDENT per-tenant full-model oracle the fan-out tier's
    bit-identity proofs compare against: one unbatched row through the
    fused weights ``{"backbone", "kernel", "bias"}`` — the program shape
    a dedicated per-tenant full-model deployment would serve.  Jitted
    independently by each test/bench (never through
    :func:`build_head_fanout_jit`), so agreement with the fan-out path
    is evidence, not tautology."""
    import jax.numpy as jnp

    feats = jnp.tanh(row @ variables["backbone"])
    return dense_head_row(
        {"kernel": variables["kernel"], "bias": variables["bias"]}, feats)


def build_head_fanout_jit(head_fn: Callable, mesh):
    """THE stacked-head dispatch program: gather-by-tenant-index + vmap,
    so K tenants' rows in one batch cost ONE head pass.

    ``fanout(stacked, idx, feats)`` takes the head bank (every tenant's
    head pytree stacked along a leading capacity axis, replicated),
    a per-row ``int32`` tenant-index vector, and the feature rows
    (both data-sharded); it gathers each row's head out of the bank and
    applies ``vmap(head_fn)``.  Gather + vmap lowers to the same
    per-row contraction a dedicated per-tenant program would emit —
    the bit-identity tests against independent full-model oracles pin
    that down.  One constructor shared with ``analysis.program`` (like
    :func:`build_dispatch_jit`), so the audited stacked program cannot
    drift from the served one."""
    import jax

    def fanout(stacked, idx, feats):
        gathered = jax.tree_util.tree_map(lambda leaf: leaf[idx], stacked)
        return jax.vmap(head_fn)(gathered, feats)

    # donate nothing: the stacked bank is long-lived state shared by
    # every dispatch, and the padded feature rows are caller-owned
    return jax.jit(
        fanout,
        donate_argnums=(),
        in_shardings=(mesh_lib.replicated_sharding(mesh),
                      mesh_lib.batch_sharding(mesh),
                      mesh_lib.batch_sharding(mesh)),
        out_shardings=mesh_lib.batch_sharding(mesh))


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class HeadBank:
    """Per-tenant head weights stacked into ONE device pytree served by
    ONE vmapped program (:func:`build_head_fanout_jit`).

    The bank holds K tenants' head pytrees stacked along a leading
    capacity axis (capacity = next power of two, so adds recompile the
    HEAD program at most log2(K) times and the backbone never).  A
    mixed-tenant feature batch dispatches as gather-by-tenant-index —
    one head pass regardless of how many tenants' rows it carries.

    Degraded mode instead of a crash (tested): a head whose pytree
    structure/shape/dtype cannot stack with the bank ("indivisible"),
    or a bank whose stacked bytes would exceed ``hbm_budget_bytes``
    (checked via ``mesh.param_sharding_stats``), flips the bank to
    per-tenant fallback — every tenant is served through the SAME
    fan-out jit object as a bank of one, so program identity and
    bit-identity survive, only the one-pass batching is lost.

    Thread-safety: all mutation and dispatch run under
    ``named_lock("engine.headbank")``, so a hot-swap under load is
    atomic — in-flight dispatches see the old bank or the new one,
    never a torn index."""

    def __init__(self, head_fn: Optional[Callable] = None, mesh=None,
                 hbm_budget_bytes: Optional[int] = None,
                 metrics: Optional[Metrics] = None):
        self.head_fn = head_fn if head_fn is not None else dense_head_row
        self.mesh = resolve_engine_mesh(mesh)
        self.hbm_budget_bytes = (None if hbm_budget_bytes is None
                                 else int(hbm_budget_bytes))
        self.metrics = metrics if metrics is not None else Metrics()
        self._lock = named_lock("engine.headbank")
        self._hosts: Dict[str, Any] = {}    # tenant -> host head pytree
        self._index: Dict[str, int] = {}    # tenant -> row in the bank
        self._order: list = []              # tenants in stacking order
        self._stacked = None                # device pytree (capacity, ...)
        self._capacity = 0
        self._leaf_sig = None               # pinned (treedef, shapes, dtypes)
        self._fallback = False
        self._fallback_reason: Optional[str] = None
        # Same module-cache recipe as InferenceEngine: one jit object per
        # (head_fn, mesh), shared across banks/servers — the head-swap
        # no-recompile proof compares id() of this object.
        mesh_key = (tuple(d.id for d in self.mesh.devices.flat),
                    tuple(self.mesh.axis_names),
                    tuple(self.mesh.devices.shape))
        key = (id(self.head_fn),) + mesh_key + ("fanout",)
        jitted = _JIT_CACHE.get(key)
        if jitted is None:
            jitted = build_head_fanout_jit(self.head_fn, self.mesh)
            _JIT_CACHE.put(key, jitted)
        self._fanout = jitted

    # -- introspection ---------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._order)

    @property
    def mode(self) -> str:
        with self._lock:
            return "fallback" if self._fallback else "stacked"

    def tenants(self) -> list:
        with self._lock:
            return list(self._order)

    def jit_info(self) -> Dict[str, Any]:
        """The head half of the no-recompile proof (the shape
        ``Server.executable_state`` uses for backbone buckets): the
        fan-out jit object's id plus its executable-cache size.  A head
        add/swap may grow ``executables`` (that's the HEAD program, by
        design at most once per capacity doubling); ``jit_id`` must
        never change."""
        try:
            size = int(self._fanout._cache_size())
        except (AttributeError, TypeError):  # older jax: identity only
            size = None
        return {"jit_id": id(self._fanout), "executables": size,
                "mode": self.mode}

    def stats(self) -> Dict[str, Any]:
        """Stacked-bank HBM accounting via ``mesh.param_sharding_stats``
        — the same ledger GC005 audits, so the budget the bank enforces
        is the budget the program auditor sees."""
        with self._lock:
            if self._fallback or not self._order:
                tree = dict(self._hosts) if self._hosts else None
            else:
                tree = self._stack_hosts(self._capacity)
            if tree is None:
                param = {"param_bytes_total": 0, "param_bytes_per_chip": 0}
            else:
                param = mesh_lib.param_sharding_stats(self.mesh, tree)
            out = dict(param)
            out.update({
                "tenants": len(self._order),
                "capacity": self._capacity,
                "mode": "fallback" if self._fallback else "stacked",
                "fallback_reason": self._fallback_reason,
                "hbm_budget_bytes": self.hbm_budget_bytes,
            })
            return out

    # -- mutation --------------------------------------------------------

    def add_head(self, tenant: str, weights) -> None:
        """Register a NEW tenant's head.  Raises ``ValueError`` if the
        tenant already has one (use :meth:`swap_head`)."""
        self._mutate(tenant, weights, op="add")

    def swap_head(self, tenant: str, weights) -> None:
        """Hot-swap an EXISTING tenant's head.  Raises ``KeyError`` if
        the tenant is unknown (use :meth:`add_head`)."""
        self._mutate(tenant, weights, op="swap")

    def remove_head(self, tenant: str) -> None:
        """Evict a departed tenant: its row leaves the bank and the
        remaining tenants re-stack (capacity may shrink)."""
        self._mutate(tenant, None, op="remove")

    def _mutate(self, tenant: str, weights, op: str) -> None:
        import jax

        tenant = str(tenant)
        with self._lock:
            # Fault site fires BEFORE any state changes: an injected
            # error aborts the mutation with the bank unchanged.
            inject("head.swap")
            if op == "remove":
                if tenant not in self._hosts:
                    raise KeyError(f"head bank has no tenant {tenant!r}")
                del self._hosts[tenant]
                self._order.remove(tenant)
            else:
                if op == "add" and tenant in self._hosts:
                    raise ValueError(
                        f"tenant {tenant!r} already has a head; "
                        "swap_head() replaces it")
                if op == "swap" and tenant not in self._hosts:
                    raise KeyError(f"head bank has no tenant {tenant!r}")
                host = jax.tree_util.tree_map(np.asarray, weights)
                sig = self._signature(host)
                if self._leaf_sig is None:
                    self._leaf_sig = sig
                elif sig != self._leaf_sig and not self._fallback:
                    self._degrade(
                        f"tenant {tenant!r} head does not stack with the "
                        f"bank (pytree/shape/dtype mismatch)")
                self._hosts[tenant] = host
                if op == "add":
                    self._order.append(tenant)
            if not self._fallback:
                cap = _next_pow2(max(1, len(self._order)))
                over = self._budget_excess(cap)
                if over is not None:
                    self._degrade(
                        f"stacked bank would hold {over} bytes per chip, "
                        f"over hbm_budget_bytes={self.hbm_budget_bytes}")
            self._rebuild()
            self.metrics.incr(f"headbank.{op}")
            flight_emit("head.swap", tenant=tenant, op=op,
                        tenants=len(self._order),
                        mode="fallback" if self._fallback else "stacked")

    def _signature(self, host):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(host)
        return (treedef,
                tuple(tuple(np.shape(x)) for x in leaves),
                tuple(str(np.asarray(x).dtype) for x in leaves))

    def _degrade(self, reason: str) -> None:
        self._fallback = True
        self._fallback_reason = reason
        self.metrics.incr("headbank.fallbacks")
        logger.warning("HeadBank degrading to per-tenant dispatch: %s",
                       reason)

    def _budget_excess(self, capacity: int):
        """Bytes-per-chip the stacked bank would occupy if it exceeds the
        budget, else None.  Uses ``param_sharding_stats`` (replicated
        layout) so the number matches GC005's ledger."""
        if self.hbm_budget_bytes is None or not self._order:
            return None
        tree = self._stack_hosts(capacity)
        stats = mesh_lib.param_sharding_stats(self.mesh, tree)
        per_chip = int(stats["param_bytes_per_chip"])
        return per_chip if per_chip > self.hbm_budget_bytes else None

    def _stack_hosts(self, capacity: int):
        import jax

        heads = [self._hosts[t] for t in self._order]
        pad = heads[0]
        rows = heads + [pad] * (capacity - len(heads))
        return jax.tree_util.tree_map(
            lambda *ls: np.stack([np.asarray(x) for x in ls]), *rows)

    def _rebuild(self) -> None:
        import jax

        self._index = {t: i for i, t in enumerate(self._order)}
        if self._fallback or not self._order:
            self._stacked = None
            self._capacity = 0 if not self._order else self._capacity
            if not self._order:
                self._capacity = 0
            return
        cap = _next_pow2(len(self._order))
        stacked_host = self._stack_hosts(cap)
        self._stacked = jax.device_put(
            stacked_host, mesh_lib.replicated_sharding(self.mesh))
        self._capacity = cap

    # -- dispatch --------------------------------------------------------

    def _row_bucket(self, n: int) -> int:
        """Pad row counts to a power of two rounded to the data axis, so
        the head program compiles O(log) executables, not one per ragged
        batch size."""
        dp = self.mesh.shape[mesh_lib.DATA_AXIS]
        p = _next_pow2(max(1, n))
        rem = p % dp
        return p + (dp - rem) if rem else p

    def dispatch(self, features, tenants) -> np.ndarray:
        """One head pass over a mixed-tenant feature batch.

        ``features`` is ``(n, ...)`` host rows (a single row is
        promoted); ``tenants`` names each row's head.  Returns host
        outputs row-aligned with the input.  Raises ``KeyError`` for a
        tenant with no registered head (a departed tenant must fail
        loudly, not serve a stale row)."""
        import jax

        features = np.asarray(features)
        if features.ndim == 1:
            features = features[None]
        tenants = [str(t) for t in tenants]
        if len(tenants) != int(features.shape[0]):
            raise ValueError(
                f"{features.shape[0]} feature rows but "
                f"{len(tenants)} tenants")
        with self._lock:
            inject("head.dispatch")
            missing = sorted({t for t in tenants if t not in self._hosts})
            if missing:
                raise KeyError(
                    f"head bank has no head for tenant(s) {missing}")
            self.metrics.incr("headbank.dispatches")
            self.metrics.incr("headbank.rows", len(tenants))
            if self._fallback:
                return self._dispatch_fallback(features, tenants)
            n = int(features.shape[0])
            idx = np.asarray([self._index[t] for t in tenants],
                             dtype=np.int32)
            padded = self._row_bucket(n)
            if padded != n:
                features = np.concatenate(
                    [features,
                     np.zeros((padded - n,) + features.shape[1:],
                              dtype=features.dtype)])
                idx = np.concatenate(
                    [idx, np.zeros(padded - n, dtype=np.int32)])
            out = self._fanout(self._stacked, idx, features)
            return np.asarray(out)[:n]

    def _dispatch_fallback(self, features, tenants) -> np.ndarray:
        """Per-tenant degraded path: each tenant's rows go through the
        SAME fan-out jit as a bank of one (same program identity, same
        numerics) — one head pass per tenant instead of one total."""
        import jax

        groups: Dict[str, list] = {}
        for i, t in enumerate(tenants):
            groups.setdefault(t, []).append(i)
        out = None
        for t, rows in groups.items():
            sel = np.asarray(rows, dtype=np.int64)
            feats_t = features[sel]
            n = int(feats_t.shape[0])
            padded = self._row_bucket(n)
            if padded != n:
                feats_t = np.concatenate(
                    [feats_t,
                     np.zeros((padded - n,) + feats_t.shape[1:],
                              dtype=feats_t.dtype)])
            bank1 = jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf)[None], self._hosts[t])
            idx = np.zeros(padded, dtype=np.int32)
            res = np.asarray(self._fanout(bank1, idx, feats_t))[:n]
            if out is None:
                out = np.zeros((len(tenants),) + res.shape[1:],
                               dtype=res.dtype)
            out[sel] = res
        return out


def _is_narrow_float(dtype) -> bool:
    """True iff ``dtype`` is an ml_dtypes narrow float (bf16/f8 families).

    These register as numpy kind 'V' (void), which also covers structured
    dtypes — ``ml_dtypes.finfo`` accepts only the float ones.
    """
    if np.dtype(dtype).kind != "V":
        return False
    try:
        import ml_dtypes

        ml_dtypes.finfo(dtype)
        return True
    except (ImportError, ValueError, TypeError, KeyError):
        return False


def _cast_floating(variables, dtype):
    import jax
    import jax.numpy as jnp

    def cast(leaf):
        arr = jnp.asarray(leaf)
        if jnp.issubdtype(arr.dtype, jnp.floating):
            return arr.astype(dtype)
        return arr

    return jax.tree_util.tree_map(cast, variables)


def _place_variables(variables, shardings):
    """``variables`` on the device under ``shardings`` (one sharding for
    every leaf, or a tree of them).  A leaf that is already a device
    array laid out as wanted is TAKEN, not copied: weights a caller has
    placed stay the one copy on the chip (a second copy of a model that
    fills half the chip does not fit)."""
    import jax

    def place(leaf, sharding):
        if (isinstance(leaf, jax.Array) and leaf.is_fully_addressable
                and leaf.sharding.is_equivalent_to(sharding, leaf.ndim)):
            return leaf
        return jax.device_put(leaf, sharding)

    if isinstance(shardings, jax.sharding.Sharding):
        return jax.tree_util.tree_map(lambda l: place(l, shardings),
                                      variables)
    return jax.tree_util.tree_map(place, variables, shardings)


class InferenceEngine:
    """Runs ``fn(variables, batch) -> out`` over arbitrarily-sized inputs in
    fixed-shape device batches on a device mesh.

    ``fn`` must be jit-traceable with a leading batch axis on ``batch`` and
    on every output leaf (outputs may be a single array or a pytree).

    Weight sharding (ISSUE 14): ``partition_rules`` (a ``(regex,
    PartitionSpec)`` rule list or a ``mesh -> rules`` factory — see
    ``mesh.match_partition_rules`` / ``mesh.default_partition_rules``)
    or an explicit ``param_shardings`` pytree split chosen param leaves
    across the mesh's ``model`` axis, ending the one-full-weight-copy-
    per-chip model: each chip holds ``bytes / model_axis`` of a sharded
    leaf and XLA's SPMD partitioner inserts the collectives the layout
    implies.  The default (both ``None``) — and any policy that
    resolves all-replicated, e.g. the default rules on a model-axis-1
    mesh — keeps the classic replicate-everything layout with
    byte-identical programs.  The policy is part of the jit-cache key
    (``sharding_digest``), so engines under different layouts never
    alias a compiled program.
    """

    def __init__(self, fn: Callable, variables: Any, *,
                 mesh=None,
                 device_batch_size: int = 64,
                 compute_dtype: Optional[Any] = None,
                 output_host_dtype: Optional[Any] = None,
                 donate_batch: bool = False,
                 partition_rules: Any = None,
                 param_shardings: Any = None,
                 dispatch_retries: int = 0,
                 dispatch_backoff_s: float = 0.05,
                 dispatch_max_backoff_s: float = 2.0,
                 dispatch_jitter: float = 0.25,
                 breaker_threshold: int = 8,
                 breaker_cooldown_s: float = 30.0,
                 on_dispatch_error: Optional[
                     Callable[[BaseException], None]] = None,
                 metrics: Optional[Metrics] = None):
        import jax

        # Scoring is per-controller by design (PERF.md topology
        # envelope): each host scores its own rows on its own devices —
        # see resolve_engine_mesh (the zoo transformers pass no mesh, so
        # the local-devices default keeps them working on pods).
        self.mesh = resolve_engine_mesh(mesh)
        self.data_parallel = self.mesh.shape[mesh_lib.DATA_AXIS]
        self.model_parallel = self.mesh.shape[mesh_lib.MODEL_AXIS]
        # Round the device batch up to a multiple of the data-axis size so
        # every chip gets identical work.
        b = effective_device_batch(device_batch_size, self.mesh)
        if b != max(1, int(device_batch_size)):
            logger.info("device_batch_size rounded up to %d (multiple of "
                        "%d-way data axis)", b, self.data_parallel)
        self.device_batch_size = b
        self.metrics = metrics if metrics is not None else Metrics()
        # Fetch device outputs in their compute dtype and cast on the HOST:
        # a bf16 model result upcast to f32 on device carries no extra
        # information, but doubles the D2H bytes of every gather — casting
        # host-side after the fetch is bit-identical and halves transfer
        # (minimise host<->device traffic).  None = return outputs as
        # produced.
        self.output_host_dtype = (np.dtype(output_host_dtype)
                                  if output_host_dtype is not None else None)

        # Failure domain (ISSUE 4): bounded retry-with-backoff for
        # TRANSIENT dispatch faults (jittered + capped via utils.retry —
        # the Spark task-retry analog at dispatch granularity; default 0
        # = fail fast, callers opt in) and a consecutive-failure circuit
        # breaker so a STICKY-dead device fails fast with a clear error
        # instead of paying the full retry budget per call forever.
        # ``on_dispatch_error`` fires on every failed ATTEMPT (even ones
        # a retry later absorbs) — the serving layer's health() hook.
        self.dispatch_retries = max(0, int(dispatch_retries))
        self.dispatch_backoff_s = max(0.0, float(dispatch_backoff_s))
        self.dispatch_max_backoff_s = float(dispatch_max_backoff_s)
        self.dispatch_jitter = float(dispatch_jitter)
        self.breaker = DispatchCircuitBreaker(
            threshold=breaker_threshold, cooldown_s=breaker_cooldown_s)
        self._on_dispatch_error = on_dispatch_error

        with get_tracer().span("engine.build",
                               device_batch_size=b) as build:
            jit_cached = self._build(fn, variables, compute_dtype,
                                     partition_rules, param_shardings,
                                     donate_batch)
            build.annotate(
                param_bytes=self._sharding_stats["param_bytes_total"],
                jit_cached=jit_cached)

    def _build(self, fn, variables, compute_dtype, partition_rules,
               param_shardings, donate_batch) -> bool:
        """The part of construction that touches the weights and the
        program — cast, sharding policy, compile cache, placement, the
        jit lookup — under ``__init__``'s ``engine.build`` span, so a
        compile it causes (a cast on the device) is parented there.
        Returns whether ``_JIT_CACHE`` already held the dispatch jit."""
        if compute_dtype is not None:
            variables = _cast_floating(variables, compute_dtype)
        self._replicated = mesh_lib.replicated_sharding(self.mesh)
        self._batch_sharding = mesh_lib.batch_sharding(self.mesh)
        # Tensor-parallel weight sharding (ISSUE 14): resolve the policy
        # to per-leaf NamedShardings.  ``param_shardings`` (a pytree of
        # PartitionSpec/NamedSharding matching ``variables``) wins over
        # ``partition_rules`` (a regex rule list, or a ``mesh -> rules``
        # factory like mesh.default_partition_rules).  An all-replicated
        # resolution COLLAPSES to the classic single replicate sharding,
        # so model-axis-1 meshes build byte-identical programs with the
        # same executable cache keys as the pre-ISSUE-14 stack.
        self.param_shardings = None
        self._param_specs = None
        if param_shardings is not None:
            # explicit leaves (PartitionSpec or NamedSharding) are
            # normalized onto THIS engine's mesh through the ONE
            # resolution path the rules share — same structure check,
            # same per-leaf divisibility fallback (an indivisible
            # explicit spec replicates instead of crashing device_put)
            self.param_shardings, self._param_specs = (
                mesh_lib.resolve_param_shardings(variables, self.mesh,
                                                 specs=param_shardings))
        elif partition_rules is not None:
            self.param_shardings, self._param_specs = (
                mesh_lib.resolve_param_shardings(variables, self.mesh,
                                                 partition_rules))
        if (self._param_specs is not None
                and mesh_lib.specs_all_replicated(self._param_specs)):
            self.param_shardings = None
            self._param_specs = None
        self.sharding_digest = mesh_lib.partition_digest(self._param_specs)
        # HBM accounting (ISSUE 14 bench rider): per-chip param bytes
        # under this layout vs the one-full-copy-per-chip baseline,
        # gauged so bench lines / varz can stamp the claim chip-free
        self._sharding_stats = mesh_lib.param_sharding_stats(
            self.mesh, variables, self._param_specs)
        self.metrics.gauge("engine.mesh_data_axis",
                           float(self.data_parallel))
        self.metrics.gauge("engine.mesh_model_axis",
                           float(self.model_parallel))
        self.metrics.gauge("engine.replicated_param_bytes",
                           float(self._sharding_stats["param_bytes_total"]))
        self.metrics.gauge("engine.param_bytes_per_chip",
                           float(self._sharding_stats["param_bytes_per_chip"]))
        # Persistent compile cache (ISSUE 13): resolve where it lives
        # (compile_cache.ensure_from_env) once per process BEFORE any
        # program of this engine compiles, so fleet deploys and
        # serving cold-starts across restarts reuse on-disk
        # executables keyed on the committed lockfile.  Disabled path
        # = one module-global read.  The FIRST engine's mesh/partition
        # policy keys the manifest (ISSUE 14): a restarted process
        # under a different sharding policy purges the population
        # cleanly instead of trusting content-addressing alone.
        from sparkdl_tpu.parallel import compile_cache

        compile_cache.ensure_from_env(policy=self.compile_policy())
        # Params live on device once — per-leaf NamedShardings when the
        # policy splits them (each chip holds bytes/model_axis of a
        # sharded leaf), the NamedSharding replicate otherwise (the TPU
        # analog of the reference's model-GraphDef broadcast).
        self.variables = _place_variables(
            variables, self.param_shardings if self.param_shardings
            is not None else self._replicated)
        # grid SHAPE is part of the key (as in train._mesh_key): a
        # (1, 8) and a (2, 4) mesh over the same 8 devices share flat
        # device ids and axis names but compile different programs
        mesh_key = (tuple(d.id for d in self.mesh.devices.flat),
                    tuple(self.mesh.axis_names),
                    tuple(self.mesh.devices.shape), bool(donate_batch),
                    self.sharding_digest)
        key = (id(fn),) + mesh_key
        compiled = _JIT_CACHE.get(key)
        jit_cached = compiled is not None
        if not jit_cached:
            compiled = build_dispatch_jit(fn, self.mesh, donate_batch,
                                          param_shardings=self.param_shardings)
            _JIT_CACHE.put(key, compiled)
        self._compiled = compiled
        return jit_cached

    # -- low level ---------------------------------------------------------
    @staticmethod
    def _leaves(batch):
        import jax

        leaves = jax.tree_util.tree_leaves(batch)
        if not leaves:
            raise ValueError("Batch pytree has no array leaves")
        n = leaves[0].shape[0]
        if any(l.shape[0] != n for l in leaves):
            raise ValueError("All batch leaves must share the leading "
                             "(batch) axis length")
        return n

    def _attempt_dispatch(self, thunk):
        """ONE gated dispatch attempt: breaker gate -> fault-injection
        site -> H2D + launch; success/failure feed the breaker and the
        ``on_dispatch_error`` health hook.  Deterministic errors
        (``NON_RETRYABLE``) bypass the breaker count — they are caller
        bugs, not device state."""
        self.breaker.gate()
        try:
            inject("engine.dispatch")
            out = thunk()
        except NON_RETRYABLE:
            # deterministic caller error: not device evidence either way
            # — but a half-open trial slot must be handed back, or the
            # breaker could never re-probe
            self.breaker.release_trial()
            raise
        except BaseException as e:  # noqa: BLE001 — device/runtime error
            self._charge_breaker(e, "engine.dispatch_errors")
            raise
        # NOTE: success is NOT recorded here.  Dispatch is an async
        # ENQUEUE — a dying device usually raises when the result is
        # forced (D2H), so the attempt is only known good at force time
        # (_force_part), which records the breaker success.
        return out

    def _charge_breaker(self, e: BaseException, counter: str) -> None:
        """Shared failure bookkeeping for both failure surfaces of an
        async dispatch (the enqueue attempt and the result force):
        metrics, breaker count, open log line, and the health hook."""
        self.metrics.incr(counter)
        if self.breaker.record_failure(e):
            self.metrics.incr("engine.breaker_opened")
            logger.warning(
                "dispatch circuit breaker OPENED after %d consecutive "
                "device errors (last: %s: %s); failing fast for %.1fs",
                self.breaker.state()["consecutive_failures"],
                type(e).__name__, e, self.breaker.cooldown_s)
        if self._on_dispatch_error is not None:
            self._on_dispatch_error(e)

    def _force_part(self, n, out, block=None):
        """Force one in-flight dispatch to its ``n`` real host rows: the
        D2H fetch + trim of both the calling-thread drain and the
        pipelined gather stage.

        The OTHER failure surface of an async dispatch: jax's enqueue
        returns before the device runs, so a dying device typically
        raises here, not in ``_attempt_dispatch``.  Errors are charged to
        the same breaker/health accounting (no retry: a failed force
        cannot be re-run without re-dispatching); a successful force
        records breaker success.  ``block`` (the gather span's
        ``block_until_ready``) waits for the device inside the caller's
        span so device wait stays attributed."""
        try:
            inject("engine.gather")
            if block is not None:
                block(out)
            part = self._trim(out, n)
        except NON_RETRYABLE:
            self.breaker.release_trial()
            raise
        except BaseException as e:  # noqa: BLE001 — device/runtime error
            self._charge_breaker(e, "engine.gather_errors")
            raise
        self.breaker.record_success()
        return part

    def _run_dispatch(self, thunk):
        """Dispatch with the engine's transient-fault retry budget:
        ``dispatch_retries`` re-executions with jittered, capped
        exponential backoff (``utils.retry``).  Deterministic failures
        and a breaker that opened mid-budget fail immediately."""
        if self.dispatch_retries <= 0:
            return self._attempt_dispatch(thunk)

        def on_retry(attempt, exc):
            self.metrics.incr("engine.dispatch_retries")

        return with_retries(
            lambda: self._attempt_dispatch(thunk),
            max_retries=self.dispatch_retries,
            non_retryable=NON_RETRYABLE + (CircuitOpenError,),
            backoff_seconds=self.dispatch_backoff_s,
            max_backoff_seconds=self.dispatch_max_backoff_s,
            jitter=self.dispatch_jitter,
            on_retry=on_retry)

    def breaker_state(self) -> Dict[str, Any]:
        """The dispatch circuit breaker's JSON-serializable snapshot."""
        return self.breaker.state()

    def compile_policy(self) -> str:
        """The mesh + partition-rule policy string keying the persistent
        compile-cache manifest (``parallel.compile_cache``): a restarted
        process whose first engine resolves a DIFFERENT policy purges
        the on-disk executable population instead of trusting
        content-addressing alone."""
        return (f"mesh={self.data_parallel}x{self.model_parallel}"
                f"|params={self.sharding_digest}")

    def sharding_info(self) -> Dict[str, Any]:
        """JSON snapshot of this engine's weight-sharding layout (ISSUE
        14): mesh shape, total vs per-chip param bytes, sharded leaf
        count, and the policy digest — what ``Server.varz`` embeds and
        the bench HBM rider stamps next to ``pad_overhead``."""
        return dict(self._sharding_stats,
                    sharding_digest=self.sharding_digest,
                    sharded=self.param_shardings is not None)

    def compiled_text(self, batch) -> str:
        """The per-batch program as XLA compiled it for ``batch`` (one
        device batch, or its ``jax.ShapeDtypeStruct``) on this engine's
        mesh and weights: the text a bring-up check searches for custom
        calls and collectives."""
        return self._compiled.lower(self.variables,
                                    batch).compile().as_text()

    @staticmethod
    def _h2d(host, sharding):
        """``device_put`` under an ``engine.h2d`` span: the HOST side of
        the transfer (staging and enqueue) — nothing here waits for the
        device, the dispatch path stays asynchronous."""
        import jax

        tracer = get_tracer()
        with tracer.span("engine.h2d") as sp:
            if tracer.enabled:
                sp.annotate(bytes=sum(
                    a.nbytes for a in jax.tree_util.tree_leaves(host)))
            return jax.device_put(host, sharding)

    def run_padded(self, batch):
        """Run one already-padded device batch (array or pytree of arrays
        sharing the leading batch axis); returns device output(s)."""
        import jax

        if self._leaves(batch) != self.device_batch_size:
            raise ValueError(
                f"run_padded expects batch of {self.device_batch_size}, "
                f"got {self._leaves(batch)}")

        # span covers H2D + async launch only (the call returns as soon
        # as the dispatch is enqueued); the device wait is bracketed by
        # whichever stage forces the result (pipeline.gather / _trim)
        def attempt():
            with get_tracer().span("engine.dispatch",
                                   rows=self.device_batch_size):
                x = self._h2d(batch, self._batch_sharding)
                return self._compiled(self.variables, x)

        return self._run_dispatch(attempt)

    def _pad(self, chunk):
        import jax

        n = self._leaves(chunk)
        # pad-to-bucket ledger (ISSUE 11): real vs padded rows per
        # dispatch piece, so the measured pad overhead GC004 budgets
        # abstractly is observable live (`engine.pad_rows /
        # (engine.rows + engine.pad_rows)`) and bench lines can stamp
        # it next to the lockfile's analytic bounds
        self.metrics.incr("engine.rows", n)
        if n == self.device_batch_size:
            return chunk
        pad_rows = self.device_batch_size - n
        self.metrics.incr("engine.pad_rows", pad_rows)

        def pad_leaf(a):
            return np.pad(a, [(0, pad_rows)] + [(0, 0)] * (a.ndim - 1))

        with get_tracer().span("engine.pad", rows=n, pad_rows=pad_rows):
            return jax.tree_util.tree_map(pad_leaf, chunk)

    def _trim(self, out, n: int):
        import jax

        def gather(a):
            host = np.asarray(a[:n])
            # cast float->float only: integer/bool leaves (e.g. argmax
            # ids) must never be silently floated.  ml_dtypes narrow
            # floats (bf16/f8) register as kind 'V', not np.floating —
            # but so do genuinely structured/void dtypes, which must
            # pass through untouched, so probe ml_dtypes explicitly.
            src_float = (np.issubdtype(host.dtype, np.floating)
                         or _is_narrow_float(host.dtype))
            if (self.output_host_dtype is not None
                    and host.dtype != self.output_host_dtype
                    and src_float
                    and np.issubdtype(self.output_host_dtype, np.floating)):
                host = host.astype(self.output_host_dtype)
            return host

        return jax.tree_util.tree_map(gather, out)

    @staticmethod
    def _slice(batch, off: int, size: int):
        import jax

        return jax.tree_util.tree_map(lambda a: a[off:off + size], batch)

    # -- whole-array API ---------------------------------------------------
    def __call__(self, batch, window: int = 2, pipeline: bool = True,
                 on_metered=None):
        """Process a full batch (array or pytree); returns host output with
        matching row count.

        ``on_metered``, when given, is invoked once per call with the
        metered wall seconds (the same span ``engine_call`` records) —
        the cost ledger's device-time feed.  Per-call rather than
        per-engine so concurrent batches on one shared bucket engine
        each observe their own span.

        Host-memory contract: the pipelined path (the default)
        PREALLOCATES the output — the one compiled program fixes the leaf
        shapes, so after the first gathered chunk the ``[n, ...]`` result
        is allocated once and every later chunk is copied into it.  Peak
        host residency is the output plus O(window + depth) chunks
        (``pipeline=False`` concatenates a per-chunk list, which
        transiently doubles the output).  Either way the OUTPUT
        materializes in host RAM — route multi-million-row frames through
        ``map_batches`` instead.

        Chunks run through ``map_batches``' bounded in-flight window, so
        device residency is O(window x device_batch) for any input, and
        pipelined outputs are bit-identical to serial ones.  An input
        that fits one device batch skips the worker threads — nothing to
        overlap — so serving-sized calls pay no thread latency.
        """
        import time

        import jax

        batch = jax.tree_util.tree_map(np.asarray, batch)
        n = self._leaves(batch)
        if n == 0:
            raise ValueError("Empty input batch")
        t0 = time.perf_counter()
        with get_tracer().span("engine.call", rows=n):
            if not pipeline or n <= self.device_batch_size:
                outs = list(self.map_batches([batch], window=window,
                                             pipeline=False))
                result = jax.tree_util.tree_map(
                    lambda *parts: np.concatenate(parts, axis=0), *outs)
            else:
                out = None
                off = 0
                for part in self.map_batches([batch], window=window,
                                             pipeline=True):
                    k = self._leaves(part)
                    if out is None:
                        # leaf trailing shapes are fixed by the one
                        # compiled program: preallocate [n, ...] per leaf
                        # and stream chunks straight in
                        out = jax.tree_util.tree_map(
                            lambda a: np.empty((n,) + a.shape[1:], a.dtype),
                            part)
                        self.metrics.incr("engine_call_prealloc")
                    for dst, src in zip(jax.tree_util.tree_leaves(out),
                                        jax.tree_util.tree_leaves(part)):
                        dst[off:off + k] = src
                    off += k
                result = out
        elapsed = time.perf_counter() - t0
        self.metrics.incr("items", n)
        self.metrics.record_time("engine_call", elapsed)
        # unbounded float accumulator (timing series are capped): THE
        # conservation reference the cost ledger's totals are proved
        # against.  Host wall time of this call (perf_counter), not time
        # measured on the device — hence the name
        self.metrics.incr("engine.call_wall_s", elapsed)
        if on_metered is not None:
            on_metered(elapsed)
        return result

    # -- streaming API -----------------------------------------------------
    def map_batches(self, batches: Iterable[Any], window: int = 2,
                    pipeline: bool = True) -> Iterator[Any]:
        """Map over an iterator of host batches with a bounded in-flight
        window (double buffering by default): batch k+1 transfers/computes
        while batch k is gathered.

        ``pipeline`` runs host prepare, H2D+dispatch and D2H gather on
        three threads (:class:`~sparkdl_tpu.parallel.pipeline.
        PipelinedRunner`): the input iterator — typically the decode
        stage — is pulled on its own; ``pipeline=False`` keeps everything
        on the calling thread, with BIT-IDENTICAL outputs."""
        if pipeline:
            return PipelinedRunner(self, window=window).run(batches)
        return self._map_batches_serial(batches, window)

    def _iter_pieces(self, batches: Iterable[Any]) -> Iterator[tuple]:
        """THE host-prepare sequence of both the calling-thread path and
        the runner's prepare stage: slice chunks into device-batch pieces
        and pad them; yields ``(n_rows, padded_piece)`` in dispatch order."""
        import jax

        for chunk in batches:
            chunk = jax.tree_util.tree_map(np.asarray, chunk)
            n = self._leaves(chunk)
            for off in range(0, n, self.device_batch_size):
                piece = self._slice(chunk, off, self.device_batch_size)
                yield self._leaves(piece), self._pad(piece)

    def _map_batches_serial(self, batches: Iterable[Any],
                            window: int = 2) -> Iterator[Any]:
        """The calling-thread path: same pieces, same program, no threads."""
        from collections import deque

        inflight: deque = deque()

        def drain(limit):
            while len(inflight) > limit:
                n, out = inflight.popleft()
                yield self._force_part(n, out)

        for n, host in self._iter_pieces(batches):
            inflight.append((n, self.run_padded(host)))
            yield from drain(window)
        yield from drain(0)

    @property
    def num_devices(self) -> int:
        return self.mesh.size


def get_cached_engine(holder, model_function, *, device_batch_size: int,
                      **engine_kwargs) -> InferenceEngine:
    """Engine cache keyed on (model_function, batch) living on ``holder``
    (typically a pipeline stage): repeated ``transform`` calls — e.g. a
    CrossValidator loop — reuse one compiled program and one device copy of
    the weights instead of recompiling per call.

    The cache entry pins the ModelFunction alive so id-keying cannot alias
    a recycled object.
    """
    cache = holder.__dict__.setdefault("_engine_cache", {})
    key = (id(model_function), device_batch_size)
    entry = cache.get(key)
    if entry is None:
        eng = InferenceEngine(model_function.fn, model_function.variables,
                              device_batch_size=device_batch_size,
                              **engine_kwargs)
        cache[key] = (model_function, eng)
        return eng
    return entry[1]
