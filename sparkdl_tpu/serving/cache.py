"""Content-addressed inference result cache + single-flight coalescing.

At production scale the traffic the stack serves is heavily repetitive:
popular images recur across tenants, client retries resend identical
payloads, and streaming replays re-score chunks a previous run already
scored.  Re-dispatching those is pure waste — the engine computes a
deterministic function of (program, weights, input), so an identical
input is an identical output.  This module is the chip-free lever
ROADMAP item 5 names: a bounded (entries AND bytes) LRU result cache
keyed on content digests, with single-flight request coalescing so N
concurrent identical requests cost exactly ONE engine dispatch.

Key schema — every entry key is a tuple::

    (namespace..., input_digest)

where ``namespace`` identifies WHICH function would have computed the
result (the fleet uses ``(model_name, version, program_fingerprint)``;
a standalone :class:`~sparkdl_tpu.serving.server.Server` gets a
process-unique default so two servers sharing the process cache can
never serve each other's rows) and ``input_digest`` is the shared
:mod:`sparkdl_tpu.utils.digest` sha256 over the request payload's
dtype/shape/bytes — the same digest core ``streaming.source.
content_chunk_id`` has used since ISSUE 8, lifted into ``utils`` so
serving and streaming agree on what "same bytes" means.

Single-flight semantics (:meth:`InferenceCache.lookup`):

* **hit** — the stored value is returned as an independent copy, after
  an integrity re-check: the output digest recorded at insert time is
  recomputed over the copy, and a mismatch (bit rot, a buggy in-place
  mutation, the injected ``cache.hit`` corruption fault) invalidates
  the entry and demotes the call to a miss instead of serving a
  corrupt row.
* **leader** — the first requester of a missing key; it runs the real
  dispatch and MUST settle the flight: :meth:`InferenceCache.settle`
  inserts the value and resolves every parked follower with its own
  copy; :meth:`InferenceCache.fail` resolves the followers with the
  leader's error and caches NOTHING — a failed dispatch can never
  poison the cache.
* **follower** — a request for a key some leader is already computing;
  it parks on a future the leader's settle/fail resolves.  Followers
  cost zero engine dispatches — the coalescing contract the tier-1
  test pins (N concurrent identical requests -> exactly 1 dispatch).

Bounds: ``max_entries`` and ``max_bytes`` both cap the store (least
recently USED entries evicted first; an entry bigger than the whole
byte budget is served but never stored).  A cap of 0 on either axis
disables storage cleanly — lookups all become leaders, settle resolves
followers but inserts nothing.

Gate: ``SPARKDL_CACHE`` (the ``SPARKDL_FAULTS`` env pattern —
consulted once, on first use)::

    unset / "0" / "off"   -> no process-default cache (the default)
    "1" / "on"            -> process-default cache, default bounds
    "entries=N,mb=M"      -> process-default cache, custom bounds

The disabled path is one module-global read + identity check
(:func:`get_default` — same budget as ``faults.inject`` with no plan,
guarded by the run-tests.sh cache-overhead stage).

Fault sites: ``cache.hit`` fires inside the hit return path (an
injected error corrupts the copy handed back, which the digest
re-check must catch); ``cache.stampede`` fires on the leader's path in
``Server.submit`` (a sleep rule holds the leader's dispatch open so
follower pile-up is observable; an error rule is a leader failure the
followers must all see).  Flight events ``cache.hit`` / ``cache.miss``
/ ``cache.coalesced`` / ``cache.evict`` / ``cache.invalidate`` make
cache behavior visible on ``tools/blackbox.py`` incident timelines.
"""

from __future__ import annotations

import itertools
import os
from concurrent.futures import Future
from typing import Any, Dict, Hashable, List, Optional, Tuple

import numpy as np

from sparkdl_tpu.analysis.lockcheck import named_lock
from sparkdl_tpu.faults import inject
from sparkdl_tpu.faults.errors import InjectedFault
from sparkdl_tpu.obs.flight import emit as flight_emit
from sparkdl_tpu.utils.digest import content_digest
from sparkdl_tpu.utils.logging import get_logger
from sparkdl_tpu.utils.metrics import Metrics

logger = get_logger(__name__)

__all__ = [
    "InferenceCache",
    "CacheFlight",
    "lockfile_model_fingerprint",
    "get_default",
    "configure",
    "configure_from_env",
    "cache_from_env",
]

#: default bounds for an env-configured cache ("1"/"on", or omitted
#: keys in the "entries=N,mb=M" form)
DEFAULT_MAX_ENTRIES = 4096
DEFAULT_MAX_BYTES = 256 << 20

_OFF = ("", "0", "false", "off", "no")
_ON = ("1", "true", "on", "yes")


def _tree_copy(value: Any) -> Any:
    """Independent deep copy of an array pytree: a cached value handed
    to one caller must never alias the stored entry (or another
    caller's row) — a consumer mutating its result in place would
    otherwise corrupt every later hit."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: np.array(a, copy=True), value)


def _tree_nbytes(value: Any) -> int:
    import jax

    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in jax.tree_util.tree_leaves(value))


class CacheFlight:
    """One in-flight single-flight computation: the leader's token.

    Followers park on :class:`~concurrent.futures.Future` s the
    leader's :meth:`InferenceCache.settle` / :meth:`InferenceCache.
    fail` resolves.  Plain data — all mutation happens under the
    cache lock."""

    __slots__ = ("key", "followers", "done")

    def __init__(self, key: Tuple[Hashable, ...]):
        self.key = key
        self.followers: List[Future] = []
        self.done = False


class _Entry:
    __slots__ = ("value", "nbytes", "digest", "hits")

    def __init__(self, value: Any, nbytes: int, digest: str):
        self.value = value
        self.nbytes = nbytes
        self.digest = digest
        self.hits = 0


class InferenceCache:
    """Bounded content-addressed LRU result store + single-flight table.

    Thread model: one lock ("serving.cache", an
    ``analysis.lockcheck``-named lock) guards the entry dict, the byte
    ledger, and the flight table; value copies are made OUTSIDE the
    lock (entries are immutable once inserted), so the lock hold is
    O(1) bookkeeping even for megabyte rows.  Metrics ride the cache's
    own registry unless one is shared in (``cache.*`` counters +
    entry/byte gauges — surfaced by ``Server.varz()``/``Fleet.varz()``
    and the bench cache config)."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES,
                 max_bytes: int = DEFAULT_MAX_BYTES,
                 metrics: Optional[Metrics] = None):
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self.metrics = metrics if metrics is not None else Metrics()
        self._lock = named_lock("serving.cache")
        self._data: Dict[Tuple[Hashable, ...], _Entry] = {}
        self._bytes = 0
        self._flights: Dict[Tuple[Hashable, ...], CacheFlight] = {}

    # -- the request path --------------------------------------------------
    def lookup(self, key: Tuple[Hashable, ...]):
        """``("hit", value)`` | ``("follower", future)`` |
        ``("leader", flight)`` — see the module docstring.  A leader
        MUST later call :meth:`settle` or :meth:`fail` with its
        flight."""
        hit = self._probe(key)
        if hit is not None:
            return "hit", hit
        fut: Optional[Future] = None
        with self._lock:
            # re-probe under the lock: a leader may have settled between
            # the optimistic probe above and here
            entry = self._data.get(key)
            if entry is not None:
                self._data.pop(key)
                self._data[key] = entry  # MRU position
                entry.hits += 1
                stored, hits = entry.value, entry.hits
            else:
                flight = self._flights.get(key)
                if flight is not None:
                    fut = Future()
                    flight.followers.append(fut)
                    n_followers = len(flight.followers)
                else:
                    flight = CacheFlight(key)
                    self._flights[key] = flight
        if entry is not None:
            # settled-while-we-looked: serve it (skip the digest
            # re-check — the entry was inserted microseconds ago,
            # under the lock we just held)
            self.metrics.incr("cache.hits")
            flight_emit("cache.hit", hits=hits)
            return "hit", _tree_copy(stored)
        if fut is not None:
            self.metrics.incr("cache.coalesced")
            flight_emit("cache.coalesced", followers=n_followers)
            return "follower", fut
        self.metrics.incr("cache.misses")
        flight_emit("cache.miss")
        return "leader", flight

    def _probe(self, key: Tuple[Hashable, ...]) -> Optional[Any]:
        """Optimistic hit probe: an independent copy of the stored
        value after the integrity re-check, or None (absent OR the
        re-check demoted a corrupt entry to a miss)."""
        with self._lock:
            entry = self._data.get(key)
            if entry is None:
                return None
            self._data.pop(key)
            self._data[key] = entry  # MRU position
            entry.hits += 1
            stored, digest, hits, nbytes = (entry.value, entry.digest,
                                            entry.hits, entry.nbytes)
        value = _tree_copy(stored)
        corrupted = False
        try:
            # chaos hook: an error rule here stands in for bit rot / an
            # aliasing bug — the copy is corrupted and the digest
            # re-check below must catch it
            inject("cache.hit")
        except InjectedFault:
            corrupted = True
            self._corrupt_in_place(value)
        if content_digest(value) != digest:
            self.metrics.incr("cache.corruptions")
            logger.warning(
                "cache entry failed its output-digest re-check "
                "(injected=%s); invalidating and re-dispatching",
                corrupted)
            self.invalidate_key(key)
            return None  # demoted to a miss: the request re-computes
        self.metrics.incr("cache.hits")
        flight_emit("cache.hit", hits=hits, nbytes=nbytes)
        return value

    def settle(self, flight: CacheFlight, value: Any,
               store: bool = True) -> None:
        """Leader success: insert ``value`` (bounded; see class
        docstring) and resolve every follower with an independent
        copy.  ``store=False`` resolves the followers without
        inserting — how a leader that outlived its server's close()
        settles (its namespace was already reclaimed; inserting now
        would orphan the entry forever)."""
        stored = _tree_copy(value)
        nbytes = _tree_nbytes(stored)
        digest = content_digest(stored)
        evicted = []
        inserted = False
        with self._lock:
            followers = flight.followers
            flight.done = True
            if self._flights.get(flight.key) is flight:
                del self._flights[flight.key]
            if (store and self.max_entries > 0 and self.max_bytes > 0
                    and nbytes <= self.max_bytes):
                if flight.key in self._data:
                    old = self._data.pop(flight.key)
                    self._bytes -= old.nbytes
                while self._data and (
                        len(self._data) >= self.max_entries
                        or self._bytes + nbytes > self.max_bytes):
                    k = next(iter(self._data))  # LRU = oldest position
                    old = self._data.pop(k)
                    self._bytes -= old.nbytes
                    evicted.append((k, old.nbytes))
                self._data[flight.key] = _Entry(stored, nbytes, digest)
                self._bytes += nbytes
                inserted = True
            entries, total = len(self._data), self._bytes
        if inserted:
            self.metrics.incr("cache.inserts")
        self.metrics.gauge("cache.entries", entries)
        self.metrics.gauge("cache.bytes", total)
        for k, nb in evicted:
            self.metrics.incr("cache.evictions")
            flight_emit("cache.evict", nbytes=nb)
        for fut in followers:
            if not fut.done():
                fut.set_result(_tree_copy(value))

    def fail(self, flight: CacheFlight, exc: BaseException) -> None:
        """Leader failure: every follower sees the leader's error;
        NOTHING is cached — a failed dispatch must never poison the
        store for the retries that follow it."""
        with self._lock:
            followers = flight.followers
            flight.done = True
            if self._flights.get(flight.key) is flight:
                del self._flights[flight.key]
        self.metrics.incr("cache.leader_failures")
        for fut in followers:
            if not fut.done():
                fut.set_exception(exc)

    # -- direct get/put (the streaming replay path) ------------------------
    def get(self, key: Tuple[Hashable, ...]) -> Optional[Any]:
        """Plain probe without single-flight: the stored value as a
        copy (digest-re-checked like :meth:`lookup`), or None.  What
        ``StreamScorer`` uses at journal replay — replay is sequential,
        so there is no stampede to coalesce, and a probe must have NO
        side effects (no flight churn, no miss accounting for a path
        that was never going to dispatch through the cache)."""
        return self._probe(key)

    def put(self, key: Tuple[Hashable, ...], value: Any) -> None:
        """Direct insert (no flight): how the streaming runner records
        each scored chunk so a journal replay can skip the
        re-dispatch."""
        flight = CacheFlight(key)
        flight.done = True
        self.settle(flight, value)

    # -- invalidation ------------------------------------------------------
    def invalidate_key(self, key: Tuple[Hashable, ...]) -> int:
        with self._lock:
            entry = self._data.pop(key, None)
            if entry is not None:
                self._bytes -= entry.nbytes
            entries, total = len(self._data), self._bytes
        if entry is None:
            return 0
        self.metrics.incr("cache.invalidations")
        self.metrics.gauge("cache.entries", entries)
        self.metrics.gauge("cache.bytes", total)
        flight_emit("cache.invalidate", scope="key", entries=1)
        return 1

    def invalidate(self, namespace: Tuple[Hashable, ...]) -> int:
        """Drop every entry whose key starts with ``namespace`` — the
        hot-swap path: a promote whose program fingerprint (or weights)
        changed makes the old version's results unreachable AND wrong
        to keep charging the byte budget for."""
        ns = tuple(namespace)
        with self._lock:
            doomed = [k for k in self._data if k[:len(ns)] == ns]
            dropped = 0
            for k in doomed:
                entry = self._data.pop(k)
                self._bytes -= entry.nbytes
                dropped += 1
            entries, total = len(self._data), self._bytes
        if dropped:
            self.metrics.incr("cache.invalidations", dropped)
            self.metrics.gauge("cache.entries", entries)
            self.metrics.gauge("cache.bytes", total)
            flight_emit("cache.invalidate", scope="namespace",
                        entries=dropped)
        return dropped

    def adopt(self, old_namespace: Tuple[Hashable, ...],
              new_namespace: Tuple[Hashable, ...]) -> int:
        """Re-key every ``old_namespace`` entry under ``new_namespace``
        (LRU order preserved) — how entries SURVIVE a hot-swap when the
        promoted version provably computes the same function (unchanged
        ``PROGRAMS.lock.json`` fingerprint + identical weight bytes;
        see ``Fleet.promote``)."""
        old = tuple(old_namespace)
        new = tuple(new_namespace)
        if old == new:
            return 0
        moved = 0
        with self._lock:
            for k in [k for k in self._data if k[:len(old)] == old]:
                entry = self._data.pop(k)
                nk = new + k[len(old):]
                existing = self._data.pop(nk, None)
                if existing is not None:
                    # a post-flip request already settled this key under
                    # the new namespace (it raced the adopt): keep the
                    # fresher entry and release the old one's bytes —
                    # silently replacing would leak the byte ledger
                    self._bytes -= entry.nbytes
                    self._data[nk] = existing
                    continue
                self._data[nk] = entry
                moved += 1
        if moved:
            self.metrics.incr("cache.adopted", moved)
        return moved

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        return len(self._data)

    @property
    def total_bytes(self) -> int:
        return self._bytes

    def info(self) -> Dict[str, Any]:
        """JSON-serializable snapshot (the ``cache`` section of
        ``Server.varz()``/``Fleet.varz()`` and the bench line rider).

        ``counters`` always carries the feature-cut keys
        (``cache.feature_hits``/``cache.feature_requests``, zero when
        the deployment has no fan-out tier): ``HeadFanoutServer.varz()``
        merges its tier's counts over them, so BOTH server types expose
        the cache section under one schema and a dashboard query never
        branches on server type (ISSUE 18 satellite)."""
        with self._lock:
            entries = len(self._data)
            total = self._bytes
            inflight = len(self._flights)
        counters = {"cache.feature_hits": 0, "cache.feature_requests": 0}
        counters.update(
            {k: v for k, v in
             self.metrics.snapshot_raw()["counters"].items()
             if k.startswith("cache.")})
        return {
            "entries": entries,
            "bytes": total,
            "max_entries": self.max_entries,
            "max_bytes": self.max_bytes,
            "inflight_leaders": inflight,
            "counters": counters,
        }

    @staticmethod
    def _corrupt_in_place(value: Any) -> None:
        """Flip one byte of the first non-empty leaf — the injected
        ``cache.hit`` corruption the digest re-check must catch."""
        import jax

        for leaf in jax.tree_util.tree_leaves(value):
            a = np.asarray(leaf)
            if a.size:
                flat = a.view(np.uint8).reshape(-1)
                flat[0] ^= 0xFF
                return


# -- swap-survival fingerprints --------------------------------------------
def lockfile_model_fingerprint(model: str,
                               path: Optional[str] = None
                               ) -> Optional[str]:
    """The committed StableHLO identity of ``model``'s serving programs:
    sha256 over the sorted ``(program_name, fingerprint)`` pairs of
    every ``PROGRAMS.lock.json`` record whose ``model`` matches.  This
    is what makes "same computation" CHECKABLE chip-free at hot-swap
    time — the cache-survival analog of the fleet's no-recompile proof,
    pinned against the same committed lockfile.  None when the model
    has no audited programs (non-zoo fns): with no fingerprint there is
    no proof, so swaps conservatively invalidate."""
    import hashlib

    from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                       read_lockfile)

    path = path or DEFAULT_LOCKFILE
    if not os.path.isfile(path):
        return None
    try:
        doc = read_lockfile(path)
    except (ValueError, OSError):
        return None
    pairs = sorted(
        (name, rec.get("fingerprint", ""))
        for name, rec in doc.get("programs", {}).items()
        if rec.get("model") == model and rec.get("kind") == "dispatch")
    if not pairs:
        return None
    h = hashlib.sha256()
    for name, fp in pairs:
        h.update(f"{name}={fp}\n".encode())
    return h.hexdigest()


def feature_namespace(model_desc: str,
                      fingerprint: Optional[str],
                      weights_digest: str) -> Tuple[str, str, str, str]:
    """The FEATURE-CUT cache namespace (head fan-out tier, ISSUE 17):
    ``("features", model_desc, backbone_program_fingerprint,
    backbone_weights_digest)``.

    Keyed on the backbone's identity and NOTHING about the heads — a
    head add/swap/evict changes neither component, so feature entries
    stay warm across head churn (a hot content digest keeps paying the
    backbone zero times); a backbone WEIGHT change rotates
    ``weights_digest`` and a backbone PROGRAM change rotates the
    lockfile fingerprint, either of which moves the namespace so stale
    features can never serve.  ``fingerprint=None`` (no audited
    programs for this backbone) pins ``"unpinned"`` — the namespace
    still rotates on weight changes, it just carries no committed
    StableHLO identity."""
    return ("features", str(model_desc),
            fingerprint if fingerprint else "unpinned",
            str(weights_digest))


# -- module default (the faults.inject / SPARKDL_TRACE pattern) ------------
_UNSET = object()   # before the first ask consults SPARKDL_CACHE
_default: Any = _UNSET
_default_lock = named_lock("serving.cache.configure")


def cache_from_env() -> Optional[InferenceCache]:
    """An :class:`InferenceCache` per the ``SPARKDL_CACHE`` grammar
    (module docstring), or None when the knob is off/unset.  Raises on
    a malformed spec — a typo'd cache config must fail loudly, never
    degrade into an uncached run."""
    raw = os.environ.get("SPARKDL_CACHE", "").strip()
    low = raw.lower()
    if low in _OFF:
        return None
    if low in _ON:
        return InferenceCache()
    entries, max_bytes = DEFAULT_MAX_ENTRIES, DEFAULT_MAX_BYTES
    for pair in raw.split(","):
        pair = pair.strip()
        if not pair:
            continue
        if "=" not in pair:
            raise ValueError(f"bad SPARKDL_CACHE clause {pair!r}; grammar: "
                             f"0|1|entries=N,mb=M")
        k, v = (s.strip() for s in pair.split("=", 1))
        try:
            if k == "entries":
                entries = int(v)
            elif k == "mb":
                max_bytes = int(float(v) * (1 << 20))
            else:
                raise ValueError(f"unknown SPARKDL_CACHE key {k!r} "
                                 f"(known: entries, mb)")
        except ValueError as e:
            if "SPARKDL_CACHE" in str(e):
                raise
            raise ValueError(f"bad SPARKDL_CACHE value {pair!r}") from None
    return InferenceCache(max_entries=entries, max_bytes=max_bytes)


def get_default() -> Optional[InferenceCache]:
    """The process-default cache (resolving ``SPARKDL_CACHE`` on first
    ask), or None.  Disabled path: one module-global read + identity
    check — the budget the run-tests.sh cache-overhead stage guards.
    First-ask resolution is serialized under the configure lock so two
    servers constructed concurrently at startup can never each build
    (and hold) their own byte budget."""
    global _default
    c = _default
    if c is not _UNSET:
        return c
    with _default_lock:
        if _default is _UNSET:
            _default = cache_from_env()
        return _default


def configure(cache: Optional[InferenceCache]) -> Optional[InferenceCache]:
    """Install ``cache`` as the process default (None disables, and
    stops consulting the env until :func:`configure_from_env`)."""
    global _default
    with _default_lock:
        _default = cache
    return cache


def configure_from_env() -> Optional[InferenceCache]:
    """(Re-)configure the process default from ``SPARKDL_CACHE``."""
    return configure(cache_from_env())


_namespace_seq = itertools.count(1)  # next() is atomic in CPython


def unique_namespace(prefix: str) -> Tuple[str, str]:
    """A process-unique default namespace for a standalone consumer
    sharing the process-default cache: two servers that never declared
    a shared identity must never serve each other's rows."""
    return (prefix, f"anon-{next(_namespace_seq)}")


def example_digest(example: Any) -> str:
    """The request-payload digest ``Server.submit`` keys on (one shared
    spelling so tests and adapters can precompute keys)."""
    return content_digest(example)


def resolve_cache(cache: Any, namespace: Optional[Any] = None,
                  prefix: str = "server"
                  ) -> Tuple[Optional[InferenceCache],
                             Tuple[Hashable, ...], bool]:
    """The ONE constructor-side resolution rule ``Server``,
    ``StreamScorer``, and ``Fleet`` share: ``(cache, namespace,
    owned)``.

    ``cache=None`` resolves the ``SPARKDL_CACHE`` process default;
    ``cache=False`` forces uncached; an :class:`InferenceCache` passes
    through.  An explicit ``namespace`` is NOT owned (its lifecycle
    belongs to whoever assigned it — the fleet's swap/rollback paths);
    with none given, a live cache gets a process-unique anon namespace
    the consumer OWNS and must reclaim on close."""
    if cache is None:
        cache = get_default()
    elif cache is False:
        cache = None
    if namespace is not None:
        return cache, tuple(namespace), False
    if cache is not None:
        return cache, unique_namespace(prefix), True
    return None, (prefix,), False


def zipfian_cache_benchmark(n_requests: int = 160,
                            universe: int = 16,
                            zipf_s: float = 1.1,
                            dispatch_ms: float = 10.0,
                            seed: int = 0,
                            feature_dim: int = 16,
                            max_batch_size: int = 8,
                            max_entries: int = DEFAULT_MAX_ENTRIES,
                            max_bytes: int = DEFAULT_MAX_BYTES
                            ) -> Dict[str, Any]:
    """Deterministic chip-free proof of the cache's throughput lever
    (the ``synthetic_overlap_benchmark`` pattern: a sleep stands in for
    the device, so the result is stable on any host and needs no
    chip).

    A seeded Zipfian request replay — ``p(rank r) ∝ 1/r^zipf_s`` over
    ``universe`` distinct payloads, the repetitive-traffic shape
    ROADMAP item 5 describes — is served twice through a real
    :class:`~sparkdl_tpu.serving.server.Server` whose bucket engines
    are wrapped with a blocking ``dispatch_ms`` sleep: once uncached
    (every request pays a dispatch) and once through an
    :class:`InferenceCache` (only single-flight leaders do).  Because
    the replay is sequential and the cache holds the whole universe,
    the analytic hit floor is EXACT: every repeat of an already-served
    payload must hit, so ``hits >= n_requests - distinct``.  Outputs
    are verified bit-identical (``np.array_equal``) between the two
    passes before timings are reported — the cached path must be a
    pure latency optimization, never an approximation."""
    import time as _time

    from sparkdl_tpu.serving.server import Server

    rng = np.random.default_rng(seed)
    variables = {"w": rng.normal(
        size=(feature_dim, feature_dim)).astype(np.float32)}

    def fn(v, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ v["w"])

    payloads = [rng.normal(size=(feature_dim,)).astype(np.float32)
                for _ in range(universe)]
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    probs = ranks ** -float(zipf_s)
    probs /= probs.sum()
    seq = [int(i) for i in rng.choice(universe, size=n_requests, p=probs)]
    distinct = len(set(seq))
    analytic_hit_rate = (n_requests - distinct) / n_requests

    def build(cache):
        srv = Server(fn, variables, max_batch_size=max_batch_size,
                     max_wait_ms=0.5, max_queue=n_requests + 16,
                     cache=cache)
        srv.warmup(payloads[0])  # compile BEFORE the sleep wrap below
        calls = [0]
        for b in srv.bucket_sizes:
            eng = srv._engine_for(b)
            real = eng.run_padded

            def slow(batch, _real=real):  # the synthetic slow device
                calls[0] += 1
                _time.sleep(dispatch_ms / 1e3)
                return _real(batch)

            eng.run_padded = slow
        return srv, calls

    srv, calls = build(cache=False)
    t0 = _time.perf_counter()
    uncached_out = [srv.predict(payloads[i]) for i in seq]
    uncached_s = _time.perf_counter() - t0
    uncached_dispatches = calls[0]
    srv.close()

    cache = InferenceCache(max_entries=max_entries, max_bytes=max_bytes)
    srv, calls = build(cache=cache)
    t0 = _time.perf_counter()
    cached_out = [srv.predict(payloads[i]) for i in seq]
    cached_s = _time.perf_counter() - t0
    cached_dispatches = calls[0]
    # snapshot occupancy BEFORE close(): the server owns its anon
    # namespace and close() reclaims it from the store
    cache_entries, cache_bytes = len(cache), cache.total_bytes
    srv.close()

    bit_identical = all(np.array_equal(a, b)
                        for a, b in zip(uncached_out, cached_out))
    counters = cache.metrics.snapshot_raw()["counters"]
    hits = counters.get("cache.hits", 0.0)
    return {
        "n_requests": n_requests,
        "universe": universe,
        "zipf_s": zipf_s,
        "distinct": distinct,
        "dispatch_ms": dispatch_ms,
        "uncached_s": round(uncached_s, 4),
        "cached_s": round(cached_s, 4),
        "speedup": round(uncached_s / cached_s, 4),
        "hit_rate": round(hits / n_requests, 4),
        "analytic_hit_rate": round(analytic_hit_rate, 4),
        "hits": int(hits),
        "misses": int(counters.get("cache.misses", 0.0)),
        "uncached_dispatches": uncached_dispatches,
        "cached_dispatches": cached_dispatches,
        "bit_identical": bit_identical,
        "cache_entries": cache_entries,
        "cache_bytes": cache_bytes,
    }


def head_fanout_benchmark(n_requests: int = 160,
                          universe: int = 16,
                          tenants: int = 64,
                          zipf_s: float = 1.1,
                          dispatch_ms: float = 10.0,
                          seed: int = 0,
                          max_batch_size: int = 8
                          ) -> Dict[str, Any]:
    """Deterministic chip-free proof of the shared-backbone fan-out
    tier (ISSUE 17) — the headline replay the tests assert and the
    ``headfanout`` bench config stamps.

    A seeded Zipf-content, ``tenants``-tenant replay is served through
    a :class:`~sparkdl_tpu.serving.server.HeadFanoutServer` whose
    backbone engines are wrapped with a blocking ``dispatch_ms`` sleep
    (the synthetic slow device — the same trick as
    :func:`zipfian_cache_benchmark`, so the result is stable on any
    host):

    * FULL-MODEL BASELINE: the same replay through an UNCACHED fan-out
      server — every request pays the backbone sleep, the per-request
      p50/p99 of a model-copy-per-tenant deployment;
    * COLD PASS (feature cache on, empty): the replay is sequential,
      so single-flight makes the floor exact — backbone dispatches MUST
      equal the number of distinct content digests (the "featurize
      once" claim, asserted here, not just reported);
    * WARM PASS: the replay again — ZERO further backbone dispatches,
      and the per-request p50/p99 is head-milliseconds only.

    Every output row (all three passes) is verified BIT-identical to
    an INDEPENDENT per-tenant full-model oracle
    (``parallel.engine.head_fanout_oracle_fn``, jitted on its own, one
    unbatched row at a time) before timings are reported: the fan-out
    tier must be a pure cost optimization, never an approximation."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.parallel.engine import (head_fanout_backbone_fn,
                                             head_fanout_oracle_fn)
    from sparkdl_tpu.serving.server import HeadFanoutServer

    d_in, d_feat, classes = 12, 16, 4
    rng = np.random.default_rng(seed)
    variables = {"backbone": rng.normal(
        size=(d_in, d_feat)).astype(np.float32)}
    heads = {f"t{i:03d}": {
        "kernel": rng.normal(size=(d_feat, classes)).astype(np.float32),
        "bias": rng.normal(size=(classes,)).astype(np.float32),
    } for i in range(tenants)}
    payloads = [rng.normal(size=(d_in,)).astype(np.float32)
                for _ in range(universe)]
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    probs = ranks ** -float(zipf_s)
    probs /= probs.sum()
    seq = [(int(c), f"t{int(t):03d}") for c, t in zip(
        rng.choice(universe, size=n_requests, p=probs),
        rng.integers(0, tenants, size=n_requests))]
    distinct = len({c for c, _ in seq})

    # no donation: the oracle reuses its weights for every row
    oracle = jax.jit(head_fanout_oracle_fn, donate_argnums=())

    def oracle_row(content: int, tenant: str) -> np.ndarray:
        h = heads[tenant]
        return np.asarray(oracle(
            {"backbone": variables["backbone"], **h},
            jnp.asarray(payloads[content])))

    def build(cache):
        srv = HeadFanoutServer(
            head_fanout_backbone_fn, variables, model_desc="headfanout",
            cache=cache, max_batch_size=max_batch_size, max_wait_ms=0.5,
            max_queue=n_requests + 16)
        for t, h in heads.items():
            srv.add_head(t, h)
        srv.warmup(payloads[0])  # compile BEFORE the sleep wrap below
        srv.warm_head(np.zeros(d_feat, np.float32))
        calls = [0]
        for b in srv.bucket_sizes:
            eng = srv.backbone._engine_for(b)
            real = eng.run_padded

            def slow(batch, _real=real):  # the synthetic slow device
                calls[0] += 1
                _time.sleep(dispatch_ms / 1e3)
                return _real(batch)

            eng.run_padded = slow
        return srv, calls

    def replay(srv):
        lat, out = [], []
        for content, tenant in seq:
            t0 = _time.perf_counter()
            y = srv.predict(payloads[content], tenant)
            lat.append(_time.perf_counter() - t0)
            out.append(np.asarray(y))
        return lat, out

    def pcts(lat):
        return (round(float(np.percentile(lat, 50)) * 1e3, 3),
                round(float(np.percentile(lat, 99)) * 1e3, 3))

    # full-model baseline: no feature cache, every request pays the
    # backbone — the per-tenant-model-copy cost shape
    srv, calls = build(cache=False)
    base_lat, base_out = replay(srv)
    baseline_dispatches = calls[0]
    srv.close()

    cache = InferenceCache()
    srv, calls = build(cache=cache)
    _, cold_out = replay(srv)
    cold_dispatches = calls[0]
    # THE headline identity: sequential replay + single-flight means a
    # hot content digest pays the backbone exactly once EVER
    if cold_dispatches != distinct:
        raise AssertionError(
            f"backbone dispatched {cold_dispatches} times for "
            f"{distinct} distinct content digests")
    warm_lat, warm_out = replay(srv)
    if calls[0] != cold_dispatches:
        raise AssertionError(
            f"warm replay re-dispatched the backbone "
            f"({calls[0] - cold_dispatches} extra)")
    snap = srv.metrics.snapshot_raw()["counters"]
    feature_hits = int(snap.get("headfanout.feature_hits", 0))
    bank = srv.head_stats()
    srv.close()

    bit_identical = all(
        np.array_equal(y, oracle_row(c, t))
        for outs in (base_out, cold_out, warm_out)
        for (c, t), y in zip(seq, outs))
    base_p50, base_p99 = pcts(base_lat)
    warm_p50, warm_p99 = pcts(warm_lat)
    return {
        "n_requests": n_requests,
        "universe": universe,
        "tenants": tenants,
        "zipf_s": zipf_s,
        "distinct": distinct,
        "dispatch_ms": dispatch_ms,
        "backbone_dispatches": cold_dispatches,
        "baseline_dispatches": baseline_dispatches,
        "dispatch_ratio": round(cold_dispatches / distinct, 4),
        "baseline_p50_ms": base_p50,
        "baseline_p99_ms": base_p99,
        "warm_p50_ms": warm_p50,
        "warm_p99_ms": warm_p99,
        "p50_reduction": round(1.0 - warm_p50 / base_p50, 4),
        "feature_hits": feature_hits,
        "bank_param_bytes_per_chip": bank.get("param_bytes_per_chip"),
        "bank_capacity": bank.get("capacity"),
        "bank_mode": bank.get("mode"),
        "bit_identical": bit_identical,
    }
