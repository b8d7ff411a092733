"""Persistent XLA compilation cache keyed on the program lockfile.

Every fleet deploy and serving cold-start re-jits each bucket's
dispatch program from scratch — seconds per bucket for real models,
paid again on every process restart even though ``PROGRAMS.lock.json``
proves the programs have not changed since the last audit.  This
module wires JAX's persistent compilation cache (an on-disk executable
store, content-addressed by the compiled program) under a
``SPARKDL_COMPILE_CACHE`` gate and adds the lockfile keying the raw
jax knob lacks: the cache directory carries a manifest recording the
committed lockfile's program records (StableHLO fingerprints, dtype
mixes, donation maps, ...), and a manifest that no longer matches the
live lockfile invalidates the population CLEANLY — stale entries are
purged before a single executable is served, and the drift is
classified back to the graftcheck rule whose invariant moved
(:func:`~sparkdl_tpu.analysis.program.lockfile.diff_records` — a
dropped donation is GC001, an f32 upcast is GC002, and so on), so an
operator reading the ``compile.invalidate`` flight event knows WHY the
cold-start got slow again.

Where the cache lives, in order:
  * ``JAX_COMPILATION_CACHE_DIR`` set — the installation PLACED the
    cache: that directory is the cache for every entry point, JAX
    already reads it, and no code here points JAX anywhere else (the
    hit/miss listener and the persist-everything thresholds still
    apply there).  A placed directory may be shared with other
    checkouts and other commits, so this module OWNS nothing in it: it
    keeps no manifest there (two commits would rewrite each other's on
    every start and report a cold start that is not one), deletes
    nothing, and leaves staleness to JAX's own content-addressed key.
  * else ``SPARKDL_COMPILE_CACHE`` (the ``SPARKDL_BLACKBOX`` grammar):
    ``""``/``0``/``false``/``off``/``no`` — DISABLED (the library
    default: nothing about compilation changes, and the per-engine
    probe is one module-global read); ``1``/``true``/``on``/``yes`` —
    enabled at :data:`DEFAULT_DIR`; anything else — a cache DIRECTORY
    this module owns (manifest, purge on drift), which is what tells
    it from a placed one.
  * the repo's own entry points (``chip_smoke.py``, ``bench.py``,
    ``tools/``) call :func:`configure_default`, which falls back to
    :data:`DEFAULT_DIR` — ONE fixed, git-ignored directory inside the
    checkout, never a temporary name, a pid or a timestamp, so two
    runs of one checkout always meet in the same place.

Resolution is the faults-pattern process singleton: the first
:class:`~sparkdl_tpu.parallel.engine.InferenceEngine` construction
(or an entry point's :func:`configure_default` before it) consults the
env exactly once (:func:`ensure_from_env`, serialized
under the configure lock) and every later engine sees the resolved
state.  Configuring NEVER initialises a JAX backend: ``bench.py``
configures the cache and then starts CPU-pinned children, which it may
only do while it does not hold the chip (the platform is part of JAX's
own cache key, so the manifest does not record it).  Configuration
failures — unwritable directory, corrupt
manifest, the injected ``compile.cache`` fault — degrade to DISABLED
(fresh compiles, a warning, never a serving outage): the cache is an
optimization, not a dependency.

Hit/miss accounting rides ``jax.monitoring``'s compilation-cache
events into :func:`stats`, which is what the cross-process proof in
run-tests.sh / tests asserts: process A compiles and populates, and a
restarted process B serving the same lockfile-pinned programs reports
ZERO fresh compiles (``misses == 0``) with bit-identical outputs; a
tampered manifest fingerprint forces a purge + clean recompile instead
of ever serving a stale executable.

Compile seconds ride the same ``jax.monitoring`` (ISSUE 39), with the
cache on or off: every program this process traces, lowers and compiles
(or loads) adds its seconds to :func:`stats` (``trace_s``, ``lower_s``,
``backend_s``; ``load_s`` and ``saved_s`` on a hit) and, where the
tracer is on, leaves three closed spans ``compile.trace``,
``compile.lower`` and ``compile.backend`` with ``program=<fun_name>``
under whatever span the compiling thread has open (``engine.dispatch``,
``engine.build``; none for a jit the caller made itself).
``compile.backend`` says how the executable came: ``cache`` =
``"hit"`` (with ``load_s``, the retrieval, and ``saved_s``, the compile
the entry's writer paid less the retrieval), ``"miss"`` (XLA compiled
and the cache took the executable) or ``"off"`` (no cache took part).
JAX reports a phase when it ends, on the thread that called the jitted
function, and traces every jitted function it meets under a program's
trace too: only the outermost phase of a thread counts, the others'
seconds are inside it.

Sharing contract (ISSUE 14), for a directory this module chose (not a
placed one): one cache directory serves ONE deployment configuration.
The manifest's ``sharding_policies`` set accumulates every engine
policy the deployment's processes note (restart-order-independent
reuse), but a process whose FIRST policy
the set has never held purges the whole population — so two
*unrelated* deployments with different sharding policies pointing at
the same directory would purge each other's executables on every
cold start.  Give them separate directories.  ``note_policy``'s
manifest union is atomic per write but not cross-process-locked: two
processes adding different NEW policies at the same instant can drop
one addition, which costs at most one later purge + repopulation,
never a stale executable.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple, Union

from sparkdl_tpu.analysis.lockcheck import named_lock
from sparkdl_tpu.faults import inject
from sparkdl_tpu.obs.flight import emit as flight_emit
from sparkdl_tpu.obs.trace import get_tracer
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = [
    "MANIFEST_NAME",
    "DEFAULT_DIR",
    "PLACED_DIR_ENV",
    "configure",
    "configure_default",
    "ensure_from_env",
    "state",
    "stats",
    "enabled",
]

#: the lockfile-keyed manifest written next to jax's cache entries;
#: upper-cased so it can never collide with a jax ``jit_*`` entry name
MANIFEST_NAME = "SPARKDL_COMPILE_CACHE_MANIFEST.json"
MANIFEST_SCHEMA = 1

#: the fixed in-checkout cache directory (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")

#: JAX's own variable: where it is set, the installation placed the cache
PLACED_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_OFF = ("", "0", "false", "off", "no")
_ON = ("1", "true", "on", "yes")

# -- process singleton (the faults.inject / serving.cache pattern) ---------
_UNSET = object()
_state: Any = _UNSET    # None = disabled; dict = the resolved snapshot
_lock = named_lock("parallel.compile_cache")
_ZERO_COUNTS = {"hits": 0, "misses": 0, "trace_s": 0.0, "lower_s": 0.0,
                "backend_s": 0.0, "load_s": 0.0, "saved_s": 0.0}
_counts = dict(_ZERO_COUNTS)
#: programs compile on whichever thread first calls them (the pipeline's
#: dispatch and gather threads, a server's workers): ``+=`` is not atomic
_counts_lock = named_lock("parallel.compile_cache.counts")
_listener = [False]
#: ``jax.monitoring``'s compile phases -> the span ``compile.<phase>``
#: and the counter ``<phase>_s``
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
#: per compiling thread: ``depth`` (compile phases open), and what the
#: cache said of the backend phase that is open (``cache``, ``load_s``,
#: ``saved_s``), kept until that phase reports
_compiling = threading.local()
#: JAX's own cache directory from before this module first re-pointed it
#: (at most one element): what ``_reset_for_tests`` puts back
_jax_dir_before: List[Optional[str]] = []


def _resolve_env() -> Tuple[Optional[str], bool]:
    """``(directory, placed)`` the environment asks for (module
    docstring): the placed one, else per the ``SPARKDL_COMPILE_CACHE``
    grammar, else ``(None, False)`` (off).  The ONE place the two
    variables are read."""
    placed = os.environ.get(PLACED_DIR_ENV, "").strip()
    if placed:
        return placed, True
    raw = os.environ.get("SPARKDL_COMPILE_CACHE", "").strip()
    low = raw.lower()
    if low in _OFF:
        return None, False
    if low in _ON:
        return DEFAULT_DIR, False
    return os.path.expanduser(raw), False


def _install_listener() -> None:
    """Count jax's compile and compilation-cache monitoring events into
    :func:`stats` and, where the tracer is on, into ``compile.*`` spans
    (module docstring).  Registered once a process, with the persistent
    cache on or off: the events fire when something compiles and never
    per dispatch, so an idle listener costs nothing."""
    if _listener[0]:
        return
    import jax.monitoring as monitoring

    def _add(key: str, amount: Union[int, float]) -> None:
        with _counts_lock:
            _counts[key] += amount

    def _count(name: str, **kwargs: Any) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _add("hits", 1)
            _compiling.cache = "hit"
        elif name == "/jax/compilation_cache/cache_misses":
            _add("misses", 1)
            _compiling.cache = "miss"

    def _begin(name: str, value: Any, **kwargs: Any) -> None:
        # jax stamps a phase's start as a scalar of the phase's name
        if name in _PHASES:
            said = _compiling.__dict__
            said["depth"] = said.get("depth", 0) + 1

    def _seconds(name: str, seconds: float, **kwargs: Any) -> None:
        phase = _PHASES.get(name)
        if phase is None:
            if name == _RETRIEVAL:
                _add("load_s", seconds)
                _compiling.load_s = seconds
            elif name == _SAVED:
                _add("saved_s", seconds)
                _compiling.saved_s = seconds
            return
        said = _compiling.__dict__
        depth = said["depth"] = max(0, said.get("depth", 1) - 1)
        attrs = {}
        if phase == "backend":      # what the cache said of it ends here
            attrs["cache"] = said.pop("cache", "off")
            load_s, saved_s = said.pop("load_s", 0.0), said.pop("saved_s", 0.0)
            if attrs["cache"] == "hit":
                attrs.update(load_s=load_s, saved_s=saved_s)
        if depth:
            # a jitted function met under a program's trace (or traced
            # by a lowering rule): its seconds are inside the outer's
            return
        _add(phase + "_s", seconds)
        # the function's own name in all three: jax wraps it, ``jit(f)``,
        # once it is lowered
        program = kwargs.get("fun_name", "")
        if program.endswith(")"):
            program = program[program.find("(") + 1:-1]
        # looked up at the event: a later configure() replaces the tracer
        get_tracer().record("compile." + phase, seconds, program=program,
                            **attrs)

    monitoring.register_event_listener(_count)
    monitoring.register_scalar_listener(_begin)
    monitoring.register_event_duration_secs_listener(_seconds)
    _listener[0] = True


def _norm(value: Any) -> Any:
    return json.loads(json.dumps(value, sort_keys=True))


def _purge(dir_path: str) -> int:
    """Drop every cache entry (the manifest is rewritten by the caller)
    so nothing stale can ever be served after an invalidation; returns
    the number of entries removed."""
    removed = 0
    for name in os.listdir(dir_path):
        if name == MANIFEST_NAME:
            continue
        try:
            os.unlink(os.path.join(dir_path, name))
            removed += 1
        except OSError:
            logger.warning("compile cache: could not purge stale entry "
                           "%s", name)
            raise  # a stale executable we cannot remove must disable
    return removed


def _validate_manifest(dir_path: str,
                       lockfile_path: Optional[str],
                       policy: Optional[str] = None
                       ) -> Tuple[Dict[str, Any], List[Tuple[str, dict]]]:
    """Compare the cache directory's manifest against the live
    committed lockfile AND the process's mesh/partition-rule policy
    (ISSUE 14 — ``InferenceEngine.compile_policy()``); purge + classify
    on drift.  The manifest records the SET of policies the populating
    deployment's engines used (``sharding_policies`` — every engine
    notes its policy via :func:`note_policy`, so a fleet mixing
    sharded and replicated entries reuses across restarts regardless
    of engine-construction order); a restart whose first policy is NOT
    in the stored set — same programs, different weight sharding —
    purges cleanly, classified GC005 (sharding layout changed),
    instead of serving/accumulating executables compiled for a layout
    this deployment no longer uses.  ``policy=None`` (test/CLI
    configures) is a wildcard: it never invalidates a populated set.
    Only ever called on a directory this module owns (never a placed
    one), and never touches a JAX backend.  Returns the state fields and
    the flight events to emit AFTER the configure lock is released (the
    recorder never runs under the locks it observes)."""
    import jax

    from sparkdl_tpu.analysis.program.lockfile import (DEFAULT_LOCKFILE,
                                                       diff_records,
                                                       read_lockfile)

    lock_path = lockfile_path or DEFAULT_LOCKFILE
    programs: Dict[str, Any] = {}
    if os.path.isfile(lock_path):
        programs = read_lockfile(lock_path).get("programs", {})
    manifest_path = os.path.join(dir_path, MANIFEST_NAME)
    # no platform here: asking JAX for it would initialise the backend
    # (and take the chip), and JAX's own cache key already carries it
    env = {"jax_version": jax.__version__}
    reused = False
    invalidated = False
    drift_rules: List[str] = []
    purged = 0
    events: List[Tuple[str, dict]] = []
    policies: List[str] = [policy] if policy else []
    if os.path.isfile(manifest_path):
        stored: Optional[Dict[str, Any]] = None
        try:
            with open(manifest_path, "r", encoding="utf-8") as fh:
                stored = json.load(fh)
        except (OSError, json.JSONDecodeError):
            stored = None  # corrupt manifest == unprovable population
        stored_policies = (list(stored.get("sharding_policies") or [])
                           if stored is not None else [])
        policy_ok = policy is None or policy in stored_policies
        if (stored is not None
                and stored.get("schema_version") == MANIFEST_SCHEMA
                and stored.get("jax_version") == env["jax_version"]
                and policy_ok
                and _norm(stored.get("programs", {})) == _norm(programs)):
            reused = True
            policies = sorted(set(stored_policies)
                              | ({policy} if policy else set()))
        else:
            invalidated = True
            if stored is not None and isinstance(
                    stored.get("programs"), dict):
                current = [{"name": n, **rec}
                           for n, rec in sorted(programs.items())]
                findings = diff_records(
                    {"programs": stored["programs"]}, current)
                drift_rules = sorted({f.code for f in findings})
                if not drift_rules and not policy_ok:
                    # same programs, different weight-sharding policy:
                    # the executables were compiled for layouts this
                    # deployment no longer uses
                    drift_rules = ["GC005"]
            purged = _purge(dir_path)
            events.append(("compile.invalidate", {
                "dir": dir_path, "purged_entries": purged,
                "drift_rules": drift_rules or ["manifest"],
            }))
            logger.warning(
                "persistent compile cache at %s invalidated: %s; purged "
                "%d stale entries (fresh compiles ahead)", dir_path,
                (f"lockfile drift classified {drift_rules}"
                 if drift_rules else "unreadable/foreign manifest"),
                purged)
    doc = {"schema_version": MANIFEST_SCHEMA, **env,
           "sharding_policies": policies, "programs": programs}
    tmp = manifest_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, sort_keys=True)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, manifest_path)
    fields = {"reused": reused, "invalidated": invalidated,
              "drift_rules": drift_rules, "purged_entries": purged,
              "lockfile_programs": len(programs),
              "sharding_policy": policy,
              "sharding_policies": policies, **env}
    events.append(("compile.persist", {
        "dir": dir_path, "reused": reused,
        "lockfile_programs": len(programs)}))
    return fields, events


def _configure_locked(dir_path: Optional[str],
                      lockfile_path: Optional[str],
                      policy: Optional[str] = None,
                      placed: bool = False
                      ) -> Tuple[Optional[Dict[str, Any]],
                                 List[Tuple[str, dict]]]:
    """Resolve the cache state (called under the configure lock);
    returns (state, flight events to emit after release).  ``placed``:
    ``dir_path`` is the one JAX already read from
    :data:`PLACED_DIR_ENV` — no manifest, no purge, no re-pointing.
    Any failure degrades to DISABLED — the cache must never take down
    serving (an entry point that needs it checks for the ``None``)."""
    _install_listener()     # compile seconds count with the cache off too
    if dir_path is None:
        return None, []
    try:
        # chaos hook: an injected error here is a corrupt cache
        # dir/manifest the configure path must absorb (degrade to
        # fresh compiles), never propagate into engine construction
        inject("compile.cache")
        os.makedirs(dir_path, exist_ok=True)
        if placed:
            # no manifest: whether an earlier population is reused
            # here is JAX's to know
            fields = {"reused": None, "invalidated": False}
            events = [("compile.persist", {"dir": dir_path, "reused": None,
                                           "placed": True})]
        else:
            fields, events = _validate_manifest(dir_path, lockfile_path,
                                                policy)
        import jax

        jax.config.update("jax_enable_compilation_cache", True)
        if not placed:  # a placed directory is already JAX's own
            if not _jax_dir_before:
                _jax_dir_before.append(jax.config.jax_compilation_cache_dir)
            jax.config.update("jax_compilation_cache_dir", dir_path)
        # cold-start elimination wants EVERY dispatch program persisted,
        # not only the slow-to-compile ones jax's defaults target
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        return {"dir": dir_path, "placed": placed, **fields}, events
    # graftlint: allow=SDL003 reason=the cache is an optimization: any configure failure (unwritable dir, corrupt manifest, injected fault) is logged and degrades to fresh compiles
    except Exception as e:  # noqa: BLE001
        logger.warning("persistent compile cache disabled: %s: %s "
                       "(serving continues with fresh compiles)",
                       type(e).__name__, e)
        return None, []


def configure(dir_path: Optional[str],
              lockfile_path: Optional[str] = None,
              policy: Optional[str] = None,
              placed: bool = False) -> Optional[Dict[str, Any]]:
    """Install (or disable, with ``None``) the persistent compile cache
    at ``dir_path`` — that directory, whatever the environment says —
    validating its manifest against ``lockfile_path`` (default: the
    committed ``PROGRAMS.lock.json``) and the process's
    mesh/partition-rule ``policy`` (ISSUE 14; ``None`` = no policy
    recorded — a later engine-driven configure with a real policy
    invalidates such a manifest once, classified GC005).  ``placed``
    is for the two callers that resolved the environment
    (:func:`configure_default`, :func:`ensure_from_env`)."""
    global _state
    with _lock:
        st, events = _configure_locked(dir_path, lockfile_path, policy,
                                       placed)
        _state = st
    for name, attrs in events:
        flight_emit(name, **attrs)
    return st


def configure_default() -> Optional[Dict[str, Any]]:
    """What the repo's own entry points call before they compile: the
    cache the environment asks for, else the fixed in-checkout
    :data:`DEFAULT_DIR`."""
    dir_path, placed = _resolve_env()
    return configure(dir_path or DEFAULT_DIR, placed=placed)


def ensure_from_env(policy: Optional[str] = None
                    ) -> Optional[Dict[str, Any]]:
    """The per-engine probe: resolve the environment exactly
    once per process (first engine construction), then one
    module-global read (plus a policy-set membership check) forever
    after.  Every engine passes its ``compile_policy()`` string: the
    first one validates the manifest against the stored policy SET,
    and later engines with NEW policies join the set via
    :func:`note_policy` — so a deployment mixing sharded and
    replicated engines reuses across restarts regardless of which
    engine constructs first, while a policy the deployment never used
    still purges."""
    global _state
    st = _state
    if st is not _UNSET:
        if policy is not None:
            note_policy(policy)
        return _state if isinstance(_state, dict) else None
    with _lock:
        if _state is _UNSET:
            dir_path, placed = _resolve_env()
            st, events = _configure_locked(dir_path, None, policy, placed)
            _state = st
        else:
            st, events = _state, []
    for name, attrs in events:
        flight_emit(name, **attrs)
    if policy is not None:
        note_policy(policy)
    return _state if isinstance(_state, dict) else None


def note_policy(policy: str) -> None:
    """Record one engine's mesh/partition policy in the manifest's
    policy SET (no purge — adding a layout to a live deployment only
    widens what a restart may reuse).  No-op while disabled, in a
    placed directory (no manifest) or when the policy is already
    recorded (the per-engine fast path)."""
    global _state

    def noted(st: Any) -> bool:
        return (not isinstance(st, dict) or st["placed"]
                or policy in st["sharding_policies"])

    if noted(_state):
        return
    with _lock:
        st = _state
        if noted(st):
            return
        manifest_path = os.path.join(st["dir"], MANIFEST_NAME)
        try:
            with open(manifest_path, "r", encoding="utf-8") as fh:
                doc = json.load(fh)
            policies = sorted(set(doc.get("sharding_policies") or [])
                              | {policy})
            doc["sharding_policies"] = policies
            tmp = manifest_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, sort_keys=True)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, manifest_path)
            _state = dict(st, sharding_policies=policies)
        except (OSError, json.JSONDecodeError) as e:
            logger.warning(
                "compile cache: could not record sharding policy in "
                "manifest (%s: %s); a restart constructing this "
                "layout's engine first will purge once",
                type(e).__name__, e)


def state() -> Optional[Dict[str, Any]]:
    """The resolved cache state (None while disabled/unresolved) —
    JSON-serializable; bench lines and the subprocess proof read it."""
    st = _state
    return dict(st) if isinstance(st, dict) else None


def stats() -> Dict[str, Union[int, float]]:
    """This process's compile counters (jax.monitoring events).
    ``hits``/``misses`` of the persistent cache: a warm restart serving
    lockfile-pinned programs shows ``misses == 0`` — the
    zero-fresh-compiles proof.  And the seconds every compile took,
    cache on or off, by phase: ``trace_s``, ``lower_s``, ``backend_s``
    (XLA's compile on a miss, the cache's retrieval on a hit), with
    ``load_s`` (retrieval alone) and ``saved_s`` (what the hits spared,
    by JAX's own reckoning) beside them."""
    with _counts_lock:
        return dict(_counts)


def enabled() -> bool:
    return isinstance(_state, dict)


def _reset_for_tests() -> None:
    """Forget the resolved state (tests re-resolve under a different
    env); where a configure pointed JAX at a directory of the module's
    own, JAX goes back to the one it had, so later engines in this
    process stop persisting there."""
    global _state
    with _lock:
        _state = _UNSET
        with _counts_lock:
            _counts.update(_ZERO_COUNTS)
        if _jax_dir_before:
            import jax

            jax.config.update("jax_compilation_cache_dir",
                              _jax_dir_before.pop())
