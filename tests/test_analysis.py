"""graftlint: static-analysis rules, pragmas, registry, and the runtime
lock-order checker (ISSUE 5).

Everything here is stdlib-fast (in-memory fixture snippets, no jax
work): the whole file must stay in the low single-digit seconds —
tier-1 runs at ~85-90% of the driver's wall budget.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading

import pytest

from sparkdl_tpu.analysis import (RULE_HELP, lint_paths, lint_source,
                                  load_site_registry, lockcheck)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SITES_FIXTURE = {"engine.dispatch", "io.decode"}


def codes(src: str, sites=None) -> list:
    return [f.code for f in lint_source(
        src, sites=SITES_FIXTURE if sites is None else sites)]


# ---------------------------------------------------------------------------
# SDL001 — thread lifecycle
# ---------------------------------------------------------------------------

def test_sdl001_unjoined_thread_fires():
    src = (
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n")
    assert codes(src) == ["SDL001"]


def test_sdl001_daemon_and_joined_pass():
    daemon = (
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=print, daemon=True)\n"
        "    t.start()\n")
    joined = (
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n"
        "    try:\n"
        "        pass\n"
        "    finally:\n"
        "        t.join(timeout=2.0)\n")
    assert codes(daemon) == []
    assert codes(joined) == []


def test_sdl001_self_attr_joined_in_other_method_passes():
    src = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=print)\n"
        "        self._t.start()\n"
        "    def close(self):\n"
        "        self._t.join()\n")
    assert codes(src) == []


def test_sdl001_thread_pool_list_joined_in_loop_passes():
    src = (
        "import threading\n"
        "def f():\n"
        "    ts = [threading.Thread(target=print),\n"
        "          threading.Thread(target=print)]\n"
        "    for t in ts:\n"
        "        t.start()\n"
        "    for t in ts:\n"
        "        t.join()\n")
    assert codes(src) == []
    comp = (
        "import threading\n"
        "def f():\n"
        "    ts = [threading.Thread(target=print) for _ in range(3)]\n"
        "    for t in ts:\n"
        "        t.join()\n")
    assert codes(comp) == []
    unjoined_pool = (
        "import threading\n"
        "def f():\n"
        "    ts = [threading.Thread(target=print)]\n"
        "    for t in ts:\n"
        "        t.start()\n")
    assert codes(unjoined_pool) == ["SDL001"]


def test_sdl001_unbound_thread_and_timer_fire():
    assert codes("import threading\n"
                 "threading.Thread(target=print).start()\n"
                 ) == ["SDL001"]
    assert codes("import threading\n"
                 "def f(cb):\n"
                 "    threading.Timer(1.0, cb).start()\n"
                 ) == ["SDL001"]


# ---------------------------------------------------------------------------
# SDL002 — lockset discipline
# ---------------------------------------------------------------------------

_SDL002_BAD = (
    "import threading\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.n = 0\n"
    "    def incr(self):\n"
    "        with self._lock:\n"
    "            self.n += 1\n"
    "    def reset(self):\n"
    "        self.n = 0\n")


def test_sdl002_unlocked_write_fires():
    found = lint_source(_SDL002_BAD, sites=SITES_FIXTURE)
    assert [f.code for f in found] == ["SDL002"]
    assert found[0].line == 10  # the reset() write, not the guarded one


def test_sdl002_all_writes_locked_pass_and_init_exempt():
    src = _SDL002_BAD.replace(
        "    def reset(self):\n        self.n = 0\n",
        "    def reset(self):\n"
        "        with self._lock:\n"
        "            self.n = 0\n")
    assert codes(src) == []


def test_sdl002_condition_counts_as_lock():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self.depth = 0\n"
        "    def a(self):\n"
        "        with self._cond:\n"
        "            self.depth += 1\n"
        "    def b(self):\n"
        "        self.depth -= 1\n")
    assert codes(src) == ["SDL002"]


def test_sdl002_pragma_suppresses():
    src = _SDL002_BAD.replace(
        "        self.n = 0\n    def incr",
        "        self.n = 0\n    def incr").replace(
        "    def reset(self):\n        self.n = 0\n",
        "    def reset(self):\n"
        "        # graftlint: allow=SDL002 reason=called before threads exist\n"
        "        self.n = 0\n")
    assert codes(src) == []


# ---------------------------------------------------------------------------
# SDL003 — broad except hygiene
# ---------------------------------------------------------------------------

def test_sdl003_swallowing_broad_except_fires():
    src = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    except Exception:\n"
           "        return None\n")
    assert codes(src) == ["SDL003"]
    bare = src.replace("except Exception:", "except:")
    assert codes(bare) == ["SDL003"]


def test_sdl003_reraise_log_and_pragma_pass():
    reraise = ("def f():\n"
               "    try:\n"
               "        g()\n"
               "    except Exception as e:\n"
               "        raise RuntimeError('x') from e\n")
    logs = ("def f():\n"
            "    try:\n"
            "        g()\n"
            "    except Exception as e:\n"
            "        logger.warning('boom: %s', e)\n")
    pragma = ("def f():\n"
              "    try:\n"
              "        g()\n"
              "    except Exception:  "
              "# graftlint: allow=SDL003 reason=probe must not raise\n"
              "        return None\n")
    narrow = ("def f():\n"
              "    try:\n"
              "        g()\n"
              "    except ValueError:\n"
              "        return None\n")
    for src in (reraise, logs, pragma, narrow):
        assert codes(src) == []


def test_sdl000_pragma_without_reason_fires():
    src = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    except Exception:  # graftlint: allow=SDL003\n"
           "        return None\n")
    assert sorted(codes(src)) == ["SDL000", "SDL003"]


def test_pragma_on_line_above_suppresses():
    src = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    # graftlint: allow=SDL003 reason=deliberate swallow\n"
           "    except Exception:\n"
           "        return None\n")
    assert codes(src) == []


def test_pragma_inside_string_literal_is_inert():
    # pragma-shaped TEXT in a string must neither fire SDL000 nor
    # suppress a genuine finding on the next line
    bogus = 'MSG = "# graftlint: allow=SDL003"\n'
    assert codes(bogus) == []
    # the string literal sits on the line directly above the handler —
    # exactly where a real pragma would suppress it
    not_a_shield = ('def f():\n'
                    '    try:\n'
                    '        s = "# graftlint: allow=SDL003 reason=nope"\n'
                    '    except Exception:\n'
                    '        return None\n')
    assert codes(not_a_shield) == ["SDL003"]


# ---------------------------------------------------------------------------
# SDL004 — fault-site registry
# ---------------------------------------------------------------------------

def test_sdl004_typo_site_fires_and_known_site_passes():
    typo = ("from sparkdl_tpu.faults import inject\n"
            "def f():\n"
            "    inject('engine.dispach')\n")
    ok = typo.replace("engine.dispach", "engine.dispatch")
    found = lint_source(typo, sites=SITES_FIXTURE)
    assert [f.code for f in found] == ["SDL004"]
    assert "engine.dispach" in found[0].message
    assert codes(ok) == []


def test_sdl004_has_rules_checked_and_missing_registry_reported():
    src = ("from sparkdl_tpu import faults\n"
           "def f():\n"
           "    return faults.has_rules('io.decodee')\n")
    assert codes(src) == ["SDL004"]
    # no registry at all: site uses are reported as unverifiable
    assert [f.code for f in lint_source(src, sites=None)] == ["SDL004"]


def test_registry_file_matches_runtime_sites():
    from sparkdl_tpu.faults import SITE_HELP, SITES

    extracted = load_site_registry([os.path.join(REPO, "sparkdl_tpu")])
    assert extracted == set(SITES) == set(SITE_HELP)


def test_fault_plan_rejects_unknown_site_at_construction():
    from sparkdl_tpu.faults import FaultPlan, FaultRule, validate_site

    with pytest.raises(ValueError, match="unknown fault site"):
        FaultRule(site="engine.dispach", action="error")
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan.parse("seed=1;engine.dispach:error:at=1")
    # even a rule mutated after construction fails at plan build
    r = FaultRule(site="engine.dispatch", action="error")
    r.site = "nope.nope"
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan([r], seed=1)
    assert validate_site("engine.dispatch") == "engine.dispatch"


# ---------------------------------------------------------------------------
# SDL005 — naming schema + span pairing
# ---------------------------------------------------------------------------

def test_sdl005_bad_metric_name_fires():
    assert codes("def f(m):\n    m.incr('Serving Requests')\n"
                 ) == ["SDL005"]
    assert codes("def f(m):\n    m.record_time('servingLatency', 1.0)\n"
                 ) == ["SDL005"]


def test_sdl005_schema_names_pass():
    src = ("def f(m, t):\n"
           "    m.incr('serving.requests')\n"
           "    m.observe('pipeline.prep_q_depth', 3)\n"
           "    m.gauge('items', 1)\n"
           "    with t.span('engine.dispatch'):\n"
           "        pass\n")
    assert codes(src) == []


def test_sdl005_covers_the_span_closed_at_birth():
    bad = "def f(t):\n    t.record('compileBackend', 0.5, program='f')\n"
    good = "def f(t):\n    t.record('compile.backend', 0.5, program='f')\n"
    # named like a span; closed when made, so nothing to pair
    assert codes(bad) == ["SDL005"]
    assert codes(good) == []
    dynamic = "def f(t, p):\n    t.record('compile.' + p, 0.5)\n"
    assert codes(dynamic) == []


def test_sdl005_leaked_span_fires():
    dead_local = ("def f(tracer):\n"
                  "    sp = tracer.start_span('serving.request')\n"
                  "    return 1\n")
    bare = "def f(tracer):\n    tracer.span('engine.call')\n"
    assert codes(dead_local) == ["SDL005"]
    assert codes(bare) == ["SDL005"]


def test_sdl005_closed_or_handed_off_spans_pass():
    finished = ("def f(tracer):\n"
                "    sp = tracer.start_span('serving.request')\n"
                "    work()\n"
                "    sp.finish()\n")
    cross_thread = ("def f(tracer, req):\n"
                    "    req.span = tracer.start_span('serving.request')\n")
    conditional = ("def f(tracer):\n"
                   "    sp = (tracer.start_span('pipeline.run')\n"
                   "          if tracer.enabled else None)\n"
                   "    try:\n"
                   "        pass\n"
                   "    finally:\n"
                   "        if sp is not None:\n"
                   "            sp.finish()\n")
    for src in (finished, cross_thread, conditional):
        assert codes(src) == []


# ---------------------------------------------------------------------------
# SDL006 — monotonic timing
# ---------------------------------------------------------------------------

def test_sdl006_wall_clock_latency_fires():
    src = ("import time\n"
           "def f():\n"
           "    t0 = time.time()\n"
           "    g()\n"
           "    return time.time() - t0\n")
    assert codes(src) == ["SDL006"]  # one finding per subtraction
    direct = ("import time\n"
              "def g(t0):\n"
              "    return time.time() - t0\n")
    assert codes(direct) == ["SDL006"]


def test_sdl006_sees_time_module_aliases():
    aliased = ("import time as time_lib\n"
               "def f():\n"
               "    t0 = time_lib.time()\n"
               "    return time_lib.time() - t0\n")
    assert codes(aliased) == ["SDL006"]
    from_import = ("from time import time as now\n"
                   "def f(t0):\n"
                   "    return now() - t0\n")
    assert codes(from_import) == ["SDL006"]
    # monotonic through the alias stays legal
    mono = ("import time as time_lib\n"
            "def f():\n"
            "    t0 = time_lib.monotonic()\n"
            "    return time_lib.monotonic() - t0\n")
    assert codes(mono) == []


def test_sdl006_stamps_and_perf_counter_pass():
    stamp = ("import time\n"
             "def f(rec):\n"
             "    rec['ts'] = time.time()\n")
    perf = ("import time\n"
            "def f():\n"
            "    t0 = time.perf_counter()\n"
            "    return time.perf_counter() - t0\n")
    assert codes(stamp) == []
    assert codes(perf) == []


# ---------------------------------------------------------------------------
# SDL007 — explicit donation decision at every jit site (ISSUE 6)
# ---------------------------------------------------------------------------

def test_sdl007_bare_jit_fires():
    src = ("import jax\n"
           "def f(fn):\n"
           "    return jax.jit(fn)\n")
    assert codes(src) == ["SDL007"]
    from_import = ("from jax import jit\n"
                   "def f(fn):\n"
                   "    return jit(fn)\n")
    assert codes(from_import) == ["SDL007"]


def test_sdl007_partial_decorator_form_fires():
    src = ("import functools\n"
           "import jax\n"
           "@functools.partial(jax.jit, static_argnames=('h',))\n"
           "def f(x, h):\n"
           "    return x\n")
    assert codes(src) == ["SDL007"]


def test_sdl007_bare_decorator_form_fires():
    # no Call node exists for @jax.jit — the decorator list is checked
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x\n")
    assert codes(src) == ["SDL007"]
    from_import = ("from jax import jit\n"
                   "@jit\n"
                   "def f(x):\n"
                   "    return x\n")
    assert codes(from_import) == ["SDL007"]


def test_sdl007_explicit_decision_passes():
    empty = ("import jax\n"
             "def f(fn):\n"
             "    return jax.jit(fn, donate_argnums=())\n")
    donated = ("import jax\n"
               "def f(fn):\n"
               "    return jax.jit(fn, donate_argnames=('x',))\n")
    partial = ("import functools\n"
               "import jax\n"
               "@functools.partial(jax.jit, donate_argnums=(0,))\n"
               "def f(x):\n"
               "    return x\n")
    assert codes(empty) == []
    assert codes(donated) == []
    assert codes(partial) == []


def test_sdl007_pragma_needs_reason():
    with_reason = ("import jax\n"
                   "def f(fn):\n"
                   "    # graftlint: allow=SDL007 reason=one-shot probe\n"
                   "    return jax.jit(fn)\n")
    assert codes(with_reason) == []
    bare = ("import jax\n"
            "def f(fn):\n"
            "    # graftlint: allow=SDL007\n"
            "    return jax.jit(fn)\n")
    # a reason-less pragma is itself a finding AND suppresses nothing
    assert codes(bare) == ["SDL000", "SDL007"]


def test_sdl007_ignores_non_jax_jit():
    src = ("import numba\n"
           "def f(fn):\n"
           "    return numba.jit(fn)\n")
    assert codes(src) == []


# ---------------------------------------------------------------------------
# the repo itself must lint clean (the acceptance gate, in-tree)
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    targets = [os.path.join(REPO, "sparkdl_tpu"),
               os.path.join(REPO, "tools"),
               os.path.join(REPO, "bench.py")]
    findings = lint_paths(targets)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n"
                   "    try:\n"
                   "        g()\n"
                   "    except Exception:\n"
                   "        return None\n")
    cli = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "SDL003" in r.stdout
    bad.write_text("X = 1\n")
    r = subprocess.run([sys.executable, cli, str(bad)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout
    r = subprocess.run([sys.executable, cli, "--list-rules"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    for code in RULE_HELP:
        assert code in r.stdout


def test_cli_json_output(tmp_path):
    """--json (ISSUE 6 satellite): stable machine-readable findings for
    CI — rule/path/line/message per finding, exit codes unchanged."""
    import json

    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "def f(fn):\n"
                   "    return jax.jit(fn)\n")
    cli = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, cli, "--json", str(bad)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["files"] == 1 and doc["rules"] == len(RULE_HELP)
    [finding] = doc["findings"]
    assert finding["rule"] == "SDL007"
    assert finding["path"] == str(bad) and finding["line"] == 3
    assert "donate_argnums" in finding["message"]
    bad.write_text("X = 1\n")
    r = subprocess.run([sys.executable, cli, "--json", str(bad)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    assert json.loads(r.stdout)["findings"] == []


def test_cli_sites_file_option(tmp_path):
    # an explicit registry file works regardless of its name/location
    reg = tmp_path / "my_sites.py"
    reg.write_text('SITE_HELP = {"custom.site": "a site"}\n')
    src = tmp_path / "code.py"
    src.write_text("def f(x):\n    inject('custom.site')\n")
    cli = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run(
        [sys.executable, cli, "--sites-file", str(reg), str(src)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    src.write_text("def f(x):\n    inject('custom.typo')\n")
    r = subprocess.run(
        [sys.executable, cli, "--sites-file", str(reg), str(src)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1 and "SDL004" in r.stdout


# ---------------------------------------------------------------------------
# runtime lock-order checker
# ---------------------------------------------------------------------------

@pytest.fixture
def checked_locks():
    lockcheck.enable()
    lockcheck.reset()
    try:
        yield
    finally:
        lockcheck.reset()
        lockcheck.disable()


def test_lockcheck_disabled_returns_plain_primitives():
    lockcheck.disable()
    try:
        lk = lockcheck.named_lock("t.plain")
        assert type(lk) is type(threading.Lock())
        cond = lockcheck.named_condition("t.plain_cond")
        assert isinstance(cond, threading.Condition)
    finally:
        lockcheck.reset()


def test_lockcheck_detects_inverted_order(checked_locks):
    a = lockcheck.named_lock("t.a")
    b = lockcheck.named_lock("t.b")
    with a:
        with b:
            pass
    with pytest.raises(lockcheck.LockOrderError) as ei:
        with b:
            with a:
                pass
    assert ei.value.cycle == ["t.a", "t.b"]
    assert "t.a" in str(ei.value) and "t.b" in str(ei.value)


def test_lockcheck_consistent_order_and_same_name_pass(checked_locks):
    a = lockcheck.named_lock("t.a")
    b = lockcheck.named_lock("t.b")
    for _ in range(3):  # repeated consistent nesting is fine
        with a:
            with b:
                pass
    # two INSTANCES of one lock class never self-edge
    b2 = lockcheck.named_lock("t.b")
    with b:
        with b2:
            pass
    assert lockcheck.order_graph() == {"t.a": ["t.b"]}


def test_lockcheck_three_way_cycle_detected(checked_locks):
    a = lockcheck.named_lock("t3.a")
    b = lockcheck.named_lock("t3.b")
    c = lockcheck.named_lock("t3.c")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with pytest.raises(lockcheck.LockOrderError) as ei:
        with c:
            with a:
                pass
    assert ei.value.cycle == ["t3.a", "t3.b", "t3.c"]


def test_lockcheck_condition_wait_keeps_stack_consistent(checked_locks):
    cond = lockcheck.named_condition("t.cond")
    state = []

    def waiter():
        with cond:
            while not state:
                cond.wait(timeout=5.0)
            state.append("woke")

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    with cond:
        state.append("go")
        cond.notify_all()
    t.join(timeout=5.0)
    assert not t.is_alive() and state == ["go", "woke"]
