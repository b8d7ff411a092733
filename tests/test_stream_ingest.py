"""Exactly-once streaming ingestion (ISSUE 8, ROADMAP item 5).

Tier-1 (CPU-only).  Pins the crash-safe continuous-scoring contracts:

* the shared JSONL torn-tail recovery (`utils.jsonl.read_jsonl` /
  `recover_jsonl`) contract-tested from BOTH callers — the bench
  artifact's writer and the streaming journal;
* source semantics: ordered content-addressed chunks, stable ids across
  seek/replay, directory-watch ordering + end marker;
* journal edge cases: cold start, torn-tail truncation on restart,
  duplicate-commit idempotence, resume offset around holes;
* StreamScorer: exactly-once vs the batch `map_batches` oracle
  (pipelined and serving-sink paths), duplicate suppression by id,
  crash-between-output-and-commit resume, `stream.resume` injection,
  source-stall watchdog -> degraded -> recovered health;
* the headline chaos test: a REAL SIGKILL between output write and
  journal commit mid-stream, restart, outputs exactly-once (no gap, no
  duplicate) and bit-identical to the batch oracle, lag recovered.

Budget note: tier-1 runs ~720-780s against an 870s driver timeout —
every in-process test here is sub-second except the two subprocess
runs of the SIGKILL headline (~10s total).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sparkdl_tpu import faults, streaming
from sparkdl_tpu.faults import FaultPlan
from sparkdl_tpu.parallel.engine import InferenceEngine
from sparkdl_tpu.streaming import (DirectorySource, Journal, MemorySource,
                                   StreamScorer, assemble_outputs,
                                   content_chunk_id, finish_directory_stream,
                                   write_directory_chunk)
from sparkdl_tpu.utils.jsonl import (CrashSafeJsonlWriter,
                                     JsonlCorruptionError, read_jsonl,
                                     recover_jsonl)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_plan():
    """Never leak a fault plan between tests (or out of the suite)."""
    from sparkdl_tpu.faults import plan as plan_mod

    prev = plan_mod._PLAN
    yield
    plan_mod._PLAN = prev


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"])


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(7)
    variables = {"w": rng.normal(size=(6, 4)).astype(np.float32)}
    return InferenceEngine(_fn, variables, device_batch_size=8)


@pytest.fixture(scope="module")
def payloads():
    rng = np.random.default_rng(11)
    return [rng.normal(size=(8, 6)).astype(np.float32) for _ in range(6)]


@pytest.fixture(scope="module")
def oracle(engine, payloads):
    """The batch half of the exactly-once acceptance check: one
    map_batches pass over the same chunks."""
    return np.concatenate(
        [np.asarray(o) for o in engine.map_batches(payloads,
                                                   pipeline=False)], axis=0)


def _scorer(engine, src, base, **kw):
    kw.setdefault("pipeline", False)
    return StreamScorer(engine, src,
                        journal_path=os.path.join(base, "journal.jsonl"),
                        out_dir=os.path.join(base, "out"), **kw)


def _assemble(base):
    return assemble_outputs(os.path.join(base, "journal.jsonl"),
                            os.path.join(base, "out"))


# -- shared JSONL: one implementation, both callers ------------------------

def test_read_jsonl_tolerates_torn_tail_and_recover_truncates(tmp_path):
    p = str(tmp_path / "a.jsonl")
    w = CrashSafeJsonlWriter(p)
    for i in range(3):
        assert w.write_line(json.dumps({"i": i}))
    w.close()
    good_size = os.path.getsize(p)
    with open(p, "ab") as f:
        f.write(b'{"i": 3, "torn')  # crash mid-append: no newline
    recs, valid = read_jsonl(p)
    assert [r["i"] for r in recs] == [0, 1, 2]
    assert valid == good_size
    recs2, discarded = recover_jsonl(p)
    assert [r["i"] for r in recs2] == [0, 1, 2] and discarded > 0
    assert os.path.getsize(p) == good_size  # tail gone, fsync'd
    # a terminated-but-unparsable FINAL line is also recoverable tail
    with open(p, "ab") as f:
        f.write(b'{"i": 3, "torn"\n')
    recs3, _ = read_jsonl(p)
    assert [r["i"] for r in recs3] == [0, 1, 2]


def test_read_jsonl_mid_file_corruption_raises(tmp_path):
    p = str(tmp_path / "a.jsonl")
    with open(p, "wb") as f:
        f.write(b'{"i": 0}\nnot json at all\n{"i": 2}\n')
    with pytest.raises(JsonlCorruptionError):
        read_jsonl(p)


def test_jsonl_contract_shared_by_bench_artifact_and_journal(tmp_path):
    """Both callers of the one implementation: a bench-style artifact
    and a streaming journal, each torn, each recovered by the same
    functions (the ISSUE 8 factoring satellite)."""
    # bench.py caller: its artifact is a CrashSafeJsonlWriter product
    import bench

    assert isinstance(bench._ARTIFACT, CrashSafeJsonlWriter)
    art = str(tmp_path / "bench_lines.jsonl")
    w = CrashSafeJsonlWriter(art)
    w.write_line(json.dumps({"config": "pipeline", "value": 1.5}))
    w.close()
    with open(art, "ab") as f:
        f.write(b'{"config": "serving", "val')  # SIGKILL mid-line
    recs, _ = recover_jsonl(art)
    assert [r["config"] for r in recs] == ["pipeline"]
    # journal caller: same torn-tail shape, recovered at Journal() open
    jp = str(tmp_path / "journal.jsonl")
    j = Journal(jp)
    j.begin("c0", 0)
    j.commit("c0", 0)
    j.close()
    with open(jp, "ab") as f:
        f.write(b'{"rec": "intent", "chunk_id": "c1"')
    j2 = Journal(jp)
    assert j2.recovered_torn_bytes > 0
    assert j2.is_committed("c0") and not j2.seen("c1")
    j2.close()


# -- sources ---------------------------------------------------------------

def test_memory_source_ordered_ids_stable_across_seek():
    rng = np.random.default_rng(0)
    src = MemorySource([rng.normal(size=(4, 3)) for _ in range(3)],
                       finished=True)
    first = [src.poll() for _ in range(3)]
    assert [c.offset for c in first] == [0, 1, 2]
    assert src.poll() is None and src.exhausted()
    src.seek(1)
    again = src.poll()
    assert again.chunk_id == first[1].chunk_id  # content-addressed, stable
    assert np.array_equal(again.payload, first[1].payload)
    ids = {c.chunk_id for c in first}
    assert len(ids) == 3  # distinct content/offset -> distinct ids


def test_directory_source_order_end_marker_seek(tmp_path):
    d = str(tmp_path / "in")
    rng = np.random.default_rng(1)
    chunks = [rng.normal(size=(4, 3)).astype(np.float32) for _ in range(3)]
    write_directory_chunk(d, 0, chunks[0])
    src = DirectorySource(d)
    c0 = src.poll()
    assert c0.offset == 0 and np.array_equal(c0.payload, chunks[0])
    assert src.poll() is None and not src.exhausted()  # nothing yet, live
    write_directory_chunk(d, 1, chunks[1])
    write_directory_chunk(d, 2, chunks[2])
    finish_directory_stream(d)
    got = [src.poll() for _ in range(2)]
    assert [c.offset for c in got] == [1, 2]
    assert src.exhausted()
    src.seek(1)  # replay: same bytes, same id
    replay = src.poll()
    assert replay.chunk_id == got[0].chunk_id
    assert replay.chunk_id == content_chunk_id(1, chunks[1])


# -- journal edge cases ----------------------------------------------------

def test_journal_cold_start_empty(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    assert j.resume_offset() == 0
    assert j.committed_count() == 0 and j.uncommitted() == []
    assert j.recovered_torn_bytes == 0
    j.close()


def test_journal_torn_tail_truncated_on_restart(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = Journal(p)
    j.begin("c0", 0)
    j.record_output("c0", 0, "out-c0.npy", "d0")
    j.commit("c0", 0)
    j.begin("c1", 1)
    j.close()
    size = os.path.getsize(p)
    with open(p, "ab") as f:
        f.write(b'{"rec": "output", "chunk_id": "c1", "off')  # torn
    j2 = Journal(p)
    assert j2.recovered_torn_bytes > 0
    assert os.path.getsize(p) == size
    assert j2.is_committed("c0")
    assert j2.uncommitted() == [{"chunk_id": "c1", "offset": 1,
                                 "has_output": False}]
    assert j2.resume_offset() == 1
    # and the recovered journal appends cleanly right where it left off
    j2.record_output("c1", 1, "out-c1.npy", "d1")
    j2.commit("c1", 1)
    j2.close()
    recs, valid = read_jsonl(p)
    assert recs[-1]["rec"] == "commit" and valid == os.path.getsize(p)


def test_journal_duplicate_commit_idempotent(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = Journal(p)
    j.begin("c0", 0)
    assert j.commit("c0", 0) is True
    assert j.commit("c0", 0) is False  # idempotent: no second record
    j.close()
    recs, _ = read_jsonl(p)
    assert sum(r["rec"] == "commit" for r in recs) == 1
    j2 = Journal(p)  # and the reopened index agrees
    assert j2.commit("c0", 0) is False
    assert j2.committed_count() == 1
    j2.close()


def test_journal_resume_offset_skips_only_contiguous_prefix(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    for cid, off in (("c0", 0), ("c2", 2)):  # hole at offset 1
        j.begin(cid, off)
        j.commit(cid, off)
    assert j.resume_offset() == 1  # seek to the hole...
    assert j.is_committed("c2")    # ...and suppress c2 by id on replay
    assert j.committed_offsets() == [0, 2]
    j.close()


# -- StreamScorer ----------------------------------------------------------

def test_exactly_once_basic_pipelined(engine, payloads, oracle, tmp_path):
    base = str(tmp_path)
    src = MemorySource(payloads, finished=True)
    sc = _scorer(engine, src, base, pipeline=True)
    summary = sc.run()
    assert summary["chunks_scored"] == len(payloads)
    assert summary["duplicates_suppressed"] == 0
    got = _assemble(base)
    assert np.array_equal(got, oracle)  # bit-identical, exactly-once
    m = sc.metrics
    assert m.counters["stream.chunks"] == len(payloads)
    assert m.counters["stream.commits"] == len(payloads)
    assert m.gauges["stream.watermark"] == len(payloads)
    h = sc.health()
    assert h["state"] == "ready" and h["watermark"] == len(payloads)
    sc.close()
    assert sc.health()["state"] == "closed" and not sc.health()["live"]


def test_default_scorer_builds_the_pipelined_runner(engine, payloads, oracle,
                                                    tmp_path, monkeypatch):
    """A ``StreamScorer`` left at its defaults over an engine sink rides
    the runner's three threads (poll/journal/prepare overlap dispatch
    and gather), not the calling thread."""
    from sparkdl_tpu.parallel import engine as engine_mod

    built = []
    real = engine_mod.PipelinedRunner

    def spy(*a, **k):
        built.append(k.get("window"))
        return real(*a, **k)

    monkeypatch.setattr(engine_mod, "PipelinedRunner", spy)
    base = str(tmp_path)
    sc = StreamScorer(engine, MemorySource(payloads, finished=True),
                      journal_path=os.path.join(base, "journal.jsonl"),
                      out_dir=os.path.join(base, "out"))
    assert sc.run()["chunks_scored"] == len(payloads)
    sc.close()
    assert built == [2]  # one runner, the scorer's default window
    assert np.array_equal(_assemble(base), oracle)


def test_duplicate_delivery_suppressed_by_id(engine, payloads, oracle,
                                             tmp_path):
    """A chunk the journal already committed (here: offset 1, committed
    out of band so the contiguous prefix stops at 0) is re-delivered by
    the seeked source and must be suppressed by id, not re-scored."""
    base = str(tmp_path)
    jp = os.path.join(base, "journal.jsonl")
    cid1 = content_chunk_id(1, payloads[1])
    j = Journal(jp)
    j.begin(cid1, 1)
    out1 = np.asarray(list(engine.map_batches([payloads[1]],
                                              pipeline=False))[0])
    from sparkdl_tpu.streaming.runner import (_array_digest,
                                              _write_artifact_atomic)

    os.makedirs(os.path.join(base, "out"), exist_ok=True)
    _write_artifact_atomic(
        os.path.join(base, "out", f"out-{cid1}.npy"), out1)
    j.record_output(cid1, 1, f"out-{cid1}.npy", _array_digest(out1))
    j.commit(cid1, 1)
    j.close()
    src = MemorySource(payloads, finished=True)
    sc = _scorer(engine, src, base)
    summary = sc.run()
    assert summary["resume_offset"] == 0
    assert summary["duplicates_suppressed"] == 1
    assert summary["chunks_scored"] == len(payloads) - 1
    assert sc.metrics.counters["stream.duplicates_suppressed"] == 1
    assert np.array_equal(_assemble(base), oracle)
    sc.close()


def test_crash_between_output_and_commit_then_resume(engine, payloads,
                                                     oracle, tmp_path):
    """The injected form of the headline: stream.commit kills run 1
    after the output artifact is durable but before the commit record;
    run 2 replays the uncommitted suffix to exactly-once output."""
    base = str(tmp_path)
    src = MemorySource(payloads, finished=True)
    sc = _scorer(engine, src, base)
    with faults.active(FaultPlan.parse(
            "stream.commit:error:exc=fatal,at=3")) as plan:
        with pytest.raises(faults.InjectedFatalError):
            sc.run()
        assert plan.fired("stream.commit") == 1
    # the crash left offsets 0,1 committed and offset 2's artifact
    # on disk without a commit — the exactly-once window
    j = Journal(os.path.join(base, "journal.jsonl"))
    assert j.resume_offset() == 2
    assert any(r["offset"] == 2 and r["has_output"]
               for r in j.uncommitted())
    j.close()
    src2 = MemorySource(payloads, finished=True)
    sc2 = _scorer(engine, src2, base)
    summary = sc2.run()
    assert summary["resume_offset"] == 2
    assert summary["redeliveries"] >= 1
    assert sc2.metrics.counters["stream.redeliveries"] >= 1
    got = _assemble(base)
    assert np.array_equal(got, oracle)
    # no duplicate commits, no artifact duplicates
    recs, _ = read_jsonl(os.path.join(base, "journal.jsonl"))
    commits = [r["chunk_id"] for r in recs if r["rec"] == "commit"]
    assert len(commits) == len(set(commits)) == len(payloads)
    arts = [f for f in os.listdir(os.path.join(base, "out"))
            if f.endswith(".npy")]
    assert len(arts) == len(payloads)
    sc2.close()


def test_replay_survives_stream_resume_injection(engine, payloads, oracle,
                                                 tmp_path):
    """stream.resume fires AT replay time: a restart that dies again
    while redelivering still converges on the next clean restart."""
    base = str(tmp_path)
    src = MemorySource(payloads, finished=True)
    sc = _scorer(engine, src, base)
    with faults.active(FaultPlan.parse("stream.commit:error:exc=fatal,at=2")):
        with pytest.raises(faults.InjectedFatalError):
            sc.run()
    with faults.active(FaultPlan.parse(
            "stream.resume:error:exc=fatal,at=1")) as plan:
        sc2 = _scorer(engine, MemorySource(payloads, finished=True), base)
        with pytest.raises(faults.InjectedFatalError):
            sc2.run()
        assert plan.fired("stream.resume") == 1
    sc3 = _scorer(engine, MemorySource(payloads, finished=True), base)
    summary = sc3.run()
    assert summary["redeliveries"] >= 1
    assert np.array_equal(_assemble(base), oracle)
    sc3.close()


def test_source_transient_fault_absorbed_by_repoll(engine, payloads, oracle,
                                                   tmp_path):
    base = str(tmp_path)
    src = MemorySource(payloads, finished=True)
    sc = _scorer(engine, src, base)
    with faults.active(FaultPlan.parse(
            "seed=5;stream.source:error:exc=transient,at=2")) as plan:
        summary = sc.run()
        assert plan.fired("stream.source") == 1
    assert summary["chunks_scored"] == len(payloads)
    assert sc.metrics.counters["stream.source_errors"] == 1
    assert np.array_equal(_assemble(base), oracle)
    # the transient left a health trace, then recovery won
    states = [t["state"] for t in sc.health()["transitions"]]
    assert "degraded" in states and sc.health()["state"] == "ready"
    sc.close()


def test_stall_watchdog_degraded_then_recovered(engine, payloads, tmp_path):
    """Source silent past the deadline -> degraded (with last_error and
    a transitions entry), seeded-backoff re-poll keeps the runner alive,
    late chunks recover it to ready — no wedged threads."""
    base = str(tmp_path)
    src = MemorySource([payloads[0]])  # live stream: not finished yet
    sc = _scorer(engine, src, base, stall_deadline_s=0.05)
    mid_state = {}

    def feeder():
        time.sleep(0.35)
        mid_state.update(sc.health())
        src.feed(payloads[1])
        src.finish()

    t = threading.Thread(target=feeder)
    t.start()
    summary = sc.run()
    t.join()
    assert summary["chunks_scored"] == 2
    assert mid_state["state"] == "degraded"
    assert mid_state["lag_s"] > 0.05
    assert mid_state["last_error"]["type"] == "StreamStallError"
    h = sc.health()
    assert h["state"] == "ready" and h["watermark"] == 2
    states = [x["state"] for x in h["transitions"]]
    assert states[-2:] == ["degraded", "ready"]
    assert sc.metrics.counters["stream.stalls"] >= 1
    assert sc.metrics.counters["stream.stall_recoveries"] >= 1
    left = [th.name for th in threading.enumerate()
            if th.name.startswith(("sparkdl-pipeline", "sparkdl-serving"))]
    assert not left, left
    sc.close()


def test_health_mirrors_server_contract(engine, payloads, tmp_path):
    """StreamScorer.health() carries every core key Server.health()
    does (live/state/last_error/transitions) with the same state
    vocabulary, plus the stream's watermark/lag surface."""
    base = str(tmp_path)
    sc = _scorer(engine, MemorySource(payloads[:1], finished=True), base)
    h = sc.health()
    for key in ("live", "state", "last_error", "transitions"):
        assert key in h
    assert h["state"] in ("ready", "degraded", "closed")
    assert h["transitions"][0]["state"] == "ready"
    assert {"watermark", "lag_s", "source_exhausted"} <= set(h)
    json.dumps(h)  # JSON-serializable like Server.health()
    sc.close()
    assert sc.health()["state"] == "closed"


def test_serving_sink_rides_online_queue(engine, payloads, tmp_path):
    from sparkdl_tpu.serving import Server

    base = str(tmp_path)
    variables = {"w": engine.variables["w"]}
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=1.0) as srv:
        src = MemorySource(payloads[:2], finished=True)
        sc = StreamScorer(srv, src,
                          journal_path=os.path.join(base, "j.jsonl"),
                          out_dir=os.path.join(base, "out"))
        summary = sc.run()
        assert summary["chunks_scored"] == 2
        got = assemble_outputs(os.path.join(base, "j.jsonl"),
                               os.path.join(base, "out"))
        assert got.shape == (16, 4)
        ref = np.concatenate(
            [np.asarray(o) for o in engine.map_batches(payloads[:2],
                                                       pipeline=False)])
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
        sc.close()


def test_stream_journal_cli_summary(engine, payloads, tmp_path, capsys):
    from tools.stream_journal import main, summarize

    base = str(tmp_path)
    src = MemorySource(payloads[:2], finished=True)
    sc = _scorer(engine, src, base)
    sc.run()
    sc.close()
    jp = os.path.join(base, "journal.jsonl")
    s = summarize(jp)
    assert s["committed"] == 2 and s["uncommitted"] == []
    assert s["resume_offset"] == 2
    assert main([jp]) == 0  # clean journal
    capsys.readouterr()
    j = Journal(jp)
    j.begin("cX", 2)
    j.close()
    assert main([jp, "--json"]) == 1  # pending replay
    out = json.loads(capsys.readouterr().out)
    assert out["uncommitted"][0]["chunk_id"] == "cX"


# -- headline chaos: SIGKILL between output write and commit ---------------

_CHILD = r"""
import json, os, signal, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from sparkdl_tpu import faults, streaming
from sparkdl_tpu.parallel.engine import InferenceEngine

base = sys.argv[1]

def _fn(variables, x):
    import jax.numpy as jnp
    return jnp.tanh(x @ variables["w"])

rng = np.random.default_rng(7)
variables = {"w": rng.normal(size=(6, 4)).astype(np.float32)}
eng = InferenceEngine(_fn, variables, device_batch_size=8)
src = streaming.DirectorySource(os.path.join(base, "in"))
sc = streaming.StreamScorer(
    eng, src, journal_path=os.path.join(base, "journal.jsonl"),
    out_dir=os.path.join(base, "out"), pipeline=False,
    stall_deadline_s=2.0)
try:
    summary = sc.run()
except faults.InjectedFatalError:
    # a REAL SIGKILL at the exact crash point the fault marks: no
    # finally blocks, no atexit, no flush — only what fsync already
    # made durable survives
    os.kill(os.getpid(), signal.SIGKILL)
print(json.dumps({"summary": summary, "health": sc.health()}))
"""


def test_sigkill_between_output_and_commit_exactly_once(engine, payloads,
                                                        oracle, tmp_path):
    """ISSUE 8 acceptance: sustained stream, SIGKILL the scoring
    process in the window between output-artifact write and journal
    commit, restart from the journal — final outputs are exactly-once
    (no gap, no duplicate), bit-identical to the batch oracle, and the
    lag/watermark metrics recover."""
    base = str(tmp_path)
    indir = os.path.join(base, "in")
    for i, p in enumerate(payloads):
        write_directory_chunk(indir, i, p)
    finish_directory_stream(indir)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "SPARKDL_TRACE": "0",
                "SPARKDL_FAULTS": "stream.commit:error:exc=fatal,at=4"})
    r1 = subprocess.run([sys.executable, "-c", _CHILD, base], cwd=REPO,
                        env=env, capture_output=True, text=True,
                        timeout=180)
    assert r1.returncode == -9, (r1.returncode, r1.stderr[-2000:])
    # the kill landed in the window: offsets 0-2 committed, offset 3's
    # artifact durable but uncommitted
    j = Journal(os.path.join(base, "journal.jsonl"))
    assert j.resume_offset() == 3
    pending = j.uncommitted()
    assert any(r["offset"] == 3 and r["has_output"] for r in pending)
    j.close()
    env2 = dict(os.environ)
    env2.update({"JAX_PLATFORMS": "cpu", "SPARKDL_TRACE": "0"})
    env2.pop("SPARKDL_FAULTS", None)
    r2 = subprocess.run([sys.executable, "-c", _CHILD, base], cwd=REPO,
                        env=env2, capture_output=True, text=True,
                        timeout=180)
    assert r2.returncode == 0, r2.stderr[-2000:]
    rec = json.loads(r2.stdout.strip().splitlines()[-1])
    assert rec["summary"]["resume_offset"] == 3
    assert rec["summary"]["redeliveries"] >= 1
    assert rec["summary"]["committed_total"] == len(payloads)
    # lag recovered: the restarted run ends ready with a full watermark
    assert rec["health"]["state"] == "ready"
    assert rec["health"]["watermark"] == len(payloads)
    assert rec["health"]["lag_s"] == 0.0  # exhausted: lag cleared
    # exactly-once and bit-correct vs the batch oracle over the same
    # chunks (same seeded weights in the child, CPU-deterministic)
    got = _assemble(base)
    assert np.array_equal(got, oracle)
    recs, _ = read_jsonl(os.path.join(base, "journal.jsonl"))
    commits = [r["chunk_id"] for r in recs if r["rec"] == "commit"]
    assert len(commits) == len(set(commits)) == len(payloads)
    arts = [f for f in os.listdir(os.path.join(base, "out"))
            if f.endswith(".npy")]
    assert len(arts) == len(payloads)
