"""The kind ``vl_trunk`` at toy widths, entered as data beside the toy
root's cells and driven through the whole of ``run_cell`` on the CPU:
correct as it is, not correct with the control in its place, refused
where the program has no vision trunk; the two new readers on made-up
counters and a made-up trace; ``vl_trunk_flops`` held to a count by hand
at the cell's widths; the generator's layout; the committed
configuration against the catalog."""

import builtins
import json
import os

import numpy as np
import pytest

from benchmark import harness
from benchmark import latent_trunk_flops as lf
from benchmark import vl_trunk_flops as vf
from benchmark.kinds import vl_trunk as kind
from benchmark.layer_metrics import (
    Observations, fetched_key_share, frame_attention_roofline,
    sa_indexer_roofline, sparse_attention_step_share, sparse_gqa_roofline)
from benchmark.reference import keye_vl2
from benchmark.tests import toy
from benchmark.traffic import video_rows

with open(os.path.join(harness.BENCH_DIR, "configs",
                       "keye_vl_2_30b_a3b.json")) as _fh:
    PUBLISHED = json.load(_fh)
CELL_NAME = "keye_vl_2_30b_a3b.video32k"

#: the committed configuration cut to widths the CPU runs in seconds
#: (tests/test_vl_trunk.py's), float32 at 'highest'
TOY = {**PUBLISHED, "name": "toy_vl", "compute_dtype": "float32",
       "matmul_precision": "highest", "hidden_size": 32,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "moe_intermediate_size": 16, "num_experts": 8,
       "num_experts_per_tok": 2, "vocab_size": 64, "num_hidden_layers": 2,
       "rope_scaling": {"mrope_section": [2, 4, 2], "rope_type": "default",
                        "type": "default"},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                     "q_chunk_size": 512, "topk": 4},
       "vision_config": {"hidden_size": 32, "intermediate_size": 40,
                         "num_hidden_layers": 2, "num_attention_heads": 4,
                         "patch_size": 2, "image_size": 8,
                         "spatial_merge_size": 2, "layer_norm_eps": 1e-6,
                         "rope_theta": 10000},
       "image_token_id": 61, "vision_start_token_id": 62,
       "vision_end_token_id": 63, "sequence_length": 32, "frames": 3,
       "frame_size": 8, "feature_size": 32, "limits": {"feature_gap": 1e-4}}
ENTRY = {"name": "toy_vl", "source": PUBLISHED["source"],
         "file": "benchmark/configs/toy_vl.json",
         "reduced": PUBLISHED["reduced"], "why": "the trunk at toy widths"}
MIX = {"generator": "video_rows", "batch_size": 1, "job_batches": 2,
       "distinct_rows": 4, "frames": 2, "warm_rows": 1,
       "sequence_length": 32, "text_ids": 14, "clip_frames": 3,
       "frame_size": 8}
CELL = {"name": "toy_vl.video", "config": "toy_vl",
        "traffic": "toy_vl_video", "chips": 1,
        "why": "frames of 2 rows of 3 frames and 14 text ids at batchSize 1"}


@pytest.fixture
def root(tmp_path):
    made, peaks = toy.make_root(tmp_path)
    config = {**TOY, "flops_per_image": vf.flops_per_row(TOY)}
    with open(os.path.join(made, ENTRY["file"]), "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(made, "benchmark", "traffic",
                           CELL["traffic"] + ".json"), "w") as fh:
        json.dump(MIX, fh)
    path = os.path.join(made, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(ENTRY)
    bench["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if CELL_NAME in m.get("workloads", []):
            m["workloads"].append(CELL["name"])
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return made, peaks


def test_the_toy_trunk_runs_through_the_whole_of_a_run(root):
    line = toy.run(*root, CELL["name"], seed=3_000_000_011)
    assert line["correct"] is True and line["failed"] == 0
    checks = line["checks"]
    assert checks["rows_off"]["value"] == 0
    assert checks["engine_rows_off"]["value"] == 0
    assert checks["feature_gap"]["value"] < 1e-5
    assert line["traffic"]["tokens_per_job"] == 2 * 32
    assert line["traffic"]["pixel_bytes_per_row"] == 3 * 8 * 8 * 3


def test_the_control_is_not_correct(root):
    line = toy.run(*root, CELL["name"], seed=11, control=True)
    assert line["correct"] is False
    assert line["checks"]["feature_gap"]["value"] > 1e-2


def test_a_traced_toy_run_reports_the_counters(root, monkeypatch):
    monkeypatch.setattr(harness, "DeviceTrace", toy.MadeUpDeviceTrace)
    line = toy.run(*root, CELL["name"], seed=11, trace=True)
    assert line["correct"] is True
    got = line["metrics"]
    # the jax.numpy form reads every key for each query
    assert got["fetched_key_share"]["value"] == pytest.approx(
        32 * 32 / lf.selected_pairs(32, 4))
    # the made-up trace has no kernel's line
    assert not {"frame_attention_roofline", "sparse_gqa_roofline",
                "sa_indexer_roofline", "sparse_attention_step_share"} & set(got)


def test_the_kind_refuses_a_program_without_a_vision_trunk(monkeypatch):
    """The parent's program: the kind says so before anything is drawn."""
    real = builtins.__import__

    def without(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "sparkdl_tpu.models" and fromlist and "vl_trunk" in fromlist:
            raise ImportError("cannot import name 'vl_trunk'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", without)
    with pytest.raises(harness.BenchmarkError, match="no vision-language"):
        kind.program_environment(PUBLISHED)


class _Trace:
    module_s = 10.0
    module_executions = 4

    def __init__(self, ops):
        self.device_ops = ops


def _observations(ops, counters):
    return Observations(window_s=12.0, jobs=[], counters=counters,
                        config=PUBLISHED, peak={"bf16_flops_per_s": 197e12,
                                                "hbm_bytes_per_s": 819e9},
                        chips=1, trace=_Trace(ops))


def test_the_readers_on_a_made_up_window():
    """Four dispatched rows of 120 frames and 32,768 positions: frames
    over the tower's attention kernel, the selected pairs over the
    grouped attention's."""
    t, layers, rows = 32768, 6, 4.0
    counters = {"engine.rows": rows, "vision.frames": rows * 120,
                "vision.patches": rows * 120 * 1024,
                "sparse.indexed_pairs": rows * layers * lf.causal_pairs(t),
                "sparse.selected_pairs": rows * layers * lf.selected_pairs(
                    t, 2048),
                "sparse.fetched_keys": 8.4 * rows * layers * lf.selected_pairs(
                    t, 2048)}
    ops = [("%frame_attention.2 bf16[8,16,1024,72] custom-call", 4.0),
           ("%indexer_scores.3 f32[4096,32768] custom-call", 0.5),
           ("%topk_select.1 s32[4096,2] custom-call", 1.0),
           ("%sparse_attention.7 bf16[32,4096,128] custom-call", 2.0),
           ("%fusion.12 f32[1] fusion", 1.0)]
    obs = _observations(ops, counters)
    flops = rows * 120 * 27 * 4 * 1024 ** 2 * 1152
    assert frame_attention_roofline.read(obs) == pytest.approx(
        100 * flops / 197e12 / 4.0)
    chosen = rows * layers * lf.selected_pairs(t, 2048)
    assert sparse_gqa_roofline.read(obs) == pytest.approx(
        100 * chosen * 4 * 32 * 128 / 197e12 / 2.0)
    scored = rows * layers * lf.causal_pairs(t)
    assert sa_indexer_roofline.read(obs) == pytest.approx(
        100 * scored * 2 * 16 * (64 + 1) / 197e12 / 0.5)
    assert sparse_attention_step_share.read(obs) == pytest.approx(35.0)
    assert fetched_key_share.read(obs) == pytest.approx(8.4)
    empty = _observations([("%fusion.1 f32[1] fusion", 1.0)], {})
    assert all(r.read(empty) is None for r in (
        frame_attention_roofline, sparse_gqa_roofline, sa_indexer_roofline))


def test_flops_by_hand_at_the_cells_widths():
    """The configuration's arithmetic: a frame 989 GFLOP (blocks 1024 x 27 x 30.45 M,
    attention 4 x 1024^2 x 1152 x 27, patch embedding, merger 256 x
    61.3 M), a position-layer 118.3 MFLOP, the pairs of a layer."""
    c = PUBLISHED
    blocks = 1024 * 27 * 2 * (4 * 1152 ** 2 + 2 * 1152 * 4304)
    attention = 4 * 1024 ** 2 * 1152 * 27
    embed = 1024 * 2 * 588 * 1152
    merger = 256 * 2 * (4608 * 4608 + 4608 * 2048)
    assert vf.frame_flops(c) == blocks + attention + embed + merger
    assert 989.0e9 < vf.frame_flops(c) < 989.5e9
    token = 2 * (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 1024
                 + 2048 * 64 + 2048 * 16 + 2048 * 128) + 8 * 6 * 2048 * 768
    assert vf.token_layer_flops(c) == token == 118_292_480
    t = 32768
    pairs = (lf.causal_pairs(t) * 2 * 16 * 65
             + lf.selected_pairs(t, 2048) * 4 * 32 * 128)
    assert vf.flops_per_row(c) == 120 * vf.frame_flops(c) + 6 * (
        t * token + pairs) == c["flops_per_image"]
    # the vision tower is 77% of a row's operations
    assert 0.76 < 120 * vf.frame_flops(c) / c["flops_per_image"] < 0.77
    # the indexer reads a position's 16 query heads and one key head of
    # 64 (bf16) and 16 float32 head weights
    assert vf.indexer_bytes(c, 1) == 17 * 64 * 2 + 16 * 4


def test_the_generators_layout():
    """1,808 text ids (never one of the three vision ids), then 120 x
    (start, 256 placeholders, end): 32,768 ids."""
    ids = video_rows.row_ids(np.random.default_rng(4), PUBLISHED, 1808, 120)
    assert ids.shape == (32768,) and ids.dtype == np.int32
    special = {151652, 151653, 151655}
    assert not special & set(ids[:1808].tolist())
    frames = ids[1808:].reshape(120, 258)
    assert (frames[:, 0] == 151652).all() and (frames[:, -1] == 151653).all()
    assert (frames[:, 1:-1] == 151655).all()
    with open(os.path.join(harness.BENCH_DIR, "traffic",
                           "video_rows_32k.json")) as fh:
        mix = json.load(fh)
    assert (mix["text_ids"], mix["clip_frames"], mix["frame_size"],
            mix["sequence_length"]) == (1808, 120, 448, 32768)



def test_the_committed_file_carries_every_published_width():
    config = PUBLISHED
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6
    assert config["expert_share"] == [0, 1]
    assert "eight-stage pipeline" in config["deployment"]
    assert config["assumed"]["weights"] == keye_vl2.DRAW
    assert config["vision_config"]["hidden_size"] == 1152
    assert config["flops_per_image"] == keye_vl2.flops_per_row(config)
    assert config["reduced"] == ["num_hidden_layers"]
    # the published widths, as the source's config.json gives them
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"], config["vocab_size"]) == (
                2048, 32, 4, 128, 128, 8, 768, 151936)
    assert config["sa_config"]["topk"] == 2048
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
