"""The kind ``expert_trunk`` at toy widths, entered as data beside the toy
root's cells and driven through the whole of ``run_cell`` on the CPU:
correct as it is, not correct with the control in its place or with a
fault planted; the count of operations by hand; the four readers on a
made-up reduced trace; the committed configuration against the catalog."""

import json
import os

import pytest

from benchmark import expert_trunk_flops as ef
from benchmark import harness, trace_reduce
from benchmark.layer_metrics import Observations
from benchmark.reference import trinity
from benchmark.tests import planted_experts, toy

with open(os.path.join(harness.BENCH_DIR, "configs",
                       "trinity_large_preview.json")) as _fh:
    PUBLISHED = json.load(_fh)
CELL_NAME = "trinity_large_preview.rows16k"

#: the committed configuration cut to widths the CPU runs in seconds: one
#: dense layer, a sliding, a full and a sliding expert layer; window 16 of
#: 64 positions; 8 experts held of 16; float32 at 'highest', where the
#: program reads 1e-6 of the feature scale and the int8 control some tenths
TOY = {**PUBLISHED, "name": "toy_experts", "compute_dtype": "float32",
       "matmul_precision": "highest", "hidden_size": 64, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 160, "moe_intermediate_size": 48,
       "num_experts": 8, "expert_share": [0, 2], "sliding_window": 16,
       "vocab_size": 97, "num_hidden_layers": 4,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "sequence_length": 64, "feature_size": 64,
       "limits": {"feature_gap": 1e-4}}
ENTRY = {"name": "toy_experts", "source": PUBLISHED["source"],
         "file": "benchmark/configs/toy_experts.json",
         "reduced": PUBLISHED["reduced"], "why": "the trunk at toy widths"}
MIX = {"generator": "token_rows", "batch_size": 2, "job_batches": 2.5,
       "distinct_rows": 4, "frames": 2, "warm_rows": 2,
       "sequence_length": 64}
#: the toy's default seed, 7, draws a row with one token whose fourth and
#: fifth scores (bias added) lie 1e-6 apart: float32 sums in another
#: order choose the other expert, and at 64 positions one token is 3% of
#: the feature's scale.  That is the mechanism PERF.md section 2 prices
#: at the cell's size; a parity test wants a seed without it
SEED = 11
CELL = {"name": "toy_experts.rows", "config": "toy_experts",
        "traffic": "toy_experts_rows", "chips": 1,
        "why": "frames of 5 rows of 64 token ids at batchSize 2"}


@pytest.fixture
def root(tmp_path):
    made, peaks = toy.make_root(tmp_path)
    config = {**TOY, "flops_per_image": trinity.flops_per_row(TOY)}
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
        if CELL_NAME in m["workloads"]:
            m["workloads"].append(CELL["name"])
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return made, peaks


def test_the_toy_trunk_runs_through_the_whole_of_a_run(root):
    line = toy.run(*root, CELL["name"], seed=SEED)
    assert line["correct"] is True and line["failed"] == 0
    checks = line["checks"]
    assert checks["rows_off"]["value"] == 0
    assert checks["engine_rows_off"]["value"] == 0
    assert checks["feature_gap"]["value"] < 1e-5
    assert line["traffic"]["tokens_per_job"] == 5 * 64
    assert line["jobs"] >= 1 and line["attempted"] == 5 * line["jobs"]


def test_the_control_is_not_correct(root):
    line = toy.run(*root, CELL["name"], seed=SEED, control=True)
    assert line["correct"] is False
    assert line["checks"]["feature_gap"]["value"] > 1e-2
    assert line["checks"]["rows_off"]["value"] == 0


@pytest.mark.parametrize("fault", planted_experts.FAULTS)
def test_a_planted_fault_is_not_correct(root, fault):
    with planted_experts.plant(fault):
        line = toy.run(*root, CELL["name"], seed=SEED)
    assert line["correct"] is False
    assert line["checks"]["rows_off"]["value"] == 0
    assert line["checks"]["feature_gap"]["value"] > 1e-3


def test_a_traced_toy_run_reports_the_counters_of_the_routing(root,
                                                              monkeypatch):
    monkeypatch.setattr(harness, "DeviceTrace", toy.MadeUpDeviceTrace)
    line = toy.run(*root, CELL["name"], seed=SEED, trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert {"pack_in_ms_per_image", "pack_out_ms_per_image",
            "transform_self_share", "pad_row_share", "step_mfu",
            "expert_pairs_per_token"} <= got
    # four of sixteen a token, eight held: two pairs a token a layer
    assert 1.6 < line["metrics"]["expert_pairs_per_token"]["value"] < 2.4
    assert line["metrics"]["pad_row_share"]["value"] == pytest.approx(
        100 * 1 / 6)
    # the made-up trace has no kernel's line
    assert not {"grouped_matmul_roofline", "grouped_matmul_step_share",
                "banded_attention_roofline"} & got


# -- the count of operations ----------------------------------------------------

def test_the_primitives_by_hand():
    # 5 positions under a window of 3: 1 + 2 + 3 + 3 + 3 pairs
    assert ef.banded_attention_pairs(5, 3) == 12
    assert ef.banded_attention_pairs(5) == ef.banded_attention_pairs(5, 9) \
        == 15
    assert ef.attention_flops(2, 4, 12) == 12 * 2 * (2 * 4 + 2 * 4)
    assert ef.routed_experts(TOY) == 16
    assert ef.expected_pairs_per_token(TOY) == 2.0
    assert ef.pair_flops(TOY) == 2 * 3 * 64 * 48
    # 8 held experts' three matrices once a call, a pair's rows in and out
    assert ef.grouped_matmul_bytes(TOY, pairs=10, layer_calls=3) == 2 * (
        3 * 3 * 8 * 64 * 48 + 10 * 2 * 64)
    assert ef.layer_windows(TOY) == [16, 16, None, 16]


def test_a_toy_trunk_by_hand():
    d, t = 64, 64
    attention = 2 * t * (d * 64 * 2 + d * 32 * 2 + 64 * d)   # q, gate, k, v, o
    dense = 2 * t * 3 * d * 160
    experts = 2 * t * (d * 16 + 3 * d * 48) + t * 2 * (2 * 3 * d * 48)
    window = 16 * 17 // 2 + (t - 16) * 16
    pairs = 3 * window + t * (t + 1) // 2
    assert trinity.flops_per_row(TOY) == (
        4 * attention + dense + 3 * experts + 4 * 16 * 4 * pairs)


def test_the_committed_file_holds_the_count_at_the_published_widths():
    config = PUBLISHED
    t = 16384
    attention = 2 * t * (2 * 3072 * 768 + 2 * 3072 * 128 + 768 * 3072)
    dense = 2 * t * 3 * 3072 * 12288
    experts = (2 * t * (3072 * 256 + 3 * 3072 * 3072)
               + t * (2 * 3 * 3072 * 3072) // 2)        # half a pair a token
    window = 4096 * 4097 // 2 + (t - 4096) * 4096
    assert (t * (t + 1) // 2, window) == (134225920, 58722304)
    pairs = 4 * window + t * (t + 1) // 2
    assert config["flops_per_image"] == (
        5 * attention + dense + 4 * experts + 4 * 128 * 6 * pairs
    ) == 11802620461056
    assert trinity.flops_per_row(config) == config["flops_per_image"]
    # a dispatch of two rows: 16,384 pairs a layer against 1.81 GB of
    # experts: 4.7 ms by operations, 2.5 by memory
    assert ef.grouped_matmul_flops(config, 16384) / 197e12 == pytest.approx(
        4.71e-3, rel=1e-2)
    assert ef.grouped_matmul_bytes(config, 16384, 1) / 819e9 == pytest.approx(
        2.46e-3, rel=1e-2)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 60, "num_dense_layers": 6,
           "num_experts": 256, "num_attention_heads": 48,
           "num_key_value_heads": 8}


def test_the_committed_file_carries_every_published_width():
    config = PUBLISHED
    assert {k: v for k, v in config["published"].items()
            if k != "layer_types"} == REDUCED
    assert len(config["published"]["layer_types"]) == 60
    assert config["layer_types"] == config["published"]["layer_types"][5:10]
    assert config["layer_types"].count("full_attention") == 1
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["vocab_size"]) == (3072, 128, 12288, 3072, 4, 4096, 200192)
    assert config["expert_share"] == [0, 8]
    assert "eight chips share each layer" in config["deployment"]
    assert config["assumed"]["weights"] == trinity.DRAW
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Trinity-Large-Preview")
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == set(REDUCED) | {"layer_types"}
    assert config["published"] == {k: row["config"][k] for k in changed}
    assert config["source"] == row["source_url"]


# -- the readers ----------------------------------------------------------------

def _observed(ops, rows=20.0, pad=0.0, pairs=655360.0, executions=10):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    reduced = trace_reduce.Reduced(window_s=10.0, busy_s=9.0, module_s=8.0,
                                   module_executions=executions,
                                   device_ops=ops, idle_gaps=[])
    counters = {"engine.rows": rows, "engine.pad_rows": pad,
                "moe.pairs": pairs, "moe.tokens": rows * 16384 * 4}
    return Observations(window_s=10.0, jobs=[], config=PUBLISHED, peak=peak,
                        counters=counters, chips=1, trace=reduced)


OPS = [("%while.22 (s32[], f32[2,16384,3072]) while", 7.9),
       ("%grouped_matmul.6 f32[24576,3072] custom-call", 0.4),
       ("%causal_attention.10 bf16[2,16384,768] custom-call", 0.5)]


def test_the_readers_know_the_kernels_by_name():
    from benchmark.layer_metrics import (banded_attention_roofline,
                                         expert_pairs_per_token,
                                         grouped_matmul_roofline,
                                         grouped_matmul_step_share)

    obs = _observed(OPS)
    # 655,360 pairs: bound by operations; the experts' 1.81 GB forty times
    flops = 655360 * 2 * 3 * 3072 * 3072
    assert flops / 197e12 > (40 * 3 * 32 * 3072 * 3072 * 2
                             + 655360 * 2 * 3072 * 2) / 819e9
    assert grouped_matmul_roofline.read(obs) == pytest.approx(
        100 * flops / 197e12 / 0.4)
    assert grouped_matmul_step_share.read(obs) == pytest.approx(100 * 0.4 / 8)
    pairs = 4 * 58722304 + 134225920
    assert banded_attention_roofline.read(obs) == pytest.approx(
        100 * 20 * 4 * 128 * 6 * pairs / 197e12 / 0.5)
    assert expert_pairs_per_token.read(obs) == 0.5
    for share in (grouped_matmul_roofline, banded_attention_roofline):
        assert 0 < share.read(obs) < 100
    # pad rows are dispatched: their pairs are the kernel's work too
    assert grouped_matmul_roofline.read(_observed(OPS, rows=10.0, pad=10.0,
                                                  pairs=327680.0)) \
        == pytest.approx(grouped_matmul_roofline.read(obs))


@pytest.mark.parametrize("ops", [
    [("%fusion.1 bf16[32768,24576] fusion", 5.0)],
    [("%grouped_matmul_like.1 bf16[8] custom-call", 1.0),
     ("%custom-call.3 bf16[8] custom-call", 1.0)],
])
def test_a_kernel_that_is_not_among_the_ten_reads_none(ops):
    from benchmark.layer_metrics import (banded_attention_roofline,
                                         grouped_matmul_roofline,
                                         grouped_matmul_step_share)

    for reader in (grouped_matmul_roofline, grouped_matmul_step_share,
                   banded_attention_roofline):
        assert reader.read(_observed(ops)) is None
        assert reader.read(_observed(OPS)._replace(trace=None)) is None


def test_a_program_that_counts_no_pairs_reads_none_never_zero():
    """The parent's program has the engine's counters and no ``moe.*``."""
    from benchmark.layer_metrics import (expert_pairs_per_token,
                                         grouped_matmul_roofline)

    obs = _observed(OPS)
    bare = obs._replace(counters={"engine.rows": 20.0})
    assert expert_pairs_per_token.read(bare) is None
    assert grouped_matmul_roofline.read(bare) is None
    assert grouped_matmul_roofline.read(
        obs._replace(counters={**obs.counters, "moe.pairs": 0.0})) is None


def test_the_new_readers_read_nothing_in_the_other_cells():
    from benchmark.layer_metrics import (banded_attention_roofline,
                                         expert_pairs_per_token,
                                         grouped_matmul_roofline)

    for name in ("inceptionv3.jpeg", "falcon_h1_34b.rows4k"):
        cell = harness.load_cell(harness.ROOT, name)
        obs = _observed(OPS)._replace(
            config=cell.config, counters={"engine.rows": 20.0})
        assert banded_attention_roofline.read(obs) is None
        assert grouped_matmul_roofline.read(obs) is None
        assert expert_pairs_per_token.read(obs) is None


def test_the_cell_is_on_the_lists_the_issue_names():
    cell = harness.load_cell(harness.ROOT, CELL_NAME)
    assert cell.chips == 1 and cell.traffic["batch_size"] == 2
    listed = {m["name"] for m in cell.per_layer}
    assert {"grouped_matmul_roofline", "grouped_matmul_step_share",
            "banded_attention_roofline", "expert_pairs_per_token",
            "step_mfu", "device_idle_share", "pad_row_share"} <= listed
    assert not {"attention_roofline", "dispatch_starved_share",
                "ssd_scan_roofline", "decode_ms_per_image"} & listed
