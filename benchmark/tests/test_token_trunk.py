"""The kind ``token_trunk`` at toy widths, entered as data beside the
toy root's cells and driven through the whole of ``run_cell`` on the
CPU: correct as it is, not correct with the control in its place or with
a fault planted; the three readers that know the kernels by name; the
committed configuration against the catalog's arithmetic."""

import json
import os

import pytest

from benchmark import harness, trace_reduce
from benchmark.layer_metrics import Observations
from benchmark.tests import planted_trunk, toy

with open(os.path.join(harness.BENCH_DIR, "configs",
                       "falcon_h1_34b.json")) as _fh:
    PUBLISHED = json.load(_fh)

#: the committed configuration cut to widths the CPU runs in a second;
#: float32 at 'highest', where the program reads 1e-6 of the feature
#: scale and the int8 control a few hundredths
TOY = {**PUBLISHED, "name": "toy_trunk", "compute_dtype": "float32",
       "matmul_precision": "highest", "hidden_size": 64,
       "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "mamba_d_ssm": 64,
       "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_n_groups": 2,
       "mamba_d_state": 16, "mamba_chunk_size": 8, "vocab_size": 97,
       "num_hidden_layers": 2, "sequence_length": 32, "feature_size": 64,
       "limits": {"feature_gap": 1e-4}}
ENTRY = {"name": "toy_trunk", "source": PUBLISHED["source"],
         "file": "benchmark/configs/toy_trunk.json",
         "reduced": PUBLISHED["reduced"], "why": "the trunk at toy widths"}
MIX = {"generator": "token_rows", "batch_size": 4, "job_batches": 2.5,
       "distinct_rows": 6, "frames": 2, "warm_rows": 2,
       "sequence_length": 32}
CELL = {"name": "toy_trunk.rows", "config": "toy_trunk",
        "traffic": "toy_trunk_rows", "chips": 1,
        "why": "frames of 10 rows of 32 token ids at batchSize 4"}


@pytest.fixture
def root(tmp_path):
    from benchmark.reference import falcon_h1

    made, peaks = toy.make_root(tmp_path)
    config = {**TOY, "flops_per_image": falcon_h1.flops_per_row(TOY)}
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
        if "falcon_h1_34b.rows4k" in m["workloads"]:
            m["workloads"].append(CELL["name"])
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return made, peaks


def test_the_toy_trunk_runs_through_the_whole_of_a_run(root):
    line = toy.run(*root, CELL["name"])
    assert line["correct"] is True and line["failed"] == 0
    checks = line["checks"]
    assert checks["rows_off"]["value"] == 0
    assert checks["engine_rows_off"]["value"] == 0
    assert checks["feature_gap"]["value"] < 1e-5
    assert line["traffic"]["tokens_per_job"] == 10 * 32
    assert line["jobs"] >= 1 and line["attempted"] == 10 * line["jobs"]


def test_the_control_is_not_correct(root):
    line = toy.run(*root, CELL["name"], control=True)
    assert line["correct"] is False
    assert line["checks"]["feature_gap"]["value"] > 1e-3
    assert line["checks"]["rows_off"]["value"] == 0


@pytest.mark.parametrize("fault", planted_trunk.FAULTS)
def test_a_planted_fault_is_not_correct(root, fault):
    with planted_trunk.plant(fault):
        line = toy.run(*root, CELL["name"])
    assert line["correct"] is False
    assert line["checks"]["rows_off"]["value"] == 0
    assert line["checks"]["feature_gap"]["value"] > 1e-3


def test_a_traced_toy_run_reports_the_spans_of_the_stage(root, monkeypatch):
    monkeypatch.setattr(harness, "DeviceTrace", toy.MadeUpDeviceTrace)
    line = toy.run(*root, CELL["name"], trace=True)
    assert line["correct"] is True
    got = set(line["metrics"])
    assert {"pack_in_ms_per_image", "pack_out_ms_per_image",
            "transform_self_share", "pad_row_share", "step_mfu"} <= got
    assert line["metrics"]["pad_row_share"]["value"] == pytest.approx(
        100 * 2 / 12)
    # the made-up trace has no kernel's line
    assert not {"ssd_scan_roofline", "ssd_scan_step_share",
                "attention_roofline"} & got


def _observed(ops, rows=16.0, pad=8.0):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    reduced = trace_reduce.Reduced(window_s=10.0, busy_s=9.0, module_s=8.0,
                                   module_executions=3, device_ops=ops,
                                   idle_gaps=[])
    return Observations(window_s=10.0, jobs=[], config=PUBLISHED, peak=peak,
                        counters={"engine.rows": rows, "engine.pad_rows": pad},
                        chips=1, trace=reduced)


def test_the_readers_know_the_kernels_by_name():
    from benchmark import sequence_flops as sf
    from benchmark.layer_metrics import (attention_roofline,
                                         ssd_scan_roofline,
                                         ssd_scan_step_share)

    ops = [("%fusion.1 bf16[32768,21504] fusion", 5.0),
           ("%ssd_scan.1 bf16[8,4096,4096] custom-call", 0.4),
           ("%causal_attention.1 bf16[8,4096,2560] custom-call", 0.5)]
    obs = _observed(ops)
    c = PUBLISHED
    calls = 24 * c["num_hidden_layers"]
    scan_bytes = 4096 * (2 * 4096 * 2 + 2 * 512 * 2 + 32 * 4)
    assert sf.scan_bytes(32, 128, 2, 256, 4096, 2) == scan_bytes
    # the scan is bound by memory, attention by operations
    assert ssd_scan_roofline.read(obs) == pytest.approx(
        100 * calls * scan_bytes / 819e9 / 0.4)
    assert ssd_scan_step_share.read(obs) == pytest.approx(100 * 0.4 / 8.0)
    pairs = 4096 * 4097 // 2
    assert attention_roofline.read(obs) == pytest.approx(
        100 * calls * 4 * 128 * 20 * pairs / 197e12 / 0.5)
    for share in (ssd_scan_roofline, attention_roofline):
        assert 0 < share.read(obs) < 100


@pytest.mark.parametrize("ops", [
    [("%fusion.1 bf16[32768,21504] fusion", 5.0)],
    [("%ssd_scan_like.1 bf16[8] custom-call", 1.0),
     ("%custom-call.3 bf16[8] custom-call", 1.0)],
])
def test_a_kernel_that_is_not_among_the_ten_reads_none(ops):
    from benchmark.layer_metrics import (attention_roofline,
                                         ssd_scan_roofline,
                                         ssd_scan_step_share)

    for reader in (ssd_scan_roofline, ssd_scan_step_share,
                   attention_roofline):
        assert reader.read(_observed(ops)) is None
        assert reader.read(_observed(ops)._replace(trace=None)) is None


def test_the_new_readers_read_nothing_in_an_image_cell():
    from benchmark.layer_metrics import attention_roofline, ssd_scan_roofline

    cell = harness.load_cell(harness.ROOT, "inceptionv3.jpeg")
    ops = [("%ssd_scan.1 bf16[8] custom-call", 1.0),
           ("%causal_attention.1 bf16[8] custom-call", 1.0)]
    obs = _observed(ops)._replace(config=cell.config)
    assert ssd_scan_roofline.read(obs) is None
    assert attention_roofline.read(obs) is None
