"""``sequence_flops`` against a count by hand, at the toy size and at the
published widths, and the committed file against the count."""

import json
import os

import pytest

from benchmark import harness
from benchmark import sequence_flops as sf
from benchmark.reference import falcon_h1


def _committed():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "falcon_h1_34b.json")) as fh:
        return json.load(fh)


def test_the_primitives_by_hand():
    # one [3, 5] matrix at 7 positions: 3*5 multiply-accumulates each
    assert sf.matmul_flops([(3, 5)], 7) == 2 * 3 * 5 * 7
    # 2 heads of 4 over 3 positions: 6 pairs, scores and values
    assert sf.causal_attention_flops(2, 4, 3) == 6 * 2 * (2 * 4 + 2 * 4)
    assert sf.causal_attention_bytes(2, 1, 4, 3, 2) == 2 * 3 * 4 * 3 * 2
    # a [4, 8] state a head: update, read-out, D x
    assert sf.scan_flops(2, 4, 8, 3) == 3 * 2 * (2 * 32 + 2 * 32 + 2 * 4)
    assert sf.scan_bytes(2, 4, 1, 8, 3, 2) == 3 * (2 * 8 * 2 + 2 * 8 * 2
                                                   + 2 * 4)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert sf.roofline_seconds(200.0, 10.0, peak) == 2.0   # by operations
    assert sf.roofline_seconds(200.0, 50.0, peak) == 5.0   # by memory


def test_a_toy_trunk_by_hand():
    from benchmark.tests.test_token_trunk import TOY

    d, ff, t = 64, 128, 32
    in_proj = 2 * 64 + 2 * 2 * 16 + 4
    by_hand = 2 * t * (d * 64 + 2 * d * 32 + 64 * d      # q, k, v, o
                       + d * in_proj + 64 * d            # in_proj, out_proj
                       + 3 * d * ff)                     # gate, up, down
    by_hand += 4 * 16 * 4 * (t * (t + 1) // 2)           # attention
    by_hand += (4 * 16 * 16 + 2 * 16) * 4 * t            # the recurrence
    assert falcon_h1.flops_per_row(TOY) == 2 * by_hand   # two blocks


def test_the_committed_file_holds_the_count_at_the_published_widths():
    config = _committed()
    t = 4096
    block = 2 * t * (5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
                     + 5120 * 9248 + 4096 * 5120 + 3 * 5120 * 21504)
    block += 4 * 128 * 20 * (t * (t + 1) // 2)
    block += (4 * 128 * 256 + 2 * 128) * 32 * t
    assert config["flops_per_image"] == 6 * block == 21758094606336
    assert falcon_h1.flops_per_row(config) == config["flops_per_image"]
    # about 18.5 KB a position a block: bound by memory at the chip's peaks
    nbytes = sf.scan_bytes(32, 128, 2, 256, t, 2)
    assert nbytes == t * 18560
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert sf.roofline_seconds(sf.scan_flops(32, 128, 256, t), nbytes,
                               peak) == nbytes / 819e9


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_committed_file_carries_every_published_width():
    config = _committed()
    assert config["published"] == {"num_hidden_layers": 72}
    assert (config["hidden_size"], config["intermediate_size"]) == (5120, 21504)
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["source"] == row["source_url"]
