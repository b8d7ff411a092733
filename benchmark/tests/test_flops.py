"""``flops.py``'s count against multiply-accumulates written out by
hand for named layers, against the number pinned in each configuration
file, and against the lockfile's XLA count for orientation."""

import json
import os

import pytest

from benchmark import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")

# (config, layer) -> 2 x MACs, from the published shapes:
# kernel rows x cols x channels in x channels out x output rows x cols
BY_HAND = {
    # 3x3/2 VALID stem on 299x299x3 -> 149x149x32
    ("inceptionv3", "stem_conv1"): 2 * 3 * 3 * 3 * 32 * 149 * 149,
    # 5x5 of mixed0 on 35x35x48 -> 35x35x64
    ("inceptionv3", "mixed0_b5x5_2"): 2 * 5 * 5 * 48 * 64 * 35 * 35,
    # 1x7 of mixed4 on 17x17x128 -> 17x17x128
    ("inceptionv3", "mixed4_b7x7_2"): 2 * 1 * 7 * 128 * 128 * 17 * 17,
    # 3x3/2 VALID of mixed8, 17x17x192 -> 8x8x320
    ("inceptionv3", "mixed8_b3x3_2"): 2 * 3 * 3 * 192 * 320 * 8 * 8,
}


@pytest.mark.parametrize("config,layer", sorted(BY_HAND))
def test_a_named_layer_matches_the_count_by_hand(config, layer):
    by_name = {g.name: g for g in flops.conv_geometries(config)}
    g = by_name[layer]
    assert flops.conv_flops(g.kh, g.kw, g.cin, g.cout, g.out_h,
                            g.out_w) == BY_HAND[(config, layer)]


@pytest.mark.parametrize("config,convs", [("inceptionv3", 94)])
def test_the_pinned_count_is_this_count(config, convs):
    with open(os.path.join(CONFIGS, config + ".json")) as fh:
        pinned = json.load(fh)
    assert len(flops.conv_geometries(config)) == convs == pinned["conv_layers"]
    assert flops.flops_per_image(config) == pinned["flops_per_image"]


@pytest.mark.parametrize("config,model", [("inceptionv3", "InceptionV3")])
def test_within_a_few_percent_of_the_lockfiles_xla_count(config, model):
    """XLA's cost analysis leaves out the taps that fall on padding and
    adds the elementwise work; the two counts agree to 5%."""
    lockfile = pytest.importorskip("sparkdl_tpu.analysis.program.lockfile")
    xla = lockfile.zoo_gflop_per_img()[model] * 1e9
    assert abs(flops.flops_per_image(config) - xla) / xla < 0.05
