"""``work.py`` counts against figures worked by hand."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def test_benchmark_resnet50_operations_match_the_hand_worked_figures():
    from benchmark.configs.resnet50 import work

    config = _config("resnet50")
    layers = list(work.conv_layers(config))
    assert len(layers) == 53                    # 1 + 3*16 blocks + 4 projections
    assert layers[0] == (7, 3, 64, 112)
    # by hand, multiply-accumulates of one 224x224 image, stage by stage:
    stem = 7 * 7 * 3 * 64 * 112 * 112                                  # 118,013,952
    stage0 = ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
              + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256)) * 56 * 56     # 3 blocks
    assert stem == 118_013_952
    assert stage0 == 212_992 * 3_136 == 667_942_912
    first_five = sum(k * k * a * b * s * s for k, a, b, s in layers[:5])
    assert first_five == stem + (64 * 64 + 9 * 64 * 64 + 64 * 256
                                 + 64 * 256) * 56 * 56
    macs = work.forward_macs_per_image(config)
    assert macs == 4_089_184_256                # the published ~4.1 GMACs (v1.5)
    flops = work.train_flops_per_image(config)
    assert flops == 3 * 2 * macs - 2 * stem     # no input gradient for the stem
    assert flops / 1e9 == pytest.approx(24.3, abs=0.05)
    step = work.step_work(config, 128)
    assert step["flops"] == 128 * flops
    assert step["bytes"] == 128 * 224 * 224 * 3 * 4 + 7 * 4 * 25_557_032
    assert step["examples"] == 128


def test_benchmark_resnet50_parameter_count_is_the_published_one():
    import numpy as np

    from benchmark.configs.resnet50 import reference

    config = _config("resnet50")
    shapes = reference.leaf_shapes(config)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == \
        config["parameters"] == 25_557_032
    assert len(shapes) == 161


def test_benchmark_widedeep_operations_match_the_hand_worked_figures():
    from benchmark.configs.criteo_widedeep import work

    config = _config("criteo_widedeep")
    # 26 * 32 + 13 = 845 inputs -> 1024 -> 512 -> 256 -> 1
    assert work.mlp_macs_per_row(config) == \
        845 * 1024 + 1024 * 512 + 512 * 256 + 256 == 1_520_896
    assert work.mlp_parameters(config) == 1_520_896 + 1024 + 512 + 256 + 1
    step = work.step_work(config, 1024)
    assert step["flops"] == 6 * 1024 * 1_520_896
    touched = 1024 * 26
    assert step["bytes"] == (5 * 4 * 33 * touched + 1024 * 160
                             + 28 * 1_522_689)
