"""The benchmark's operation and byte counts against hand counts."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops  # noqa: E402

CNN = dict(image_shape=(32, 32, 3), channels=(32, 64), d_ff=256, num_classes=10)
MLP = dict(image_shape=(28, 28, 1), channels=(), d_ff=200, num_classes=10)
P_CNN = 1_070_794


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(ROOT, "bench", "workloads", name + ".json")) as f:
        return json.load(f)


def test_cnn_forward_flops_hand_count():
    # conv1 32x32x(3*9)x32, conv2 16x16x(32*9)x64, fc 4096x256, fc 256x10
    hand = 2 * (32 * 32 * 27 * 32 + 16 * 16 * 288 * 64 + 4096 * 256 + 256 * 10)
    assert hand == 13_308_928
    assert flops.forward_flops(**CNN) == hand


@pytest.mark.parametrize("shapes, params", [(MLP, 159_010), (CNN, P_CNN)])
def test_param_counts(shapes, params):
    assert flops.param_count(**shapes) == params


@pytest.mark.parametrize("config", ["mnist-mlp-paper"])
def test_config_states_the_param_count_of_its_shapes(config):
    s = _cfg(config)["shapes"]
    assert flops.param_count(s["image_shape"], s["channels"], s["d_ff"],
                             s["num_classes"]) == s["params"]


@pytest.mark.parametrize("rule, nbytes", [
    # rows read (fp32) + their weights + params in/out (+ m, v in/out)
    ("fedavg", 10 * P_CNN * 4 + 10 * 4 + 2 * P_CNN * 4),
    ("fedadam", 10 * P_CNN * 4 + 10 * 4 + 6 * P_CNN * 4),
    ("fedbuff", 18 * P_CNN * 4 + 18 * 4 + 2 * P_CNN * 4),
])
def test_server_update_bytes_per_rule(rule, nbytes):
    assert flops.server_update_cost(rule, 10, 8, P_CNN, 4)[1] == nbytes


def test_paper_lane_model_flops_hand_count():
    shapes = dict(CNN, params=P_CNN)
    fl = _cfg("mnist-mlp-paper")["fl"]  # the paper's deployment, as the CNN runs it
    mix = dict(_mix("paper-full-grid"), rounds=5, eval_every=1)
    fwd = 13_308_928
    train = 5 * 10 * (512 // 64) * 64 * 3 * fwd  # 5 rounds x 10 clients x 8 steps
    warm = 100 * 64 * 3 * fwd  # one batch on every vehicle
    ev = 5 * 2_000 * fwd  # an eval every round
    got = flops.lane_model_flops(shapes, fl, mix)
    assert got == train + warm + ev == 1_410_746_368_000


def test_mlp_paper_lane_model_flops_hand_count():
    cfg, mix = _cfg("mnist-mlp-paper"), _mix("paper-full-grid")
    fwd = 2 * (784 * 200 + 200 * 10)
    train = 10 * 10 * 8 * 64 * 3 * fwd  # 10 rounds x 10 clients x 8 steps of 64
    warm = 100 * 64 * 3 * fwd  # one batch on every vehicle
    ev = 2 * 2_000 * fwd  # evals after rounds 5 and 10
    assert flops.lane_model_flops(cfg["shapes"], cfg["fl"], mix) == train + warm + ev


@pytest.mark.parametrize("rounds, every, evals", [(10, 5, 2), (10, 1, 10), (7, 5, 2),
                                                  (3, 5, 1)])
def test_eval_rounds_count_the_last_round(rounds, every, evals):
    assert flops.eval_rounds({"rounds": rounds, "eval_every": every}) == evals


def test_roofline_share_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(100.0, 50.0, 10.0, peak) == pytest.approx(50.0)
    assert flops.roofline_share(1000.0, 5.0, 10.0, peak) == pytest.approx(100.0)
    assert flops.roofline_share(1.0, 1.0, 0.0, peak) is None
