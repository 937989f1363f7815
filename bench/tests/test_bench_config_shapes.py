"""Each configuration's model shapes against the model registry, off the chip."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402


def configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {c["name"]: c for c in json.load(f)["configs"]}


@pytest.mark.parametrize("config", sorted(configs()))
def test_every_config_states_its_models_shapes(config):
    """A run refuses a model whose shapes differ from the configuration's;
    the data files are held to the model registry here, off the chip."""
    from repro.configs import get_config

    c = run.load_json(os.path.join(ROOT, configs()[config]["file"]))
    model, shapes = get_config(c["model"]), c["shapes"]
    assert list(model.image_shape) == shapes["image_shape"]
    assert list(model.channels) == shapes["channels"]
    assert (model.d_ff, model.num_classes) == (shapes["d_ff"], shapes["num_classes"])
