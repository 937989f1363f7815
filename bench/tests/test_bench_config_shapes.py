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
    the data files are held to the model registry here, off the chip,
    through the shape check of the client model each configuration names."""
    cell = next(w["name"] for w in run.load_json(os.path.join(ROOT, "BENCHMARK.json"))[
        "workloads"] if w["config"] == config)
    c = run.load_cell(cell)
    assert c["model_file"] == os.path.join(ROOT, "bench", "models",
                                           c["cfg"]["client_model"] + ".py")
    run.model_config(c)  # raises SystemExit on a mismatch
