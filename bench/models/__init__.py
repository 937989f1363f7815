"""The client models of the lane reference, one module a model family.

A configuration file names its family under `client_model`, and
`bench/run.py` resolves `bench/models/<client_model>.py` under the
checkout's root: a new client architecture comes in as a new module, a
configuration, a traffic mix and limits, with no edit to a file the
benchmark already has.  Every function of a module takes the
configuration's `shapes` (a dict; its `params` is P, the payload's size)
and imports nothing of the program under test:

- `check_shapes(model, shapes)`: raises `ValueError` where the program's
  `ModelConfig` differs from what the configuration states;
- `init(key, shapes)`: the payload's leaves in flat order, what a vehicle
  trains and sends.  Weights that no vehicle trains, such as a frozen
  base, are the module's own;
- `client_data(key, dataset, shapes, regions, n, classes_per_client)`:
  `(x, y, test_x, test_y)`, the ``n`` samples of each vehicle of the given
  home regions (leading axes (vehicles, n)) and the global test set;
- `loss(leaves, x, y, shapes, dtype)`: the mean loss and the accuracy of a
  batch, its forward pass rounded to ``dtype`` (`float32` or `bfloat16`);
- `null_loss(shapes)`: the loss of a model that knows nothing, in nats;
- `train_flops(shapes)`, `eval_flops(shapes)`: forward FLOPs of one
  training sample and of one test sample; `eval_samples(shapes)`: the
  number of test samples an eval round scores.
"""
from __future__ import annotations

import functools
import importlib.util
import os


@functools.lru_cache(maxsize=None)
def load(path: str):
    """The module in the file at ``path``, run once a path: a client model
    here, or a metric's reader (`bench/run.py`)."""
    parent, name = os.path.split(os.path.splitext(path)[0])
    spec = importlib.util.spec_from_file_location(
        f"bench_{os.path.basename(parent)}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
