"""The lane reference against its golden output, bit for bit: every
`Metrics` field of eight small lanes on the CPU (an MLP and a CNN image
classifier, the CNN training in bfloat16; fedavg, fedadam and fedbuff;
contextual and data elections; ring and rush-hour roads; two fedbuff lanes
that park a straggler and drain it).  `data/reference-golden.json` holds
each lane's cell, seed and output, as the reference computed them before
its client model moved into `bench/models/image_classifier.py`."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402

GOLDEN = os.path.join(ROOT, "bench", "tests", "data", "reference-golden.json")
MODEL = os.path.join(ROOT, "bench", "models", "image_classifier.py")


def golden():
    with open(GOLDEN) as f:
        return json.load(f)


def lane_of(g, rec):
    cell, common = g["cells"][rec["cell"]], g["common"]
    fl = cell["fl"]
    return reference.Lane(
        cell["dataset"], MODEL, reference.pairs(cell["shapes"]), reference.pairs(fl),
        tuple(cell["strategies"]), tuple(cell["aggregators"]), common["rounds"],
        common["eval_every"], common["warmup"], fl["compute_dtype"], fl["param_dtype"],
        matmul=common["matmul"], strategy=rec["strategy"], aggregator=rec["aggregator"],
        scenario=rec["scenario"])


LANES = golden()["lanes"]


@pytest.mark.parametrize("index", range(len(LANES)), ids=[
    f"{r['cell']}-{r['strategy']}-{r['aggregator']}-{r['scenario']}" for r in LANES])
def test_reference_lane_is_bit_equal_to_its_golden_output(index):
    g = golden()
    rec = g["lanes"][index]
    got = reference.run_lane(lane_of(g, rec), rec["seed"])
    assert set(got._fields) == set(rec["metrics"])
    for field in got._fields:
        want = rec["metrics"][field]
        a = np.asarray(getattr(got, field))
        b = np.asarray(want["values"], dtype=want["dtype"])
        assert a.dtype == b.dtype, field
        np.testing.assert_array_equal(a, b, err_msg=field)  # NaN where NaN
        if a.dtype.kind == "f":
            assert np.array_equal(np.signbit(a), np.signbit(b)), field
