"""The FL kernels compile for a described TPU v5e at ``chip_smoke.py``'s shapes.

Interpret mode never applies Mosaic's lowering rules: an integer-only
iota, block shapes aligned to the (8, 128) tile or equal to the array, the
VMEM budget.  These tests lower each kernel the grid program runs, vmapped
as the grid vmaps it, through the TPU compiler for a chip that is
described and not attached, and find its ``tpu_custom_call`` in the
compiled text.  They run on the CPU host; nothing executes.

One test compiles a whole 2-lane grid program at the benchmark cell's
per-lane shapes and reads the SGD step loop of the compiled text.

The topology is described inside a fixture (never at import or
collection), so every test worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""
import importlib.util
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.scenarios import scenario_config, scenario_params, stack_scenarios
from repro.kernels.fedavg_reduce import fedavg_reduce
from repro.kernels.ops import pick_block_p, pick_rsu_blocks
from repro.kernels.rsu_reduce import rsu_reduce
from repro.kernels.rttg_latency import rttg_latency
from repro.kernels.server_update import server_update, server_update_buffered

_SMOKE = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    """``chip_smoke.py`` as a module: its phases name the shapes."""
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one, so the persistent cache stays off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *args):
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=one_chip),
        args,
    )
    return jax.jit(fn).lower(*sds).compile().as_text()


def _lanes(smoke, phase):
    return max(2, getattr(smoke, phase).lanes)


def _shape(*dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype)


@pytest.mark.parametrize("phase", ["PAPER", "FLEET"])
@pytest.mark.parametrize("predict", [True, False])
def test_rttg_latency_compiles(smoke, one_chip, phase, predict):
    ph = getattr(smoke, phase)
    s = smoke.kernel_shapes(ph)
    G, N = _lanes(smoke, phase), s["N"]
    scn = stack_scenarios(
        [scenario_params(scenario_config(ph.scenarios[-1], num_vehicles=N))] * G
    )
    vec = _shape(G, N)

    def lanes(pos, speed, accel, t, mb, cfg):
        return jax.vmap(
            lambda *a: rttg_latency(*a[:5], None, a[5], predict=predict,
                                    want_rid=True)
        )(pos, speed, accel, t, mb, cfg)

    text = _compiled_text(lanes, one_chip, vec, vec, vec, _shape(G), _shape(G),
                          scn)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("buffered", [False, True])
@pytest.mark.parametrize("rows", [jnp.float32, jnp.bfloat16])
def test_server_update_compiles(smoke, one_chip, buffered, rows):
    s = smoke.kernel_shapes(smoke.PAPER)
    G, K, Kb, P = _lanes(smoke, "PAPER"), s["K"], s["Kb"], s["P"]
    item = jnp.dtype(rows).itemsize
    vec, scalar = _shape(G, P), _shape(G, dtype=jnp.int32)
    if buffered:
        bp = pick_block_p(K + Kb, P, itemsize=item)
        fn = lambda u, w, b, bw, p, m, v, a, r, d: jax.vmap(
            lambda *x: server_update_buffered(*x, block_p=bp)
        )(u, w, b, bw, p, m, v, a, r, d)
        args = (_shape(G, K, P, dtype=rows), _shape(G, K),
                _shape(G, Kb, P, dtype=rows), _shape(G, Kb), vec, vec, vec,
                scalar, scalar, _shape(G, dtype=jnp.bool_))
    else:
        bp = pick_block_p(K, P, itemsize=item)
        fn = lambda u, w, p, m, v, a, r: jax.vmap(
            lambda *x: server_update(*x, block_p=bp)
        )(u, w, p, m, v, a, r)
        args = (_shape(G, K, P, dtype=rows), _shape(G, K), vec, vec, vec,
                scalar, scalar)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *args)


# the k-blocked walk at the smallest row tile Mosaic takes for the dtype
@pytest.mark.parametrize("rows,block_k", [
    (jnp.float32, None), (jnp.float32, 8),
    (jnp.bfloat16, None), (jnp.bfloat16, 16),
])
def test_rsu_reduce_compiles(smoke, one_chip, rows, block_k):
    s = smoke.kernel_shapes(smoke.FLEET)
    G, K, P, R = _lanes(smoke, "FLEET"), s["block"], s["P"], s["R"]
    bk, bp = pick_rsu_blocks(K, P, R, itemsize=jnp.dtype(rows).itemsize)
    block_k = block_k or bk
    assert block_k < K or block_k == bk
    fn = lambda u, w, r: jax.vmap(
        lambda *x: rsu_reduce(*x, R, block_p=bp, block_k=block_k,
                              out_dtype=rows)
    )(u, w, r)
    text = _compiled_text(fn, one_chip, _shape(G, K, P, dtype=rows),
                          _shape(G, K), _shape(G, K, dtype=jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_compiles(smoke, one_chip, rows):
    s = smoke.kernel_shapes(smoke.FLEET)
    G, R, P = _lanes(smoke, "FLEET"), s["R"], s["P"]
    bp = pick_block_p(R, P, itemsize=jnp.dtype(rows).itemsize)
    fn = lambda u, w: jax.vmap(lambda *x: fedavg_reduce(*x, block_p=bp))(u, w)
    text = _compiled_text(fn, one_chip, _shape(G, R, P, dtype=rows),
                          _shape(G, R))
    assert "tpu_custom_call" in text


# ---- the grid program's SGD step, as the chip's compiler lays it out ----------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)"
                    r"|(?:branch_computations|called_computations)=\{([^}]*)\}")
_ARRAY = re.compile(r"\w+\[([\d,]*)\](?:\{([\d,]*))?")


def _computations(text):
    """Compiled HLO text -> {computation: [(name, shape, opcode, callees, line)]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line.startswith("ENTRY ") or (line.startswith("%")
                                          and line.rstrip().endswith("{")):
            cur = line.split()[1 if line.startswith("ENTRY ") else 0]
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTR.match(line)):
            callees = []
            for one, many in _CALLS.findall(line):
                callees += [one] if one else [c.strip() for c in many.split(",")]
            comps[cur].append((m.group(1), m.group(2), m.group(3), callees, line))
    return comps


def _array(shape):
    """(dims, minor-to-major layout) of an array shape, (None, None) else."""
    m = _ARRAY.match(shape)
    if not m:
        return None, None
    dims = [int(d) for d in m.group(1).split(",") if d]
    layout = [int(d) for d in m.group(2).split(",")] if m.group(2) else None
    return dims, layout


def _loop_body(comps, body):
    """The computations one trip of a loop runs, nested loops left out."""
    seen, todo = set(), [body]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [x for i in comps[c] if i[2] != "while" for x in i[3]]
    return seen


def test_sgd_step_gathers_feature_minor_rows(one_chip):
    """The `fl.train` SGD step of the cell's grid program (2 lanes, K = 10
    clients of n = 512 MNIST samples, batch 64) gathers its batch as rows
    with the D = 784 features minor, and nothing in the step loop is as
    large as one lane's (K, n, D) cohort block."""
    from repro.config import FLConfig
    from repro.configs import get_config
    from repro.fl.engine import ExperimentEngine, _eval_flags, _recluster_flags
    from repro.fl.rounds import experiment_key

    fl = FLConfig()
    G, K, n, bs, D = 2, fl.n_select, fl.samples_per_client, fl.batch_size, 784
    eng = ExperimentEngine(get_config("fl-mnist-mlp"), fl, "mnist",
                           strategies=("contextual",))
    eng._ensure_spec()
    scns = stack_scenarios([
        scenario_params(scenario_config(s, num_vehicles=fl.num_clients))
        for s in ("ring", "rush_hour")
    ])
    keys = jnp.stack([experiment_key("mnist", "contextual", 0)] * G)
    lane = jnp.arange(G, dtype=jnp.int32)
    args = (keys, (keys, scns), scns, lane * 0, lane * 0, lane,
            (_eval_flags(10, 5), _recluster_flags(10, fl.recluster_every)))
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=one_chip),
        args,
    )
    with jax.default_matmul_precision("highest"):
        text = eng._grid_fn.lower(*sds, warm=True).compile().as_text()
    comps = _computations(text)

    gathers = [(c, i) for c, ins in comps.items() for i in ins
               if i[2] == "gather" and "fl.train" in i[4]
               and i[1].startswith("f32")
               and math.prod(_array(i[1])[0] or [0]) == G * K * bs * D]
    assert len(gathers) == 1, [i[:3] for _, i in gathers]
    (where, gather), = gathers
    dims, layout = _array(gather[1])
    assert dims[layout[0]] == D, gather[1]

    loops = [i for ins in comps.values() for i in ins if i[2] == "while"
             and where in _loop_body(comps, re.search(
                 r"body=(%[\w.\-]+)", i[4]).group(1))]
    assert len(loops) == 1, [i[:3] for i in loops]
    step = _loop_body(comps, re.search(r"body=(%[\w.\-]+)",
                                       loops[0][4]).group(1))
    views = ("parameter", "get-tuple-element", "tuple", "bitcast")
    large = [i[:3] for c in step for i in comps[c] if i[2] not in views
             and math.prod(_array(i[1])[0] or [0]) >= K * n * D]
    assert not large, large
