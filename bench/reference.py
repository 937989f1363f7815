"""Plain reference of one FL experiment lane, for the benchmark's `correct`.

One experiment (strategy, aggregator, seed, scenario) of the V2X federated
learning simulation, written out in plain `jax.numpy` from the semantics
of the paper's four-stage selector and the round economics: a ring-road
traffic twin, CAM/CPM observations and their inverse-variance fusion, the
kinematic predictor, nearest-RSU attachment and the Shannon latency model,
the election strategies, cohort SGD, FedAvg / FedAdam / FedBuff server
rules, sketch k-means and the test evaluation.

It imports nothing of the program under test.  It runs one lane with no
vmap, no sharding, no Pallas kernel, no chunked cohort and no two-tier
reduce: the cohort update is one weighted sum over the round's survivors
(every RSU of the catalogued roads is live).  Its randomness follows the same named PRNG streams as
the paper simulation (`fold_in_str` tags), so the same seed gives the same
traffic and the same data.  Matrix products run at the lane's `matmul`
precision, `highest` unless the control asks for less.

`train_dtype` and `eval_dtype` are the precisions of the client model's
forward pass in local training and in the test evaluation, `float32` or
`bfloat16`, as the configuration's compute and parameter dtypes state;
`geometry_dtype` that of the twin, the observations and the radio.  The
benchmark's control runs this reference one precision below what the
configuration states (`bench/calibrate.py`).
"""
from __future__ import annotations

import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Scenario definitions: the traffic and radio constants of each named road.
_ROAD = dict(
    ring_length_m=10_000.0, rsu_spacing_m=1_000.0, mean_speed_mps=14.0,
    speed_std_mps=6.0, accel_std=0.8, ou_theta=0.3, carrier_ghz=5.9,
    bandwidth_hz=8e6, eirp_dbm=33.0, noise_dbm=-95.0, snr_min_db=3.0,
    backhaul_s=0.010, queue_s_per_vehicle=0.010, overhead_bytes=2_048.0,
    sim_dt_s=0.1, predict_horizon_s=5.0, rush_amp=0.0, rush_period_s=900.0,
    compute_lognorm_std=0.35,
)
SCENARIOS = {
    "ring": dict(_ROAD),
    "rush_hour": dict(
        _ROAD, ring_length_m=8_000.0, rsu_spacing_m=800.0, mean_speed_mps=10.0,
        speed_std_mps=4.0, accel_std=1.0, queue_s_per_vehicle=0.012,
        rush_amp=2.5, rush_period_s=600.0,
    ),
}

# Synthetic image datasets: (H, W, C), sample noise std, prototype scale.
DATASETS = {
    "mnist": ((28, 28, 1), 0.85, 1.0),
    "cifar10": ((32, 32, 3), 1.60, 1.0),
}
NUM_CLASSES = 10
N_TEST = 2_000
N_REGIONS = 10
TWIN_SUBSTEPS = 15  # equal sub-steps per twin advance
KMEANS_ITERS = 25
MAX_PERCEIVED = 8
# Margins by which a round's discrete decisions must hold for the round to
# be determined to float32 rounding (see `Metrics.decided`): a relative
# change of each predicted latency in the election, metres between the
# nearest and the next RSU, dB between an SNR and its floor, and cosine
# similarity between a sketch's best and next centroid; and a clustering
# must split the same way with its sketches moved a little.
ELECT_REL = 1e-4
ATTACH_M = 0.02
SNR_DB = 1e-3
KMEANS_COS = 2e-2
KMEANS_NUDGE = 5e-3  # the size of each move of the sketches
KMEANS_TRIES = 3
CLUSTERED = ("contextual", "data")  # the elections that read the clusters
PERCEPTION_M = 150.0
BIG = 1e30
LIGHT = 299_792_458.0


class Metrics(NamedTuple):
    """Per-round outputs of one lane, each a (rounds,) numpy array."""

    sim_time: np.ndarray
    duration: np.ndarray
    n_selected: np.ndarray
    n_succeeded: np.ndarray
    n_buffered: np.ndarray
    n_drained: np.ndarray
    mean_pred_latency: np.ndarray
    mean_real_latency: np.ndarray
    test_acc: np.ndarray
    test_loss: np.ndarray
    # the round and every one before it made each discrete decision (RSU
    # attachment, connectivity, election, clustering) by its margin, so
    # a correct program, computing in another order, makes the same ones
    decided: np.ndarray


def fold_in_str(key, tag: str):
    digest = hashlib.sha256(tag.encode("utf-8")).digest()
    return jax.random.fold_in(key, int.from_bytes(digest[:4], "little"))


def experiment_key(dataset: str, strategy: str, seed: int):
    return fold_in_str(jax.random.key(seed), f"fl-sim/{strategy}/{dataset}")


# ---- the client model -------------------------------------------------------

def init_model(key, image_shape, channels, d_ff):
    """Truncated-normal fan-in init; returns the leaves in flat order."""
    H, W, C = image_shape
    ks = jax.random.split(key, 2 + 2 * max(len(channels), 1))
    tn = lambda k, shape, fan: (1.0 / np.sqrt(fan)) * jax.random.truncated_normal(
        k, -2.0, 2.0, shape, jnp.float32)
    leaves, in_c, h, w = [], C, H, W
    for i, out_c in enumerate(channels):
        leaves += [jnp.zeros((out_c,)), tn(ks[i], (3, 3, in_c, out_c), 9 * in_c)]
        in_c, h, w = out_c, h // 2, w // 2
    flat = h * w * in_c
    leaves += [jnp.zeros((d_ff,)), tn(ks[-2], (flat, d_ff), flat)]
    leaves += [jnp.zeros((NUM_CLASSES,)), tn(ks[-1], (d_ff, NUM_CLASSES), d_ff)]
    return leaves


def _rounder(dtype: str):
    """Value rounding of the model's forward pass in ``dtype``."""
    if dtype == "float32":
        return lambda x: x.astype(jnp.float32)
    if dtype == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16)
    raise ValueError(f"unknown model dtype {dtype!r}")


def logits_fn(leaves, images, n_convs, dtype):
    r = _rounder(dtype)
    x = r(images)
    p = [r(a) for a in leaves]
    for i in range(n_convs):
        b, w = p[2 * i], p[2 * i + 1]
        x = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = r(jax.nn.relu(r(x) + b))
        x = jax.lax.reduce_window(x, np.array(-np.inf, x.dtype), jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    b1, w1, b2, w2 = p[-4:]
    x = r(jax.nn.relu(r(x @ w1) + b1))
    return (r(x @ w2) + b2).astype(jnp.float32)


def loss_fn(leaves, images, labels, n_convs, dtype):
    logits = logits_fn(leaves, images, n_convs, dtype)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return jnp.mean(logz - gold), acc


def flatten(leaves):
    return jnp.concatenate([a.reshape(-1) for a in leaves])


def unflatten(vec, like):
    out, off = [], 0
    for a in like:
        out.append(vec[off:off + a.size].reshape(a.shape))
        off += a.size
    return out


# ---- data -------------------------------------------------------------------

def client_data(key, dataset, regions, n, classes_per_client):
    (H, W, C), noise, scale = DATASETS[dataset]
    kd = fold_in_str(key, f"data/{dataset}")
    protos = scale * jax.random.normal(fold_in_str(kd, f"proto/{dataset}"),
                                       (NUM_CLASSES, H, W, C))
    k = classes_per_client
    own = jnp.mod(regions[:, None] + jnp.arange(k)[None, :], NUM_CLASSES)
    kl = jax.random.split(fold_in_str(kd, "labels"), regions.shape[0])
    pick = jax.vmap(lambda kk: jax.random.randint(kk, (n,), 0, k))(kl)
    labels = jnp.take_along_axis(own, pick, axis=1)
    kn = jax.random.split(fold_in_str(kd, "noise"), regions.shape[0])
    images = protos[labels] + jax.vmap(
        lambda kk: noise * jax.random.normal(kk, (n, H, W, C)))(kn)
    kt = fold_in_str(kd, "test")
    test_y = jax.random.randint(fold_in_str(kt, "labels"), (N_TEST,), 0, NUM_CLASSES)
    test_x = protos[test_y] + noise * jax.random.normal(
        fold_in_str(kt, "noise"), (N_TEST, H, W, C))
    return images, labels, test_x, test_y


# ---- the traffic twin and the radio ------------------------------------------

class Twin(NamedTuple):
    t: jax.Array
    pos: jax.Array
    speed: jax.Array
    accel: jax.Array
    compute: jax.Array


def ring_dist(a, b, length):
    d = jnp.abs(a - b)
    return jnp.minimum(d, length - d)


def congestion(t, s):
    ph = jnp.sin(jnp.pi * t / jnp.maximum(s["rush_period_s"], 1e-3))
    return 1.0 + s["rush_amp"] * ph * ph


def init_twin(key, s, N):
    k1, k2, _, k4 = jax.random.split(key, 4)
    pos = jax.random.uniform(k1, (N,), jnp.float32, 0.0, s["ring_length_m"])
    speed = jnp.clip(s["mean_speed_mps"] + s["speed_std_mps"]
                     * jax.random.normal(k2, (N,)), 2.0, 2.5 * s["mean_speed_mps"])
    compute = jnp.exp(s["compute_lognorm_std"] * jax.random.normal(k4, (N,)))
    g = s["ring_length_m"].dtype  # the geometry's precision
    return Twin(*(x.astype(g) for x in (jnp.float32(0.0), pos, speed,
                                         jnp.zeros((N,)), compute)))


def advance(tw: Twin, s, key, duration):
    """Exact OU acceleration over equal sub-steps; congestion drags travel."""
    g = tw.pos.dtype
    dt = (duration / TWIN_SUBSTEPS).astype(g)
    decay = jnp.exp(-s["ou_theta"] * dt)
    std = s["accel_std"] * jnp.sqrt((1.0 - decay ** 2) / (2.0 * s["ou_theta"]))

    def step(i, tw):
        eps = jax.random.normal(jax.random.fold_in(key, i), tw.pos.shape).astype(
            tw.pos.dtype)
        accel = tw.accel * decay + std * eps
        speed = jnp.clip(tw.speed + accel * dt, 1.0, 3.0 * s["mean_speed_mps"])
        pos = jnp.mod(tw.pos + speed / congestion(tw.t, s) * dt, s["ring_length_m"])
        return tw._replace(t=(tw.t + dt).astype(g), pos=pos.astype(g),
                           speed=speed.astype(g), accel=accel.astype(g))

    return jax.lax.fori_loop(0, TWIN_SUBSTEPS, step, tw)


def observe(tw: Twin, s, key):
    """CAM self-reports and CPM detections of the 8 nearest neighbours,
    fused per vehicle by inverse-variance weighting on the unit circle."""
    N, L, P = tw.pos.shape[0], s["ring_length_m"], MAX_PERCEIVED
    g = tw.pos.dtype
    normal = lambda k, shape: jax.random.normal(k, shape).astype(g)
    k1, k2, k3 = jax.random.split(fold_in_str(key, "cam"), 3)
    cam_pos = jnp.mod(tw.pos + normal(k1, (N,)), L)
    cam_speed = tw.speed + 0.3 * normal(k2, (N,))
    cam_accel = tw.accel + 0.1 * normal(k3, (N,))
    k1, k2, k3 = jax.random.split(fold_in_str(key, "cpm"), 3)
    d = ring_dist(tw.pos[:, None], tw.pos[None, :], L)
    d = d.at[jnp.arange(N), jnp.arange(N)].add(1e9)  # no vehicle sees itself
    neg, obj = jax.lax.top_k(-d, P)
    dist = -neg
    scale = 1.0 + dist / PERCEPTION_M
    w = (dist < PERCEPTION_M).astype(g) / (3.0 * scale) ** 2
    cpm_pos = jnp.mod(tw.pos[obj] + 3.0 * scale * normal(k1, (N, P)), L)
    cpm_speed = tw.speed[obj] + scale * normal(k2, (N, P))
    cpm_accel = tw.accel[obj] + 0.2 * normal(k3, (N, P))
    th = 2 * jnp.pi / L
    o, wf = obj.reshape(-1), w.reshape(-1)
    add = lambda v: jnp.zeros((N,), g).at[o].add(wf * v.reshape(-1))
    sw = add(jnp.ones((N, P), g)) + 1.0
    sc = add(jnp.cos(cpm_pos * th)) + jnp.cos(cam_pos * th)
    ss = add(jnp.sin(cpm_pos * th)) + jnp.sin(cam_pos * th)
    sv = add(cpm_speed) + cam_speed
    sa = add(cpm_accel) + cam_accel
    pos = jnp.mod(jnp.arctan2(ss / sw, sc / sw) / th, L)
    return pos, sv / sw, sa / sw


def link(t, pos, speed, model_bytes, s, R):
    """Latency (s), connectivity and RSU id of each vehicle at positions,
    with ``R`` RSUs on the ring."""
    L = s["ring_length_m"]
    d_along = ring_dist(pos[:, None], (jnp.arange(R) * s["rsu_spacing_m"])[None, :], L)
    rid = jnp.argmin(d_along, axis=1)
    near = jnp.sort(d_along, axis=1)
    d = jnp.sqrt(near[:, 0] ** 2 + 15.0 ** 2 + 5.0 ** 2)
    load = jnp.zeros((R,), pos.dtype).at[rid].add(1.0)[rid] * congestion(t, s)
    path_loss = (32.4 + 20.0 * jnp.log10(jnp.asarray(s["carrier_ghz"], jnp.float32))
                 + 30.0 * jnp.log10(jnp.maximum(d, 1.0)))
    snr = s["eirp_dbm"] - path_loss - s["noise_dbm"]
    rate = s["bandwidth_hz"] / jnp.maximum(load, 1.0) * jnp.log2(
        1.0 + jnp.power(10.0, snr / 10.0))
    rate = jnp.maximum(rate, 1e4)  # a floor for vehicles off coverage
    bits = 8.0 * (model_bytes + s["overhead_bytes"])
    edge = d / (0.5 * s["rsu_spacing_m"])
    lat = (2.0 * bits / rate + (2.0 * d / LIGHT + 2.0 * s["backhaul_s"])
           + s["queue_s_per_vehicle"] * load
           + 0.2 * jnp.clip(edge - 0.7, 0.0, 1.0) * speed / s["mean_speed_mps"])
    firm = ((near[:, 1] - near[:, 0] > ATTACH_M) if R > 1 else True) & (
        jnp.abs(snr - s["snr_min_db"]) > SNR_DB)
    return lat, snr >= s["snr_min_db"], rid, jnp.all(firm)


def predict(pos, speed, accel, s, steps, dt):
    """Deterministic OU-mean kinematics over ``steps`` steps of ``dt``."""

    def step(_, c):
        pos, speed, accel = c
        accel = accel * (1.0 - s["ou_theta"] * dt)
        speed = jnp.clip(speed + accel * dt, 1.0, 3.0 * s["mean_speed_mps"])
        return jnp.mod(pos + speed * dt, s["ring_length_m"]), speed, accel

    pos, speed, _ = jax.lax.fori_loop(0, steps, step, (pos, speed, accel))
    return pos, speed


# ---- election and clustering -------------------------------------------------

def smallest_k(score, k):
    """Mask of the k smallest scores (lower index first on ties)."""
    idx = jnp.argsort(score, stable=True)[:k]
    return jnp.zeros(score.shape, bool).at[idx].set(True) & (score < BIG)


def cluster_rank(score, clusters):
    N = score.shape[0]
    order = jnp.lexsort((jnp.arange(N), score, clusters))
    sc = clusters[order]
    first = jnp.searchsorted(sc, sc, side="left")
    return jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(N) - first)


def elect(name, key, conn, lat, clusters, n_select, gamma, num_clusters):
    key = fold_in_str(key, name)
    if name == "greedy":
        return conn
    if name in ("gossip", "data"):
        score = jnp.where(conn, jax.random.uniform(key, conn.shape), BIG)
        if name == "gossip":
            return smallest_k(score, n_select)
        rank = cluster_rank(score, clusters)
        return smallest_k(jnp.where(conn, rank * 1e6 + score, BIG), n_select)
    score = jnp.where(conn, lat, BIG)
    if name == "network":
        return smallest_k(score, n_select)
    rank = cluster_rank(score, clusters)
    size = jnp.zeros((clusters.shape[0] + num_clusters,), jnp.int32).at[
        clusters].add(conn.astype(jnp.int32))[clusters]
    quota = jnp.maximum(jnp.ceil(gamma * size), 1.0)
    mask = conn & (rank < quota)
    return smallest_k(jnp.where(mask, rank * 1e6 + score, BIG), n_select)


def sketch(vec, sign, dim):
    x = jnp.pad(vec.astype(jnp.float32), (0, sign.shape[0] - vec.shape[0])) * sign
    acc = x.reshape(-1, dim).sum(0)
    return acc / jnp.maximum(jnp.linalg.norm(acc), 1e-12)


def _gap(v, axis=-1):
    """Distance between the largest and the next value along ``axis``."""
    top = jax.lax.top_k(v, 2)[0]
    return top[..., 0] - top[..., 1]


def _lloyd(x, key, k):
    """Cosine k-means, farthest-point seeded, fixed Lloyd iterations ->
    (assignments, the least gap of any point's best over its next centroid
    in any iteration)."""
    first = jax.random.randint(fold_in_str(key, "kmeans-init"), (), 0, x.shape[0])
    cents = jnp.zeros((k, x.shape[1])).at[0].set(x[first])

    def seed(i, cents):  # the point least like any centroid so far
        best = jnp.max(jnp.where(jnp.arange(k)[None] < i, x @ cents.T, -jnp.inf), 1)
        return cents.at[i].set(x[jnp.argmin(best)])

    def lloyd(_, c):
        cents, gap = c
        sim = x @ cents.T
        onehot = jax.nn.one_hot(jnp.argmax(sim, 1), k)
        counts = onehot.sum(0)
        new = (onehot.T @ x) / jnp.maximum(counts[:, None], 1e-9)
        new = jnp.where(counts[:, None] > 0, new, x[jnp.argmin(jnp.max(sim, 1))][None])
        new = new / jnp.maximum(jnp.linalg.norm(new, axis=1, keepdims=True), 1e-12)
        return new, jnp.minimum(gap, jnp.min(_gap(sim)))

    cents = jax.lax.fori_loop(1, k, seed, cents)
    cents, gap = jax.lax.fori_loop(0, KMEANS_ITERS, lloyd, (cents, jnp.float32(jnp.inf)))
    sim = x @ cents.T
    return jnp.argmax(sim, axis=1).astype(jnp.int32), jnp.minimum(gap, jnp.min(_gap(sim)))


def kmeans(x, key, k):
    """Cosine k-means of the rows of ``x`` -> (assignments, firm).

    Firm: every assignment of every iteration held by ``KMEANS_COS``, and
    the sketches moved by a few thousandths in ``KMEANS_TRIES`` directions
    split into the same clusters (the farthest-point seeding may then pick
    other points, as a computation in another order may)."""
    unit = lambda v: v / jnp.maximum(jnp.linalg.norm(v, axis=1, keepdims=True), 1e-12)
    x = unit(x)
    labels, gap = _lloyd(x, key, k)
    same = lambda a: jnp.all((a[:, None] == a[None]) == (labels[:, None] == labels[None]))
    firm = gap > KMEANS_COS
    for t in range(KMEANS_TRIES):
        noise = jax.random.normal(jax.random.fold_in(key, 7919 + t), x.shape)
        moved = unit(x + KMEANS_NUDGE * unit(noise))
        firm &= same(_lloyd(moved, key, k)[0])
    return labels, firm


# ---- one lane ---------------------------------------------------------------

class Lane(NamedTuple):
    """What one lane runs: the configuration's sizes, the cell's grid axes
    (one compiled program serves every lane of a cell) and the lane's own."""

    dataset: str
    image_shape: tuple
    channels: tuple
    d_ff: int
    fl: tuple  # sorted (field, value) pairs of the FL configuration
    strategies: tuple  # the cell's strategies ...
    aggregators: tuple  # ... and server rules
    rounds: int
    eval_every: int
    warmup: bool
    train_dtype: str  # the client model's forward pass in local training
    eval_dtype: str  # ... and in the test evaluation
    geometry_dtype: str = "float32"  # the twin, the observations and the radio
    matmul: str = "highest"  # the precision of every matrix product
    strategy: str = ""  # this lane's
    aggregator: str = ""
    scenario: str = ""
    fault: str = ""  # a planted fault: "frozen" rounds or "half" the cohort


def local_sgd(leaves, images, labels, key, fl, epochs, n_convs, dtype):
    n, bs = images.shape[0], fl["batch_size"]
    spe = max(n // bs, 1)
    keys = jax.random.split(key, epochs)
    order = jnp.concatenate([jax.random.permutation(k, n)[: spe * bs] for k in keys])
    grad = jax.grad(lambda p, x, y: loss_fn(p, x, y, n_convs, dtype)[0])

    def step(i, leaves):
        b = jax.lax.dynamic_slice_in_dim(order, i * bs, bs)
        g = grad(leaves, images[b], labels[b])
        return [w - fl["learning_rate"] * gw for w, gw in zip(leaves, g)]

    return jax.lax.fori_loop(0, epochs * spe, step, leaves)


STATIC = ("sim_dt_s", "predict_horizon_s")  # fix loop counts


def road(name: str):
    """A scenario -> (its traced constants, its RSU count, its predictor
    steps, all its constants)."""
    sc = SCENARIOS[name]
    traced = {k: jnp.float32(v) for k, v in sc.items() if k not in STATIC}
    steps = max(int(round(sc["predict_horizon_s"] / sc["sim_dt_s"])), 1)
    return traced, max(int(sc["ring_length_m"] / sc["rsu_spacing_m"]), 1), steps, sc


@functools.lru_cache(maxsize=8)
def _programs(lane: Lane, R: int, steps: int, dt: float, horizon: float):
    """The jitted init, warm-up and round functions of every lane of a cell
    whose roads have ``R`` RSUs and a ``steps`` x ``dt`` predictor."""
    fl = dict(lane.fl)
    for k, v in (("connection_rate", 1.0), ("dirichlet_alpha", 0.0), ("fedprox_mu", 0.0),
                 ("param_dtype", "float32")):
        if fl[k] != v:
            raise ValueError(f"the reference runs {k}={v!r} only, not {fl[k]!r}")
    N, K = fl["num_clients"], max(int(round(fl["select_fraction"] * fl["num_clients"])), 1)
    n_convs = len(lane.channels)
    half = fl["compute_dtype"] == "bfloat16"  # updates travel in bf16
    like = jax.eval_shape(lambda k: init_model(k, lane.image_shape, lane.channels,
                                               lane.d_ff), jax.random.key(0))
    P = sum(a.size for a in like)
    D = fl["sketch_dim"]
    model_bytes = 4.0 * P * (0.5 if half else 1.0)
    Kb = fl["buffer_size"]
    timeout = fl["round_timeout_s"]
    unknown = set(lane.aggregators) - {"fedavg", "fedadam", "fedbuff"}
    if unknown:
        raise ValueError(f"the reference has no server rule for {sorted(unknown)}")
    rule = lambda name: lane.aggregators.index(name) if name in lane.aggregators else -1
    electors = [functools.partial(elect, name, n_select=K, gamma=fl["gamma"],
                                  num_clusters=fl["num_clusters"])
                for name in lane.strategies]

    g = jnp.dtype(lane.geometry_dtype)

    def init(key, s):
        s = {k: v.astype(g) for k, v in s.items()}
        leaves = init_model(fold_in_str(key, "model-init"), lane.image_shape,
                            lane.channels, lane.d_ff)
        bits = jax.random.bernoulli(fold_in_str(fold_in_str(key, "selector"), "sketch-sign"),
                                    0.5, (P + (-P) % D,))
        twin = init_twin(fold_in_str(fold_in_str(key, "traffic-twin"), "init"), s, N)
        regions = jnp.floor(twin.pos / s["ring_length_m"] * N_REGIONS).astype(
            jnp.int32) % N_REGIONS
        data = client_data(key, lane.dataset, regions, fl["samples_per_client"],
                           fl["classes_per_client"])
        return flatten(leaves), jnp.where(bits, 1.0, -1.0), twin, data

    def update(params, x, y, key, epochs):
        leaves = unflatten(params, like)
        new = local_sgd(leaves, x, y, key, fl, epochs, n_convs, lane.train_dtype)
        return flatten(new) - params

    def warm(params, sign, key, data):
        """Every client reports the sketch of one SGD step; first k-means."""
        bs = fl["batch_size"]
        keys = jax.random.split(fold_in_str(key, "warmup"), N)
        sks = jax.lax.map(
            lambda a: sketch(update(params, a[0], a[1], a[2], 1), sign, D),
            (data[0][:, :bs], data[1][:, :bs], keys))
        return sks, *kmeans(sks, fold_in_str(jax.random.fold_in(key, 0), "kmeans"),
                            fl["num_clusters"])

    def round_fn(st, key, data, r, sidx, aidx, s, do_recluster, do_eval):
        st_in = st
        s = {k: v.astype(g) for k, v in s.items()}
        (params, m, v, twin, sks, clusters, buf, buf_arrive, buf_sent,
         buf_w, buf_mask, sim_time, sign) = st
        images, labels, test_x, test_y = data
        is_adam, is_buff = aidx == rule("fedadam"), aidx == rule("fedbuff")
        rk = jax.random.fold_in(key, r)
        # stages 1-2: fuse the observations, predict, price the links
        pos, speed, accel = observe(twin, s, fold_in_str(rk, "observe"))
        ppos, pspeed = predict(pos, speed, accel, s, steps, dt)
        lat_pred, conn, _, firm_pred = link(twin.t + horizon, ppos, pspeed,
                                            model_bytes, s, R)
        # stage 4: elect, then gather the cohort in ascending client order
        mask = jax.lax.switch(sidx, electors, rk, conn, lat_pred, clusters)
        # the same election with each elected latency a little later and
        # every other a little sooner: a near tie at the cut would change it
        nudged = lat_pred * (1.0 + ELECT_REL * jnp.where(mask, 1.0, -1.0))
        firm_elect = jnp.all(mask == jax.lax.switch(sidx, electors, rk, conn, nudged,
                                                    clusters))
        nsel = jnp.sum(mask)
        idx = jnp.sort(jnp.where(mask, jnp.arange(N), N + jnp.arange(N)))[:K]
        valid = idx < N
        idx = jnp.where(valid, idx, 0)
        # the round's economics on the evolved topology
        compute = fl["local_epochs"] * fl["compute_s_per_epoch"] * twin.compute[idx]
        nsel_f = jnp.maximum(nsel.astype(jnp.float32), 1.0)
        mean_compute = jnp.sum(jnp.where(valid, compute, 0.0)) / nsel_f
        mid = advance(twin, s, fold_in_str(rk, "mid"), mean_compute)
        real_lat, still, _, firm_mid = link(mid.t, mid.pos, mid.speed, model_bytes, s, R)
        ok = valid & still[idx]
        ok_any = jnp.any(ok)
        per_slot = real_lat[idx] + compute
        dur = jnp.max(jnp.where(valid, jnp.where(ok, per_slot, timeout), -jnp.inf))
        duration = jnp.where(nsel > 0, dur + fl["server_agg_s"], timeout)
        counts = jnp.full((K,), float(fl["samples_per_client"]))
        w = jnp.where(ok, counts, 0.0)
        if lane.fault == "half":  # half the cohort left out of the mean
            w = jnp.where(jnp.arange(K) % 2 == 0, w, 0.0)
        w = w / jnp.maximum(jnp.sum(w), 1e-9)
        # cohort training; survivors report sketches
        keys = jax.random.split(fold_in_str(rk, "local"), K)
        vecs = jax.vmap(lambda i, k: update(params, images[i], labels[i], k,
                                            fl["local_epochs"]))(idx, keys)
        vecs = vecs * valid[:, None]
        if half:
            vecs = vecs.astype(jnp.bfloat16).astype(jnp.float32)
        new_sks = jax.vmap(lambda u: sketch(u, sign, D))(vecs)
        sks = sks.at[jnp.where(ok, idx, N)].set(new_sks, mode="drop")
        # server: the survivors' weighted mean; on a fedbuff lane the
        # stragglers park in the ring and arrived slots drain into the mean
        n_buffered = n_drained = jnp.int32(0)
        upd = ok_any
        delta = w @ vecs
        if "fedbuff" in lane.aggregators:
            end = sim_time + duration
            arrived = buf_mask & (buf_arrive <= end)
            n_arr = jnp.sum(arrived)
            fire = is_buff & (n_arr >= fl["buffer_fill"])
            lateness = jnp.maximum(end - buf_sent, 0.0)
            mass = jnp.sum(jnp.where(arrived, buf_w, 0.0))
            bw = jnp.where(fire & arrived, buf_w * (timeout / (timeout + lateness))
                           / jnp.maximum(mass, 1e-9), 0.0)
            delta = delta + bw @ buf
            keep = buf_mask & ~(fire & arrived)
            strag = valid & ~ok & is_buff
            free = jnp.sort(jnp.where(keep, Kb + jnp.arange(Kb), jnp.arange(Kb)))
            rank = jnp.cumsum(strag) - 1
            slot = jnp.where(strag & (rank < Kb), free[jnp.clip(rank, 0, Kb - 1)], 2 * Kb)
            n_buffered = jnp.sum(strag & (slot < Kb)).astype(jnp.int32)
            n_drained = jnp.where(fire, n_arr, 0).astype(jnp.int32)
            upd = jnp.where(is_buff, ok_any | fire, ok_any)
            buf = jnp.where(keep[:, None], buf, 0.0).at[slot].set(vecs, mode="drop")
            buf_arrive = jnp.where(keep, buf_arrive, 0.0).at[slot].set(
                sim_time + jnp.maximum(per_slot, timeout), mode="drop")
            buf_sent = jnp.where(keep, buf_sent, 0.0).at[slot].set(
                jnp.full((K,), sim_time), mode="drop")
            buf_w = jnp.where(keep, buf_w, 0.0).at[slot].set(counts, mode="drop")
            buf_mask = keep.at[slot].set(True, mode="drop")
        # fedadam keeps EMA moments and steps by m / (sqrt(v) + tau); fedavg
        # and fedbuff add the mean
        b1, b2 = fl["server_beta1"], fl["server_beta2"]
        m2 = b1 * m + (1.0 - b1) * delta
        v2 = b2 * v + (1.0 - b2) * (delta * delta)
        adam = params + fl["server_lr"] * m2 / (jnp.sqrt(v2) + fl["server_tau"])
        new = jnp.where(is_adam, adam, params + delta)
        m = jnp.where(upd & is_adam, m2, m)
        v = jnp.where(upd & is_adam, v2, v)
        params = jnp.where(upd, new, params)
        # the twin runs on to the end of the round
        base = jax.tree_util.tree_map(lambda a, b: jnp.where(ok_any, a, b), mid, twin)
        rem = jnp.maximum(duration - jnp.where(ok_any, mean_compute, 0.0), 1e-3)
        twin = advance(base, s, fold_in_str(rk, "adv"), rem)
        clusters, firm_clusters = jax.lax.cond(
            do_recluster,
            lambda: kmeans(sks, fold_in_str(jax.random.fold_in(key, r + 1), "kmeans"),
                           fl["num_clusters"]),
            lambda: (clusters, jnp.bool_(True)))
        nan = jnp.float32(jnp.nan)
        loss, acc = jax.lax.cond(
            do_eval,
            lambda: loss_fn(unflatten(params, like), test_x, test_y, n_convs,
                            lane.eval_dtype),
            lambda: (nan, nan))
        sim_time = sim_time + duration
        some = nsel > 0
        out = (sim_time, duration, nsel, jnp.sum(ok), n_buffered, n_drained,
               jnp.where(some, jnp.sum(jnp.where(mask, lat_pred, 0.0)) / nsel_f, nan),
               jnp.where(some, jnp.sum(jnp.where(valid, real_lat[idx], 0.0)) / nsel_f,
                         nan), acc, loss, firm_pred & firm_elect & firm_mid, firm_clusters)
        if lane.fault == "frozen":  # the round hands its state on unchanged
            return st_in, out
        st = (params, m, v, twin, sks, clusters, buf, buf_arrive, buf_sent, buf_w,
              buf_mask, sim_time, sign)
        return st, out

    return (jax.jit(init), jax.jit(warm),
            jax.jit(round_fn), N, P, Kb)


def run_lane(lane: Lane, seed: int, rounds: int | None = None) -> Metrics:
    """The first ``rounds`` rounds (all by default) of one lane from its
    seed; returns its per-round metrics."""
    fl = dict(lane.fl)
    s, R, steps, sc = road(lane.scenario)
    cell = lane._replace(strategy="", aggregator="", scenario="")
    init, warm, round_fn, N, P, Kb = _programs(
        cell, R, steps, sc["sim_dt_s"], sc["predict_horizon_s"])
    key = experiment_key(lane.dataset, lane.strategy, seed)
    sidx = jnp.int32(lane.strategies.index(lane.strategy))
    aidx = jnp.int32(lane.aggregators.index(lane.aggregator))
    with jax.default_matmul_precision(lane.matmul):
        params, sign, twin, data = init(key, s)
        sks = jnp.zeros((N, fl["sketch_dim"]))
        clusters = jnp.zeros((N,), jnp.int32)
        firm = True
        if lane.warmup:
            sks, clusters, firm = warm(params, sign, key, data)
        z = jnp.zeros((Kb,))
        st = (params, jnp.zeros((P,)), jnp.zeros((P,)), twin, sks, clusters,
              jnp.zeros((Kb, P)), z, z, z, jnp.zeros((Kb,), bool), jnp.float32(0.0),
              sign)
        outs = []
        for r in range(lane.rounds if rounds is None else rounds):
            recluster = (r + 1) % max(fl["recluster_every"], 1) == 0
            do_eval = (r + 1) % max(lane.eval_every, 1) == 0 or r == lane.rounds - 1
            st, out = round_fn(st, key, data, jnp.int32(r), sidx, aidx, s,
                               jnp.bool_(recluster), jnp.bool_(do_eval))
            outs.append(out)
    cols = [np.stack([np.asarray(x) for x in c]) for c in zip(*jax.device_get(outs))]
    # round r is decided when it and every round before it decided firmly,
    # on clusters that were themselves chosen firmly where the lane's
    # election reads them
    this_round, clusters_after = cols[-2], cols[-1]
    before = np.concatenate([[bool(firm)], clusters_after[:-1]])
    if lane.strategy not in CLUSTERED:
        before[:] = True
    decided = np.logical_and.accumulate(this_round & before)
    return Metrics(*cols[:-2], decided)
