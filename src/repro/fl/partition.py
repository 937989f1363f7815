"""Non-iid data partitioning across CAV clients — traceable end to end.

Default paper setting: each client owns ``classes_per_client`` of the 10
classes (§IV footnote 2: 2 of 10); Fig. 4 sweeps this "class ratio" from
1 class (extreme non-iid) to 10 (iid).  A Dirichlet(alpha) mode
(``FLConfig.dirichlet_alpha > 0``) draws per-client class proportions
instead.  Class prototypes are shared across clients (same dataset key)
while sample noise is per-client, so clients with the same classes have
genuinely similar distributions — the property stage-3 clustering exploits.

Shape conventions:

  * ``partition_labels``  -> (C, n) int32 — the *index map*: which shared
    prototype each of client c's n samples points at;
  * ``client_images``     -> (C, n, D), D = H*W*ch — materialization of that
    map (``protos[labels] + noise``) as one lane-dense feature-minor row
    per sample, pure jnp so it runs eagerly on the host OR traced inside a
    jitted program;
  * ``partition_clients`` -> both, the legacy one-call API.

Every function here is a pure function of (key, static config, traced
``regions``), which is what lets the batched engine build client shards
ON DEVICE inside its compiled grid program (``repro.fl.rounds
.make_round_data``) instead of host-materializing one (C, n, D)
copy per data row — grids then scale past host RAM: the host only ever
stacks per-experiment PRNG keys (under device-resident init even the
(C,) region ids are re-derived in-program from the twin spawn).  Data
rows are deduplicated per (strategy, seed, ``scenarios.data_signature``):
the signature is what lets platoon scenarios — whose convoy spawn
regroups the home regions — carry their own shards while every other
scenario mix keeps sharing one row per (strategy, seed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import FLConfig
from repro.data.synthetic import class_prototypes, dataset_spec
from repro.utils import fold_in_str


def client_class_sets(key, num_clients: int, num_classes: int, k: int) -> jax.Array:
    """(C, k) class ids owned per client (uniform random assignment)."""
    ks = jax.random.split(fold_in_str(key, "class-sets"), num_clients)
    perm = jax.vmap(lambda kk: jax.random.permutation(kk, num_classes))(ks)
    return perm[:, :k]  # (C, k) class ids


def geographic_class_sets(regions: jax.Array, num_classes: int, k: int) -> jax.Array:
    """(C, k) class ids from each client's road region.

    C-ITS data heterogeneity is *spatially correlated* — CAVs in the same
    road segment see the same scenes/scenarios, so neighbours share classes
    (DESIGN.md §9).  Client in region r owns classes {r, r+1, ..., r+k-1}
    mod num_classes.  This coupling of topology and data is what the
    contextual pipeline exploits: network-only selection concentrates on
    well-connected regions and silently drops the classes of poorly
    connected ones.
    """
    r = regions.astype(jnp.int32)[:, None]
    return jnp.mod(r + jnp.arange(k)[None, :], num_classes)


def partition_labels(key, dataset: str, cfg: FLConfig, regions=None) -> jax.Array:
    """(C, n) int32 per-client sample labels — the traced shard index map.

    Dirichlet mode (``cfg.dirichlet_alpha > 0``) draws per-client class
    proportions; otherwise each client owns ``classes_per_client`` classes
    (geographic when ``regions`` is given, uniform-random otherwise).
    Pure jnp: jit/vmap-safe given static ``dataset``/``cfg``.
    """
    spec = dataset_spec(dataset)
    C, n = cfg.num_clients, cfg.samples_per_client
    kd = fold_in_str(key, f"data/{dataset}")

    if cfg.dirichlet_alpha > 0:
        ka = fold_in_str(kd, "dirichlet")
        alphas = jnp.full((spec.num_classes,), cfg.dirichlet_alpha)
        props = jax.random.dirichlet(ka, alphas, (C,))  # (C, classes)
        kl = jax.random.split(fold_in_str(kd, "labels"), C)
        labels = jax.vmap(
            lambda kk, p: jax.random.categorical(kk, jnp.log(p + 1e-9), shape=(n,))
        )(kl, props)
    else:
        k = max(min(cfg.classes_per_client, spec.num_classes), 1)
        if regions is not None:
            own = geographic_class_sets(regions, spec.num_classes, k)
        else:
            own = client_class_sets(kd, C, spec.num_classes, k)  # (C, k)
        kl = jax.random.split(fold_in_str(kd, "labels"), C)
        pick = jax.vmap(lambda kk: jax.random.randint(kk, (n,), 0, k))(kl)
        labels = jnp.take_along_axis(own, pick, axis=1)  # (C, n)
    return labels


def client_images(key, dataset: str, labels: jax.Array) -> jax.Array:
    """Materialize (C, n, D) sample rows from a (C, n) label index map.

    ``protos[labels] + noise`` with prototypes shared across clients and
    noise per-client; deterministic in (key, labels), so the host path and
    the on-device path produce identical arrays.  Each sample is one
    contiguous row of D = H*W*ch features (the row-major flattening of its
    image), so on the chip the features lie in the lanes and a training
    step gathers its batch as whole rows (``fl.client``).
    """
    spec = dataset_spec(dataset)
    C, n = labels.shape
    kd = fold_in_str(key, f"data/{dataset}")
    protos = class_prototypes(kd, spec)  # shared across clients
    kn = jax.random.split(fold_in_str(kd, "noise"), C)
    D = protos[0].size
    noise = jax.vmap(
        lambda kk: spec.noise * jax.random.normal(kk, (n, D))
    )(kn)
    return protos.reshape(-1, D)[labels] + noise


def client_sample_counts(labels: jax.Array) -> jax.Array:
    """(C,) f32 usable-sample counts straight from the shard label map.

    Negative labels mark padding slots (none of the current partitioners
    emit any, so counts == ``samples_per_client`` everywhere today and
    FedAvg weighting is bitwise-unchanged); a ragged partitioner only has
    to pad with ``-1`` for its clients to be weighted by what they
    actually hold.  Rides ``RoundData.counts`` so the round core never
    reads the config constant.
    """
    return jnp.sum(labels >= 0, axis=1).astype(jnp.float32)


def rsu_sample_mass(weights: jax.Array, rid: jax.Array, n_rsu: int) -> jax.Array:
    """(R,) per-RSU aggregation mass: scatter-sum of weights by attachment.

    The edge half of two-tier FedAvg weighting: each RSU's mass is the sum
    of its attached clients' (masked) sample-count weights, and the server
    normalizes by the sum of LIVE RSU masses.  ``client_sample_counts``
    values are integer-valued floats, so this scatter-add reassociation is
    EXACT — summing per-RSU masses equals summing the flat weight vector
    bit for bit, which is what keeps sample-count-weighted FedAvg bitwise
    between the flat and hierarchical lanes
    (tests/test_hierarchical.py pins the regression).
    """
    return jnp.zeros((n_rsu,), jnp.float32).at[rid].add(
        weights.astype(jnp.float32)
    )


def partition_clients(key, dataset: str, cfg: FLConfig, regions=None):
    """Returns (images (C, n, H*W*ch) rows, labels (C, n)) for all C clients.

    ``regions``: optional (C,) road-region ids enabling geographic non-iid.
    """
    labels = partition_labels(key, dataset, cfg, regions)
    return client_images(key, dataset, labels), labels


def shard_local_rows(data_idx, n_shards: int):
    """Plan shard-local RoundData placement for a sharded grid.

    ``data_idx``: (G,) global dedup-row index per grid lane, G divisible by
    ``n_shards`` (the engine pads first); lanes are split contiguously over
    shards (``shard_map`` on the leading grid axis).  Returns

      * ``shard_rows`` — (n_shards, M) int32: which GLOBAL rows each shard
        materializes, M = max over shards of locally-referenced unique rows
        (shards needing fewer repeat their first row — harmless duplicate
        work bounded by the worst shard);
      * ``local_idx``  — (G,) int32: each lane's row as an index into ITS
        shard's M-row slice.

    Host-side and static: ``data_idx`` is host-known at grid-build time, so
    the per-shard row sets (and therefore all shapes) are static.  With
    this plan each device expands only the seeds its own lanes gather —
    seed-heavy grids' client-data footprint scales ~1/n_shards instead of
    replicating every dedup row on every device.  Pure-numpy sibling of the
    traced partitioners above.
    """
    import numpy as np

    didx = np.asarray(data_idx, np.int32)
    G = didx.shape[0]
    assert G % n_shards == 0, (G, n_shards)
    per = G // n_shards
    locals_: list = []
    for s in range(n_shards):
        rows = list(dict.fromkeys(didx[s * per:(s + 1) * per].tolist()))
        locals_.append(rows)
    M = max(len(r) for r in locals_)
    shard_rows = np.stack([
        np.asarray(r + [r[0]] * (M - len(r)), np.int32) for r in locals_
    ])
    local_idx = np.empty((G,), np.int32)
    for s, rows in enumerate(locals_):
        pos = {g: i for i, g in enumerate(rows)}
        for lane in range(s * per, (s + 1) * per):
            local_idx[lane] = pos[didx[lane]]
    return shard_rows, local_idx


def make_test_set(key, dataset: str, n_test: int = 2_000):
    """Global iid test set with the same shared prototypes."""
    spec = dataset_spec(dataset)
    kd = fold_in_str(key, f"data/{dataset}")  # same proto stream as clients
    protos = class_prototypes(kd, spec)
    kt = fold_in_str(kd, "test")
    labels = jax.random.randint(fold_in_str(kt, "labels"), (n_test,), 0, spec.num_classes)
    noise = spec.noise * jax.random.normal(fold_in_str(kt, "noise"), (n_test, *spec.shape))
    return protos[labels] + noise, labels
