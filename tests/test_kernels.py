"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True).

Explicitly ``tier1``: every PR exercises the kernel tiling geometry in
interpret mode, whatever the backend — the shape grids below deliberately
include NON-multiples of every block size (both just-under and just-over a
block boundary) and the K=1 degenerate cohort, so the padding edges of the
BlockSpecs are part of the contract, not an accident of the sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fedavg_reduce, pairwise_cosine, ref, ssd_scan, swa_decode
from repro.kernels.rsu_reduce import rsu_reduce
from repro.kernels.server_update import server_update, server_update_buffered

pytestmark = pytest.mark.tier1


@pytest.mark.parametrize("n,d", [
    (7, 64), (100, 1024), (128, 512), (33, 2000),
    # padding edges: one under / one over the (block_n=128, block_k=512)
    # tile boundaries, and a single-row Gram
    (127, 511), (129, 513), (1, 512), (256, 1)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_cosine_matches_ref(n, d, dtype):
    x = jax.random.normal(jax.random.key(n * d), (n, d)).astype(dtype)
    out = pairwise_cosine(x, interpret=True)
    expect = ref.pairwise_cosine(x)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol)
    # cosine contract: unit diagonal, symmetry, range
    np.testing.assert_allclose(np.diag(np.asarray(out)), 1.0, atol=tol)
    assert float(jnp.max(jnp.abs(out - out.T))) < 5e-5 + (0.05 if dtype == jnp.bfloat16 else 0)


@pytest.mark.parametrize("k,p", [
    (4, 100), (16, 5000), (100, 2048), (3, 130000),
    # padding edges: K=1 cohorts and P one off either side of the default
    # 2048 tile (plus an exact multiple, which must not gain a pad block)
    (1, 1), (1, 2047), (1, 130000), (5, 2047), (5, 2049), (5, 4096),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_matches_ref(k, p, dtype):
    u = jax.random.normal(jax.random.key(k), (k, p)).astype(dtype)
    w = jax.random.uniform(jax.random.key(p), (k,))
    w = w / w.sum()
    out = fedavg_reduce(u, w, interpret=True)
    expect = ref.fedavg_reduce(u, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol, rtol=tol)
    assert out.shape == (p,)


def test_fedavg_reduce_respects_pick_block_p_geometry():
    """The round step's tile policy (kernels.ops.pick_block_p) drives the
    same kernel the sweep above validates — parity must hold at exactly
    the tile the policy picks for the engine's hot shapes."""
    from repro.kernels import pick_block_p

    for k, p in [(2, 163_840), (100, 38_656), (1, 512)]:
        u = jax.random.normal(jax.random.key(k), (k, p))
        w = jnp.ones((k,)) / k
        out = fedavg_reduce(u, w, block_p=pick_block_p(k, p), interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.fedavg_reduce(u, w)),
            atol=1e-5, rtol=1e-5,
        )


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (64, 0.0), (0, 30.0), (37, 50.0)])
@pytest.mark.parametrize("b,hkv,g,d,c", [(2, 4, 2, 64, 300), (1, 1, 8, 128, 512), (3, 2, 1, 32, 65)])
def test_swa_decode_matches_ref(window, softcap, b, hkv, g, d, c):
    ks = jax.random.split(jax.random.key(b * c + d), 5)
    q = jax.random.normal(ks[0], (b, hkv, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, c, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, c, hkv, d), jnp.float32)
    kvp = jnp.broadcast_to(jnp.arange(c)[None], (b, c)).astype(jnp.int32)
    n_valid = max(c - 10, 1)
    kvp = kvp.at[:, n_valid:].set(-1)
    pos = jax.random.randint(ks[3], (b,), n_valid - 1, n_valid).astype(jnp.int32)
    out = swa_decode(q, k, v, kvp, pos, window=window, softcap=softcap,
                     block_c=128, interpret=True)
    expect = ref.swa_decode(q, k, v, kvp, pos, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-5)


def test_swa_decode_ring_buffer_semantics():
    """Slot order must not matter — only absolute positions."""
    b, hkv, g, d, c = 1, 2, 2, 32, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, c, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, c, hkv, d), jnp.float32)
    kvp = jnp.broadcast_to(jnp.arange(c)[None], (b, c)).astype(jnp.int32)
    pos = jnp.array([c - 1], jnp.int32)
    out1 = swa_decode(q, k, v, kvp, pos, window=17, block_c=32, interpret=True)
    perm = jax.random.permutation(jax.random.key(9), c)
    out2 = swa_decode(q, k[:, perm], v[:, perm], kvp[:, perm], pos,
                      window=17, block_c=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


def test_fedavg_kernel_agrees_with_tree_weighted_sum():
    """The Pallas kernel and the pytree server contraction are one contract."""
    from repro.utils import flatten_to_vector, tree_weighted_sum, unflatten_from_vector

    tree = {
        "a": jax.random.normal(jax.random.key(1), (5, 16, 3)),
        "b": {"c": jax.random.normal(jax.random.key(2), (5, 7))},
    }
    w = jnp.array([0.1, 0.2, 0.3, 0.25, 0.15])
    expect = tree_weighted_sum(tree, w)
    flat = jax.vmap(lambda i: flatten_to_vector(
        jax.tree_util.tree_map(lambda x: x[i], tree))[0])(jnp.arange(5))
    out_vec = fedavg_reduce(flat, w, interpret=True)
    _, spec = flatten_to_vector(jax.tree_util.tree_map(lambda x: x[0], tree))
    out = unflatten_from_vector(out_vec, spec)
    for a, b in zip(jax.tree_util.tree_leaves(expect), jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("b,s,nh,hp,ds,q", [(2, 48, 3, 16, 8, 16), (1, 40, 2, 8, 32, 8),
                                            (3, 33, 4, 32, 16, 16)])
def test_ssd_scan_matches_naive_recurrence(b, s, nh, hp, ds, q):
    ks = jax.random.split(jax.random.key(b * s), 6)
    x = jax.random.normal(ks[0], (b, s, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
    Bs = jax.random.normal(ks[3], (b, s, ds))
    Cs = jax.random.normal(ks[4], (b, s, ds))
    h0 = jax.random.normal(ks[5], (b, nh, hp, ds))
    y_ref, h_ref = ref.ssd_naive(x, dt, A, Bs, Cs, h0)
    y, h = ssd_scan(x, dt, A, Bs, Cs, chunk=q, h0=h0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=5e-4, rtol=5e-4)


def test_ssd_scan_matches_training_path():
    """Pallas serving kernel == pure-JAX training-path SSD (models/ssm.py)."""
    from repro.models.ssm import ssd_scan as ssd_jnp

    ks = jax.random.split(jax.random.key(7), 5)
    b, s, nh, hp, ds = 2, 64, 4, 16, 16
    x = jax.random.normal(ks[0], (b, s, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
    Bs = jax.random.normal(ks[3], (b, s, ds))
    Cs = jax.random.normal(ks[4], (b, s, ds))
    y1, h1 = ssd_scan(x, dt, A, Bs, Cs, chunk=16, interpret=True)
    y2, h2 = ssd_jnp(x, dt, A, Bs, Cs, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2, dtype=np.float32),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=5e-4, rtol=5e-4)


# the FL reductions as compiled for a TPU: (K=8, P=256) fp32 rows, 4 RSUs
_HP = dict(eta=0.1, beta1=0.9, beta2=0.99, tau=1e-3)
_FL_REDUCTIONS = {
    "fedavg_reduce": lambda u, w, i: fedavg_reduce(u, w, block_p=256),
    "rsu_reduce": lambda u, w, i: rsu_reduce(u, w, i % 4, 4, block_p=256,
                                             block_k=8),
    "server_update": lambda u, w, i: server_update(
        u, w, u[0], u[1], u[2], i[0], i[1], block_p=256, **_HP),
    "server_update_buffered": lambda u, w, i: server_update_buffered(
        u, w, u, w, u[0], u[1], u[2], i[0], i[1], True, block_p=256, **_HP),
}


@pytest.mark.parametrize("name", sorted(_FL_REDUCTIONS))
def test_fl_reductions_contract_in_fp32_on_the_chip(name):
    """Every dot of the compiled FL reductions asks for HIGHEST precision:
    Mosaic's default contracts an f32 dot in one bf16 MXU pass, which put
    the kernels ~1e-3 off their fp32 refs on a TPU v5e.  Tracing builds
    the kernel body without compiling it, so this runs on the CPU."""
    u = jnp.ones((8, 256), jnp.float32)
    w, i = jnp.ones((8,), jnp.float32), jnp.arange(8, dtype=jnp.int32)
    text = str(jax.make_jaxpr(_FL_REDUCTIONS[name])(u, w, i))
    dots = text.count("dot_general[")
    assert dots >= 1
    assert text.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") == dots
