"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs the pure-jnp
oracle in kernels/ref.py."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.ivf_scan import ivf_scan, ivf_scan_clustermajor
from repro.kernels.pairwise_l2 import pairwise_l2


@pytest.mark.parametrize("n,m,d", [(8, 16, 8), (128, 128, 128),
                                   (100, 257, 96), (33, 64, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2_sweep(n, m, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * m + d))
    a = jax.random.normal(k1, (n, d), dtype)
    b = jax.random.normal(k2, (m, d), dtype)
    got = pairwise_l2(a, b, bn=32, bm=64, bd=64, interpret=True)
    want = ref.pairwise_l2_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("c,l,d,b,p", [(16, 8, 16, 4, 4), (64, 32, 64, 8, 16),
                                       (10, 16, 24, 3, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ivf_scan_sweep(c, l, d, b, p, dtype):
    key = jax.random.PRNGKey(c + l + d)
    k1, k2, k3 = jax.random.split(key, 3)
    postings = jax.random.normal(k1, (c, l, d), dtype)
    queries = jax.random.normal(k2, (b, d), dtype)
    cids = jax.random.randint(k3, (b, p), 0, c)
    mask = jax.random.bernoulli(k3, 0.7, (b, p))
    got = ivf_scan(postings, cids, mask, queries, interpret=True)
    want = ref.ivf_scan_ref(postings, cids, mask, queries)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)
    # masked probes are +inf in both
    assert np.all(np.isinf(np.asarray(got)[~np.asarray(mask)]))


@pytest.mark.parametrize("c,l,d,b,a_n", [(16, 8, 16, 4, 6), (32, 16, 32, 8, 12)])
def test_ivf_scan_clustermajor_sweep(c, l, d, b, a_n):
    key = jax.random.PRNGKey(a_n)
    k1, k2, k3 = jax.random.split(key, 3)
    postings = jax.random.normal(k1, (c, l, d))
    queries = jax.random.normal(k2, (b, d))
    active = jax.random.randint(k3, (a_n,), 0, c)
    qsel = jax.random.bernoulli(k3, 0.5, (a_n, b))
    got = ivf_scan_clustermajor(postings, active, qsel, queries, interpret=True)
    want = ref.ivf_scan_clustermajor_ref(postings, active, qsel, queries)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,d,k,bn", [(300, 32, 17, 64), (1000, 24, 33, 128),
                                      (37, 130, 5, 8), (8, 8, 8, 512),
                                      (257, 48, 129, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_update_sweep(n, d, k, bn, dtype):
    """Fused assign/update kernel (interpret) vs the jnp oracle: exact
    assignments and counts, tolerance on the float accumulations."""
    from repro.kernels.kmeans_assign import kmeans_assign_update

    k1, k2 = jax.random.split(jax.random.PRNGKey(n + d + k))
    x = jax.random.normal(k1, (n, d), dtype)
    c = jax.random.normal(k2, (k, d), dtype)
    a, md, s, cnt = kmeans_assign_update(x, c, bn=bn, interpret=True)
    ar, mr, sr, cr = ref.kmeans_assign_update_ref(x, c)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ar))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cr))
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(md), np.asarray(mr),
                               rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=tol, atol=tol * 10)
    # the set reduction is closed: every point lands in exactly one centroid
    assert float(np.asarray(cnt).sum()) == n
    np.testing.assert_allclose(np.asarray(s).sum(0),
                               np.asarray(x, np.float32).sum(0),
                               rtol=tol * 10, atol=tol * 100)


@pytest.mark.parametrize("impl", ["kernel", "ref", "ops"])
def test_kmeans_assign_update_n_valid_masks_padding(impl):
    """Rows at or past ``n_valid`` are padding: the live rows get the
    unpadded call's result, the padding adds nothing to sums or counts and
    reports min-dist -inf (so a worst-served pick never takes it)."""
    from repro.kernels.kmeans_assign import kmeans_assign_update

    n, pad, d, k = 300, 212, 24, 7
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (k, d))
    xp = jnp.pad(x, ((0, pad), (0, 0)), constant_values=3.0)
    run = {"kernel": lambda a, nv=None: kmeans_assign_update(
               a, c, nv, bn=64, interpret=True),
           "ref": lambda a, nv=None: ref.kmeans_assign_update_ref(a, c, nv),
           "ops": lambda a, nv=None: ops.kmeans_assign_update(
               a, c, chunk=128, n_valid=nv)}[impl]
    a0, m0, s0, c0 = run(x)
    a1, m1, s1, c1 = run(xp, jnp.int32(n))
    np.testing.assert_array_equal(np.asarray(a1)[:n], np.asarray(a0))
    np.testing.assert_allclose(np.asarray(m1)[:n], np.asarray(m0),
                               rtol=1e-6, atol=1e-6)
    assert np.all(np.isneginf(np.asarray(m1)[n:]))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=1e-5, atol=1e-5)


def test_kmeans_bucketed_rows_compile_once_per_bucket():
    """The device Lloyd loop pads points to a power-of-two bucket, so
    k-means at two point counts in one bucket reuses the compiled
    assign program (the build's recursive splitter calls it at a
    different count for every node)."""
    from repro.build.kmeans import kmeans

    rng = np.random.default_rng(3)
    x = rng.normal(size=(700, 8)).astype(np.float32)
    kmeans(x[:600], 4, iters=2, fused=True)
    before = ops._ref_assign_tile._cache_size()
    cents, assign, inertia = kmeans(x[:650], 4, iters=2, fused=True)
    assert ops._ref_assign_tile._cache_size() == before
    assert assign.shape == (650,) and assign.max() < 4
    # inertia is measured before the last M-step, which cannot raise it
    after = ((x[:650] - cents[assign]) ** 2).sum()
    assert 0 < after <= inertia * (1 + 1e-5)


def test_kmeans_assign_update_accumulates_across_blocks():
    """Multi-block grids must fold partial sums into the SAME revisited
    VMEM block — catch any init/flush bug by making every block contribute
    to every centroid."""
    from repro.kernels.kmeans_assign import kmeans_assign_update

    n, d, k = 64, 16, 4
    rng = np.random.default_rng(0)
    c = rng.normal(size=(k, d)).astype(np.float32)
    x = np.repeat(c, n // k, axis=0) + 1e-3 * rng.normal(
        size=(n, d)).astype(np.float32)
    order = rng.permutation(n)            # interleave: all blocks hit all k
    x = x[order]
    a, _, s, cnt = kmeans_assign_update(
        jnp.asarray(x), jnp.asarray(c), bn=8, interpret=True)
    assert np.asarray(cnt).tolist() == [n // k] * k
    want = np.stack([x[np.asarray(a) == j].sum(0) for j in range(k)])
    np.testing.assert_allclose(np.asarray(s), want, rtol=1e-5, atol=1e-5)


def test_kmeans_assign_update_chunked_wrapper_matches_single():
    """ops.kmeans_assign_update chunking over N is invisible in the result."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (500, 20))
    c = jax.random.normal(k2, (13, 20))
    a0, m0, s0, c0 = ops.kmeans_assign_update(x, c, chunk=10_000)
    a1, m1, s1, c1 = ops.kmeans_assign_update(x, c, chunk=64)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    np.testing.assert_allclose(np.asarray(m0), np.asarray(m1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


def test_kmeans_assign_matches_argmin():
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(k1, (300, 32))
    c = jax.random.normal(k2, (17, 32))
    assign, mind = ops.kmeans_assign(x, c, chunk=128)
    d = ref.pairwise_l2_ref(x, c)
    np.testing.assert_array_equal(np.asarray(assign), np.argmin(np.asarray(d), 1))
    # fused-vs-unfused float noise near zero: atol-dominated comparison
    np.testing.assert_allclose(np.asarray(mind), np.min(np.asarray(d), 1),
                               rtol=1e-4, atol=1e-4)


def test_ops_wrappers_dispatch():
    """ops.* must run without explicit interpret flags on this backend."""
    a = jnp.ones((16, 8))
    b = jnp.zeros((4, 8))
    out = ops.pairwise_l2(a, b)
    np.testing.assert_allclose(np.asarray(out), np.full((16, 4), 8.0), rtol=1e-6)


@pytest.mark.parametrize("k,d", [(5, 8), (37, 19), (129, 130), (128, 128)])
@pytest.mark.parametrize("n_empty", [0, 1, 4])
def test_kmeans_mstep_kernel_matches_ref(k, d, n_empty):
    """Fused M-step kernel (interpret) vs jnp oracle vs the host formula:
    exact division for live clusters, exact rank-ordered reseed for empties
    (the e-th empty cluster takes the e-th worst-served candidate)."""
    from repro.kernels.kmeans_mstep import kmeans_mstep

    rng = np.random.default_rng(k * 1000 + d + n_empty)
    sums = (rng.normal(size=(k, d)) * 10).astype(np.float32)
    counts = rng.integers(1, 5, size=k).astype(np.float32)
    empties = rng.choice(k, size=min(n_empty, k), replace=False)
    counts[empties] = 0.0
    reseed = rng.normal(size=(k, d)).astype(np.float32)
    out = np.asarray(kmeans_mstep(jnp.asarray(sums), jnp.asarray(counts),
                                  jnp.asarray(reseed), interpret=True))
    out_ref = np.asarray(ref.kmeans_mstep_ref(
        jnp.asarray(sums), jnp.asarray(counts), jnp.asarray(reseed)))
    np.testing.assert_array_equal(out, out_ref)
    empty = counts <= 0
    want = sums / np.maximum(counts, 1.0)[:, None]
    want[empty] = reseed[(np.cumsum(empty) - empty)[empty]]
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


def test_kmeans_device_mstep_matches_host_path():
    """Whole-Lloyd-iteration parity: the device-resident loop (fused assign
    kernel + top-k worst-served gather + M-step kernel) reproduces the host
    M-step path — same assignments, same centroids, same inertia."""
    from repro.build.kmeans import kmeans

    rng = np.random.default_rng(11)
    # two tight blobs + k larger than the natural cluster count so empty
    # clusters actually occur and the reseed path is exercised
    x = np.concatenate([
        rng.normal(loc=0.0, scale=0.05, size=(200, 8)),
        rng.normal(loc=9.0, scale=0.05, size=(200, 8)),
    ]).astype(np.float32)
    cd, ad, inertia_d = kmeans(x, 12, iters=5, seed=2, fused=True,
                               device_mstep=True)
    ch, ah, inertia_h = kmeans(x, 12, iters=5, seed=2, fused=True,
                               device_mstep=False)
    np.testing.assert_array_equal(ad, ah)
    np.testing.assert_allclose(cd, ch, rtol=2e-6, atol=2e-6)
    assert abs(inertia_d - inertia_h) <= 1e-3 * max(abs(inertia_h), 1.0)


def test_kmeans_mstep_ops_dispatch():
    sums = jnp.asarray(np.eye(4, 8, dtype=np.float32) * 6.0)
    counts = jnp.asarray(np.array([2.0, 0.0, 3.0, 0.0], np.float32))
    reseed = jnp.asarray(np.arange(32, dtype=np.float32).reshape(4, 8))
    out = np.asarray(ops.kmeans_mstep(sums, counts, reseed))
    np.testing.assert_allclose(out[0], np.eye(4, 8)[0] * 3.0)
    np.testing.assert_allclose(out[2], np.eye(4, 8)[2] * 2.0)
    np.testing.assert_allclose(out[1], reseed[0])    # 1st empty -> 1st worst
    np.testing.assert_allclose(out[3], reseed[1])    # 2nd empty -> 2nd worst
