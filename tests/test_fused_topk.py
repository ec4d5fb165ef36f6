"""Candidate-compressed serving data path: fused-topk kernels vs the ref.py
oracles (interpret mode), merge edge cases, engine equivalence old vs new,
and the level-cache hygiene fixes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.distance import dedup_topk, merge_candidate_topk
from repro.kernels import ref
from repro.kernels.ivf_scan import ivf_scan_topk, plan_tile_probes
from repro.kernels.ivf_scan_q8 import ivf_scan_q8_topk


def _assert_candidates_match(gd, gi, wd, wi, tol=1e-4):
    """Distances must match elementwise; ids must match except inside tied
    groups (equal distances), where only the id SET must agree."""
    gd, gi, wd, wi = map(np.asarray, (gd, gi, wd, wi))
    np.testing.assert_allclose(gd, wd, rtol=tol, atol=tol * 10)
    for r in range(gd.shape[0]):
        # compare ids where the distance is unique within the row
        for j in range(gd.shape[1]):
            if np.isinf(wd[r, j]):
                assert gi[r, j] == -1 and wi[r, j] == -1
                continue
            tied = np.isclose(wd[r], wd[r, j], rtol=tol, atol=tol * 10)
            if tied.sum() == 1:
                assert gi[r, j] == wi[r, j], (r, j, gi[r], wi[r])
            else:
                assert set(gi[r][tied].tolist()) == set(wi[r][tied].tolist())


@pytest.mark.parametrize("c,l,d,b,p,bq", [(16, 8, 16, 4, 4, 2),
                                          (64, 32, 64, 8, 16, 4),
                                          (10, 16, 24, 3, 5, 8),
                                          (32, 16, 32, 13, 7, 4)])
def test_ivf_scan_topk_matches_oracle(c, l, d, b, p, bq):
    key = jax.random.PRNGKey(c * l + d + b)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    postings = jax.random.normal(k1, (c, l, d))
    queries = jax.random.normal(k2, (b, d))
    cids = jax.random.randint(k3, (b, p), 0, c)
    mask = jax.random.bernoulli(k4, 0.7, (b, p))
    pids = jax.random.randint(k1, (c, l), -1, 4 * c * l)
    k2c = 12
    gd, gi, _ = ivf_scan_topk(postings, pids, cids, mask, queries,
                              k2=k2c, bq=bq, interpret=True)
    wd, wi = ref.ivf_scan_topk_ref(postings, pids, cids, mask, queries, k2c)
    assert gd.shape == (b, k2c) and gi.shape == (b, k2c)
    _assert_candidates_match(gd, gi, wd, wi)


def test_ivf_scan_topk_all_masked_and_dup_probes():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    postings = jax.random.normal(k1, (8, 4, 8))
    queries = jax.random.normal(k2, (4, 8))
    # every query probes cluster 3 four times (duplicate probes must not
    # produce duplicate candidates), query 0 fully masked
    cids = jnp.full((4, 4), 3, jnp.int32)
    mask = jnp.ones((4, 4), bool).at[0].set(False)
    pids = jnp.arange(8 * 4, dtype=jnp.int32).reshape(8, 4)
    gd, gi, _ = ivf_scan_topk(postings, pids, cids, mask, queries,
                              k2=8, bq=2, interpret=True)
    gd, gi = np.asarray(gd), np.asarray(gi)
    assert np.all(np.isinf(gd[0])) and np.all(gi[0] == -1)
    for r in range(1, 4):
        valid = gi[r][gi[r] >= 0]
        assert len(valid) == 4                      # L=4 slots, scanned once
        assert len(set(valid.tolist())) == len(valid)


def test_ivf_scan_q8_topk_matches_oracle():
    for (c, l, d, b, p, bq) in [(16, 8, 16, 4, 4, 2), (32, 16, 32, 6, 8, 4)]:
        key = jax.random.PRNGKey(c + l + d)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        cents = jax.random.normal(k1, (c, d))
        post = cents[:, None, :] + 0.1 * jax.random.normal(k2, (c, l, d))
        r = post - cents[:, None, :]
        amax = jnp.max(jnp.abs(r), axis=(1, 2), keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q8 = jnp.clip(jnp.round(r / scale), -127, 127).astype(jnp.int8)
        norm2 = (scale ** 2)[:, :, 0] * jnp.sum(
            q8.astype(jnp.float32) ** 2, axis=-1)
        queries = jax.random.normal(k3, (b, d))
        cids = jax.random.randint(k4, (b, p), 0, c)
        mask = jax.random.bernoulli(k5, 0.8, (b, p))
        pids = jax.random.randint(k4, (c, l), 0, 10_000)
        gd, gi, _ = ivf_scan_q8_topk(q8, scale, norm2, cents, pids, cids,
                                     mask, queries, k2=10, bq=bq,
                                     interpret=True)
        wd, wi = ref.ivf_scan_q8_topk_ref(q8, scale, norm2, cents, pids,
                                          cids, mask, queries, 10)
        _assert_candidates_match(gd, gi, wd, wi, tol=1e-3)


def test_plan_tile_probes_covers_union_once():
    cids = jnp.asarray([[1, 5, 1, 7], [5, 5, 2, 0]], jnp.int32)
    mask = jnp.asarray([[True, True, True, False], [True, False, True, True]])
    tc, qsel = plan_tile_probes(cids, mask, bq=2, n_clusters=8)
    tc, qsel = np.asarray(tc), np.asarray(qsel)
    live = qsel.any(axis=-1)[0]
    # union of live probes = {0, 1, 2, 5}; each exactly once
    assert sorted(tc[0][live].tolist()) == [0, 1, 2, 5]
    # cluster 5: probed (live) by BOTH queries -> one slot serves both
    s5 = int(np.nonzero((tc[0] == 5) & live)[0][0])
    assert qsel[0, s5].tolist() == [1, 1]
    # sorted block table => duplicate clusters adjacent (DMA revisit skip)
    assert (np.diff(tc[0]) >= 0).all()


def test_plan_tile_probes_chunked_parity():
    # tile-chunking only bounds the membership intermediate; the plan must
    # be bit-identical for any chunk size (incl. the degenerate chunk=1)
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    cids = jax.random.randint(k1, (48, 16), -1, 30)
    mask = jax.random.bernoulli(k2, 0.7, (48, 16))
    tc0, qs0 = plan_tile_probes(cids, mask, bq=8, n_clusters=30)
    for chunk in (1, 2, 5):
        tc, qs = plan_tile_probes(cids, mask, bq=8, n_clusters=30,
                                  tile_chunk=chunk)
        np.testing.assert_array_equal(np.asarray(tc0), np.asarray(tc))
        np.testing.assert_array_equal(np.asarray(qs0), np.asarray(qs))


# -------------------------------------------------------------------------
# the gated merge against the ungated merge it replaced
# -------------------------------------------------------------------------
def _extract_topk_ungated(acc_d, acc_i, new_d, new_i, k2):
    """The merge the fused kernels ran on every grid step before it was
    gated: k2 min-extraction passes over the sorted accumulator and the
    block, ties to the accumulator, then the lowest column; each pass kills
    every other copy of the emitted id."""
    bq = acc_d.shape[0]
    acol = jax.lax.broadcasted_iota(jnp.int32, acc_d.shape, 1)
    ncol = jax.lax.broadcasted_iota(jnp.int32, new_d.shape, 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, k2), 1)
    none = acc_d.shape[1] + new_d.shape[1]
    out_d = jnp.full((bq, k2), jnp.inf, jnp.float32)
    out_i = jnp.full((bq, k2), -1, jnp.int32)
    for j in range(k2):
        m = jnp.minimum(jnp.min(acc_d, axis=1, keepdims=True),
                        jnp.min(new_d, axis=1, keepdims=True))
        apos = jnp.min(jnp.where(acc_d == m, acol, none), axis=1,
                       keepdims=True)
        npos = jnp.min(jnp.where(new_d == m, ncol, none), axis=1,
                       keepdims=True)
        ahit = acol == apos
        nhit = (ncol == npos) & (apos == none)
        pid = (jnp.sum(jnp.where(ahit, acc_i, 0), axis=1, keepdims=True)
               + jnp.sum(jnp.where(nhit, new_i, 0), axis=1, keepdims=True))
        ok = m < jnp.inf
        out_d = jnp.where((kcol == j) & ok, m, out_d)
        out_i = jnp.where((kcol == j) & ok, pid, out_i)
        dup = (pid >= 0) & ok
        acc_d = jnp.where(ahit | ((acc_i == pid) & dup), jnp.inf, acc_d)
        new_d = jnp.where(nhit | ((new_i == pid) & dup), jnp.inf, new_d)
    return out_d, out_i


def _ungated_merge(d, pids, qsel_ref, od_ref, oi_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        od_ref[...] = jnp.full(od_ref.shape, jnp.inf, od_ref.dtype)
        oi_ref[...] = jnp.full(oi_ref.shape, -1, oi_ref.dtype)

    qs = qsel_ref[0]
    scol = jax.lax.broadcasted_iota(jnp.int32, qs.shape, 1)
    sel = jnp.max(jnp.where(scol == s, qs, 0), axis=1, keepdims=True) > 0
    ids = jnp.broadcast_to(pids.astype(jnp.int32), d.shape)
    d = jnp.where(sel & (ids >= 0), d, jnp.inf)
    nd, ni = _extract_topk_ungated(od_ref[...], oi_ref[...], d, ids,
                                   od_ref.shape[-1])
    od_ref[...] = nd
    oi_ref[...] = ni


def _ungated_scan(kind, payload, pids, cids, mask, queries, k2, bq):
    """Today's fused kernels with the ungated merge, in interpret mode."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.ivf_scan import _l2_tile, _pick_row, _row_spec, tile_plan
    from repro.kernels.ivf_scan_q8 import _residual_l2

    C, L, D = payload[0].shape
    B = cids.shape[0]
    queries, tc, _, qsel = tile_plan(cids, mask, queries, bq, C)
    nb, s_len = tc.shape
    spec = pl.BlockSpec((bq, k2), lambda t, s, *_: (t, 0))
    out_shape = (jax.ShapeDtypeStruct((nb * bq, k2), jnp.float32),
                 jax.ShapeDtypeStruct((nb * bq, k2), jnp.int32))
    tile = pl.BlockSpec((bq, D), lambda t, s, *_: (t, 0))
    qsel_spec = pl.BlockSpec((1, bq, s_len), lambda t, s, *_: (t, 0, 0))
    block = pl.BlockSpec((1, L, D), lambda t, s, tc, *_: (tc[t, s], 0, 0))
    row = lambda w: _row_spec(C, w, lambda t, s, tc, *_: tc[t, s])
    pids = pids.astype(jnp.int32)
    if kind == "f32":
        def kernel(tc_ref, q_ref, pids_ref, qsel_ref, post_ref, od, oi):
            c = tc_ref[pl.program_id(0), pl.program_id(1)]
            d = _l2_tile(q_ref[...], post_ref[0])
            _ungated_merge(d, _pick_row(pids_ref, c), qsel_ref, od, oi)
        n_scalar, in_specs = 1, [tile, row(L), qsel_spec, block]
        args = (tc, queries, pids, qsel, payload[0])
    else:
        q8, scale, norm2, cents = payload
        step_scale = scale.reshape(C)[tc]

        def kernel(tc_ref, sc_ref, q_ref, cent_ref, n2_ref, pids_ref,
                   qsel_ref, q8_ref, od, oi):
            t, s = pl.program_id(0), pl.program_id(1)
            c = tc_ref[t, s]
            d = _residual_l2(q_ref[...], _pick_row(cent_ref, c), q8_ref[0],
                             sc_ref[t, s], _pick_row(n2_ref, c))
            _ungated_merge(d, _pick_row(pids_ref, c), qsel_ref, od, oi)
        n_scalar = 2
        in_specs = [tile, row(D), row(L), row(L), qsel_spec, block]
        args = (tc, step_scale, queries, cents, norm2, pids, qsel, q8)
    od, oi = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=True,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalar, grid=(nb, s_len),
            in_specs=in_specs, out_specs=[spec, spec]),
    )(*args)
    return od[:B], oi[:B]


def _q8_payload(postings, cents):
    """int8 residual codes of ``postings`` against their clusters' centroids
    (the core/quantize layout): (q8, scale (C,1,1), norm2 (C,L), cents)."""
    r = postings - cents[:, None, :]
    amax = jnp.max(jnp.abs(r), axis=(1, 2), keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q8 = jnp.clip(jnp.round(r / scale), -127, 127).astype(jnp.int8)
    norm2 = (scale ** 2)[:, :, 0] * jnp.sum(q8.astype(jnp.float32) ** 2, -1)
    return q8, scale, norm2, cents


def _gated(kind, payload, *probe, k2, bq):
    fn = ivf_scan_topk if kind == "f32" else ivf_scan_q8_topk
    return fn(*payload, *probe, k2=k2, bq=bq, interpret=True)


def _oracle(kind, payload, *probe, k2):
    fn = (ref.ivf_scan_topk_ref if kind == "f32"
          else ref.ivf_scan_q8_topk_ref)
    return fn(*payload, *probe, k2)


def _merge_case(name):
    """(postings, cents, pids, cids, mask, queries, k2, bq) of one case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    c, l, d, b, bq, k2 = 12, 8, 16, 8, 4, 6
    cents = rng.normal(size=(c, d)).astype(np.float32) * 2.0
    post = cents[:, None, :] + rng.normal(size=(c, l, d)).astype(np.float32)
    pids = np.arange(c * l, dtype=np.int32).reshape(c, l)
    q = rng.normal(size=(b, d)).astype(np.float32)
    cids = np.tile(np.arange(c, dtype=np.int32), (b, 1))
    mask = np.ones((b, c), bool)
    if name == "random":
        b, bq, k2 = 13, 4, 10
        q = rng.normal(size=(b, d)).astype(np.float32)
        cids = rng.integers(0, c, size=(b, 7)).astype(np.int32)
        mask = rng.random((b, 7)) < 0.7
        pids = rng.integers(-1, 2 * c * l, size=(c, l)).astype(np.int32)
    elif name.startswith("closure_dup"):
        # id 999 is held by cluster 2 (an early step) and cluster 9 (a later
        # one); both copies lie far below every query's k2-th distance
        u = rng.normal(size=d).astype(np.float32)
        u /= np.linalg.norm(u)
        near, far = (0.5, 0.1) if name == "closure_dup_later_smaller" \
            else (0.1, 0.3)
        post[2, 0] = q[0] + near * u
        post[9, 3] = q[0] + far * u
        pids[2, 0] = pids[9, 3] = 999
    elif name in ("best_first", "best_last"):
        src = 0 if name == "best_first" else c - 1
        q = post[src, :b] + 0.01 * rng.normal(size=(b, d)).astype(np.float32)
        k2 = 4
    elif name == "padded_tile":
        mask[bq:] = False                  # the second tile is all pad rows
    elif name == "all_masked":
        mask[:] = False
    elif name == "k2_over_live":
        cids, mask, k2 = cids[:, :3], mask[:, :3], 64
        pids[1, 5:] = -1                   # pad slots are never candidates
    elif name == "boundary_ties":
        # every entry of a query is the same distance: the k2-th slot
        # falls inside a tie group spanning every step
        post[:] = post[0, 0]
        cents[:] = cents[0]
        k2 = 5
    return (jnp.asarray(post), jnp.asarray(cents), jnp.asarray(pids),
            jnp.asarray(cids), jnp.asarray(mask), jnp.asarray(q), k2, bq)


MERGE_CASES = ["random", "closure_dup_later_smaller",
               "closure_dup_later_larger", "best_first", "best_last",
               "padded_tile", "all_masked", "k2_over_live", "boundary_ties"]


@pytest.mark.parametrize("kind", ["f32", "q8"])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_gated_merge_matches_ungated(case, kind):
    """The gated kernels emit exactly what the ungated merge emitted (same
    distances, same ids in the same order, ties included) and agree with
    the oracle up to order inside tie groups; their counters obey
    steps = nb * S >= live >= merged and merged <= passes <= k2 * merged."""
    from repro.kernels.ivf_scan import tile_plan

    post, cents, pids, cids, mask, q, k2, bq = _merge_case(case)
    payload = (post,) if kind == "f32" else _q8_payload(post, cents)
    probe = (pids, cids, mask, q)
    gd, gi, st = _gated(kind, payload, *probe, k2=k2, bq=bq)
    ud, ui = _ungated_scan(kind, payload, pids, cids, mask, q, k2, bq)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(ud))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ui))
    wd, wi = _oracle(kind, payload, *probe, k2=k2)
    if case == "boundary_ties":
        np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-5)
    else:
        _assert_candidates_match(gd, gi, wd, wi,
                                 tol=1e-4 if kind == "f32" else 1e-3)
    _, tc, live, _ = tile_plan(cids, mask, q, bq, post.shape[0])
    steps, n_live, merged, passes, compaction = np.asarray(st).tolist()
    assert compaction == 0                 # k2 <= NARROW_K2: no compaction
    assert steps == tc.size
    assert n_live == int(np.asarray(live).sum())
    assert merged <= n_live <= steps
    assert merged <= passes <= k2 * merged
    if case == "all_masked":
        assert (n_live, merged, passes) == (0, 0, 0)
        assert np.all(np.asarray(gi) == -1)
    if case == "k2_over_live":             # the threshold never leaves +inf
        assert merged == n_live


@pytest.mark.parametrize("kind", ["f32", "q8"])
@pytest.mark.parametrize("setup,want", [
    ("every_block_enters", [6, 3, 3, 12, 0]),
    ("first_block_fills", [6, 3, 1, 1, 0]),
    ("all_masked", [6, 0, 0, 0, 0]),
    ("wide", [6, 3, 3, 12, 93]),
])
def test_scan_counters_on_a_known_plan(kind, setup, want):
    """Two queries probing clusters 0, 1, 2 in one tile: 6 grid steps, 3
    live blocks of 4 entries.  With k2 above the 12 entries every live step
    merges with 4 passes; with k2 = 1 and each query a vector of cluster 0,
    cluster 0 fills the accumulator with 1 pass and no later entry is below
    its distance.  At k2 = 200 the buffered merge appends the 4 entries of
    each live step and compacts once, at the tile's last step: two bitonic
    sorts of 512 positions (45 passes each) and three passes more."""
    rng = np.random.default_rng(5)
    c, l, d = 3, 4, 8
    cents = rng.normal(size=(c, d)).astype(np.float32) * 4.0
    post = cents[:, None, :] + rng.normal(size=(c, l, d)).astype(np.float32)
    pids = np.arange(c * l, dtype=np.int32).reshape(c, l)
    cids = np.tile(np.arange(c, dtype=np.int32), (2, 1))
    mask = np.full((2, c), setup != "all_masked")
    k2 = {"first_block_fills": 1, "wide": 200}.get(setup, 16)
    q = post[0, :2].copy() if setup == "first_block_fills" \
        else rng.normal(size=(2, d)).astype(np.float32)
    post, cents = jnp.asarray(post), jnp.asarray(cents)
    payload = (post,) if kind == "f32" else _q8_payload(post, cents)
    _, _, st = _gated(kind, payload, jnp.asarray(pids), jnp.asarray(cids),
                      jnp.asarray(mask), jnp.asarray(q), k2=k2, bq=2)
    assert np.asarray(st).tolist() == want


def test_merge_step_share_reader():
    """``scan.merge_step_share`` reads the counters as a percentage, and
    nothing from batches that lack them (a program without the counters)."""
    import importlib.util
    import os
    import types

    path = os.path.join(os.path.dirname(__file__), "..", "bench", "metrics",
                        "scan.merge_step_share.py")
    spec = importlib.util.spec_from_file_location("merge_step_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ns = types.SimpleNamespace
    run = ns(batches=[ns(scan_steps=2048, scan_steps_merged=100),
                      ns(scan_steps=4096, scan_steps_merged=207)])
    assert mod.read(run) == pytest.approx(100.0 * 307 / 6144)
    assert mod.read(ns(batches=[ns(size=3), ns(size=5)])) is None
    assert mod.read(ns(batches=[])) is None
    assert mod.read(ns(batches=[ns(scan_steps=0,
                                   scan_steps_merged=0)])) is None


# -------------------------------------------------------------------------
# the top-k at wide k2: the buffered merge against the oracle, exactly
# -------------------------------------------------------------------------
def _integer_case(k2):
    """An exact-arithmetic scan: integer centroids, residual codes (scale
    1) and queries, so the kernel and the oracle compute the same integer
    distances bit for bit, with many ties.  Every query probes 40 of 48
    clusters of 128 slots in ascending cluster order, which makes the
    oracle's tie order (probe order) the kernel's (arrival order); 10% of
    the slots are pads, and 300 slots of the second half copy a vector of
    the first half under its id (closure duplicates)."""
    rng = np.random.default_rng(7 + k2)
    c, l, d, b, p = 48, 128, 16, 16, 40
    cents = rng.integers(-3, 4, size=(c, d)).astype(np.float32)
    q8 = rng.integers(-2, 3, size=(c, l, d)).astype(np.int8)
    pids = np.arange(c * l, dtype=np.int32).reshape(c, l)
    pids[rng.random((c, l)) < 0.1] = -1
    src = (rng.integers(0, c // 2, size=300), rng.integers(0, l, size=300))
    dst = (rng.integers(c // 2, c, size=300), rng.integers(0, l, size=300))
    pids[dst] = pids[src]
    q8[dst] = (cents[src[0]] + q8[src] - cents[dst[0]]).astype(np.int8)
    post = cents[:, None, :] + q8.astype(np.float32)
    q = rng.integers(-3, 4, size=(b, d)).astype(np.float32)
    cids = np.sort(np.stack([rng.choice(c, size=p, replace=False)
                             for _ in range(b)]), axis=1).astype(np.int32)
    mask = rng.random((b, p)) < 0.9
    scale = np.ones((c, 1, 1), np.float32)
    norm2 = (q8.astype(np.float32) ** 2).sum(-1)
    payload = {"f32": (jnp.asarray(post),),
               "q8": tuple(map(jnp.asarray, (q8, scale, norm2, cents)))}
    probe = tuple(map(jnp.asarray, (pids, cids, mask, q)))
    return payload, probe


@pytest.mark.parametrize("kind", ["f32", "q8"])
@pytest.mark.parametrize("k2", [24, 200, 2000])
def test_topk_equals_oracle_at_width(kind, k2):
    """Both fused kernels emit exactly the oracle's candidates, distances
    and ids in order, at the narrow width and on both sides of the buffered
    merge's compactions (k2 = 200: many; k2 = 2000: few), with ties and
    closure duplicates.  Only the buffered merge counts compaction
    passes."""
    from repro.kernels.ivf_scan import NARROW_K2

    payload, probe = _integer_case(k2)
    gd, gi, st = _gated(kind, payload[kind], *probe, k2=k2, bq=8)
    wd, wi = _oracle(kind, payload[kind], *probe, k2=k2)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    steps, live, merged, admitted, compaction = np.asarray(st).tolist()
    assert merged <= live <= steps and merged <= admitted
    assert (compaction > 0) == (k2 > NARROW_K2)


def _reader(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "bench", "metrics",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name,field,base,want", [
    ("topk.select_passes_per_live_step", "scan_select_passes",
     "scan_steps_live", (93 + 279) / (40 + 60)),
    ("rerank.rows_per_query", "rerank_rows", "size", (93 + 279) / (40 + 60)),
])
def test_wide_topk_readers(name, field, base, want):
    """The wide top-k's counter readers: a ratio of summed stamps, and
    nothing from batches that lack the stamp (a program without it) or
    that counted none."""
    import types

    read = _reader(name)
    ns = types.SimpleNamespace

    def batch(v, n):
        return ns(**{field: v, base: n, "size": n})

    assert read(ns(batches=[batch(93, 40), batch(279, 60)])) \
        == pytest.approx(want)
    assert read(ns(batches=[ns(size=3, scan_steps_live=3)])) is None
    assert read(ns(batches=[])) is None
    assert read(ns(batches=[batch(0, 0)])) is None


# -------------------------------------------------------------------------
# merge edge cases
# -------------------------------------------------------------------------
def test_merge_candidate_topk_matches_dedup_topk(rng):
    for n, k, n_ids in [(8, 4, 3), (24, 10, 40), (16, 20, 6)]:
        dists = rng.uniform(0, 10, size=(5, n)).astype(np.float32)
        ids = rng.integers(-1, n_ids, size=(5, n)).astype(np.int32)
        vm, im = merge_candidate_topk(jnp.asarray(dists), jnp.asarray(ids), k)
        vd, id_ = dedup_topk(jnp.asarray(dists), jnp.asarray(ids), k)
        np.testing.assert_allclose(np.asarray(vm), np.asarray(vd),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(im), np.asarray(id_))


def test_merge_candidate_topk_all_duplicates():
    dists = jnp.asarray([[3.0, 1.0, 2.0, 5.0]])
    ids = jnp.asarray([[7, 7, 7, 7]], jnp.int32)
    vals, out = merge_candidate_topk(dists, ids, 3)
    assert out[0, 0] == 7 and vals[0, 0] == 1.0       # keeps the min
    assert np.all(np.asarray(out)[0, 1:] == -1)
    assert np.all(np.isinf(np.asarray(vals)[0, 1:]))


def test_merge_candidate_topk_all_masked():
    dists = jnp.full((2, 4), jnp.inf)
    ids = jnp.full((2, 4), -1, jnp.int32)
    vals, out = merge_candidate_topk(dists, ids, 3)
    assert np.all(np.asarray(out) == -1)
    assert np.all(np.isinf(np.asarray(vals)))


def test_merge_candidate_topk_k_exceeds_candidates():
    dists = jnp.asarray([[2.0, 1.0]])
    ids = jnp.asarray([[4, 9]], jnp.int32)
    vals, out = merge_candidate_topk(dists, ids, 6)
    assert out.shape == (1, 6)
    assert np.asarray(out)[0, :2].tolist() == [9, 4]
    assert np.all(np.asarray(out)[0, 2:] == -1)


# -------------------------------------------------------------------------
# engine equivalence: candidate-compressed path vs legacy full-distance path
# -------------------------------------------------------------------------
def _mk_cfg(**kw):
    from repro.core.search import SearchConfig
    return SearchConfig(**kw)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_serve_step_fused_matches_legacy(small_corpus, small_index, use_kernel):
    from repro.core.search import serve_step
    x, q, _ = small_corpus
    qj = jnp.asarray(q[:24] if use_kernel else q)
    tk = jnp.full((qj.shape[0],), 10, jnp.int32)
    outs = []
    for fused in (False, True):
        cfg = _mk_cfg(k=10, nprobe_max=16, pruning="none",
                      use_kernel=use_kernel, fused_topk=fused)
        outs.append(serve_step(small_index, None, qj, tk, cfg))
    np.testing.assert_allclose(np.asarray(outs[0]["dists"]),
                               np.asarray(outs[1]["dists"]),
                               rtol=1e-5, atol=1e-5)
    # identical recall by construction (same unique-id top-k)
    a, b = np.asarray(outs[0]["ids"]), np.asarray(outs[1]["ids"])
    for ra, rb in zip(a, b):
        assert set(ra.tolist()) == set(rb.tolist())


def test_sharded_engine_fused_matches_legacy(small_corpus, small_index):
    from repro.core.search import make_sharded_serve
    x, q, _ = small_corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tk = jnp.full((q.shape[0],), 10, jnp.int32)
    outs = []
    for fused in (False, True):
        cfg = _mk_cfg(k=10, nprobe_max=16, pruning="none", use_kernel=False,
                      fused_topk=fused)
        serve = make_sharded_serve(mesh, cfg)
        d, i, _ = serve(small_index.centroids, small_index.postings,
                        small_index.posting_ids, None, jnp.asarray(q), tk)
        outs.append((np.asarray(d), np.asarray(i)))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-5, atol=1e-5)


def test_quantized_sharded_engine_fused_matches_legacy(small_corpus,
                                                       small_index):
    from repro.core.quantize import quantize_postings
    from repro.core.search import make_sharded_serve_quantized
    x, q, _ = small_corpus
    qp = quantize_postings(small_index.postings, small_index.centroids)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tk = jnp.full((q.shape[0],), 10, jnp.int32)
    outs = []
    for fused in (False, True):
        cfg = _mk_cfg(k=10, nprobe_max=16, pruning="none", use_kernel=False,
                      fused_topk=fused)
        serve = make_sharded_serve_quantized(mesh, cfg)
        d, i, _ = serve(small_index.centroids, qp.q8, qp.scale, qp.norm2,
                        small_index.posting_ids, None, jnp.asarray(q), tk)
        outs.append((np.asarray(d), np.asarray(i)))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------------------
# level-cache hygiene
# -------------------------------------------------------------------------
def test_level_cache_is_lru_bounded():
    from repro.core import search as s
    s._LEVEL_CACHE.clear()
    for i in range(3 * s._LEVEL_CACHE_MAX):
        s._level_cache_lookup(("key", i), lambda: object())
    assert len(s._LEVEL_CACHE) == s._LEVEL_CACHE_MAX
    # most-recent keys survive
    assert ("key", 3 * s._LEVEL_CACHE_MAX - 1) in s._LEVEL_CACHE
    assert ("key", 0) not in s._LEVEL_CACHE
    s._LEVEL_CACHE.clear()


def test_index_token_stable_and_id_reuse_safe():
    from repro.core import search as s

    class Obj:  # weakref-able stand-in
        pass

    a = Obj()
    t1 = s._index_token(a)
    assert s._index_token(a) == t1          # stable for the live object
    b = Obj()
    assert s._index_token(b) != t1          # distinct objects never alias
    # simulate id() reuse: plant a's entry under another object's id, as if
    # the allocator reused the address — the weakref validation must mint a
    # fresh token instead of returning a's stale one
    c = Obj()
    s._INDEX_TOKENS[id(c)] = s._INDEX_TOKENS[id(a)]
    t3 = s._index_token(c)
    assert t3 != t1
