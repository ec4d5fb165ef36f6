"""The comparison that decides ``correct``: the reference is exact, its
control fails the limits, and a run whose served path is broken underneath
comes out not correct.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from conftest import REPO

from bench import corpus, reference, registry, run


def _limits(tier: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs",
                           f"sift200k-{tier}.json")) as f:
        return json.load(f)["correct"]


@pytest.fixture(scope="module")
def small():
    x = corpus.make_vectors(4000, 128, 64, 0.25, 2**31 + 1)
    q, _ = corpus.make_pool(96, 128, 64, 0.25, 2**31 + 1, 2**31 + 9, 1.2)
    return x, q


def test_reference_is_exact_brute_force(small):
    x, q = small
    d, i = reference.exact_topk(x, q, 10)
    full = ((q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
            ** 2).sum(-1)
    want = np.argsort(full, axis=1)[:, :10]
    np.testing.assert_array_equal(np.sort(i, 1), np.sort(want, 1))
    np.testing.assert_allclose(d, np.take_along_axis(full, want, 1),
                               rtol=1e-4)


@pytest.mark.parametrize("tier", ["q8", "f32"])
def test_control_fails_and_the_reference_passes(small, tier):
    x, q = small
    _, true_ids = reference.exact_topk(x, q, 10)
    d, i = reference.exact_topk(x, q, 10)
    ok, _ = reference.judge(reference.compare(x, q, i, d, true_ids, 0),
                            _limits(tier))
    assert ok
    cd, ci = reference.control_answers(x, q, 10)
    numbers = reference.compare(x, q, ci, cd, true_ids, 0)
    ok, rows = reference.judge(numbers, _limits(tier))
    assert not ok
    assert numbers["dist_rel_err"] > _limits(tier)["dist_rel_err"]


def test_exact_checks_catch_bad_answers(small):
    x, q = small
    d, i = reference.exact_topk(x, q[:4], 10)
    bad_i, bad_d = i.copy(), d.copy()
    bad_i[0, 1] = bad_i[0, 0]            # a repeated id
    bad_i[1, 2] = len(x)                 # out of range
    bad_d[2] = bad_d[2][::-1]            # not ascending
    bad_d[3, 4] = np.nan                 # not finite
    n = reference.compare(x, q[:4], bad_i, bad_d, i, 3)
    assert n["bad_answers"] == 4 and n["missing"] == 3
    assert np.isfinite(n["dist_rel_err"])
    # a no-neighbour mark (-1, +inf) says something wrong only where it is
    # not at the answer's tail or not so marked; at the tail it is a miss
    d, i = reference.exact_topk(x, q[:4], 10)
    short_i, short_d = i.copy(), d.copy()
    short_i[:, 6:], short_d[:, 6:] = -1, np.inf
    n = reference.compare(x, q[:4], short_i, short_d, i, 0)
    assert n["bad_answers"] == 0 and n["recall_miss"] == pytest.approx(0.4)
    assert reference.short_answers(short_i, short_d) == 4
    short_i[0, 9], short_d[0, 9] = i[0, 9], d[0, 9]     # an id after a mark
    short_d[1, 8] = 1.0                                 # a mark, finite
    short_i[2, 7] = -2                                  # out of range
    n = reference.compare(x, q[:4], short_i, short_d, i, 0)
    assert n["bad_answers"] == 3


def _harvest_with(fault):
    from repro.runtime.pipeline import PrefetchPipeline

    real = PrefetchPipeline.harvest

    def harvest(self, infl):
        return fault(real(self, infl))

    return harvest


def _altered(result):
    """One answer altered where it is produced: a distance off by 1%."""
    result.dists = np.array(result.dists, copy=True)
    result.dists[0, 0] *= 1.01
    return result


def _half_left_out(result):
    """Half of the batch left out: its second half answered with the first
    half's results."""
    b = len(result.ids)
    if b >= 2:
        h = b // 2
        result.ids = np.array(result.ids, copy=True)
        result.dists = np.array(result.dists, copy=True)
        result.ids[h:2 * h] = result.ids[:h]
        result.dists[h:2 * h] = result.dists[:h]
    return result


def _run(root, tier, monkeypatch, cache):
    import jax

    monkeypatch.setattr(run, "CACHE", cache)
    monkeypatch.setattr(run, "check_mosaic", lambda pipe, batch: None)
    cell = registry.resolve(root, f"tiny-{tier}.steady")
    peak = roofline_peak()
    return run.run_cell(cell, 2**31 + 7, 2.0, False, jax.devices()[:1], peak)


def roofline_peak() -> dict:
    from bench import roofline

    return roofline.peaks("TPU v5 lite")


@pytest.mark.parametrize("tier", ["q8", "f32"])
def test_a_sound_run_is_correct(tiny_root, tier, monkeypatch, tmp_path):
    out = _run(tiny_root, tier, monkeypatch, str(tmp_path / "cache"))
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 120
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"qps", "p50_ms", "recall_at_10",
                                   "setup_s"}


@pytest.mark.parametrize("fault", [_altered, _half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
def test_a_broken_path_is_not_correct(tiny_root, fault, monkeypatch,
                                      tmp_path):
    from repro.runtime.pipeline import PrefetchPipeline

    monkeypatch.setattr(PrefetchPipeline, "harvest", _harvest_with(fault))
    out = _run(tiny_root, "q8", monkeypatch, str(tmp_path / "cache"))
    assert out["correct"] is False
