"""``batch.idle_release_share``: the share of a window's batches that the
batcher released early, read from the ``StageTimes`` stamps.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import types

from bench import registry, run

from repro.runtime import StageTimes

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sift200k-q8.steady"


def _read(batches):
    cell = registry.resolve(REPO, CELL)
    read = registry.metric_reader(REPO, "batch.idle_release_share")
    return read(run.RunData(cell, batches, None, {}))


def test_share_of_stamped_batches():
    batches = [StageTimes(size=s, released_idle=e)
               for s, e in ((1, True), (3, False), (2, True), (9, False))]
    assert _read(batches) == 50.0
    assert _read(batches[:1]) == 100.0
    assert _read(batches[1:2]) == 0.0


def test_nothing_without_the_stamp():
    """A program that does not stamp the release (an older ``StageTimes``)
    reads nothing, as does a window with no batch."""
    assert _read([types.SimpleNamespace(size=4)]) is None
    assert _read([]) is None
