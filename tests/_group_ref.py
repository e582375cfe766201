"""The same-root grouping as it was before it ran on the window's lanes
alone (ISSUE 37): a ``vcap``-row scratch filled with the sentinel, the
lane indices scatter-min'd into it keyed by root, and gathered back.
Kept as the plain reference ``forest.group_reps`` is held to (plain
``jnp``, one chip: under a ``vertices`` axis the lanes are whole on
every chip, so every chip fills a whole scratch of its own), beside a
numpy twin for id spaces whose scratch no test can afford."""

import jax.numpy as jnp
import numpy as np
import pytest

from gelly_streaming_tpu.summaries import forest

_I32_MAX = np.iinfo(np.int32).max


def scratch_group(r, tmask, vcap: int):
    """``forest.group_reps`` over a table-sized scratch."""
    lanes = jnp.arange(r.shape[0], dtype=jnp.int32)
    scratch = jnp.full(vcap, _I32_MAX, jnp.int32).at[
        jnp.where(tmask, r, vcap)
    ].min(jnp.where(tmask, lanes, _I32_MAX), mode="drop")
    return jnp.where(tmask, scratch[jnp.where(tmask, r, 0)], lanes)


def numpy_group(r, tmask) -> np.ndarray:
    """Each live lane's smallest lane with the same root, a pad lane
    itself; no array has a row per vertex."""
    r, tmask = np.asarray(r), np.asarray(tmask)
    lanes = np.arange(len(r))
    _roots, group = np.unique(r, return_inverse=True)
    first = np.full(len(r), len(r))
    np.minimum.at(first, group[tmask], lanes[tmask])
    return np.where(tmask, first[group], lanes).astype(np.int32)


@pytest.fixture
def scratch_steps(monkeypatch):
    """-> a call that rebuilds every step over :func:`scratch_group`."""
    def swap():
        monkeypatch.setattr(forest, "group_reps", scratch_group)
        forest._STEP_CACHE.clear()

    yield swap
    monkeypatch.undo()
    forest._STEP_CACHE.clear()
