"""The single filter/score cascade (engine/scan.py `score_pod` and
`filter_and_score`) merges the per-pod skippable stages into one lax.cond
per family: the count-plane score terms share one branch pair, the
Open-Local plan and raw score share one, and the hard spread and interpod
filters share one.  The merge is exact only because a DORMANT stage —
one whose skip predicate is False for the pod — evaluated inside the taken
live branch returns exactly the constant its skip branch returns.  Each
case below feeds one stage's live kernel a dormant pod-side input, with
every other input drawn at random, and pins that equality bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simtpu.kernels.filters import interpod_filter, topology_spread_filter
from simtpu.kernels.scores import (
    MAX_NODE_SCORE,
    interpod_score,
    maxabs_normalize,
    minmax_normalize,
    selector_spread_score,
    topology_spread_score,
)
from simtpu.kernels.storage import open_local_score

T, N, V = 6, 16, 2


def _counts(rng, shape):
    """Integer-valued f32 count planes, as the engine carries them."""
    return rng.integers(0, 9, shape).astype(np.float32)


def _mask(rng):
    m = rng.random(N) < 0.7
    m[rng.integers(N)] = True  # at least one feasible node
    return m


def _own_rows(rng):
    """[T] interpod row of each pod term (-1 = not an interpod term) and
    an s_match that selects only non-interpod terms: the pod touches no
    interpod-owned term, as the skip predicates require."""
    ip_row = rng.integers(-1, 2, T)
    ip_row[0], ip_row[1] = -1, 0  # both kinds present
    s_match = (rng.random(T) < 0.6) & (ip_row < 0)
    s_match[0] = True
    return ip_row, s_match


def _gather_own(rng, ip_row):
    """A random [T, N] own plane gathered through ip_row: -1 rows gather
    as zeros, like take_rows' one-hot matmul."""
    return np.where((ip_row >= 0)[:, None], _counts(rng, (T, N)), 0.0).astype(
        np.float32
    )


def _no_terms(dtype):
    """The dormant pod-side [T] input: no term of this family."""
    return np.zeros(T, dtype)


# each case: (live kernel, its inputs drawn from rng, the skip constant
# scan.py's skip branch returns for the stage).  The inputs reach the
# kernel as jit arguments, so the compiler cannot fold the dormant zeros.


def _interpod_score(rng):
    ip_row, s_match = _own_rows(rng)

    def live(cnt, own_aff, w_own_aff, w_own_anti, s_match, w_aff, w_anti, m):
        raw = interpod_score(
            cnt, own_aff, w_own_aff, w_own_anti, s_match, w_aff, w_anti
        )
        return maxabs_normalize(raw, m)

    args = (
        _counts(rng, (T, N)),
        _gather_own(rng, ip_row),
        _gather_own(rng, ip_row),
        _gather_own(rng, ip_row),
        s_match,
        _no_terms(np.float32),  # no preferred affinity weights
        _no_terms(np.float32),  # no preferred anti-affinity weights
        _mask(rng),
    )
    return live, args, np.zeros(N, np.float32)


def _topology_spread_score(rng):
    args = (
        _counts(rng, (T, N)),
        _no_terms(np.float32),  # no ScheduleAnyway constraint
        _mask(rng),
    )
    return topology_spread_score, args, np.full(N, MAX_NODE_SCORE, np.float32)


def _selector_spread_score(rng):
    args = (
        _counts(rng, (T, N)),
        _no_terms(bool),  # no hostname counting terms
        _no_terms(bool),  # no zone counting terms
        _mask(rng),
    )
    return selector_spread_score, args, np.full(N, MAX_NODE_SCORE, np.float32)


def _topology_spread_filter(rng):
    args = (
        _counts(rng, (T, N)),
        rng.random((T, N)) < 0.8,
        _no_terms(np.float32),  # maxSkew 0 on every term: inactive
        _mask(rng),
    )
    return topology_spread_filter, args, np.ones(N, bool)


def _interpod_filter(rng):
    ip_row, s_match = _own_rows(rng)
    args = (
        _counts(rng, (T, N)),
        _gather_own(rng, ip_row),
        rng.random((T, N)) < 0.8,
        _counts(rng, (T,)),
        s_match,
        _no_terms(bool),  # no required affinity term
        _no_terms(bool),  # no required anti-affinity term
    )
    return interpod_filter, args, np.ones(N, bool)


def _open_local_score(rng):
    def live(alloc, vg_cap, dev_tight, lvm_size, dev_size, m):
        raw = open_local_score(
            alloc,
            vg_cap,
            dev_tight,
            jnp.sum(lvm_size > 0),
            jnp.sum(dev_size > 0),
        )
        return minmax_normalize(raw, m)

    args = (
        rng.uniform(0.0, 50.0, (N, V)).astype(np.float32),
        rng.uniform(100.0, 1000.0, (N, V)).astype(np.float32),
        rng.random(N).astype(np.float32),
        np.zeros(V, np.float32),  # no LVM claim
        np.zeros(V, np.float32),  # no device claim
        _mask(rng),
    )
    return live, args, np.zeros(N, np.float32)


STAGES = {
    "interpod_score": _interpod_score,
    "topology_spread_score": _topology_spread_score,
    "selector_spread_score": _selector_spread_score,
    "topology_spread_filter": _topology_spread_filter,
    "interpod_filter": _interpod_filter,
    "open_local_score": _open_local_score,
}


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_dormant_live_kernel_equals_skip_constant(stage):
    """A dormant stage's live kernel, compiled as the merged branch
    compiles it, reproduces the stage's skip constant exactly."""
    for seed in range(4):
        live, args, want = STAGES[stage](np.random.default_rng(seed))
        got = np.asarray(jax.jit(live)(*args))
        assert got.dtype == want.dtype, stage
        assert got.shape == want.shape, stage
        assert np.array_equal(got, want), (stage, seed, got)
