"""Circulant gossip specs and the no-exchange operand.

Clients mix through one operand, :class:`~repro.core.schedule.MixSchedule`
(a :class:`~repro.core.mixing.MixPlan` is its constant case).  What is left
here is the circulant bookkeeping the tests cross-check plans against
(:func:`torus_circulant_spec`, :func:`circulant_from_mixer_spec`) and
:data:`identity_mixer`, the identity plan that the round program's
collective-free local steps pass.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.mixing import MixPlan

#: W = I: "no exchange" as a mixing operand.
identity_mixer = MixPlan.identity()


def torus_grid_shape(n: int) -> tuple[int, int]:
    """The near-square a×b grid of ``torus_graph`` and the circulant torus."""
    a = int(np.floor(np.sqrt(n)))
    while n % a != 0:
        a -= 1
    return a, n // a


def torus_circulant_spec(n: int):
    """(offsets_weights, self_weight) of the *circulant* torus on n clients.

    This is deliberately NOT the same matrix as ``topology.torus_graph(n)``:
    the grid torus has neighbor (r, (c+1) mod b), which is client i+1 only
    when the column does not wrap, whereas a circulant can only shift by a
    fixed offset — it connects i to (i±1) mod n and (i±b) mod n globally.
    Both are symmetric doubly stochastic (Assumption 2 holds for either),
    both are degree-4 wrap-around graphs with comparable spectral lambda,
    but they are different graphs whenever b < n — including every
    *square* grid.  The circulant is the form that maps onto ``ppermute``:
    ``MixPlan.circulant(*spec)`` runs one per offset, so cross-backend
    equivalence tests must compare that plan against
    ``circulant_from_mixer_spec``/this spec's dense W, never against
    ``torus_graph``.  On n = 2b the ±b offsets coincide and the shared
    edge absorbs both weights (still symmetric, doubly stochastic).
    Returns the ring spec when the factorisation degenerates (a < 2).
    """
    a, b = torus_grid_shape(n)
    if a < 2:
        if n < 3:
            return None, None  # degenerate: use complete
        return [(+1, 1.0 / 3), (-1, 1.0 / 3)], 1.0 / 3
    return [(+1, 0.2), (-1, 0.2), (+b, 0.2), (-b, 0.2)], 0.2


def circulant_from_mixer_spec(
    n: int, offsets_weights: Sequence[tuple[int, float]], self_weight: float
) -> np.ndarray:
    """Dense W of the circulant plan ``MixPlan.circulant(offsets_weights,
    self_weight)`` on n clients — the numpy reference plans are checked
    against."""
    W = np.zeros((n, n))
    for i in range(n):
        W[i, i] += self_weight
        for off, w in offsets_weights:
            W[i, (i + off) % n] += w
    return W
