"""Beyond-paper extensions the paper's conclusion/related-work point at:

* **Byzantine-resilient gossip** ("we aim to integrate ... Byzantine-resilient
  variants", Sec. VI): coordinate-wise trimmed-mean aggregation over each
  client's neighborhood.  Robust to up to ``trim`` arbitrary neighbors per
  client, at the cost of the doubly-stochastic property (the tracking
  identity holds only approximately under attack — the price of robustness,
  cf. Yin et al. 2018).
* **Compressed gossip** (cf. [58] Yan et al., compressed decentralized prox
  SGD; CHOCO-gossip, Koloskova et al. 2019): exchange top-k sparsified
  *increments* against shared public copies x̂ — the x̂ table is the
  compression memory, so untransmitted mass is retried, never lost.  Cuts
  per-round gossip bytes to k/d of dense while still reaching consensus.

  **Deprecated**: compression is now a first-class traced operand —
  :class:`repro.core.compression.CompressionSpec` attaches to any
  :class:`~repro.core.schedule.MixSchedule`
  (``schedule.with_compression(spec)``), rides both execution backends
  (packed payloads on the shard_map collectives), and sweeps over rates as
  one compiled program.  The functions below remain as thin shims over
  those primitives, with the *legacy numerics pinned* by
  ``tests/test_robust_compressed.py`` (the one observable difference: the
  new path keeps the running mix ``s = W @ xhat`` incrementally instead of
  recomputing it dense each round — the shim recomputes, exactly as
  before).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import _topk_rows
from repro.core.mixing import MixPlan, apply_mix
from repro.core.schedule import MixSchedule, ScheduleMixer

PyTree = jax.Array


# ---------------------------------------------------------------------------
# Byzantine-resilient trimmed-mean gossip
# ---------------------------------------------------------------------------

def make_trimmed_mean_mixer(W: np.ndarray, trim: int = 1):
    """Coordinate-wise trimmed mean over each client's closed neighborhood.

    For client i: gather {x_j : w_ij > 0} (incl. itself), drop the ``trim``
    largest and smallest values per coordinate, average the rest.  Requires
    every neighborhood to have > 2*trim members.

    Returns a :class:`~repro.core.schedule.ScheduleMixer` ``mix(tree, r)``
    (the same every round) whose schedule is the constant plan of W: it
    exchanges over W's edges, which is what its byte accounting reads.
    """
    adj = np.asarray(W) > 0
    np.fill_diagonal(adj, True)
    counts = adj.sum(1)
    if (counts <= 2 * trim).any():
        raise ValueError(f"trim={trim} too large for degree "
                         f"{int(counts.min()) - 1} neighborhoods")
    adj_j = jnp.asarray(adj)

    def mix(tree, r):
        def leaf(x):
            n = x.shape[0]
            flat = x.reshape(n, -1)

            def one_client(mask):
                # push non-neighbors to +/- inf so sorting isolates them,
                # then drop (trim) from each *valid* end
                big = jnp.float32(3.4e38)
                vals = jnp.where(mask[:, None], flat.astype(jnp.float32), big)
                asc = jnp.sort(vals, axis=0)          # neighbors first
                k = mask.sum()
                lo, hi = trim, k - trim               # keep [lo, hi)
                idx = jnp.arange(n)[:, None]
                keep = (idx >= lo) & (idx < hi)
                s = jnp.where(keep, asc, 0.0).sum(0) / jnp.maximum(hi - lo, 1)
                return s

            mixed = jax.vmap(one_client)(adj_j)       # (n, dflat)
            return mixed.reshape(x.shape).astype(x.dtype)

        return jax.tree_util.tree_map(leaf, tree)

    return ScheduleMixer(mix, MixSchedule.constant(MixPlan.dense(W)))


# ---------------------------------------------------------------------------
# Compressed gossip with error feedback (CHOCO-style)
# ---------------------------------------------------------------------------

def topk_compress(x: jax.Array, k: int):
    """Keep the k largest-magnitude coordinates per client row; zero rest.

    Deprecated shim: delegates to the traced-rate row compressor behind
    ``CompressionSpec.topk`` (``repro.core.compression``), which uses the
    same threshold semantics (ties at the k-th magnitude all survive).
    """
    n = x.shape[0]
    flat = x.reshape(n, -1)
    d = flat.shape[1]
    k = min(int(k), d)
    return _topk_rows(flat, k / d).reshape(x.shape)


class CompressedGossipState(NamedTuple):
    xhat: jax.Array    # (n, ...) public copies every client agrees on


def init_compressed(x: jax.Array) -> CompressedGossipState:
    return CompressedGossipState(xhat=jnp.zeros_like(x))


def compressed_gossip_round(
    x: jax.Array,
    st: CompressedGossipState,
    W: np.ndarray,
    k: int,
    step: float = 0.3,
):
    """One CHOCO-gossip round (Koloskova et al. 2019) on the stacked states.

    Clients broadcast q_i = C_k(x_i - xhat_i) and everyone updates the
    shared copies xhat += q — the xhat table itself is the compression
    memory (the un-transmitted residual x - xhat is retried next round, so
    nothing is lost).  States then take a damped gossip step on the public
    copies:  x <- x + step * (W - I) xhat.  Returns (new_x, new_state,
    bytes_fraction = k/d traffic relative to dense gossip).

    Deprecated shim: recomposed from the ``repro.core.compression``
    primitives in the legacy order (compress -> xhat update -> *fresh*
    dense mix of the public copies), so old trajectories reproduce.  New
    code should attach a spec to its schedule
    (``MixSchedule.with_compression(CompressionSpec.topk(rate))``) and let
    ``depositum.step`` run the error-feedback exchange — same math, but
    with the running mix ``s = W @ xhat`` maintained incrementally so only
    the compressed increment ever crosses the wire.
    """
    q = topk_compress(x - st.xhat, k)
    xhat = st.xhat + q
    mixed = apply_mix(MixPlan.dense(jnp.asarray(W, x.dtype)), xhat)
    x_new = x + step * (mixed - xhat)
    d = x[0].size
    return x_new, CompressedGossipState(xhat=xhat), k / d
