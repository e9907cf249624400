"""Reference DEPOSITUM run: n clients on a Metropolis mixing matrix W.

Per step t, for every client i (Polyak momentum, l1 prox, T0 = comm period):

    nu  <- gamma nu + (1 - gamma) y
    x   <- soft_threshold(x - alpha nu, alpha lam)
    x   <- W x                          if (t + 1) % T0 == 0
    g'  <- grad f_i(x; batch_i,t)
    y   <- y + beta (g' - g);  g <- g'
    y   <- W y                          if (t + 1) % T0 == 0

from x = x0 (every client) and y = nu = g = 0.  The mix is a dense
contraction with W over the client axis; the clients' state is spread over
the cell's chips on that axis, and each chip computes the gradients of its
own clients one after another, in blocks of rows, to bound memory.

Between updates every variable is held as ``numerics`` says (the
configuration's dtype for the reference); the arithmetic is float32.

Faults for the check's own test: ``half_batch`` takes each gradient over
the first half of the rows only, ``no_mix`` replaces W by the identity.
"""
from __future__ import annotations

import importlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import init as ref_init
from bench.reference.common import NUMERICS, Numerics

#: bytes of the recurrence's saved states that one block of rows may hold
#: in its backward pass (the sequential scan keeps one state per token);
#: more than one block costs a float32 gradient accumulator besides
SCAN_RESIDUAL_BYTES = 4 << 30


def adjacency(topology: str, n: int) -> np.ndarray:
    """Edges of a ring, a star (client 0 the hub) or a complete graph."""
    A = np.zeros((n, n), bool)
    for i in range(n):
        if topology == "ring":
            A[i, (i + 1) % n] = A[(i + 1) % n, i] = True
        elif topology == "star":
            A[0, i] = A[i, 0] = i != 0
        elif topology == "complete":
            A[i] = np.arange(n) != i
        else:
            raise ValueError(f"unknown topology {topology!r}")
    np.fill_diagonal(A, False)
    return A


def mixing_matrix(topology: str, n: int) -> np.ndarray:
    """Metropolis weights: W_ij = 1 / (1 + max(deg_i, deg_j)) on each edge,
    the rest of each row on the diagonal."""
    A = adjacency(topology, n)
    deg = A.sum(1)
    W = np.where(A, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    W[np.diag_indices(n)] = 1.0 - W.sum(1)
    return W


def family(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['family']}")


def row_block(cfg: dict, batch: int, seq: int) -> int:
    """Largest divisor of the batch whose scan residuals fit the budget."""
    di = cfg["ssm_expand"] * cfg["d_model"]
    per_row = seq * (di // cfg["ssm_head_dim"]) * cfg["ssm_state"] * \
        cfg["ssm_head_dim"] * 4
    rows = max(1, min(batch, SCAN_RESIDUAL_BYTES // per_row))
    while batch % rows:
        rows -= 1
    return rows


def norms(tree):
    """Per-client L2 norm of every leaf: {path: (n,)} in float32."""
    def one(v):
        v = v.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim))))
    return jax.tree_util.tree_map(one, tree)


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(tree)]


class ReferenceRun:
    """Builds the jitted pieces of one reference configuration; ``init``
    is the benchmark's ``key -> weights`` (``Cell.weights``)."""

    def __init__(self, cfg: dict, traffic: dict, devices, init, *,
                 numerics: Numerics | str = "reference",
                 half_batch: bool = False, no_mix: bool = False):
        self.cfg, self.traffic = cfg, traffic
        self.num = (NUMERICS[numerics] if isinstance(numerics, str)
                    else numerics)
        n = traffic["n_clients"]
        if n % len(devices):
            raise ValueError(f"{n} clients do not split over "
                             f"{len(devices)} devices")
        self.n = n
        self.mesh = Mesh(np.asarray(devices), ("clients",))
        self.shard = NamedSharding(self.mesh, P("clients"))
        self.repl = NamedSharding(self.mesh, P())
        W = np.eye(n) if no_mix else mixing_matrix(traffic["topology"], n)
        self.W = jax.device_put(jnp.asarray(W, jnp.float32), self.repl)
        self.half_batch = half_batch
        self._fam = family(cfg)
        self.init = init
        self._build()

    def _build(self):
        cfg, num, n = self.cfg, self.num, self.n
        tr = self.traffic
        alpha, beta, gamma = tr["alpha"], tr["beta"], tr["gamma"]
        thr = tr["alpha"] * tr["lam"]
        store = num.storer(jnp.dtype(cfg["dtype"]))
        tm = jax.tree_util.tree_map
        fam = self._fam
        batch = tr["batch"] // 2 if self.half_batch else tr["batch"]
        rows = row_block(cfg, batch, tr["seq_len"])

        def start(p):
            x = tm(lambda v: store(jnp.broadcast_to(
                v.astype(jnp.float32)[None], (n,) + v.shape)), p)
            z = lambda: tm(jnp.zeros_like, x)
            return x, z(), z(), z()

        self._start = jax.jit(start, out_shardings=self.shard)

        def pre(x, y, nu):
            nu = tm(lambda a, b: gamma * a + (1 - gamma) * b, nu, y)
            x = tm(lambda v, m: store(jnp.sign(v - alpha * m) * jnp.maximum(
                jnp.abs(v - alpha * m) - thr, 0.0)), x, nu)
            return x, tm(store, nu)

        self._pre = jax.jit(pre, donate_argnums=(0, 2))

        def mix_leaf(W, v):
            return store(jnp.einsum("ij,j...->i...", W, v,
                                    precision=jax.lax.Precision.HIGHEST))

        # donated though the product cannot be written in place: the old
        # leaf is freed as soon as its mix is made
        self._mix = jax.jit(mix_leaf, donate_argnums=1,
                            out_shardings=self.shard)

        def client_vg(params, tokens, labels):
            if batch != tokens.shape[0]:
                tokens, labels = tokens[:batch], labels[:batch]
            vg = jax.value_and_grad(
                lambda p, t, l: fam.loss(p, t, l, cfg, num))
            nb = batch // rows
            if nb == 1:
                return vg(params, tokens, labels)
            blk = lambda a: a.reshape((nb, rows) + a.shape[1:])

            def body(acc, tl):
                l, g = vg(params, *tl)
                return (acc[0] + l / nb,
                        tm(lambda s, gg: s + gg / nb, acc[1], g)), None

            zero = (jnp.zeros((), jnp.float32), tm(jnp.zeros_like, params))
            out, _ = jax.lax.scan(body, zero, (blk(tokens), blk(labels)))
            return out

        def grads(x, tokens, labels):
            def local(xs, ts, ls):
                return jax.lax.map(lambda a: client_vg(*a), (xs, ts, ls))
            spec = P("clients")
            return jax.shard_map(local, mesh=self.mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=(spec, spec),
                                 check_vma=False)(x, tokens, labels)

        self._grads = jax.jit(grads)

        # y + beta (g' - g) in two halves, so that g is freed before the
        # gradient pass allocates g'
        def untrack(y, g):
            return tm(lambda a, b: a - beta * b, y, g)

        def track(y, gn):
            gn = tm(store, gn)
            return tm(lambda a, c: store(a + beta * c), y, gn), gn

        self._untrack = jax.jit(untrack, donate_argnums=0)
        self._track = jax.jit(track, donate_argnums=(0, 1))
        self._norms = jax.jit(norms)

        def change(x, p):
            return norms(tm(lambda a, b: a - b.astype(jnp.float32)[None],
                            x, p))

        self._change = jax.jit(change)

    def mix(self, tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [self._mix(self.W, v) for v in leaves])

    def run(self, seed: int, rounds: np.ndarray) -> dict:
        """Run ``len(rounds)`` rounds on host token blocks
        (R, T0, n, B, L+1); returns per-round mean losses of the comm step,
        the first gradient's norms and the change of x after the last round,
        each per client and leaf."""
        T0 = self.traffic["comm_period"]
        key = ref_init.seed_key(seed)
        with jax.default_matmul_precision("highest"), \
                warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            x, y, nu, g = self._start(self.init(key))
            losses, first = [], None
            for r in range(rounds.shape[0]):
                for s in range(T0):
                    block = rounds[r, s]
                    tok = jax.device_put(block[..., :-1], self.shard)
                    lab = jax.device_put(block[..., 1:], self.shard)
                    x, nu = self._pre(x, y, nu)
                    comm = s == T0 - 1
                    if comm:
                        x = self.mix(x)
                    y = self._untrack(y, g)
                    del g
                    loss, gn = self._grads(x, tok, lab)
                    if first is None:
                        first = jax.device_get(self._norms(gn))
                    y, g = self._track(y, gn)
                    if comm:
                        y = self.mix(y)
                        losses.append(float(jnp.mean(loss)))
            change = jax.device_get(self._change(x, self.init(key)))
        names = leaf_names(first)
        flat = lambda t: [np.asarray(v) for v in jax.tree_util.tree_leaves(t)]
        return {"loss": losses, "names": names, "grad_norm": flat(first),
                "change_norm": flat(change)}
