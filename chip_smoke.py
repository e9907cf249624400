#!/usr/bin/env python3
"""Smoke run of DEPOSITUM's training path on a TPU.

One chip (the default): the full mamba2-130m (24 layers, d_model 768,
vocab 50 280, bf16) trains as 4 clients on a ring through
``FederatedTrainer``, with the fused Pallas update kernels compiled by Mosaic
(``fused="require"``).  The kernels are first checked against the jnp
reference (``kernels/prox/ref.py``) on every leaf shape of the model's
state; then the round program is compiled, and one warm-up round and
``TIMED_ROUNDS`` timed rounds run.

Four chips (``--chips 4``): the 4-client ring with one client per chip on
the shard_map ppermute backend, against the same rounds on the
stacked-vmap backend on one device of the host, at full width cut to
``MULTICHIP_LAYERS`` layers and in float32, so that a disagreement beyond
rounding shows (see the tolerance below).  Nothing else runs.

    python chip_smoke.py
    python chip_smoke.py --chips 4

Unless JAX's first device is a TPU it exits non-zero and prints no result.
Data and weights come from ``--seed``.  The numbers printed on the way are
bring-up observations, not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import sys
import time
from pathlib import Path

ARCH = "mamba2-130m"
N_CLIENTS = 4
COMM_PERIOD = 2      # T0: one collective-free step, one gossip step per round
BATCH = 4            # sequences per client per step
SEQ = 512            # a multiple of the config's ssm_chunk (256)
TIMED_ROUNDS = 3

# Kernel parity tolerance.  Kernel and reference both compute in f32 from
# the same bf16 inputs and round once to bf16; they may differ only in f32
# operation order (Mosaic vs XLA fusion), a few f32 ulps, which the final
# rounding can turn into one bf16 ulp (2^-7 relative) of the output.  The
# absolute floor covers outputs that cancel to near zero (soft threshold).
PARITY_RTOL = 2.0 ** -7
PARITY_ATOL = 2.0 ** -20
# Hyperparameters of the parity run, chosen so that every prox branch
# (zeroed, shrunk, identity) is taken on init-scale weights.
PARITY_HP = dict(lam=0.01, theta=4.0, alpha=0.5, gamma=0.8, beta=1.0)
PARITY_MASK = (1.0, 0.0, 1.0, 1.0)

# Four chips: shard_map (one client per chip) against stacked-vmap (one
# chip), MULTICHIP_ROUNDS rounds of the full-width model cut to
# MULTICHIP_LAYERS layers, in float32 with float32 matmuls.  In bf16 rounding
# cannot be told apart from a bug: the zero-initialised norm scales and conv
# bias hold nothing but their updates, gradients reduced over every token of
# a client in bf16, which a one-client and a four-client program fuse and
# order differently; those leaves ended 2.5-3.8% apart (relative L2) after 2
# rounds while the large ones agreed to 1e-5.
# In f32 both programs do the same arithmetic per client; XLA orders the
# reductions of a one-client program and a four-client vmapped one
# differently, which perturbs each step's gradient by a few f32 ulps, and
# the ring sum rounds each mixed value in another order.  Bound, per x leaf:
#   ||x_sm - x_vm|| <= MULTICHIP_RTOL ||x_vm - x_0|| + MULTICHIP_ULPS eps ||x_vm||
# i.e. 2^-10 of the distance the leaf moved from its init, plus 16 f32 ulps
# of the leaf for the rounding of the mix.  A client updated with another's
# rows, or mixed with the wrong neighbour, misses that by orders of magnitude.
MULTICHIP_LAYERS = 8
MULTICHIP_BATCH = 2
MULTICHIP_SEQ = 256
MULTICHIP_ROUNDS = 2
MULTICHIP_RTOL = 2.0 ** -10
MULTICHIP_ULPS = 16


def _log(msg: str) -> None:
    print(msg, flush=True)


def depositum_config(comm_period: int = COMM_PERIOD):
    from repro.core import DepositumConfig

    return DepositumConfig(alpha=0.02, beta=1.0, gamma=0.8, momentum="polyak",
                           comm_period=comm_period, prox_name="l1",
                           prox_kwargs={"lam": 1e-5}, fused="require")


def describe(cfg, n_params: int) -> str:
    d_inner = cfg.ssm_expand * cfg.d_model
    return (f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"d_inner {d_inner}, {d_inner // cfg.ssm_head_dim} SSD heads of "
            f"{cfg.ssm_head_dim}, state {cfg.ssm_state}, vocab "
            f"{cfg.vocab_size}, {cfg.dtype}, {n_params} parameters per client")


# ---------------------------------------------------------------------------
# Kernel parity: Mosaic kernels against kernels/prox/ref.py on chip
# ---------------------------------------------------------------------------

def _misses(out, ref):
    """(elements outside tolerance, max abs error) of one output leaf."""
    import jax.numpy as jnp

    o, r = out.astype(jnp.float32), ref.astype(jnp.float32)
    err = jnp.abs(o - r)
    bad = err > PARITY_ATOL + PARITY_RTOL * jnp.abs(r)
    return jnp.sum(bad), jnp.max(err)


def _parity_programs(kind: str):
    """(fused update, tracking) checks over a whole state tree, one jit each:
    kernel and reference on (1, C, *leaf) operands, per-leaf misses out."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.prox.kernel import (fused_tracking_sweep_pallas,
                                           fused_update_sweep_pallas)
    from repro.kernels.prox.ref import fused_update_ref

    f32 = lambda a: a.astype(jnp.float32)

    def live(mask, leaf):
        return (mask.reshape(mask.shape + (1,) * (leaf.ndim - 2)) > 0)

    def update(xs, ys, nus, params, mask):
        lam, theta, alpha, gamma = (params[0, i] for i in range(4))
        out = []
        for x, y, nu in zip(xs, ys, nus):
            xo, nuo = fused_update_sweep_pallas(x, y, nu, params, mask,
                                                kind=kind)
            xr, nur = fused_update_ref(f32(x), f32(y), f32(nu), lam, alpha,
                                       gamma, prox_kind=kind, theta=theta)
            if mask is not None:
                xr = jnp.where(live(mask, x), xr, f32(x))
                nur = jnp.where(live(mask, x), nur, f32(nu))
            out.append((_misses(xo, xr.astype(x.dtype)),
                        _misses(nuo, nur.astype(nu.dtype))))
        return out

    def tracking(ys, gns, gos, params, mask):
        beta = params[0, 4]
        out = []
        for y, gn, go in zip(ys, gns, gos):
            yo, gk = fused_tracking_sweep_pallas(y, gn, go, params, mask)
            yr = f32(y) + beta * (f32(gn) - f32(go))
            gr = gn
            if mask is not None:
                yr = jnp.where(live(mask, y), yr, f32(y))
                gr = jnp.where(live(mask, y), gn, go)
            out.append((_misses(yo, yr.astype(y.dtype)), _misses(gk, gr)))
        return out

    return jax.jit(update), jax.jit(tracking)


def kernel_parity(params, seed: int) -> None:
    """Check every fused kernel variant on the leaf shapes of the model
    ``params``, as (1, N_CLIENTS, *leaf) operands with x the weights and
    y, nu drawn from ``seed``; raises on any element outside the
    tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.prox.kernel import sweep_params_table

    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    xs = [jnp.broadcast_to(leaf, (1, N_CLIENTS) + leaf.shape)
          for leaf in jax.tree_util.tree_leaves(params)]
    key = jax.random.PRNGKey(seed)
    rand = lambda i: [
        (0.02 * jax.random.normal(jax.random.fold_in(key, 100 * i + j),
                                  x.shape)).astype(x.dtype)
        for j, x in enumerate(xs)]
    ys, nus = rand(1), rand(2)
    table = sweep_params_table(**PARITY_HP)
    mask = jnp.asarray([PARITY_MASK], jnp.float32)
    shapes = sorted({tuple(x.shape[1:]) for x in xs})
    _log(f"parity: {len(xs)} leaves, shapes (C, *leaf) {shapes}, "
         f"{xs[0].dtype}; tolerance |kernel - ref| <= {PARITY_ATOL:g} + "
         f"{PARITY_RTOL:g} |ref| (one bf16 ulp)")
    failed = []
    variants = [("update", "l1", False), ("update", "l1", True),
                ("update", "mcp", False), ("update", "scad", False),
                ("tracking", "-", False), ("tracking", "-", True)]
    for which, kind, gated in variants:
        update, tracking = _parity_programs(kind)
        m = mask if gated else None
        t = time.perf_counter()
        if which == "update":
            res = update(xs, ys, nus, table, m)
            outs = ("x", "nu")
        else:
            res = tracking(ys, nus, xs, table, m)
            outs = ("y", "g")
        res = jax.device_get(res)
        dt = time.perf_counter() - t
        worst = max(float(e) for leaf in res for _, e in leaf)
        n_bad = sum(int(b) for leaf in res for b, _ in leaf)
        tag = f"{which} {kind} {'gated' if gated else 'ungated'}"
        _log(f"parity {tag}: {n_bad} elements outside tolerance, max abs "
             f"err {worst:.3g} ({dt:.1f} s incl. compile)")
        for name, leaf in zip(names, res):
            for out, (bad, err) in zip(outs, leaf):
                if int(bad):
                    failed.append(f"{tag} {name} {out}: {int(bad)} misses, "
                                  f"max abs err {float(err):.3g}")
    if failed:
        raise AssertionError("kernel parity failed:\n  " + "\n  ".join(failed))


# ---------------------------------------------------------------------------
# One chip: train the full config
# ---------------------------------------------------------------------------

def _count_compiles():
    import jax

    count = [0]

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def train_one_chip(cfg, *, seed: int):
    """Parity, compile, one warm-up round and ``TIMED_ROUNDS`` timed
    rounds of the 4-client ring on the default device."""
    import jax

    from repro.data import make_federated_lm_streams
    from repro.models import build_model
    from repro.training.train_loop import (FederatedTrainer, TrainerConfig,
                                           lm_batch_iterator)

    model = build_model(cfg)
    # before the state exists: the two never share HBM
    kernel_parity(model.init(jax.random.PRNGKey(seed))[0], seed)
    tc = TrainerConfig(n_clients=N_CLIENTS, topology="ring",
                       depositum=depositum_config(), seed=seed, log_every=1)
    trainer = FederatedTrainer(model, tc)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(state.x))
    _log(describe(cfg, n_params // N_CLIENTS))
    _log(f"clients {N_CLIENTS} on a ring ({trainer.backend.name} backend), "
         f"T0 {tc.depositum.comm_period}, per-client batch {BATCH} x seq "
         f"{SEQ}, fused={tc.depositum.fused_mode()}")

    stream = make_federated_lm_streams(cfg.vocab_size, N_CLIENTS, seed=seed)
    it = lm_batch_iterator(stream, tc, batch=BATCH, seq_len=SEQ)
    first = next(it)
    t = time.perf_counter()
    compiled = trainer.lower_round(state, first).compile()
    compile_s = time.perf_counter() - t
    has_kernel = "tpu_custom_call" in compiled.as_text()
    _log(f"compile_s {compile_s:.2f}")
    _log(f"round program contains tpu_custom_call: {has_kernel}")
    mem = compiled.memory_analysis()
    if mem is not None:
        _log(f"compiled round: argument {mem.argument_size_in_bytes} B, "
             f"temp {mem.temp_size_in_bytes} B, alias "
             f"{mem.alias_size_in_bytes} B")

    losses = []
    t = time.perf_counter()
    state, hist = trainer.run(state, itertools.chain([first], it), 1)
    _log(f"warm-up round: {time.perf_counter() - t:.3f} s, loss "
         f"{hist[-1]['loss']:.4f}")
    losses.append(hist[-1]["loss"])
    compiles = _count_compiles()
    times = []
    for r in range(TIMED_ROUNDS):
        t = time.perf_counter()
        state, hist = trainer.run(state, it, 1)   # ends in block_until_ready
        times.append(time.perf_counter() - t)
        losses.append(hist[-1]["loss"])
        _log(f"round {r + 1}: {times[-1]:.4f} s, loss {losses[-1]:.4f}")
    _log(f"steady s/round {sum(times) / len(times):.4f} (mean of "
         f"{TIMED_ROUNDS}; {[round(x, 4) for x in times]})")
    _log(f"compilations during timed rounds: {compiles[0]}")
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    return has_kernel, losses


# ---------------------------------------------------------------------------
# Four chips: shard_map ppermute ring vs stacked-vmap on one device
# ---------------------------------------------------------------------------

def _ring_run(cfg, backend, *, seed: int, show_shardings: bool = False):
    """``MULTICHIP_ROUNDS`` rounds of the 4-client ring; returns the
    backend's name, the x leaf names, and x at init and at the end, on the
    host."""
    import jax
    import numpy as np

    from repro.core import MixPlan, MixSchedule
    from repro.data import make_federated_lm_streams
    from repro.models import build_model
    from repro.training.train_loop import (FederatedTrainer, TrainerConfig,
                                           lm_batch_iterator)

    model = build_model(cfg)
    tc = TrainerConfig(n_clients=N_CLIENTS, topology="ring",
                       depositum=depositum_config(), seed=seed, log_every=1)
    ring = MixSchedule.constant(
        MixPlan.from_topology("ring", N_CLIENTS, prefer="sparse"))
    trainer = FederatedTrainer(model, tc, schedule=ring, backend=backend)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    host_x = lambda st: [np.asarray(leaf)
                         for leaf in jax.tree_util.tree_leaves(st.x)]
    x0 = host_x(state)
    stream = make_federated_lm_streams(cfg.vocab_size, N_CLIENTS, seed=seed)
    it = lm_batch_iterator(stream, tc, batch=MULTICHIP_BATCH,
                           seq_len=MULTICHIP_SEQ)
    name = trainer.backend.name
    for r in range(MULTICHIP_ROUNDS):
        t = time.perf_counter()
        state, hist = trainer.run(state, it, 1)
        loss = hist[-1]["loss"]
        _log(f"{name} round {r + 1}: {time.perf_counter() - t:.3f} s, "
             f"loss {loss:.6f}")
        if not math.isfinite(loss):
            raise AssertionError(f"{name}: non-finite loss {loss}")
        if show_shardings and r == 0:
            alone = []
            for path, leaf in jax.tree_util.tree_leaves_with_path(state):
                key = jax.tree_util.keystr(path)
                _log(f"  {key} {tuple(leaf.shape)}: {leaf.sharding}")
                if len(leaf.sharding.device_set) != len(jax.devices()):
                    alone.append(key)
            if alone:
                raise AssertionError(
                    f"state leaves not spread over all devices: {alone}")
            _log(f"every state leaf spans all {len(jax.devices())} devices; "
                 "none sits on device 0 alone")
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(state.x)]
    x = host_x(state)
    del state
    return name, names, x0, x


def multichip(cfg, *, seed: int) -> float:
    """shard_map (one client per device) against stacked-vmap (one
    device); returns the worst per-leaf ratio of the x distance to its
    bound (pass: <= 1)."""
    import jax
    import numpy as np

    from repro.training.backends import StackedVmapBackend

    _log(f"{cfg.name} at full width, {cfg.n_layers} layers, {cfg.dtype}, "
         f"matmul precision {jax.config.jax_default_matmul_precision}; "
         f"{N_CLIENTS} clients on a ring, T0 {COMM_PERIOD}, per-client "
         f"batch {MULTICHIP_BATCH} x seq {MULTICHIP_SEQ}, {MULTICHIP_ROUNDS} "
         "rounds")
    name, names, x0, x_sm = _ring_run(cfg, None, seed=seed,
                                      show_shardings=True)
    if name != "shard_map":
        raise AssertionError(f"suggest_backend chose {name}, not shard_map")
    _, _, _, x_vm = _ring_run(cfg, StackedVmapBackend(), seed=seed)
    eps = float(np.finfo(np.float32).eps)
    worst = 0.0
    for key, a, b, b0 in zip(names, x_sm, x_vm, x0):
        diff = float(np.linalg.norm(a - b))
        moved = float(np.linalg.norm(b - b0))
        bound = MULTICHIP_RTOL * moved + MULTICHIP_ULPS * eps * float(
            np.linalg.norm(b))
        ratio = diff / bound if bound > 0 else (0.0 if diff == 0 else math.inf)
        worst = max(worst, ratio)
        _log(f"  x{key}: |x_sm - x_vm| {diff:.3g}, moved {moved:.3g}, "
             f"bound {bound:.3g}, ratio {ratio:.3g}")
    _log(f"shard_map vs stacked-vmap after {MULTICHIP_ROUNDS} rounds: "
         f"worst ratio of |x_sm - x_vm| to its bound {worst:.3g} "
         f"(pass <= 1; bound {MULTICHIP_RTOL:g} x distance moved + {MULTICHIP_ULPS} f32 ulps)")
    if not worst <= 1.0:
        raise AssertionError(f"shard_map and stacked-vmap disagree: {worst}")
    return worst


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: parity + training on one chip; 4: shard_map "
                         "on four chips against stacked-vmap on one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device_kind {dev.device_kind}, {len(devices)} device(s), "
         f"jax {jax.__version__}")
    cache = Path(enable_compile_cache())
    warm = cache.is_dir() and any(cache.iterdir())
    _log(f"compile cache: {cache} ({'warm' if warm else 'cold'})")
    cfg = get_config(ARCH)
    if args.chips == 1:
        has_kernel, _ = train_one_chip(cfg, seed=args.seed)
        if not has_kernel:
            raise AssertionError("fused='require' but no Mosaic kernel "
                                 "(tpu_custom_call) in the round program")
        count = 1
    else:
        jax.config.update("jax_default_matmul_precision", "float32")
        multichip(dataclasses.replace(cfg, dtype="float32",
                                      n_layers=MULTICHIP_LAYERS),
                  seed=args.seed)
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
