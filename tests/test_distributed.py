"""Distributed-semantics tests (subprocess: needs >1 host device).

These spawn a fresh python with xla_force_host_platform_device_count=8 so
the in-process jax (single CPU device) is untouched.
"""
import os
import subprocess
import sys
import textwrap

import pytest

# each test spawns a fresh 8-device python: minutes, not seconds
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, timeout=560) -> str:
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_ppermute_gossip_equals_dense_mix():
    """shard_map ring ppermute mixer == dense einsum with the Metropolis W."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.mixing import MixPlan, apply_mix
        from repro.core.topology import mixing_matrix

        mesh = jax.make_mesh((8,), ("data",))
        n, d = 8, 16
        x = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                        jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))

        W = mixing_matrix("ring", n)
        dense = jax.jit(lambda t: apply_mix(MixPlan.dense(W), t))(xs)

        def body(blk):
            perm_f = [((s + 1) % n, s) for s in range(n)]
            perm_b = [((s - 1) % n, s) for s in range(n)]
            return (blk + jax.lax.ppermute(blk, "data", perm_f)
                    + jax.lax.ppermute(blk, "data", perm_b)) / 3.0
        pp = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                               out_specs=P("data")))(xs)
        err = float(jnp.max(jnp.abs(dense - pp)))
        assert err < 1e-5, err
        print("OK", err)
    """))
    assert "OK" in out


def test_depositum_distributed_equals_host():
    """One DEPOSITUM comm step on an 8-device mesh == single-device result."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import (DepositumConfig, MixPlan, init, step,
                                mixing_matrix)

        n, d = 8, 32
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (n, d, d))
        A = jnp.einsum("nij,nkj->nik", A, A) / d + 0.5 * jnp.eye(d)
        b = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
        def grad_fn(x, batch):
            return jnp.einsum("nij,nj->ni", A, x) - b, {}
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5, comm_period=1,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        W = mixing_matrix("ring", n)
        mixer = MixPlan.dense(W)

        st_host = init(jnp.zeros(d), n)
        for _ in range(5):
            st_host, _ = step(st_host, None, grad_fn, cfg, mixer,
                              is_comm_step=True)

        mesh = jax.make_mesh((8,), ("data",))
        sh = NamedSharding(mesh, P("data"))
        st = init(jnp.zeros(d), n)
        st = jax.tree_util.tree_map(
            lambda v: jax.device_put(v, sh) if v.ndim > 0 else v, st)
        stepj = jax.jit(lambda s: step(s, None, grad_fn, cfg, mixer,
                                       is_comm_step=True)[0])
        for _ in range(5):
            st = stepj(st)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree_util.tree_leaves(st_host)[:5],
                                  jax.tree_util.tree_leaves(st)[:5]))
        assert err < 1e-5, err
        print("OK", err)
    """))
    assert "OK" in out


def test_topology_sweep_shardmap_backend_equals_sequential():
    """A stacked-W topology sweep under the shard_map backend (vmap over a
    shard_map'd client mesh: dense all_gather+contract, W a traced operand)
    must match sweep_run_sequential on the stacked-vmap backend — the
    sweep x shard_map equivalence the MixPlan refactor promises."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (DepositumConfig, Hyper, MixPlan,
                                stack_hypers, stack_mixplans)
        from repro.training.backends import get_backend
        from repro.training.sweep import sweep_run, sweep_run_sequential

        N, D, T0, ROUNDS = 8, 12, 3, 5
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (N, 16, D))
        w_true = jax.random.normal(jax.random.fold_in(key, 1), (D,))
        b = jnp.einsum("nmd,d->nm", A, w_true)
        def grad_fn(w, batch):
            r = jnp.einsum("nmd,nd->nm", A, w) - b
            return jnp.einsum("nmd,nm->nd", A, r) / A.shape[1], {}

        cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        mesh = jax.make_mesh((8,), ("clients",))
        be = get_backend("shard_map", mesh=mesh, axis_name="clients",
                         n_clients=N)

        topos = ["complete", "ring", "star", "torus"]
        plans = stack_mixplans([MixPlan.from_topology(t, N) for t in topos])
        h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
        hypers = stack_hypers([h] * len(topos))
        batches = jnp.zeros((ROUNDS, T0, 1))

        fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, plans, hypers,
                          batches, n_clients=N, backend=be)
        fseq, _ = sweep_run_sequential(jnp.zeros(D), grad_fn, cfg, plans,
                                       hypers, batches, n_clients=N)
        err = float(jnp.max(jnp.abs(fs.x - fseq.x)))
        assert err < 1e-5, err

        # circulant (ppermute) sweep point == dense ring point
        pr = MixPlan.circulant([(+1, 1/3), (-1, 1/3)], 1/3)
        f1, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, pr, stack_hypers([h]),
                          batches, n_clients=N, backend=be)
        err2 = float(jnp.max(jnp.abs(f1.x[0] - fseq.x[topos.index("ring")])))
        assert err2 < 1e-5, err2
        print("OK", err, err2)
    """))
    assert "OK" in out


def test_placement_shardmap_mixer_all_topologies():
    """launch.gossip_dist executes any named topology exactly: ring/complete
    via ppermute/pmean, star/torus via the dense all_gather+contract plan —
    all matching the dense plan on an 8-device host mesh."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.sharding import Placement, _RULES_REPLICATED
        from repro.launch.gossip_dist import make_shardmap_mixer
        from repro.core.mixing import MixPlan, apply_mix
        from repro.core.topology import mixing_matrix

        mesh = jax.make_mesh((8, 1), ("data", "model"))
        placement = Placement(mode="replicated", mesh=mesh,
                              clients_axes=("data",),
                              rules=dict(_RULES_REPLICATED))
        n, d = 8, 16
        x = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                        jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        axes = ("clients", "mlp")
        shapes = jax.ShapeDtypeStruct((n, d), jnp.float32)
        for topo in ("ring", "complete", "star", "torus"):
            plan = MixPlan.from_topology(topo, n, prefer="sparse")
            mix = make_shardmap_mixer(placement, axes, shapes, plan)
            got = jax.jit(lambda t: mix(t, 0))(xs)
            ref = apply_mix(MixPlan.dense(mixing_matrix(topo, n)), x)
            err = float(jnp.max(jnp.abs(got - ref)))
            assert err < 1e-5, (topo, err)
        print("OK")
    """))
    assert "OK" in out


def test_schedule_kinds_shardmap_equal_stacked_vmap():
    """Every MixSchedule kind on the shard_map backend (per-round
    shard_body variants: gathered round plans, active-edge-masked
    ppermute/all_gather lazy rounds, unrolled chebyshev collectives) must
    equal the stacked-vmap simulation round for round — and a constant
    schedule must equal the static plan bit-exactly."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (DepositumConfig, MixPlan, MixSchedule,
                                apply_schedule, init as dep_init,
                                local_then_comm_round, mixing_matrix)
        from repro.training.backends import get_backend

        N, D, T0, ROUNDS = 8, 12, 3, 5
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (N, 16, D))
        b = jnp.einsum("nmd,d->nm", A,
                       jax.random.normal(jax.random.fold_in(key, 1), (D,)))
        def grad_fn(w, batch):
            r = jnp.einsum("nmd,nd->nm", A, w) - b
            return jnp.einsum("nmd,nm->nd", A, r) / 16, {}
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5,
                              momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        mesh = jax.make_mesh((8,), ("clients",))
        be = get_backend("shard_map", mesh=mesh, axis_name="clients",
                         n_clients=N)

        W = mixing_matrix("ring", N)
        pc = MixPlan.circulant([(+1, 1/3), (-1, 1/3)], 1/3)
        scheds = {
          "constant": MixSchedule.constant(MixPlan.dense(W)),
          "stacked": MixSchedule.stacked(
              [MixPlan.dense(mixing_matrix(t, N))
               for t in ("ring", "star", "complete", "torus", "ring")]),
          "alternating": MixSchedule.alternating(
              [MixPlan.dense(W),
               MixPlan.dense(mixing_matrix("star", N))]),
          "lazy-dense": MixSchedule.lazy(MixPlan.dense(W), 0.6,
                                         rounds=ROUNDS, seed=3),
          "lazy-circulant": MixSchedule.lazy(pc, 0.5, rounds=ROUNDS,
                                             n=N, seed=7),
          "chebyshev": MixSchedule.chebyshev(pc, 3, n=N),
        }

        def run(mixer):
            st = dep_init(jnp.zeros(D), N)
            rnd = jax.jit(functools.partial(
                local_then_comm_round, grad_fn=grad_fn, config=cfg,
                mixer=mixer))
            for _ in range(ROUNDS):
                st, _ = rnd(st, batches=jnp.zeros((T0, 1)))
            return st

        for name, s in scheds.items():
            got = run(be.mixer_for(s))
            ref = run(s)  # stacked-vmap apply_schedule
            err = max(float(jnp.max(jnp.abs(a - c)))
                      for a, c in zip(jax.tree_util.tree_leaves(got)[:5],
                                      jax.tree_util.tree_leaves(ref)[:5]))
            assert err < 1e-5, (name, err)

        static = run(MixPlan.dense(W))
        const = run(be.mixer_for(MixSchedule.constant(MixPlan.dense(W))))
        ref_const = run(MixSchedule.constant(MixPlan.dense(W)))
        err = float(jnp.max(jnp.abs(ref_const.x - static.x)))
        assert err == 0.0, f"constant schedule not bit-exact: {err}"
        print("OK")
    """))
    assert "OK" in out


def test_schedule_sweep_vmap_of_shardmap():
    """A schedule sweep (p_active grid x chebyshev orders, densified to one
    stacked operand) rides vmap-of-shard_map and matches the sequential
    stacked-vmap reference — schedules are a sweep dimension on the
    distributed path too."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (DepositumConfig, Hyper, MixPlan, MixSchedule,
                                as_stacked_schedule, stack_hypers,
                                stack_schedules, mixing_matrix)
        from repro.training.backends import get_backend
        from repro.training.sweep import sweep_run, sweep_run_sequential

        N, D, T0, ROUNDS = 8, 12, 3, 5
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (N, 16, D))
        b = jnp.einsum("nmd,d->nm", A,
                       jax.random.normal(jax.random.fold_in(key, 1), (D,)))
        def grad_fn(w, batch):
            r = jnp.einsum("nmd,nd->nm", A, w) - b
            return jnp.einsum("nmd,nm->nd", A, r) / 16, {}
        cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        mesh = jax.make_mesh((8,), ("clients",))
        be = get_backend("shard_map", mesh=mesh, axis_name="clients",
                         n_clients=N)

        base = MixPlan.dense(mixing_matrix("ring", N))
        native = ([MixSchedule.lazy(base, p, rounds=ROUNDS, seed=2)
                   for p in (0.3, 0.6, 1.0)]
                  + [MixSchedule.chebyshev(base, k) for k in (2, 3)])
        grid = stack_schedules([as_stacked_schedule(s, ROUNDS, N)
                                for s in native])
        h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
        hypers = stack_hypers([h] * len(native))
        batches = jnp.zeros((ROUNDS, T0, 1))

        fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, grid, hypers,
                          batches, n_clients=N, backend=be)
        fseq, _ = sweep_run_sequential(jnp.zeros(D), grad_fn, cfg, grid,
                                       hypers, batches, n_clients=N)
        err = float(jnp.max(jnp.abs(fs.x - fseq.x)))
        assert err < 1e-5, err

        # a native (undensified) lazy grid also rides the shard backend
        lazy_grid = stack_schedules(native[:3])
        fl, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, lazy_grid,
                          stack_hypers([h] * 3), batches, n_clients=N,
                          backend=be)
        err2 = float(jnp.max(jnp.abs(fl.x - fs.x[:3])))
        assert err2 < 1e-5, err2
        print("OK", err, err2)
    """))
    assert "OK" in out


def test_cohort_schedule_shardmap_equals_stacked_vmap():
    """Cohort schedules (padded client axis, on-device per-round sampling)
    on the shard_map backend must equal the stacked-vmap simulation —
    sampler masks are redrawn identically on every shard from the
    replicated key, and the round program freezes inactive/padding rows
    identically on both paths.  Full participation must stay bit-exact
    against the constant schedule."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (CohortSampler, DepositumConfig, MixPlan,
                                MixSchedule, init as dep_init,
                                local_then_comm_round, mixing_matrix,
                                pad_plan)
        from repro.training.backends import get_backend

        N_MAX, N_EFF, D, T0, ROUNDS = 8, 5, 12, 3, 5
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (N_MAX, 16, D))
        b = jnp.einsum("nmd,d->nm", A,
                       jax.random.normal(jax.random.fold_in(key, 1), (D,)))
        def grad_fn(w, batch):
            r = jnp.einsum("nmd,nd->nm", A, w) - b
            return jnp.einsum("nmd,nm->nd", A, r) / 16, {}
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5,
                              momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        mesh = jax.make_mesh((8,), ("clients",))
        be = get_backend("shard_map", mesh=mesh, axis_name="clients",
                         n_clients=N_MAX)

        W = mixing_matrix("ring", N_MAX)
        scheds = {
          "full": MixSchedule.cohort(MixPlan.dense(W),
                                     CohortSampler.full(N_MAX)),
          "bernoulli": MixSchedule.cohort(
              MixPlan.dense(W),
              CohortSampler.bernoulli(0.6, N_MAX, seed=3)),
          "fixed": MixSchedule.cohort(
              MixPlan.dense(W),
              CohortSampler.fixed_size(3, N_MAX, seed=5)),
          "padded": MixSchedule.cohort(
              pad_plan(MixPlan.from_topology("ring", N_EFF), N_MAX),
              CohortSampler.bernoulli(0.7, N_MAX, seed=9, n_eff=N_EFF)),
        }

        def run(mixer, n_eff=None):
            st = dep_init(jnp.zeros(D), n_eff or N_MAX,
                          n_max=N_MAX if n_eff else None)
            rnd = jax.jit(functools.partial(
                local_then_comm_round, grad_fn=grad_fn, config=cfg,
                mixer=mixer))
            for _ in range(ROUNDS):
                st, _ = rnd(st, batches=jnp.zeros((T0, 1)))
            return st

        for name, s in scheds.items():
            n_eff = N_EFF if name == "padded" else None
            got = run(be.mixer_for(s), n_eff)
            ref = run(s, n_eff)  # stacked-vmap apply_schedule
            err = max(float(jnp.max(jnp.abs(a - c)))
                      for a, c in zip(jax.tree_util.tree_leaves(got)[:5],
                                      jax.tree_util.tree_leaves(ref)[:5]))
            assert err < 1e-5, (name, err)
            if name == "padded":  # padding rows frozen on the shard path too
                assert float(jnp.abs(got.y[N_EFF:]).max()) == 0.0
                assert float(jnp.abs(got.x[N_EFF:]).max()) == 0.0

        const = run(be.mixer_for(MixSchedule.constant(MixPlan.dense(W))))
        full = run(be.mixer_for(scheds["full"]))
        err = max(float(jnp.max(jnp.abs(a - c)))
                  for a, c in zip(jax.tree_util.tree_leaves(full)[:5],
                                  jax.tree_util.tree_leaves(const)[:5]))
        assert err == 0.0, f"full cohort not bit-exact on shard_map: {err}"
        print("OK")
    """))
    assert "OK" in out


def test_compressed_schedule_shardmap_equals_stacked_vmap():
    """Compressed gossip on the shard_map backend must equal the
    stacked-vmap simulation for every compressor kind — including the
    *packed wire* path (value/index pairs, int8 words + row norm on the
    collectives), which is exact whenever the payload fits its capacity.
    ``spec=none`` must stay bit-exact against the plain dense path, and
    the qsgd wire program must actually put int8 on the all_gather."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (CompressionSpec, DepositumConfig, MixPlan,
                                MixSchedule, as_schedule,
                                init as dep_init, local_then_comm_round,
                                mixing_matrix)
        from repro.training.backends import get_backend

        N, D, T0, ROUNDS = 8, 32, 3, 5
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (N, 16, D))
        b = jnp.einsum("nmd,d->nm", A,
                       jax.random.normal(jax.random.fold_in(key, 1), (D,)))
        def grad_fn(w, batch):
            r = jnp.einsum("nmd,nd->nm", A, w) - b
            return jnp.einsum("nmd,nm->nd", A, r) / 16, {}
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5,
                              momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3})
        mesh = jax.make_mesh((8,), ("clients",))
        be = get_backend("shard_map", mesh=mesh, axis_name="clients",
                         n_clients=N)

        dense_ring = as_schedule(MixPlan.dense(mixing_matrix("ring", N)))
        circ_ring = as_schedule(
            MixPlan.circulant([(+1, 1/3), (-1, 1/3)], 1/3))
        scheds = {
          # dense-shaped q on the collective (no packed form, wire_k=0)
          "topk-sim": dense_ring.with_compression(
              CompressionSpec.topk(0.25)),
          # packed value/index pairs, capacity >= k: exact
          "topk-wire": dense_ring.with_compression(
              CompressionSpec.topk(0.25, wire_k=16)),
          # Bernoulli rows can fill the whole row: full capacity
          "randk-wire": dense_ring.with_compression(
              CompressionSpec.randk(0.25, seed=4, wire_k=32)),
          # int8 words + inf-norm scale: exact for levels <= 127
          "qsgd-wire": dense_ring.with_compression(
              CompressionSpec.qsgd(4, seed=5)),
          # packed payload through ppermute instead of all_gather
          "topk-wire-circulant": circ_ring.with_compression(
              CompressionSpec.topk(0.25, wire_k=16)),
        }

        def run(mixer, sched):
            st = dep_init(jnp.zeros(D), N, compress=sched)
            rnd = jax.jit(functools.partial(
                local_then_comm_round, grad_fn=grad_fn, config=cfg,
                mixer=mixer))
            for _ in range(ROUNDS):
                st, _ = rnd(st, batches=jnp.zeros((T0, 1)))
            return st

        for name, s in scheds.items():
            got = run(be.mixer_for(s), s)
            ref = run(s, s)  # stacked-vmap apply_schedule path
            err = max(float(jnp.max(jnp.abs(a - c)))
                      for a, c in zip(jax.tree_util.tree_leaves(got)[:5],
                                      jax.tree_util.tree_leaves(ref)[:5]))
            # 1e-4 (not the usual 1e-5): rand-k rescales by 1/rate, which
            # amplifies contraction-order noise across the backends
            assert err < 1e-4, (name, err)

        # wire and simulation forms of the SAME compressor agree exactly
        # (the packed payload fits: nnz <= wire_k)
        sim = run(be.mixer_for(scheds["topk-sim"]), scheds["topk-sim"])
        wire = run(be.mixer_for(scheds["topk-wire"]), scheds["topk-wire"])
        err = float(jnp.max(jnp.abs(sim.x - wire.x)))
        assert err < 1e-6, f"packed wire != dense-q collective: {err}"

        # spec=none rides the byte-identical dense program
        s_none = dense_ring.with_compression(CompressionSpec.none())
        got = run(be.mixer_for(s_none), s_none)
        plain = run(be.mixer_for(dense_ring), dense_ring)
        err = float(jnp.max(jnp.abs(got.x - plain.x)))
        assert err == 0.0, f"spec=none not bit-exact on shard_map: {err}"

        # the qsgd wire program ships int8 over the collective
        wm = be.mixer_for(scheds["qsgd-wire"])
        assert wm.wire_fn is not None
        x = jnp.zeros((N, D))
        txt = jax.jit(lambda t: wm.wire_fn(t, 0)).lower(x).as_text()
        assert "i8" in txt, "no int8 payload in the lowered wire program"
        print("OK")
    """))
    assert "OK" in out


def test_tiny_dryrun_mesh_compiles():
    """A miniature dry-run (2x4 mesh, reduced arch) exercises the launch
    path end-to-end inside a subprocess."""
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core import DepositumConfig, MixPlan
        from repro.launch.sharding import Placement, _RULES_REPLICATED
        from repro.launch.dryrun import state_specs
        from repro.launch.specs import train_batch_specs
        from repro.launch.sharding import tree_shardings
        from repro.launch.steps import build_train_step
        from repro.models import build_model

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        placement = Placement(mode="replicated", mesh=mesh,
                              clients_axes=("data",),
                              rules=dict(_RULES_REPLICATED))
        cfg = get_config("qwen3-1.7b", reduced=True)
        model = build_model(cfg)
        n = placement.n_clients
        st_shapes, st_axes = state_specs(model, n)
        import repro.configs.base as base
        b_shapes = {
            "tokens": jax.ShapeDtypeStruct((n, 2, 64), np.int32),
            "labels": jax.ShapeDtypeStruct((n, 2, 64), np.int32),
        }
        b_axes = {"tokens": ("clients", "batch", "seq"),
                  "labels": ("clients", "batch", "seq")}
        st_sh = tree_shardings(placement, st_axes, st_shapes)
        b_sh = tree_shardings(placement, b_axes, b_shapes)
        dep = DepositumConfig(alpha=1e-3, prox_name="l1",
                              prox_kwargs={"lam": 1e-6})
        stepfn = build_train_step(model, dep, n,
                                  MixPlan.from_topology("ring", n))
        jitted = jax.jit(stepfn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None))
        compiled = jitted.lower(st_shapes, b_shapes).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, list):  # older jax returns [per-device dict]
            ca = ca[0]
        print("OK", ca["flops"] > 0)
    """))
    assert "OK True" in out
