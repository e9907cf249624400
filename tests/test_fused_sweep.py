"""Sweep-major fused kernel guarantees (interpret mode on CPU; the same
programs lower to Mosaic on TPU):

* **oracle parity** — the (S, C, tiles)-grid kernels equal the per-config
  jnp reference across all three prox kinds, non-tile-aligned shapes and
  per-config SMEM params rows;
* **bit-exact freezing** — rows gated off by the (S, C) cohort mask come
  back bit-for-bit unchanged;
* **zero retraces across configs** — one compiled sweep-major program
  serves a stacked-Hyper grid; swapping the grid's values never retraces
  (the acceptance criterion, pinned via the kernels' TRACE_COUNTS);
* the ``fused="auto" | "require" | "off"`` knob — which configurations
  take the fused path, and that ``"require"`` raises on ineligibility.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CohortSampler,
    DepositumConfig,
    Hyper,
    MixPlan,
    MixSchedule,
    init as dep_init,
    local_then_comm_round,
    mixing_matrix,
    stack_hypers,
    step,
)
from repro.kernels.prox.kernel import (
    TRACE_COUNTS,
    ClientShards,
    fused_tracking_sweep_pallas,
    fused_update_sweep_pallas,
    sweep_layout,
    sweep_params_table,
)
from repro.kernels.prox.ref import fused_update_ref
from repro.training.backends import StackedVmapBackend, SweepBackend
from repro.training.sweep import make_sweep_round, sweep_init, sweep_run

S, C = 3, 4
# deliberately lane/sublane-hostile: scalars, sub-lane vectors, odd
# trailing dims that only pad out to (rows, 128) tiles
SHAPES = [(), (1,), (100,), (777,), (5, 33)]


def _make(key, shape, scale=0.1):
    return jax.random.normal(key, (S, C) + shape, jnp.float32) * scale


def _table():
    return sweep_params_table(
        lam=jnp.asarray([1e-3, 5e-3, 1e-2]),
        theta=4.0,
        alpha=jnp.asarray([0.05, 0.1, 0.2]),
        gamma=jnp.asarray([0.0, 0.5, 0.9]),
        beta=jnp.asarray([1.0, 0.5, 1.5]),
    )


def _ref_rows(x, y, nu, params, kind):
    """Per-config reference: row s of the SMEM table applied to slice s."""
    xs, nus = [], []
    for s in range(S):
        lam, theta, alpha, gamma, _ = [float(v) for v in params[s]]
        xr, nur = fused_update_ref(x[s], y[s], nu[s], lam, alpha, gamma,
                                   prox_kind=kind, theta=theta)
        xs.append(xr)
        nus.append(nur)
    return jnp.stack(xs), jnp.stack(nus)


# leaf layouts of the (R, K) view: a trailing dim that is a multiple of
# 128 split evenly into two row tiles, and a ragged leaf whose row and
# column tiles both end in a partial edge block
LAYOUT_SHAPES = [(1024, 256), (70, 4100)]


@functools.partial(jax.jit, static_argnames="kind")
def _ref_rows_f32(x, y, nu, params, kind):
    """Per-config reference with f32 hyperparameters, exactly as the SMEM
    table holds them (Python floats would round ``1 - gamma`` in f64),
    compiled as one program like the kernel body (op-by-op dispatch
    rounds the fused expressions differently)."""
    xs, nus = [], []
    for s in range(x.shape[0]):
        lam, theta, alpha, gamma, _ = (params[s, i] for i in range(5))
        xr, nur = fused_update_ref(x[s], y[s], nu[s], lam, alpha, gamma,
                                   prox_kind=kind, theta=theta)
        xs.append(xr)
        nus.append(nur)
    return jnp.stack(xs), jnp.stack(nus)


@pytest.mark.parametrize("shape", SHAPES + LAYOUT_SHAPES)
@pytest.mark.parametrize("kind", ["l1", "mcp", "scad"])
def test_sweep_kernel_matches_oracle(kind, shape):
    key = jax.random.PRNGKey(hash((kind, shape)) % 2**31)
    x = _make(key, shape)
    y = _make(jax.random.fold_in(key, 1), shape)
    nu = _make(jax.random.fold_in(key, 2), shape)
    params = _table()
    xo, nuo = fused_update_sweep_pallas(x, y, nu, params, kind=kind)
    xr, nur = _ref_rows(x, y, nu, np.asarray(params), kind)
    np.testing.assert_allclose(np.asarray(xo), np.asarray(xr),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(nuo), np.asarray(nur),
                               atol=1e-6, rtol=1e-6)
    if shape in LAYOUT_SHAPES:  # the in-place view is bit-exact
        xb, nub = _ref_rows_f32(x, y, nu, params, kind)
        np.testing.assert_array_equal(np.asarray(xo), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(nuo), np.asarray(nub))


@pytest.mark.parametrize("kind", ["l1", "mcp", "scad"])
def test_sweep_kernel_mask_freezes_rows_bit_exact(kind):
    key = jax.random.PRNGKey(11)
    shape = (333,)
    x = _make(key, shape)
    y = _make(jax.random.fold_in(key, 1), shape)
    nu = _make(jax.random.fold_in(key, 2), shape)
    params = _table()
    # a different frozen set per config row, incl. an all-frozen config
    mask = jnp.asarray([[1, 0, 1, 0], [0, 0, 0, 0], [1, 1, 0, 1]],
                       jnp.float32)
    xo, nuo = fused_update_sweep_pallas(x, y, nu, params, mask, kind=kind)
    xr, nur = _ref_rows(x, y, nu, np.asarray(params), kind)
    m = np.asarray(mask)
    for s in range(S):
        for c in range(C):
            if m[s, c] > 0:
                np.testing.assert_allclose(np.asarray(xo[s, c]),
                                           np.asarray(xr[s, c]),
                                           atol=1e-6, rtol=1e-6)
            else:  # frozen rows: written back bit-for-bit
                np.testing.assert_array_equal(np.asarray(xo[s, c]),
                                              np.asarray(x[s, c]))
                np.testing.assert_array_equal(np.asarray(nuo[s, c]),
                                              np.asarray(nu[s, c]))


@pytest.mark.parametrize("shape", [(257,)] + LAYOUT_SHAPES)
@pytest.mark.parametrize("gated", [False, True])
def test_tracking_sweep_matches_oracle(gated, shape):
    key = jax.random.PRNGKey(21)
    y = _make(key, shape)
    gn = _make(jax.random.fold_in(key, 1), shape)
    go = _make(jax.random.fold_in(key, 2), shape)
    params = _table()
    mask = (jnp.asarray([[1, 0, 1, 1], [0, 1, 1, 0], [1, 1, 1, 1]],
                        jnp.float32) if gated else None)
    yo, gk = fused_tracking_sweep_pallas(y, gn, go, params, mask)
    beta = np.asarray(params)[:, 4].reshape((S, 1) + (1,) * len(shape))
    yr = np.asarray(y) + beta * (np.asarray(gn) - np.asarray(go))
    if shape in LAYOUT_SHAPES:  # the in-place view is bit-exact
        yb = np.asarray(jax.jit(lambda a, b, c, p: a + p[:, 4].reshape(
            beta.shape) * (b - c))(y, gn, go, params))
        live = (np.ones((S, C)) if mask is None else np.asarray(mask)) > 0
        want = np.where(live.reshape(live.shape + (1,) * len(shape)), yb,
                        np.asarray(y))
        np.testing.assert_array_equal(np.asarray(yo), want)
    if not gated:
        np.testing.assert_allclose(np.asarray(yo), yr, atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(gn))
        return
    m = np.asarray(mask)
    for s in range(S):
        for c in range(C):
            if m[s, c] > 0:
                np.testing.assert_allclose(np.asarray(yo[s, c]), yr[s, c],
                                           atol=1e-6, rtol=1e-6)
                np.testing.assert_array_equal(np.asarray(gk[s, c]),
                                              np.asarray(gn[s, c]))
            else:
                np.testing.assert_array_equal(np.asarray(yo[s, c]),
                                              np.asarray(y[s, c]))
                np.testing.assert_array_equal(np.asarray(gk[s, c]),
                                              np.asarray(go[s, c]))


@pytest.mark.parametrize("gated", [False, True])
def test_client_shards_match_unsharded_bit_exact(gated):
    """With ``ClientShards`` the kernels run under ``shard_map`` over the
    client dim (the shard_map backend's split); per device they are the
    same kernel, so the result is bit-identical to the unsharded call."""
    mesh = jax.make_mesh((1,), ("clients",))
    shards = ClientShards(mesh, "clients")
    key = jax.random.PRNGKey(31)
    shape = (70, 130)
    x, y, nu = (_make(jax.random.fold_in(key, i), shape) for i in range(3))
    params = _table()
    mask = (jnp.asarray([[1, 0, 1, 1], [0, 1, 1, 0], [1, 1, 1, 1]],
                        jnp.float32) if gated else None)

    def run(sh):
        return (fused_update_sweep_pallas(x, y, nu, params, mask, kind="mcp",
                                          shards=sh)
                + fused_tracking_sweep_pallas(y, x, nu, params, mask,
                                              shards=sh))

    for a, b in zip(run(shards), run(None)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# a forced device layout: (order, per-client leaf shape); the first swaps
# the last two dims (in_proj on a TPU), the second moves the last dim
# above the other two
PERMUTED = [((0, 1, 3, 2), (70, 130)), ((0, 1, 4, 2, 3), (6, 20, 33))]


@pytest.fixture
def forced_order(monkeypatch):
    """Make the kernels view (S, C, *p) operands of a given rank in a given
    axis order, as they do under Mosaic where the device stores a leaf
    that way; traces are cleared on both sides, since they are cached by
    shape and not by order."""
    from repro.kernels.prox import kernel

    def force(order):
        monkeypatch.setattr(
            kernel, "view_order",
            lambda shape, dtype: (order if len(shape) == len(order)
                                  else tuple(range(len(shape)))))
        jax.clear_caches()

    yield force
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("order,shape", PERMUTED, ids=["swap", "roll"])
@pytest.mark.parametrize("gated", [False, True])
def test_permuted_view_bit_exact(forced_order, order, shape, gated):
    """Viewing the leaves in another axis order only reorders the kernel's
    elementwise work: through the round program's entries, vmapped over
    S = 2 configs (the sweep rule), with and without the cohort gate, both
    kernels equal the row-major view and the reference bit for bit, and
    each trace of a kernel counts its one permuted leaf once."""
    from repro.kernels.prox.ops import fused_local_update, fused_tracking

    key = jax.random.PRNGKey(41)
    x, y, nu = (_make(jax.random.fold_in(key, i), shape)[:2]
                for i in range(3))
    params = _table()[:2]
    mask = (jnp.asarray([[1, 0, 1, 1], [0, 1, 1, 0]], jnp.float32)
            if gated else None)
    m = () if mask is None else (mask,)

    def run():
        xo, nuo = jax.vmap(lambda a, b, c, p, *g: fused_local_update(
            a, b, c, p, *g, kind="scad"))(x, y, nu, params, *m)
        yo, gk = jax.vmap(fused_tracking)(y, x, nu, params, *m)
        return xo, nuo, yo, gk

    counts = lambda: np.array([TRACE_COUNTS[k] for k in (
        "permuted_view", "fused_sweep", "tracking_sweep")])
    before = counts()
    row_major = run()
    views, *traces = counts() - before
    assert views == 0 and min(traces) > 0
    forced_order(order)
    before = counts()
    permuted = run()
    views, *traces = counts() - before
    assert min(traces) > 0 and views == sum(traces)  # one leaf per trace
    for a, b in zip(permuted, row_major):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    xr, nur = _ref_rows_f32(x, y, nu, params, "scad")
    yr = jax.jit(lambda a, b, c, p: a + p[:, 4].reshape(
        (2,) + (1,) * (a.ndim - 1)) * (b - c))(y, x, nu, params)
    live = (np.ones((2, C)) if mask is None else np.asarray(mask)) > 0
    live = live.reshape(live.shape + (1,) * len(shape))
    want = (np.where(live, xr, x), np.where(live, nur, nu),
            np.where(live, yr, y), np.where(live, x, nu))
    for a, b in zip(permuted, want):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_sweep_layout_tiles():
    """Leaves are viewed in place as (R, K) — K the leaf's own last dim —
    and tiled by full dims or aligned blocks; nothing is padded."""
    for shape, (rows, cols, br, bc) in [
            ((), (1, 1, 1, 1)),
            ((1025,), (1, 1025, 1, 1025)),
            ((24, 24), (24, 24, 24, 24)),
            ((50432, 768), (50432, 768, 128, 768)),
            ((24, 768, 3352), (18432, 3352, 32, 3352)),
            ((1179648,), (1, 1179648, 1, 4096))]:
        lay = sweep_layout(shape)
        assert (lay.rows, lay.cols, lay.block_rows, lay.block_cols) == (
            rows, cols, br, bc), shape
        assert lay.size == int(np.prod(shape, dtype=np.int64))
        if br != rows:  # a tiled dim is sublane-aligned for f32 and bf16
            assert br % 16 == 0
        if bc != cols:
            assert bc % 128 == 0


def test_params_swap_does_not_retrace():
    """New SMEM-table values reuse the compiled sweep-major program."""
    key = jax.random.PRNGKey(3)
    shape = (200,)
    x = _make(key, shape)
    y = _make(jax.random.fold_in(key, 1), shape)
    nu = _make(jax.random.fold_in(key, 2), shape)
    jax.block_until_ready(
        fused_update_sweep_pallas(x, y, nu, _table(), kind="mcp"))
    before = TRACE_COUNTS["fused_sweep"]
    other = sweep_params_table(lam=2e-3, theta=3.5,
                               alpha=jnp.asarray([0.01, 0.02, 0.03]),
                               gamma=0.7, beta=0.9)
    jax.block_until_ready(
        fused_update_sweep_pallas(x, y, nu, other, kind="mcp"))
    assert TRACE_COUNTS["fused_sweep"] == before


# ---------------------------------------------------------------------------
# Through the engine: stacked-Hyper grid on one compiled program
# ---------------------------------------------------------------------------

N, D, T0, ROUNDS = 6, 12, 2, 4


def linear_problem(seed=0):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (N, 16, D))
    w_true = jax.random.normal(jax.random.fold_in(key, 1), (D,))
    b = jnp.einsum("nmd,d->nm", A, w_true)

    def grad_fn(w_stacked, batch):
        r = jnp.einsum("nmd,nd->nm", A, w_stacked) - b
        return jnp.einsum("nmd,nm->nd", A, r) / A.shape[1], {}

    return grad_fn


def _grid(scale=1.0):
    return stack_hypers([
        Hyper.create(alpha=0.05 * scale, beta=1.0, gamma=0.5, lam=1e-3,
                     theta=4.0),
        Hyper.create(alpha=0.1 * scale, beta=0.5, gamma=0.2, lam=5e-3,
                     theta=4.0),
        Hyper.create(alpha=0.02 * scale, beta=1.5, gamma=0.8, lam=1e-4,
                     theta=4.0),
    ])


@pytest.mark.parametrize("prox", ["l1", "mcp", "scad"])
def test_sweep_run_fused_matches_unfused(prox):
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    hypers = _grid()
    batches = jnp.zeros((ROUNDS, T0, 1))
    out = {}
    for fused in (False, True):
        kwargs = {"lam": 1e-3} if prox == "l1" else {"lam": 1e-3,
                                                     "theta": 4.0}
        cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                              prox_name=prox, prox_kwargs=kwargs,
                              use_fused_kernel=fused)
        fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, hypers,
                          batches, n_clients=N)
        out[fused] = fs
    for name in ("x", "y", "nu", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(out[False], name)),
            np.asarray(getattr(out[True], name)),
            atol=1e-5, rtol=1e-5, err_msg=f"leaf {name}")


def test_stacked_grid_zero_retrace_across_configs():
    """Acceptance: one compiled sweep-major program serves the stacked
    grid; feeding a NEW hyperparameter grid (same shapes) reuses it with
    zero fused-kernel retraces."""
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                          prox_name="l1", prox_kwargs={"lam": 1e-3},
                          use_fused_kernel=True)
    round_fn = make_sweep_round(grad_fn, cfg, mixer, batch_axis=None)
    states = sweep_init(jnp.zeros(D), N, 3)
    batches = jnp.zeros((T0, 1))
    states, _ = round_fn(states, _grid(), batches)
    jax.block_until_ready(states.x)
    assert TRACE_COUNTS["fused_sweep"] > 0  # the fused path engaged
    before = dict(TRACE_COUNTS)
    states, _ = round_fn(states, _grid(scale=0.5), batches)
    jax.block_until_ready(states.x)
    assert dict(TRACE_COUNTS) == before  # value swap: zero retraces


def test_cohort_round_fused_matches_unfused_and_freezes_padding():
    """Fused cohort rounds: active rows match the unfused reference, and
    padded rows (never eligible) stay bit-frozen at their init values."""
    n_eff, n_max = 5, 8
    grad_fn_pad = linear_problem()
    key = jax.random.PRNGKey(4)
    A = jax.random.normal(key, (n_eff, 16, D))
    b = jnp.einsum("nmd,d->nm", A,
                   jax.random.normal(jax.random.fold_in(key, 1), (D,)))

    def grad_fn(w_stacked, batch):
        r = jnp.einsum("nmd,nd->nm", A, w_stacked[:n_eff]) - b
        g = jnp.einsum("nmd,nm->nd", A, r) / A.shape[1]
        return jnp.concatenate([g, jnp.zeros((n_max - n_eff, D))]), {}

    sched = MixSchedule.cohort(
        MixPlan.from_topology("complete", n_max),
        CohortSampler.bernoulli(0.7, n_max, seed=0, n_eff=n_eff))
    out = {}
    for fused in (False, True):
        cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-3},
                              use_fused_kernel=fused)
        st = dep_init(jnp.ones(D), n_eff, n_max=n_max)
        for _ in range(ROUNDS):
            st, _ = local_then_comm_round(st, jnp.zeros((T0, 1)), grad_fn,
                                          cfg, sched)
        out[fused] = st
    for name in ("x", "y", "nu", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(out[False], name))[:n_eff],
            np.asarray(getattr(out[True], name))[:n_eff],
            atol=1e-5, rtol=1e-5, err_msg=f"leaf {name}")
    # padding rows never activate: bit-identical to init (x=0 here)
    np.testing.assert_array_equal(np.asarray(out[True].x)[n_eff:], 0.0)
    np.testing.assert_array_equal(np.asarray(out[True].y)[n_eff:], 0.0)


# ---------------------------------------------------------------------------
# the fused="auto" | "require" | "off" knob
# ---------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(momentum="polyak", comm_period=1, prox_name="l1",
                prox_kwargs={"lam": 1e-3})
    base.update(kw)
    return DepositumConfig(**base)


def _one_step(cfg, d=32, n=4, hyper=None):
    A = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    st = dep_init(jnp.ones(d), n)
    mixer = MixPlan.dense(mixing_matrix("complete", n))
    return step(st, None, lambda x, b: (A * x, {}), cfg, mixer,
                is_comm_step=True, hyper=hyper)


def test_fused_mode_resolution():
    assert _cfg().fused_mode() == "off"
    assert _cfg(use_fused_kernel=True).fused_mode() == "auto"
    assert _cfg(use_fused_kernel=True, fused="off").fused_mode() == "off"
    assert _cfg(fused="require").fused_mode() == "require"
    with pytest.raises(ValueError):
        _cfg(fused="always").fused_mode()
    with pytest.raises(ValueError):
        _cfg(fused="always").validate()


def test_fused_off_never_traces_kernel():
    before = dict(TRACE_COUNTS)
    _one_step(_cfg(use_fused_kernel=True, fused="off"), d=47)
    assert dict(TRACE_COUNTS) == before


def test_fused_auto_engages_and_falls_back():
    before = TRACE_COUNTS["fused_sweep"]
    _one_step(_cfg(fused="auto"), d=53)
    assert TRACE_COUNTS["fused_sweep"] > before  # eligible: kernel traced
    before = dict(TRACE_COUNTS)
    _one_step(_cfg(fused="auto", momentum="nesterov", gamma=0.5), d=53)
    assert dict(TRACE_COUNTS) == before  # ineligible: silent fallback


def test_fused_require_raises_for_nesterov():
    with pytest.raises(ValueError, match="polyak"):
        _one_step(_cfg(fused="require", momentum="nesterov", gamma=0.5))


def test_fused_require_raises_for_stacked_hyper():
    with pytest.raises(ValueError, match="stacked Hyper"):
        _one_step(_cfg(fused="require"), hyper=_grid())


def test_fused_require_raises_for_nonfloat_params_at_boundary():
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    cfg = _cfg(fused="require", comm_period=T0)
    with pytest.raises(ValueError, match="non-float"):
        sweep_run(jnp.zeros(D, jnp.int32), grad_fn, cfg, mixer, _grid(),
                  jnp.zeros((ROUNDS, T0, 1)), n_clients=N)


def test_fused_require_raises_for_optout_backend():
    @dataclasses.dataclass(frozen=True)
    class NoFused:
        name: str = "no-fused"
        supports_fused_sweep: bool = False

        def mixer_for(self, plan):
            return StackedVmapBackend().mixer_for(plan)

    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    cfg = _cfg(fused="require", comm_period=T0)
    with pytest.raises(ValueError, match="opts out"):
        sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, _grid(),
                  jnp.zeros((ROUNDS, T0, 1)), n_clients=N,
                  backend=NoFused())


def test_fused_require_happy_path_runs():
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    cfg = _cfg(fused="require", comm_period=T0)
    fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, _grid(),
                      jnp.zeros((ROUNDS, T0, 1)), n_clients=N)
    assert bool(jnp.isfinite(fs.x).all())


def test_backends_advertise_fused_sweep():
    assert StackedVmapBackend().supports_fused_sweep
    assert SweepBackend().supports_fused_sweep
    assert not SweepBackend(
        inner=type("B", (), {"supports_fused_sweep": False,
                             "name": "x"})()).supports_fused_sweep
