"""The dense-mix Pallas kernel (``kernels/mix.py``) in interpret mode,
against the einsum it replaces on the chip.

W is cast to the leaf's dtype on both paths and the kernel sums in
float32, rounding once.  So bf16 leaves equal the einsum bit for bit, for
a W exact in bf16 (the star's quarters) and for the ring's thirds rounded
to bf16.  float32 products and sums round, and the einsum sums in an
order of its own: float32 leaves lie within the float32 rounding bound of
the exact sum, as the einsum does.

On the CPU ``apply_mix`` keeps the einsum; tests that run the kernel
through ``apply_mix`` steer its gate (``_kernel_takes``) in the test.
Compiling it with Mosaic for a described chip is
``tests/test_tpu_compile.py``'s.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from repro.core import DepositumConfig, init as dep_init, local_then_comm_round
from repro.core.mixing import MixPlan, apply_mix, stack_mixplans
from repro.core.schedule import MixSchedule, apply_schedule
from repro.core.topology import mixing_matrix
from repro.kernels import mix
from repro.kernels.prox.kernel import TRACE_COUNTS

# per-client leaf shapes: a scalar, 1-D leaves, last dims that are not a
# multiple of 128 (in_proj's 3352), several row tiles with a ragged edge
# (1100 rows) and several column tiles (5000 > 4096 columns)
SHAPES = [(), (768,), (1000,), (3, 3352), (2, 5, 130), (1100, 130),
          (2, 5000)]
DTYPES = [jnp.bfloat16, jnp.float32]


def _leaf(C, shape, dtype, seed=0):
    x = jax.random.normal(jax.random.key(seed), (C,) + shape, jnp.float32)
    return x.astype(dtype)


def _einsum(W, x):
    return jnp.einsum("ij,j...->i...", W.astype(x.dtype), x,
                      precision=jax.lax.Precision.HIGHEST)


def _W(kind, C):
    """The star's or ring's W, or "skew": a non-symmetric W in sixteenths
    (exact in bf16), so a transposed W cannot pass for W."""
    if kind == "skew":
        rng = np.random.default_rng(C)
        return jnp.asarray(rng.integers(0, 16, (C, C)) / 16, jnp.float32)
    return jnp.asarray(mixing_matrix(kind, C), jnp.float32)


def _kernel(W, x):
    return mix._mix_one(W.astype(x.dtype), x)


def _assert_mix_equal(got, W, x):
    """bf16: the einsum's bits.  float32: within n + 1 float32 roundings
    of the exact sum of |W_ij x_j| (products and the running sum)."""
    got = np.asarray(got)
    if x.dtype == jnp.bfloat16:
        np.testing.assert_array_equal(got, np.asarray(_einsum(W, x)))
        return
    W64 = np.asarray(W.astype(x.dtype), np.float64)
    x64 = np.asarray(x, np.float64).reshape(x.shape[0], -1)
    exact = (W64 @ x64).reshape(x.shape)
    bound = ((x.shape[0] + 1) * 2.0 ** -24
             * (np.abs(W64) @ np.abs(x64)).reshape(x.shape))
    assert np.all(np.abs(got - exact) <= bound)


@pytest.fixture
def kernel_in_apply_mix(monkeypatch):
    """``apply_mix``'s dense arm runs the (interpret-mode) kernel."""
    monkeypatch.setattr(mix, "_kernel_takes", lambda x: True)


@pytest.mark.parametrize("topology", ["star", "ring", "skew"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("C", [2, 4, 8])
def test_kernel_matches_einsum(C, dtype, topology):
    W = _W(topology, C)
    for k, shape in enumerate(SHAPES):
        x = _leaf(C, shape, dtype, seed=k)
        got = _kernel(W, x)
        assert got.shape == x.shape and got.dtype == x.dtype
        _assert_mix_equal(got, W, x)


def test_star_weights_are_exact_in_bf16():
    """The dyadic case: the star's W survives the cast to bf16 unchanged,
    so the kernel's bf16 mix is the float32-W mix rounded once."""
    W = jnp.asarray(mixing_matrix("star", 4), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(W.astype(jnp.bfloat16).astype(jnp.float32)),
        np.asarray(W))
    x = _leaf(4, (3, 3352), jnp.bfloat16)
    want = jnp.einsum("ij,j...->i...", W, x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(
        np.asarray(_kernel(W, x)), np.asarray(want.astype(jnp.bfloat16)))


def test_layout_blocks_fit_the_budget():
    """A block holds every client; it shrinks by rows to fit the update
    kernels' VMEM budget, and a leaf whose block cannot fit is refused."""
    in_proj = (1, 4, 24, 768, 3352)
    lay = mix.mix_layout(in_proj, jnp.bfloat16)
    assert (lay.rows, lay.cols, lay.block_rows) == (24 * 768, 3352, 32)
    embed = (1, 4, 50432, 768)
    rows = {(C, dt): mix.mix_layout((1, C) + embed[2:], dt).block_rows
            for C in (4, 8) for dt in DTYPES}
    assert rows == {(4, jnp.bfloat16): 128, (4, jnp.float32): 64,
                    (8, jnp.bfloat16): 64, (8, jnp.float32): 32}
    for C in (2, 4, 8):
        for shape in SHAPES:
            for dt in DTYPES:
                lay = mix.mix_layout((1, C) + shape, dt)
                assert lay is not None
                assert (mix._block_bytes(lay, C, np.dtype(dt).itemsize)
                        <= mix.VMEM_BUDGET)
    # 16 clients of a 3-row leaf: the rows pad to a sublane tile, and the
    # 3352 columns are one block
    assert mix.mix_layout((1, 16, 3, 3352), jnp.bfloat16) is None
    assert mix.mix_layout((1, 256, 8192), jnp.bfloat16) is None
    assert mix.mix_layout((1, 4, 8), jnp.int32) is None
    assert mix.mix_layout((1, 4, 8), jnp.float64) is None
    with pytest.raises(ValueError):
        mix.dense_mix_pallas(jnp.ones((1, 256, 256)),
                             jnp.ones((1, 256, 8192), jnp.bfloat16))


def test_grid_covers_ragged_tiles():
    """Several row and column tiles, ragged at the edge, each written."""
    for shape, tiles in [((1100, 130), (5, 1)), ((2, 5000), (1, 2))]:
        lay = mix.mix_layout((1, 4) + shape, jnp.float32)
        assert lay.tiles == tiles, shape
        x = _leaf(4, shape, jnp.float32, seed=7)
        W = jnp.asarray(mixing_matrix("ring", 4), jnp.float32)
        _assert_mix_equal(_kernel(W, x), W, x)


def test_traced_W_under_jit_does_not_retrace():
    traces = []

    @jax.jit
    def f(W, x):
        traces.append(1)
        return _kernel(W, x)

    x = _leaf(4, (3, 3352), jnp.bfloat16)
    for topology in ("star", "ring", "complete"):
        W = jnp.asarray(mixing_matrix(topology, 4), jnp.float32)
        _assert_mix_equal(f(W, x), W, x)
    assert len(traces) == 1


@pytest.mark.parametrize("batched", ["both", "W", "x"])
def test_vmap_is_one_kernel_over_the_sweep_axis(batched):
    """Under ``jax.vmap`` the sweep axis becomes the kernel's grid axis 0:
    one pallas_call for the stack, equal to the per-point mixes."""
    S, C, shape = 2, 4, (2, 5, 130)
    Ws = jnp.stack([_W(t, C) for t in ("skew", "ring")])
    xs = jnp.stack([_leaf(C, shape, jnp.bfloat16, seed=s)
                    for s in range(S)])
    W_axis, x_axis = {"both": (0, 0), "W": (0, None),
                      "x": (None, 0)}[batched]
    W_in = Ws if W_axis == 0 else Ws[0]
    x_in = xs if x_axis == 0 else xs[0]
    fn = jax.vmap(_kernel, in_axes=(W_axis, x_axis))
    text = str(jax.make_jaxpr(fn)(W_in, x_in))
    assert text.count("pallas_call") == 1
    got = fn(W_in, x_in)
    for s in range(S):
        W = Ws[s] if W_axis == 0 else Ws[0]
        x = xs[s] if x_axis == 0 else xs[0]
        np.testing.assert_array_equal(np.asarray(got[s]),
                                      np.asarray(_einsum(W, x)))


def test_stacked_plan_through_apply_mix(kernel_in_apply_mix):
    """A stacked S = 2 plan, vmapped as the sweep engine does, mixes each
    point as the einsum does, for bf16 and float32 leaves."""
    C = 4
    plans = [MixPlan.from_topology(t, C) for t in ("star", "ring")]
    stacked = stack_mixplans(plans)
    tree = {"a": _leaf(C, (3, 3352), jnp.bfloat16),
            "b": _leaf(C, (768,), jnp.float32, seed=1)}
    got = jax.jit(jax.vmap(apply_mix, in_axes=(0, None)))(stacked, tree)
    for s, p in enumerate(plans):
        for name, x in tree.items():
            _assert_mix_equal(got[name][s], p.W, x)


def test_lazy_schedule_through_apply_mix(kernel_in_apply_mix):
    """A lazy schedule's per-round masked W is traced; the kernel mixes
    with it as the einsum does."""
    C = 4
    sched = MixSchedule.lazy(MixPlan.from_topology("star", C), 0.5,
                             rounds=4, seed=3)
    x = {"a": _leaf(C, (2, 5, 130), jnp.bfloat16)}
    fn = jax.jit(lambda r, t: apply_schedule(sched, r, t))
    for r in range(4):
        got = fn(jnp.int32(r), x)["a"]
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(mix, "_kernel_takes", lambda leaf: False)
            want = fn.__wrapped__(jnp.int32(r), x)["a"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_round_with_kernel_mix_is_bitexact(monkeypatch):
    """A DEPOSITUM round of bf16 clients on the star: the kernel's mix
    gives the einsum's state, bit for bit."""
    C, T0 = 4, 2
    target = _leaf(C, (3, 130), jnp.float32, seed=5)

    def grad_fn(x, batch):
        return jax.tree_util.tree_map(
            lambda p: (p.astype(jnp.float32) - target).astype(p.dtype),
            x), {}

    cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                          prox_name="l1", prox_kwargs={"lam": 1e-3})
    plan = MixPlan.from_topology("star", C)
    out = {}
    for takes in (False, True):
        monkeypatch.setattr(mix, "_kernel_takes", lambda x, t=takes: t)
        before = TRACE_COUNTS["mix"]
        st = dep_init(jnp.zeros((3, 130), jnp.bfloat16), C)
        for _ in range(2):
            st, _ = local_then_comm_round(st, jnp.zeros((T0, 1)), grad_fn,
                                          cfg, plan)
        out[takes] = st
        assert (TRACE_COUNTS["mix"] > before) == takes
    for name in ("x", "y", "nu", "g"):
        np.testing.assert_array_equal(np.asarray(getattr(out[True], name)),
                                      np.asarray(getattr(out[False], name)),
                                      err_msg=name)


def _counts():
    return {k: TRACE_COUNTS[k] for k in ("mix", "mix_fallback")}


def test_cpu_keeps_the_einsum_and_counts_nothing():
    before = _counts()
    plan = MixPlan.from_topology("star", 4)
    tree = {"a": _leaf(4, (3, 3352), jnp.bfloat16)}
    text = str(jax.make_jaxpr(apply_mix)(plan, tree))
    assert "dot_general" in text and "pallas_call" not in text
    assert _counts() == before


@pytest.fixture
def mosaic_trace(monkeypatch):
    """Trace (never compile) as under Mosaic, on the CPU's layouts; no
    trace cached on either side of the switch is reused."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_mosaic_counts_kernel_and_fallback_leaves(mosaic_trace):
    """Under Mosaic each dense leaf the kernel takes counts ``mix`` and
    holds a pallas_call; one whose 256-client block cannot fit VMEM counts
    ``mix_fallback`` and keeps the einsum."""
    plan = MixPlan.from_topology("star", 4)
    tree = {"a": jnp.zeros((4, 24, 3352), jnp.bfloat16),
            "b": jnp.zeros((4, 768), jnp.float32),
            "c": jnp.zeros((4,), jnp.bfloat16)}
    before = _counts()
    text = str(jax.make_jaxpr(apply_mix)(plan, tree))
    assert _counts() == {"mix": before["mix"] + 3,
                         "mix_fallback": before["mix_fallback"]}
    assert text.count("pallas_call") == 3 and "dot_general" not in text
    big = MixPlan.from_topology("ring", 256)
    before = _counts()
    text = str(jax.make_jaxpr(apply_mix)(
        big, jnp.zeros((256, 8192), jnp.bfloat16)))
    assert _counts() == {"mix": before["mix"],
                         "mix_fallback": before["mix_fallback"] + 1}
    assert "dot_general" in text and "pallas_call" not in text


def test_mosaic_leaves_a_leaf_split_over_devices_to_the_einsum(mosaic_trace):
    """XLA cannot partition a Mosaic kernel: a leaf whose clients are split
    over two devices keeps the einsum, which XLA partitions."""
    from jax.sharding import AbstractMesh, AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = AbstractMesh((2,), ("clients",), axis_types=(AxisType.Auto,))
    leaf = jax.ShapeDtypeStruct((4, 24, 3352), jnp.bfloat16,
                                sharding=NamedSharding(mesh, P("clients")))
    before = _counts()
    text = str(jax.make_jaxpr(apply_mix)(MixPlan.from_topology("star", 4),
                                         leaf))
    assert _counts() == {"mix": before["mix"],
                         "mix_fallback": before["mix_fallback"] + 1}
    assert "dot_general" in text and "pallas_call" not in text
