"""Compile the fused update kernels for a described TPU v5e chip.

Nothing runs: each test lowers a sweep-major kernel at a leaf shape of the
full mamba2-130m state (4 clients, bf16) with Mosaic, compiles it for one
chip of a described ``v5e:2x2`` topology, and checks that the program holds
the Mosaic kernel (``tpu_custom_call``).  This catches what interpret mode
cannot: block shapes the TPU's tiling refuses, VMEM overruns, and layouts
its compiler stalls on (a flat (S, C, d) view of the 50 280 x 768 embedding
did not finish compiling in minutes).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

import repro.kernels
from repro.kernels.prox.kernel import (
    ClientShards,
    fused_tracking_sweep_pallas,
    fused_update_sweep_pallas,
)

C = 4
# per-client leaf shapes of mamba2-130m (24 layers stacked); the embedding
# table is padded to 50 432 rows, 50 280 is the published vocab
EMBED = (50432, 768)
EMBED_VOCAB = (50280, 768)
LEAVES = [
    (768,), (24, 24), (24, 768), (24, 1536), (24, 1792), (24, 4, 1792),
    (24, 768, 3352), (24, 1536, 768), EMBED, EMBED_VOCAB,
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels with Mosaic although the process runs on the CPU.

    Traces of these kernels are cached by shape, so caches are cleared on
    both sides (no CPU test may reuse a Mosaic trace, nor this a CPU one);
    the persistent cache stays off, since a compile for a described chip
    cannot be read back."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)


def _ids(case):
    """'l1-24x768' style ids instead of pytest's 'leaf3'."""
    kind, leaf = case if isinstance(case[0], str) else (None, case)
    dims = "x".join(map(str, leaf))
    return f"{kind}-{dims}" if kind else dims


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


UPDATE_CASES = ([("l1", leaf) for leaf in LEAVES]
                + [(k, leaf) for k in ("mcp", "scad")
                   for leaf in (EMBED, EMBED_VOCAB)])


@pytest.mark.parametrize("kind,leaf", UPDATE_CASES,
                         ids=[_ids(c) for c in UPDATE_CASES])
@pytest.mark.parametrize("gated", [False, True])
def test_fused_update_compiles(one_chip, mosaic, kind, leaf, gated):
    x = _spec((1, C) + leaf, jnp.bfloat16, one_chip)
    params = _spec((1, 5), jnp.float32, one_chip)
    mask = _spec((1, C), jnp.float32, one_chip)
    if gated:
        fn = lambda x, y, nu, p, m: fused_update_sweep_pallas(
            x, y, nu, p, m, kind=kind)
        args = (x, x, x, params, mask)
    else:
        fn = lambda x, y, nu, p: fused_update_sweep_pallas(
            x, y, nu, p, kind=kind)
        args = (x, x, x, params)
    assert "tpu_custom_call" in _compiled_text(fn, args)


@pytest.mark.parametrize("leaf", LEAVES, ids=[_ids(l) for l in LEAVES])
@pytest.mark.parametrize("gated", [False, True])
def test_tracking_compiles(one_chip, mosaic, leaf, gated):
    y = _spec((1, C) + leaf, jnp.bfloat16, one_chip)
    params = _spec((1, 5), jnp.float32, one_chip)
    mask = _spec((1, C), jnp.float32, one_chip)
    if gated:
        fn, args = fused_tracking_sweep_pallas, (y, y, y, params, mask)
    else:
        fn = lambda y, gn, go, p: fused_tracking_sweep_pallas(y, gn, go, p)
        args = (y, y, y, params)
    assert "tpu_custom_call" in _compiled_text(fn, args)


@pytest.mark.parametrize("gated", [False, True])
def test_client_sharded_kernels_compile_per_chip(topo, mosaic, gated):
    """On the shard_map backend's 4-chip mesh (one client per chip) the
    kernels run under ``shard_map``: each chip updates its own client, and
    the program moves nothing between chips."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((C,), ("clients",), axis_types=(AxisType.Auto,),
                         devices=topo.devices)
    shards = ClientShards(mesh, "clients")
    x = _spec((1, C) + EMBED, jnp.bfloat16,
              NamedSharding(mesh, P(None, "clients")))
    params = _spec((1, 5), jnp.float32, NamedSharding(mesh, P()))
    mask = (_spec((1, C), jnp.float32, NamedSharding(mesh, P(None, "clients")))
            if gated else None)

    def fn(x, y, nu, p, m):
        xo, nuo = fused_update_sweep_pallas(x, y, nu, p, m, kind="l1",
                                            shards=shards)
        yo, _ = fused_tracking_sweep_pallas(y, x, nu, p, m, shards=shards)
        return xo, nuo, yo

    text = _compiled_text(fn, (x, x, x, params, mask))
    assert text.count("tpu_custom_call") >= 2
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text, collective
