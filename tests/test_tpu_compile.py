"""Compile the fused update kernels, the dense mix and the Mamba-2 block
for a described TPU v5e chip.

Nothing runs: each kernel test lowers a sweep-major kernel at a leaf shape
of the full mamba2-130m state (4 clients, bf16) with Mosaic, compiles it for
one chip of a described ``v5e:2x2`` topology, and checks that the program
holds the Mosaic kernel (``tpu_custom_call``).  This catches what interpret
mode cannot: block shapes the TPU's tiling refuses, VMEM overruns, layouts
its compiler stalls on (a flat (S, C, d) view of the 50 280 x 768 embedding
did not finish compiling in minutes), and relayout copies around the
kernel where its view of a leaf disagrees with the chip's default layout.
The block's test reads what the TPU compiler made of the SSD scan.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Layout

import repro.kernels
from repro.configs import get_config
from repro.kernels.prox.kernel import (
    TRACE_COUNTS,
    ClientShards,
    fused_tracking_sweep_pallas,
    fused_update_sweep_pallas,
)
from repro.kernels.prox.ops import fused_local_update, fused_tracking
from repro.models.common import Initializer, split_params
from repro.models.mamba2 import init_mamba, mamba_block

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
def mosaic(monkeypatch, topo):
    """Lower the kernels with Mosaic although the process runs on the CPU,
    with their views following the described chip's default layouts.

    Traces of these kernels are cached by shape, so caches are cleared on
    both sides (no CPU test may reuse a Mosaic trace, nor this a CPU one);
    the persistent cache stays off, since a compile for a described chip
    cannot be read back."""
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(repro.kernels, "layout_device",
                        lambda: topo.devices[0])
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


_ARRAY = r"\w+\[[\d,]*\]\{[^}]*\}"


def _relayouts(text):
    """Result types of the module's copies and transposes that change an
    array's layout.  One that differs from its operand only in memory
    space (XLA moving a small operand into VMEM or SMEM) moves no element
    within the array and is none of them."""
    space = lambda t: re.sub(r"S\(\d+\)", "", t)
    types = dict(re.findall(rf"(%[\w.\-]+) = ({_ARRAY}) ", text))
    moved = [dst for dst, src in re.findall(
        rf"= ({_ARRAY}) (?:copy|transpose)\((%[\w.\-]+)\)", text)
        if space(dst) != space(types.get(src, ""))]
    for dst, src in re.findall(rf"= \(({_ARRAY}), ({_ARRAY}),[^=]*"
                               rf" copy-start\(", text):
        if space(dst) != space(src):
            moved.append(dst)
    return moved


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


def _client_sharded_text(topo, leaf, gated):
    """Compiled update + tracking of (1, C, *leaf) leaves split one client
    per chip over the 4-chip mesh."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((C,), ("clients",), axis_types=(AxisType.Auto,),
                         devices=topo.devices)
    shards = ClientShards(mesh, "clients")
    x = _spec((1, C) + leaf, jnp.bfloat16,
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
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text, collective
    return text


@pytest.mark.parametrize("gated", [False, True])
def test_client_sharded_kernels_compile_per_chip(topo, mosaic, gated):
    """On the shard_map backend's 4-chip mesh (one client per chip) the
    kernels run under ``shard_map``: each chip updates its own client, and
    the program moves nothing between chips."""
    text = _client_sharded_text(topo, EMBED, gated)
    assert text.count("tpu_custom_call") >= 2
    assert not _relayouts(text)


@pytest.mark.parametrize("gated", [False, True])
def test_client_sharded_view_follows_layout(topo, mosaic, gated):
    """Under ``shard_map`` each chip's (1, 1, 24, 768, 3352) in_proj shard
    is viewed in the chip's 768-minor layout too: both kernels permute it,
    and nothing is relaid around them."""
    before = TRACE_COUNTS["permuted_view"]
    text = _client_sharded_text(topo, (24, 768, 3352), gated)
    assert TRACE_COUNTS["permuted_view"] - before == 2
    assert text.count("tpu_custom_call") >= 2
    assert not _relayouts(text)


def _default_layout(device, spec):
    pjrt = device.client.get_default_layout(np.dtype(spec.dtype), spec.shape,
                                            device)
    return Layout.from_pjrt_layout(pjrt)


ROUND_CALLS = [(kernel, leaf) for kernel in ("update", "tracking")
               for leaf in LEAVES]


@pytest.mark.parametrize("kernel,leaf", ROUND_CALLS,
                         ids=[_ids(c) for c in ROUND_CALLS])
@pytest.mark.parametrize("gated", [False, True])
def test_round_call_reads_default_layout(topo, one_chip, mosaic, kernel,
                                         leaf, gated):
    """The round program's call, (C, *p) leaves in the chip's default
    layout, compiles with no relayout of the leaf around the kernel: its
    view follows that layout (in_proj's (24, 768, 3352) is kept 768-minor,
    and was copied to row-major and back), and every argument and result
    keeps the default layout."""
    x = _spec((C,) + leaf, jnp.bfloat16, one_chip)
    hp = _spec((5,), jnp.float32, one_chip)
    mask = _spec((C,), jnp.float32, one_chip)
    entry = fused_local_update if kernel == "update" else fused_tracking
    extra = {"kind": "l1"} if kernel == "update" else {}

    def fn(a, b, c, h, *m):
        out = entry(a, b, c, h, *m, **extra)
        # ungated, the tracking call hands g_new back as g_kept; a program
        # returning its own argument copies it, the round program does not
        return out[0] if kernel == "tracking" and not gated else out

    args = (x, x, x, hp) + ((mask,) if gated else ())
    before = TRACE_COUNTS["permuted_view"]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    permuted = TRACE_COUNTS["permuted_view"] - before
    assert permuted == (1 if leaf == (24, 768, 3352) else 0)
    moved = _relayouts(text)
    if len(leaf) == 1:
        # a rank-1 leaf is stored (C, K) with its clients in the sublanes
        # of one tile; the per-client grid axis needs a tile per client,
        # so x, y, nu and the results are retiled: C x K elements each
        assert moved and all(m.startswith(f"bf16[1,{C},1,{leaf[0]}]")
                             for m in moved), moved
    else:
        assert not moved, moved
    dev = topo.devices[0]
    outs = jax.tree_util.tree_leaves(compiled.output_formats)
    assert len(outs) == (1 if kernel == "tracking" and not gated else 2)
    for fmt, spec in zip(list(compiled.input_formats[0]) + outs,
                         args + (x,) * len(outs)):
        assert fmt.layout == _default_layout(dev, spec), spec


def test_mamba_block_grad_has_no_windowed_reduction(one_chip):
    """The gradient of one mamba2-130m block over two 256-token chunks: the
    SSD's cumulative log-decay is a matmul with the triangular ones, at
    HIGHEST precision in the forward and in its transpose, and neither pass
    holds a ``reduce-window`` (what ``cumsum`` lowers to on the TPU)."""
    cfg = get_config("mamba2-130m")
    init = lambda k: split_params(
        init_mamba(Initializer(k, jnp.bfloat16), cfg))[0]
    params = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(init, jax.random.key(0)))
    u = _spec((1, 2 * cfg.ssm_chunk, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(p, u):
        out, _ = mamba_block(p, u, cfg)
        return jnp.sum(out.astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), (params, u))
    assert "reduce-window" not in text
    cumsum = [line for line in text.splitlines()
              if re.search(r" (?:convolution|dot)\(", line)
              and "bksh,ts->bkht" in line]
    assert any("transpose(" in line for line in cumsum), cumsum
    assert any("transpose(" not in line for line in cumsum), cumsum
    for line in cumsum:
        assert "operand_precision={highest,highest}" in line, line


MIX_LEAVES = [(2, 768, 3352), (1024, 768)]


def test_dense_mix_compiles_in_state_layout(one_chip, mosaic):
    """``apply_mix`` with a dense 4-client plan mixes each leaf with the
    Pallas kernel in the leaf's own layout: no ``convolution`` (what the
    einsum lowers to), and no ``copy`` back from a client-interleaved
    layout.  Every leaf is the kernel's, none the einsum's."""
    from repro.core.mixing import MixPlan, apply_mix

    W = _spec((C, C), jnp.float32, one_chip)
    tree = [_spec((C,) + leaf, jnp.bfloat16, one_chip) for leaf in MIX_LEAVES]
    before = {k: TRACE_COUNTS[k] for k in ("mix", "mix_fallback")}
    text = _compiled_text(lambda w, t: apply_mix(MixPlan.dense(w), t),
                          (W, tree))
    assert TRACE_COUNTS["mix"] - before["mix"] == len(MIX_LEAVES)
    assert TRACE_COUNTS["mix_fallback"] == before["mix_fallback"]
    assert text.count("tpu_custom_call") >= len(MIX_LEAVES)
    assert not re.search(r" (?:copy|copy-start|convolution)\(", text), text
