"""Sharding-rule logic (pure python, no multi-device compile needed)."""
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.sharding import (
    POD_AS_CLIENT_ARCHS,
    make_placement,
    spec_for,
)

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(scope="module")
def mesh():
    # 1-device "mesh" cannot express 16x16; use an abstract mesh instead
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def multi_mesh():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_replicated_placement_basics(mesh):
    pl = make_placement("qwen3-1.7b", mesh, role="train")
    assert pl.mode == "replicated"
    assert pl.clients_axes == ("data",)
    assert pl.n_clients == 16
    # weight: (clients, layers, embed, mlp)
    spec = spec_for(pl, ("clients", "layers", "embed", "mlp"),
                    (16, 28, 2048, 6144))
    assert spec == P("data", None, None, "model")


def test_divisibility_fallback(mesh):
    """grok's 8 experts cannot shard over a 16-way axis -> replicated."""
    pl = make_placement("grok-1-314b", mesh, role="train")
    assert pl.mode == "pod"
    assert pl.n_clients == 1  # single pod: centralized limit
    spec = spec_for(pl, ("experts", "embed", "mlp"), (8, 6144, 32768))
    # experts (8) % data (16) != 0 -> skipped; embed -> data; mlp -> model
    assert spec == P(None, "data", "model")


def test_greedy_no_axis_reuse(mesh):
    """One mesh axis may appear at most once per spec."""
    pl = make_placement("qwen3-moe-235b-a22b", mesh, role="train")
    spec = spec_for(pl, ("experts", "embed", "mlp"), (128, 4096, 1536))
    # experts -> data (128%16==0), embed wants data too -> skipped, mlp->model
    assert spec == P("data", None, "model")


def test_multi_pod_clients(multi_mesh):
    pl = make_placement("qwen3-1.7b", multi_mesh, role="train")
    assert pl.clients_axes == ("pod", "data")
    assert pl.n_clients == 32
    spec = spec_for(pl, ("clients", "embed", "qkv"), (32, 2048, 2048))
    assert spec == P(("pod", "data"), None, "model")

    pl2 = make_placement("grok-1-314b", multi_mesh, role="train")
    assert pl2.clients_axes == ("pod",)
    assert pl2.n_clients == 2


def test_serve_cache_context_parallel(mesh):
    """decode caches shard over the sequence dim (perf iteration #2)."""
    pl = make_placement("qwen2.5-14b", mesh, role="serve")
    spec = spec_for(pl, ("layers", "dbatch", "cache", "kv", "hd"),
                    (48, 128, 32768, 8, 128))
    assert spec == P(None, "data", "model")  # batch->data, seq->model


def test_scalar_axes(mesh):
    pl = make_placement("qwen3-1.7b", mesh, role="train")
    assert spec_for(pl, (), ()) == P()


def test_pod_as_client_set():
    assert POD_AS_CLIENT_ARCHS == {"grok-1-314b", "qwen3-moe-235b-a22b"}
