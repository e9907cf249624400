"""Required per-architecture smoke tests: reduced config, one forward/train
step on CPU, asserting output shapes + finiteness (no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import build_model
from repro.models.common import pad_vocab

ARCHS = list_archs()


def make_batch(cfg, key, B=2, L=32):
    batch = {
        "tokens": jax.random.randint(key, (B, L), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, L), 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_model), cfg.jnp_dtype
        )
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (B, 16, cfg.d_model), cfg.jnp_dtype
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward_shapes_and_finite(arch):
    cfg = get_config(arch, reduced=True)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params, axes = model.init(key)
    # axes pytree mirrors params pytree
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)
    ) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, axes,
                               is_leaf=lambda x: isinstance(x, tuple))
    )
    B, L = 2, 32
    batch = make_batch(cfg, key, B, L)
    logits, aux = model.forward_train(params, batch)
    V = pad_vocab(cfg.vocab_size)
    expect_len = L + (cfg.n_vision_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (B, expect_len, V)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    """One DEPOSITUM round on the reduced config: loss finite, params move."""
    from repro.core import DepositumConfig
    from repro.training.train_loop import FederatedTrainer, TrainerConfig

    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    tc = TrainerConfig(
        n_clients=2, topology="complete",
        depositum=DepositumConfig(alpha=0.02, beta=1.0, gamma=0.5,
                                  comm_period=2, prox_name="l1",
                                  prox_kwargs={"lam": 1e-6}),
    )
    trainer = FederatedTrainer(model, tc)
    key = jax.random.PRNGKey(1)
    state = trainer.init_state(key)

    def batches():
        b = make_batch(cfg, key, B=2, L=32)
        return jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v[None, None],
                                       (2, 2) + v.shape), b
        )

    state, aux = trainer._round(state, batches())
    leaves = jax.tree_util.tree_leaves(state.x)
    assert all(bool(jnp.isfinite(l.astype(jnp.float32)).all()) for l in leaves)
    assert float(jnp.mean(aux["ce"])) > 0.0
    # params moved away from init (the round consumes its input state)
    x1 = [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(state.x)]
    state2, _ = trainer._round(state, batches())
    moved = sum(
        float(np.sum(np.abs(a - np.asarray(b, np.float32))))
        for a, b in zip(x1, jax.tree_util.tree_leaves(state2.x))
    )
    assert moved > 0.0
