"""FLOP and byte counters against hand-worked values for the cells' shapes."""
import jax
import jax.numpy as jnp
import pytest

from bench.flops import hybrid, ssm
from bench.metrics import update_roofline
from bench.reference import init as ref_init
from bench.reference.depositum import family
from bench.spec import pair_cell

# mamba2-130m, per token, forward: per layer in_proj 2*768*3352 = 5148672,
# out_proj 2*1536*768 = 2359296, SSD 2*256*128 + 2*256*24*64
# + 2 * 2*24*128*64 = 1638400 -> 9146368; x 24 = 219512832; head
# 2*768*50432 = 77463552; forward 296976384; training x 3
MAMBA_TRAIN = 890_929_152
# zamba2 one period: mamba layer 2*2560*10448 + 2*5120*2560 + 2*256*64
# + 2*256*80*64 + 2 * 2*80*64*64 = 83673088, x 5 = 418365440; shared block
# 8*2560^2 + 4*1024*2560 + 6*2560*10240 = 220200960; head 2*2560*32000
# = 163840000; forward 802406400; training x 3
ZAMBA_TRAIN = 2_407_219_200
# weights of one client (bf16): 129100224 and 468146480 parameters
MAMBA_PARAMS, ZAMBA_PARAMS = 129_100_224, 468_146_480


# the configurations and traffic of the cells, and the four-chip zamba2
# pair kept for a later cell
PAIRS = [("mamba2-130m", "star4.b4x512", 1),
         ("mamba2-130m", "star4.b1x256", 1),
         ("zamba2-2.7b-1period", "complete4.4chip.b2x1024", 4)]


@pytest.mark.parametrize("pair,fam,want", zip(
    PAIRS, [ssm, ssm, hybrid], [MAMBA_TRAIN, MAMBA_TRAIN, ZAMBA_TRAIN]))
def test_train_flops_per_token(pair, fam, want):
    c = pair_cell(*pair)
    assert fam.train_flops_per_token(c.model, c.traffic["seq_len"]) == want


@pytest.mark.parametrize("pair,params,per_device", zip(
    PAIRS, [MAMBA_PARAMS, MAMBA_PARAMS, ZAMBA_PARAMS], [4, 4, 1]))
def test_update_bytes_per_step(pair, params, per_device):
    c = pair_cell(*pair)
    leaves = jax.eval_shape(
        lambda k: ref_init.build(family(c.model).leaves(c.model), k,
                                 jnp.bfloat16), jax.random.key(0))
    sizes = [l.size * l.dtype.itemsize
             for l in jax.tree_util.tree_leaves(leaves)]
    assert sum(sizes) == 2 * params
    assert c.traffic["n_clients"] // c.chips == per_device
    # 10 model-sized sweeps of every client the device holds
    assert update_roofline.bytes_per_step(sizes, per_device) == \
        10 * 2 * params * per_device
