"""Sharding rules: divisibility safety for every arch on the production
mesh (AbstractMesh — no devices needed) + ZeRO-1 state sharding."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models.io_spec import params_spec
from repro.sharding import rules

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(spec_tree, shape_tree, mesh):
    leaves_s = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    leaves_a = jax.tree_util.tree_leaves(shape_tree)
    assert len(leaves_s) == len(leaves_a)
    for spec, aval in zip(leaves_s, leaves_a):
        for i, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert aval.shape[i] % size == 0, (spec, aval.shape)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    p_abs = params_spec(cfg)
    specs = rules.param_specs(p_abs, MESH)
    _check_divisible(specs, p_abs, MESH)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "qwen2.5-14b",
                                  "rwkv6-7b", "jamba-v0.1-52b"])
def test_zero1_specs_divisible(arch):
    cfg = get_config(arch)
    p_abs = params_spec(cfg)
    pspecs = rules.param_specs(p_abs, MESH)
    ospecs = rules.zero1_specs(pspecs, p_abs, MESH)
    _check_divisible(ospecs, p_abs, MESH)


def test_zero1_adds_data_axis_somewhere():
    cfg = get_config("granite-8b")
    p_abs = params_spec(cfg)
    pspecs = rules.param_specs(p_abs, MESH)
    ospecs = rules.zero1_specs(pspecs, p_abs, MESH)
    flat = jax.tree_util.tree_leaves(
        ospecs, is_leaf=lambda x: isinstance(x, P))

    def has_data(spec):
        return any(a == "data" or (isinstance(a, tuple) and "data" in a)
                   for a in spec if a is not None)

    n_data = sum(1 for s in flat if has_data(s))
    assert n_data > len(flat) // 2  # most big tensors get ZeRO-sharded


def test_moe_expert_sharding_strategy():
    """EP when expert count divides the model axis; TP fallback else."""
    # deepseek: 64 experts on 16-way axis -> EP on dim 0
    cfg = get_config("deepseek-v2-lite-16b")
    specs = rules.param_specs(params_spec(cfg), MESH)
    up = specs["periods"]["b0"]["ffn"]["w_up"]
    assert up == P(None, "model", None, None)  # (period, E, D, F)
    # mixtral: 8 experts < 16 -> fall back to hidden-dim TP
    cfg = get_config("mixtral-8x7b")
    specs = rules.param_specs(params_spec(cfg), MESH)
    up = specs["periods"]["b0"]["ffn"]["w_up"]
    assert up == P(None, None, None, "model")


def test_internvl_embed_replicated():
    """151655 vocab is not 16-divisible; D dim shards instead."""
    cfg = get_config("internvl2-1b")
    specs = rules.param_specs(params_spec(cfg), MESH)
    assert specs["embed"] == P(None, "model")


def test_attention_projections_column_row():
    cfg = get_config("granite-8b")
    specs = rules.param_specs(params_spec(cfg), MESH)
    blk = specs["periods"]["b0"]
    assert blk["mix"]["wq"] == P(None, None, "model")
    assert blk["mix"]["wo"] == P(None, "model", None)
    assert blk["ffn"]["w_up"] == P(None, None, "model")
    assert blk["ffn"]["w_down"] == P(None, "model", None)


def test_batch_axes_multi_pod():
    assert rules.batch_axes(MESH_MP) == ("pod", "data")
    assert rules.batch_axes(MESH) == ("data",)
