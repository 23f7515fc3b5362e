"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at a registered model's published
widths for one chip of a ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode accepts (blocks that break
the (8, 128) tiling rule, too much VMEM). The topology is described inside
a module fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq,window", [(4096, None), (8192, 4096)])
def test_flash_attention_compiles_at_granite_8b_widths(one_chip, seq,
                                                        window):
    from repro.kernels.flash_attention.ops import flash_attention
    cfg = get_config("granite-8b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window),
        [((1, seq, H, hd), jnp.bfloat16), ((1, seq, KV, hd), jnp.bfloat16),
         ((1, seq, KV, hd), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in txt


def test_rwkv6_compiles_at_rwkv6_7b_widths(one_chip):
    from repro.kernels.rwkv6.ops import rwkv6
    cfg = get_config("rwkv6-7b")
    hd = cfg.rwkv.head_dim
    H = cfg.d_model // hd
    x = ((1, 2048, H, hd), jnp.bfloat16)
    txt = _compiled_text(lambda r, k, v, w, u: rwkv6(r, k, v, w, u),
                         [x, x, x, x, ((H, hd), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in txt
