"""The main-path Pallas kernels compile for a TPU v5e at the shapes the
member tower gives them.

Nothing here runs: each case compiles for a described ``v5e:2x2`` chip
(no chip attached), which is where Mosaic refuses layouts and tilings
that interpret mode accepts. Kernels are called with ``interpret=False``
because ``ops.default_interpret()`` sees this host's CPU. The topology
is described only inside the module fixture: only one process at a time
may load the TPU library, so nothing touches it at import or collection.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


# (batch, heads, tokens, head_dim): the kernel tower's attn_block at
# batch 512 (tokens=8, dim=64, heads=4 -> head_dim 16), and a long
# sequence with wide heads
@pytest.mark.parametrize("shape", [(512, 4, 8, 16), (256, 8, 128, 64)])
def test_flash_attention_compiles_for_v5e(one_chip, shape):
    q = _sds(shape, one_chip)
    compiled = ops.flash_attention.lower(q, q, q, causal=False,
                                         interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# rows = batch x tokens as the quantize block sees them: 512 x 8 (a full
# batch after attention), a 512-row batch, and the Table 1 epoch's
# 87-row tail batch x 8 tokens
@pytest.mark.parametrize("rows", [4096, 512, 696])
def test_quantize_int8_compiles_for_v5e(one_chip, rows):
    compiled = ops.quantize_int8.lower(_sds((rows, 64), one_chip),
                                       interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the MoE share's grouped expert products (``moe.expert_ffn``) at
# DeepSeek-V2-Lite's widths: 8 held experts of width 1,408 over d 2,048,
# with the dropless buffer of 16 users x 512 tokens x top-6 rows, and a
# one-user buffer; forward and the gradient of both operands
@pytest.mark.parametrize("rows", [49152, 3072])
@pytest.mark.parametrize("grad", [False, True])
def test_expert_grouped_product_compiles_for_v5e(one_chip, rows, grad):
    from repro.models import moe
    d, f, held = 2048, 1408, 8
    xs = _sds((rows, d), one_chip)
    params = {"w_gate": _sds((held, d, f), one_chip),
              "w_up": _sds((held, d, f), one_chip),
              "w_down": _sds((held, f, d), one_chip)}
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)

    def fwd(xs, params, sizes):
        return moe.expert_ffn(xs, params, sizes)
    fn = fwd
    if grad:
        def fn(xs, params, sizes):
            return jax.grad(lambda x, p: jnp.sum(fwd(x, p, sizes)),
                            (0, 1))(xs, params)
    with jax.default_matmul_precision("highest"):
        text = jax.jit(fn).lower(xs, params, sizes).compile().as_text()
    # the grouped products are the chip's ragged-dot kernel, not a dense
    # product over every expert
    assert "ragged-dot" in text and "tpu_custom_call" in text
