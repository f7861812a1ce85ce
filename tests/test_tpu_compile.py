"""The causal flash kernels compile for the v5e at real widths.

Mosaic compiles for a chip that is described and not attached, so what the
chip's compiler would refuse (a slice off the tiling, too much VMEM) fails
here, at no chip time; interpret mode shows none of it.  Nothing runs: a
compile that passes says nothing about results or times.  The topology is
described inside a fixture, never at import: only the worker that runs this
file may load the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,h,s,d,seg", [
    (8, 16, 2048, 128, False),  # gpt13_s2048_plain_scan2's attention call
    (8, 16, 2048, 128, True),   # gpt13_s2048_docmask's
    (1, 2, 2048, 256, True),    # (512, 1024) blocks: two crossing offsets
    (1, 2, 512, 64, False),     # one block, all of it diagonal
])
def test_flash_kernels_compile_for_v5e(one_chip, b, h, s, d, seg):
    from cxxnet_tpu.ops import pallas_kernels as pk
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def fwd_bwd(q, k, v, g, ids):
        attn = ((lambda q, k, v: pk.flash_attention_segmented(
            q, k, v, ids, interpret=False)) if seg else
            (lambda q, k, v: pk.flash_attention(q, k, v, True, None, False)))
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(g)

    text = jax.jit(fwd_bwd).lower(x, x, x, x, ids).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
