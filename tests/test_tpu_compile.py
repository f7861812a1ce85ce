"""The causal flash, the RMSNorm and the Mamba-2 chunk kernels and the routed
experts' grouped products compile for the v5e at real widths.

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


# the calls the cells run inside a loop: gpt13's scan of steps, ouro26's
# loop over passes
_IN_A_LOOP = {(8, 16, 2048, 128, False), (1, 16, 4096, 128, True)}


@pytest.mark.parametrize("b,h,s,d,seg", [
    (8, 16, 2048, 128, False),  # gpt13_s2048_plain_scan2's attention call
    (8, 16, 2048, 128, True),   # gpt13_s2048_docmask's
    (1, 16, 4096, 128, True),   # ouro26_s4096_loop4_docmask's
    (1, 2, 2048, 256, True),    # (512, 1024) blocks: two crossing offsets
    (1, 2, 512, 64, False),     # one block, all of it diagonal
])
def test_flash_kernels_compile_for_v5e(one_chip, b, h, s, d, seg):
    """Each query head its own K/V head: the forward and the fused backward
    pass, two calls, inside a ``lax.scan`` where the cell runs them in a
    loop (a call there gets 16 MiB of VMEM, which none of them asks more
    than: they compile at the default scoped limit)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    assert pk.flash_bwd_pass(s, d, d) == "fused"
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def fwd_bwd(q, k, v, g, ids):
        attn = ((lambda q, k, v: pk.flash_attention_segmented(
            q, k, v, ids, interpret=False)) if seg else
            (lambda q, k, v: pk.flash_attention(q, k, v, True, None, False)))
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(g)

    def in_scan(q, k, v, g, ids):
        def body(q, _):
            out, dq, dk, dv = fwd_bwd(q, k, v, g, ids)
            return (q + dq + out).astype(q.dtype), (dk, dv)
        return jax.lax.scan(body, q, None, length=2)

    scan = (b, h, s, d, seg) in _IN_A_LOOP
    text = jax.jit(in_scan if scan else fwd_bwd).lower(
        x, x, x, x, ids).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("seg", [True, False])
def test_latent_flash_kernels_compile_for_v5e(one_chip, seg):
    """joyai_s8192_docmask_b1's latent attention: 32 heads, q and K of 192
    over V of 128, one row of 8,192, causal and segmented, the forward and
    the fused backward pass on (512, 512) blocks; the output and dV leave
    the kernels 128 wide."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    assert pk.flash_bwd_pass(8192, 192, 128) == "fused"
    b, h, s = 1, 32, 8192
    qk = jax.ShapeDtypeStruct((b, h, s, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, s, 128), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def fwd_bwd(q, k, v, g, ids):
        attn = ((lambda q, k, v: pk.flash_attention_segmented(
            q, k, v, ids, interpret=False)) if seg else
            (lambda q, k, v: pk.flash_attention(q, k, v, True, None, False)))
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(g)

    compiled = jax.jit(fwd_bwd).lower(qk, qk, v, v, ids).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert [o.shape for o in jax.eval_shape(fwd_bwd, qk, qk, v, v, ids)] \
        == [(b, h, s, 128), (b, h, s, 192), (b, h, s, 192), (b, h, s, 128)]


@pytest.mark.parametrize("h,nkv,d,window", [
    (64, 8, 128, 512),  # laguna_s8192_docmask_b1's window layers: the band
    (48, 8, 128, 0),    # its full layers: the triangle, a group of 6
    (32, 8, 64, 0),     # lfm2moe_s8192_docmask_b1, granite4h_docmask_b1
])
def test_grouped_flash_kernels_compile_for_v5e(one_chip, h, nkv, d, window):
    """The segmented kernels with K/V at their own head count, one row of
    8,192, at the heads a step ``_fa_heads`` gives (the band: 4 heads of 8
    a step, two steps; the triangles: one head a step, the group's steps on
    one K/V block): the stacked q-side blocks, the K/V index maps and the
    dkv kernel's sub-group axis fit the default scoped VMEM, and dK, dV
    leave the dkv kernel at ``nkv`` heads."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    s = 8192
    plan = pk._fa_plan(s, d, "fwd", window, h // nkv)
    assert (plan.g, plan.subs) == ((4, 2) if window else (1, h // nkv))
    q = jax.ShapeDtypeStruct((1, h, s, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, nkv, s, d), jnp.bfloat16,
                              sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def fwd_bwd(q, k, v, g, ids):
        out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention_segmented(
            q, k, v, ids, interpret=False, window=window), q, k, v)
        return (out,) + vjp(g)

    text = jax.jit(fwd_bwd).lower(q, kv, kv, q, ids).compile().as_text()
    calls = [ln for ln in text.split("\n")
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 3
    assert sum(f"(bf16[{nkv},{s},{d}]" in ln.split(" custom-call(")[0]
               for ln in calls) == 1


@pytest.mark.parametrize("rows,d,x_dtype,gain", [
    # ouro26_s4096_loop4_docmask's norm call
    (4096, 2048, jnp.bfloat16, jnp.float32),
    (4096, 2048, jnp.bfloat16, jnp.bfloat16),
    # float32 rows, the trainer's default dtype: twice the pipelined bytes
    (4096, 2048, jnp.float32, jnp.float32),
    (512, 4096, jnp.float32, jnp.float32),
    (16384, 1024, jnp.float32, jnp.float32),
    (16384, 128, jnp.bfloat16, jnp.float32),    # 512-row blocks
    (520, 2048, jnp.bfloat16, jnp.float32),     # 8-row blocks
])
def test_rmsnorm_kernels_compile_for_v5e(one_chip, rows, d, x_dtype, gain):
    """The row blocks ``_ln_rows`` chooses fit the default scoped VMEM under
    the RMSNorm kernels too, for rows of either width."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    assert pk.rmsnorm_pallas_supported(rows, d)
    x = jax.ShapeDtypeStruct((rows, d), x_dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((d,), gain, sharding=one_chip)

    def fwd_bwd(x, g, dy):
        y, vjp = jax.vjp(lambda x, g: pk.rmsnorm_pallas(x, g, 1e-6, False),
                         x, g)
        return (y,) + vjp(dy)

    text = jax.jit(fwd_bwd).lower(x, g, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("chunk,h,p,n,g", [
    (256, 64, 64, 128, 1),   # granite4h_docmask_b1's mamba2 layers
    (256, 16, 128, 128, 2),  # heads of a whole unit, two groups
])
def test_ssd_kernel_pair_compiles_for_v5e(one_chip, chunk, h, p, n, g):
    """A chunk trip's kernel pair (``ops/pallas_ssd``) at the head block
    ``layers/ssm.ssd_head_block`` chooses, under its raised VMEM limit."""
    from cxxnet_tpu.layers import ssm
    from cxxnet_tpu.ops import pallas_ssd
    hb = ssm.ssd_head_block(chunk, h, p, n, g)
    assert hb
    bf, f32 = jnp.bfloat16, jnp.float32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    inner = h * p
    args = (sds((1, chunk, inner + 2 * g * n), bf), sds((1, chunk, inner), bf),
            sds((1, chunk, h), bf), sds((1, h, p, n), f32), sds((h,), f32),
            sds((h,), f32), sds((h,), f32), sds((inner,), f32))
    seg, before = sds((1, chunk), jnp.int32), sds((1,), jnp.int32)

    def fwd_bwd(seg, before, act, z, dt, *rest):
        out, vjp = jax.vjp(
            lambda act, z, dt, *rest: pallas_ssd.ssd_chunk(
                act, z, dt, seg, before, *rest, g, hb, 1e-5, False),
            act, z, dt, *rest)
        return out, vjp(out)

    text = jax.jit(fwd_bwd).lower(seg, before, *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_routed_experts_compile_for_v5e(one_chip):
    """``lfm2moe_s8192_docmask_b1``'s expert layer, forward and backward: one
    row of 8,192 tokens, 4 of 32 experts a token, 8 held.  XLA lowers each
    ragged dot to one Mosaic call: two products forward, their two input
    gradients and two weight gradients, none a second time (the backward
    pass keeps the first product's output and gates the rows again), each
    ONCE in the program: the windows of 12,288 rows are the trips of one
    ``while`` a pass, and nothing chooses between copies of a pass."""
    import re

    from cxxnet_tpu.layers import moe
    t, d, f, experts, held, k = 8192, 2048, 1792, 32, 8, 4
    m = moe.window_rows(t, k, held, experts)
    assert m == 12288
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, router, bias, w13, w2):
        sel, weights, _ = moe.route(x, router, bias, top_k=k)
        return moe.expert_ffn(x, sel, weights, w13.reshape(held, d, 2 * f),
                              w2.reshape(held, f, d), first=0, held=held,
                              experts=experts)[0]

    def fwd_bwd(x, router, bias, w13, w2, g):
        out, vjp = jax.vjp(lambda *a: layer(a[0], a[1], bias, a[2], a[3]),
                           x, router, w13, w2)
        return (out,) + vjp(g)

    text = jax.jit(fwd_bwd).lower(
        sds((t, d)), sds((experts, d)), sds((experts,), jnp.float32),
        sds((held * d, 2 * f)), sds((held * f, d)), sds((t, d))
    ).compile().as_text()
    products = [ln for ln in text.split("\n")
                if ln.lstrip().startswith("%ragged-dot-none")]
    assert len(products) == 6
    assert all('custom_call_target="tpu_custom_call"' in ln
               for ln in products)
    # the parent's count of Mosaic calls: the products and their metadata
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    # a window's rows are m, whatever share of the pairs is held
    written = [ln.split(" custom-call(")[0] for ln in products]
    assert sum(f"bf16[{m},{2 * f}]" in w for w in written) == 1
    assert sum(f"bf16[{m},{d}]" in w for w in written) == 2
    assert sum(f"bf16[{m},{f}]" in w for w in written) == 1
    assert sum(f"bf16[{held}," in w for w in written) == 2
    assert len(re.findall(r" while\(", text)) == 2
    assert not re.findall(r" conditional\(", text)
