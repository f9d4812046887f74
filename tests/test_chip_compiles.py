"""Compiles for the chip without the chip: the TPU's compiler is
installed here and compiles for a described v5e 2x2 host what the CPU's
interpret mode cannot refuse. Nothing runs, so these say nothing of
results or times. All such tests live in this one file: the process that
describes the topology keeps the TPU's library until it exits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def mosaic(monkeypatch):
    """The kernels as the chip gets them: no interpret mode, and no
    cache entry that only a chip could read back."""
    import keystone_tpu.ops.flash_attention as fa

    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fa, "interpret_default", lambda: False)
    kept = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", kept)
    compilation_cache.reset_cache()


def _laguna_expert_layer(mesh):
    """Shapes of one Laguna-XS.2 expert layer (32 of 256 experts held)
    and of 4 x 2048 tokens in bfloat16, whole on every device but for
    the batch, which is split over ``data``."""
    from keystone_tpu.ops.moe import MoELayer

    layer = jax.eval_shape(
        lambda: MoELayer.create(
            jax.random.key(0), 2048, 512, 256, held=32, top_k=8, swiglu=True,
            shared_ff=512, scoring="sigmoid", routed_scale=2.5,
        )
    )
    whole = NamedSharding(mesh, P())
    layer = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=whole), layer
    )
    x = jax.ShapeDtypeStruct(
        (4, 2048, 2048), jnp.bfloat16, sharding=NamedSharding(mesh, P("data"))
    )
    return layer, x


def _loss_and_grads(mesh):
    def loss(m, t):
        out, counters = m(t, mesh)
        return jnp.sum(jnp.square(out.astype(jnp.float32))), counters

    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def test_routed_experts_compile_for_four_chips_only_under_their_mesh(topo, mosaic):
    """The grouped product is a Mosaic kernel, which GSPMD refuses to
    partition: told its mesh, the layer shard_maps it and the backward's
    weight gradients are all-reduced over the mesh; not told, the
    compiler refuses the program (what a routed model on the four-chip
    host would have met)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    layer, x = _laguna_expert_layer(mesh)
    text = _loss_and_grads(mesh).lower(layer, x).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _loss_and_grads(None).lower(layer, x).compile()


@pytest.mark.parametrize("heads,window", [(48, 0), (64, 512)])
def test_attention_backward_kernels_compile_at_the_cells_shapes(
    topo, mosaic, monkeypatch, heads, window
):
    """``jax.grad`` of the trainable attention at Laguna-XS.2's two layer
    shapes (2 x 8192 tokens, head 128, 8 K/V heads, bfloat16): the
    backward is the ``attn_bwd`` Mosaic call, one a layer and no loop of
    XLA's around it, and with a K/V head's 8192 keys held as one segment
    it fits the scoped VMEM limit of the described chip (Mosaic refuses
    a kernel that does not)."""
    import re

    import keystone_tpu.ops.flash_attention as fa
    from jax.sharding import SingleDeviceSharding
    from keystone_tpu.plan.costs import device_peaks

    limit = device_peaks(topo.devices[0].device_kind).vmem_limit
    monkeypatch.setattr(fa, "_vmem_limit_bytes", lambda: limit)
    _block_q, block_k, seg_blocks = fa._bwd_blocks(8192, 8192, 128, 2)
    assert block_k * seg_blocks == 8192
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, heads, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 128), jnp.bfloat16, sharding=one_chip)
    assert fa._dense_bwd_bytes(q, kv) > fa._DENSE_BWD_MAX_BYTES

    def loss(q, k, v):
        with jax.named_scope("attn_window" if window else "attn_full"):
            out = fa.flash_attention_trainable(q, k, v, True, window)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    calls = re.findall(r"^\s*%(\S+) = .*custom-call\(.*tpu_custom_call", text, re.M)
    backward = [c for c in calls if "attn_bwd" in c]
    assert len(backward) == 1 and len(calls) == 2, calls
    # the forward's readers match attn_full / attn_window in an op's name
    assert not any("attn_full" in c or "attn_window" in c for c in backward)
    assert " while(" not in text


def test_the_scan_kernel_compiles_at_the_state_space_cells_shapes(topo, mosaic, monkeypatch):
    """``jax.grad`` of the scan at granite-4.0-h-micro's shapes (1 x 8192
    positions, 64 heads of 64, state 128, chunks of 256, bfloat16): the
    forward is the ``ssd_chunk`` Mosaic call and nothing else is one (the
    backward is XLA's), within the scoped VMEM limit of the described
    chip; what the backward holds at once stays under half a GB. The
    kernel reads x, dt, B and C as the mixer holds them, so the compiled
    forward has nothing of XLA's around it that transposes x or y, adds
    up the decays or holds x in float32."""
    import re

    from jax.sharding import SingleDeviceSharding
    from keystone_tpu.ops import ssm
    from keystone_tpu.plan.costs import device_peaks

    limit = device_peaks(topo.devices[0].device_kind).vmem_limit
    monkeypatch.setattr(ssm, "interpret_default", lambda: False)
    monkeypatch.setattr(ssm, "_vmem_limit_bytes", lambda: limit)
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    assert ssm._use_kernel(256, 64, 64) and not ssm._use_kernel(256, 12, 8)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x = shape((1, 8192, 64, 64), jnp.bfloat16)
    dt, a = shape((1, 8192, 64), jnp.float32), shape((64,), jnp.float32)
    bc = shape((1, 8192, 1, 128), jnp.bfloat16)

    def scan(x, dt, a, b, c):
        with jax.named_scope("ssm_scan"):
            return ssm.ssd_scan(x, dt, a, b, c, chunk=256)

    def loss(*args):
        return jnp.sum(scan(*args).astype(jnp.float32))

    def mosaic_calls(text):
        return re.findall(r"^\s*%(\S+) = .*custom-call\(.*tpu_custom_call", text, re.M)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(x, dt, a, bc, bc).compile()
    calls = mosaic_calls(compiled.as_text())
    assert len(calls) == 1 and "ssd_chunk" in calls[0], calls
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20

    def ops_of(text):
        # (result shape, opcode) of every instruction with one result
        return re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w-]+)\(", text, re.M)

    def moved(ops, shapes):
        return [
            (shp, op) for shp, op in ops
            if op in ("copy", "transpose") and re.match(shapes, shp)
        ]

    forward = jax.jit(scan).lower(x, dt, a, bc, bc).compile().as_text()
    assert len(mosaic_calls(forward)) == 1
    ops = ops_of(forward)
    assert not [op for _shape, op in ops if op == "reduce-window"]
    assert not moved(ops, r"\w+\[1,(8192,64|64,8192),64\]")
    wide = [shp for shp, _op in ops if re.match(r"f32\[(1,)?8192,(4096|64,64)\]", shp)]
    assert not wide, wide

    # and in its place: one mixer's forward moves no array of x's size at
    # all (a jitted ssd_scan's 4-D parameters get layouts of their own)
    mixer = jax.eval_shape(
        lambda: ssm.Mamba2Mixer.create(
            jax.random.key(0), 2048, heads=64, head_dim=64, state=128, groups=1
        )
    )
    mixer = jax.tree.map(lambda l: shape(l.shape, l.dtype), mixer)
    layer = jax.jit(lambda m, y: m(y)).lower(mixer, shape((1, 8192, 2048), jnp.bfloat16))
    assert not moved(ops_of(layer.compile().as_text()), r"\w+\[(1,)?(8192|64),")


def test_the_grouped_mixer_and_the_latent_experts_compile_at_their_cells_shapes(
    topo, mosaic, monkeypatch
):
    """``jax.grad`` of ``nemotron_3_super``'s two new parts at the cell's
    shapes (2 x 8192 positions of width 4096, bfloat16): a Mamba-2 mixer
    of 32 heads of 64 in 2 groups, state 128, chunks of 128, whose scan
    runs in ``ssd_chunk`` (16 heads a group: a head block of 1024 lanes);
    and an expert layer of 8 of 512 relu² experts of 2688 in a latent of
    1024, 22 a token, on the window path: two grouped products a pass
    (two ``gmm`` forward, four backward, two ``tgmm``), no third."""
    import re

    from jax.sharding import SingleDeviceSharding
    from keystone_tpu.ops import moe, ssm
    from keystone_tpu.plan.costs import device_peaks

    limit = device_peaks(topo.devices[0].device_kind).vmem_limit
    monkeypatch.setattr(ssm, "interpret_default", lambda: False)
    monkeypatch.setattr(ssm, "_vmem_limit_bytes", lambda: limit)
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    assert ssm._use_kernel(128, 16, 64)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), tree)

    y = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    mixer = placed(jax.eval_shape(lambda: ssm.Mamba2Mixer.create(
        jax.random.key(0), 4096, heads=32, head_dim=64, state=128, groups=2, chunk=128)))
    experts = placed(jax.eval_shape(lambda: moe.MoELayer.create(
        jax.random.key(0), 4096, 2688, 512, held=8, top_k=22, shared_ff=1344,
        scoring="sigmoid", routed_scale=5.0, latent=1024, activation="relu2")))
    assert moe.window_rows(16384 * 22, 8, 512) == 8192

    def loss(m, y):
        out, _counters = m(y)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    def mosaic_calls(text):
        return re.findall(r"^\s*%(\S+) = .*custom-call\(.*tpu_custom_call", text, re.M)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    calls = mosaic_calls(grad.lower(mixer, y).compile().as_text())
    assert len(calls) == 1 and "ssd_chunk" in calls[0], calls
    compiled = grad.lower(experts, y).compile()
    calls = [re.sub(r"\.\d+$", "", c) for c in mosaic_calls(compiled.as_text())]
    assert sorted(calls) == ["gmm"] * 6 + ["tgmm"] * 2, calls
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
