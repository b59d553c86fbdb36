"""Compile the main-path Pallas conv kernels for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: tiling-
misaligned blocks, strided vector slices, shape casts across sublane
tiles, an int16 matmul.  Each case here compiles one jitted conv core at a
real AlexNet/VGG16 geometry, batch 4, for one chip of a described v5e:2x2
(the TPU compiler is installed; no chip is attached and nothing runs) and
asserts the Mosaic kernel (``tpu_custom_call``) is in the compiled
program.  Tiles are the defaults a TPU resolves to while the committed
tuner cache holds no TPU entries.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""
import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.substrate import QWeight, conv_pads
from repro.core.tuning import default_block
from repro.kernels.conv2d import ops

N = 4

#: name -> (h, k, stride, cin, cout, padding): real layer geometries.  Odd
#: widths (27, 13) are where tiling breaks.
GEOMETRIES = {
    "vgg16_conv1_2": (224, 3, 1, 64, 64, "SAME"),
    "vgg16_conv3": (56, 3, 1, 256, 256, "SAME"),
    "vgg16_conv5": (14, 3, 1, 512, 512, "SAME"),
    "alexnet_conv1": (227, 11, 4, 3, 96, "VALID"),
    "alexnet_conv2": (27, 5, 1, 96, 256, "SAME"),
    "alexnet_conv3": (13, 3, 1, 256, 384, "SAME"),
}

CASES = (
    [("implicit", g) for g in GEOMETRIES]
    + [("implicit_pool", g) for g in ("vgg16_conv3", "alexnet_conv2")]
    + [("winograd", g) for g in ("vgg16_conv1_2", "vgg16_conv3",
                                 "vgg16_conv5", "alexnet_conv3")]
    + [("systolic", "alexnet_conv2")]
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with contextlib.ExitStack() as stack:
        mp = stack.enter_context(pytest.MonkeyPatch.context())
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Index maps must trace as 32-bit, as they do in production: other
        # test modules switch x64 on process-wide.
        stack.enter_context(jax.enable_x64(False))
        # A compile for a described chip is written to a persistent cache
        # but cannot be read back without one: keep the cache out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        stack.callback(jax.config.update, "jax_enable_compilation_cache", was)
        stack.callback(compilation_cache.reset_cache)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])


def _core(kind, h, k, stride, cin, cout, padding):
    variant, bits = "karatsuba", 7
    if kind == "winograd":
        block = default_block("winograd", kh=k, kw=k, stride=stride, h=h,
                              cin=cin, cout=cout, variant=variant,
                              base_bits=bits)
        return lambda x, w: ops._conv2d_winograd_core(
            x, w, padding=padding, variant=variant, base_bits=bits,
            block=block, use_pallas=True, interpret=False)
    if kind == "systolic":
        bh, bc = default_block("systolic", kh=k, kw=k, stride=stride, h=h,
                               cin=cin, cout=cout, variant=variant,
                               base_bits=bits)
        return lambda x, w: ops._conv2d_systolic_core(
            x, w, stride=stride, padding=padding, block_h=bh, block_c=bc,
            variant=variant, base_bits=bits, interpret=False)
    block = default_block("implicit", kh=k, kw=k, stride=stride, h=h,
                          cin=cin, cout=cout, variant=variant, base_bits=bits)
    pool = (2, 2, "VALID") if kind == "implicit_pool" else None
    return lambda x, w: ops._conv2d_implicit_core(
        x, w, stride=stride, padding=padding, variant=variant,
        base_bits=bits, block=block, fold_every=None, use_pallas=True,
        interpret=False, pool=pool, k_pipeline=True)


def _pooled_kernel_shape(h, k, stride, cin, cout, padding):
    """The pooled epilogue's kernel output: (N, rows, cols, cout)."""
    bm = default_block("implicit", kh=k, kw=k, stride=stride, h=h, cin=cin,
                       cout=cout, variant="karatsuba", base_bits=7)[0]
    ho, wo, _ = conv_pads(h, h, k, k, stride, padding)
    ho_pad, wo_pad = -(-ho // bm) * bm, -(-wo // 8) * 8
    return f"{N}x{ho_pad // 2}x{(wo_pad - 2) // 2 + 1}x{cout}xf32"


@pytest.mark.parametrize("kind,geom", CASES,
                         ids=[f"{k}-{g}" for k, g in CASES])
def test_conv_core_compiles_for_v5e(one_chip, kind, geom):
    h, k, stride, cin, cout, padding = GEOMETRIES[geom]
    x = jax.ShapeDtypeStruct((N, h, h, cin), jnp.float32, sharding=one_chip)
    w = QWeight(
        values=jax.ShapeDtypeStruct((k, k, cin, cout), jnp.int16,
                                    sharding=one_chip),
        scale=jax.ShapeDtypeStruct((cout,), jnp.float32, sharding=one_chip),
        base_bits=7)
    lowered = jax.jit(_core(kind, *GEOMETRIES[geom])).lower(x, w)
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    if kind == "implicit_pool":
        # The pool ran in the kernel's epilogue, not as a fallback pass.
        calls = [ln for ln in lowered.as_text().splitlines()
                 if "tpu_custom_call" in ln]
        want = _pooled_kernel_shape(*GEOMETRIES[geom])
        assert any(want in ln for ln in calls), (want, calls)
