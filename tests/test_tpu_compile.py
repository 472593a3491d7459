"""Compile the serving path's Pallas kernels at ViTDet-L widths for a
described TPU v5e — no chip attached.  The TPU compiler refuses what
interpret mode accepts (misaligned blocks, VMEM overruns), so these
catch a kernel that would not build on the chip, without a chip.

The topology is described inside a module fixture that skips where it
cannot be (never at import: the test workers must collect the same
tests).  Every entry point is called with ``interpret=False``, since
off-TPU the ops.py default would interpret."""
import os
import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fused_serving.ops import fused_pack_pos, fused_restore
from repro.kernels.int8_matmul.ops import int8_matmul
from repro.kernels.mixed_res_pool.ops import avg_pool_2d, nn_upsample_2d
from repro.kernels.window_attention.ops import window_attention

# ViTDet-L (configs/vitdet_l.py): 16 heads of 64, d_model 1024, a 64x64
# patch grid in 8x8-token windows, 16 regions of 2x2 windows each.
H, DH, D, W2, NR, DD = 16, 64, 1024, 64, 16, 4
T = 64 * 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from JAX's
    # persistent cache, so keep it out of the way for this module
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _window(s):
    q = s((1, T, H, DH), jnp.float32)
    return (lambda q, k, v, nw: window_attention(
        q, k, v, W2, win_valid=nw, interpret=False),
        q, q, q, s((1,), jnp.int32))


def _flash(s, b=1):
    q = s((b, T, H, DH), jnp.float32)
    return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
            q, q, q)


def _flash_b4(s):
    # the grid's largest B bucket: the default blocks fit VMEM there too
    return _flash(s, 4)


def _flash_masked(s, b, t):
    # the padded pre-restoration global block, per-row lengths on
    q = s((b, t, H, DH), jnp.float32)
    return (lambda q, k, v, n: flash_attention(q, k, v, kv_len=n,
                                               interpret=False),
            q, q, q, s((b,), jnp.int32))


def _flash_masked_1536(s):
    return _flash_masked(s, 1, 24 * W2)


def _flash_masked_3072_b4(s):
    return _flash_masked(s, 4, 48 * W2)


def _pool(s):
    return (lambda x: avg_pool_2d(x, 2, interpret=False),
            s((8, 1024, 1024, 3), jnp.float32))


def _pool_patches(s):
    return (lambda x: avg_pool_2d(x, 2, interpret=False),
            s((2, 64, 64, D), jnp.float32))


def _upsample(s):
    return (lambda x: nn_upsample_2d(x, 2, interpret=False),
            s((2 * NR, 8, 8, D), jnp.float32))


def _pack(s):
    bank = NR * DD + NR
    return (lambda b, p, src, nw: fused_pack_pos(b, p, src, nw,
                                                 interpret=False),
            s((2, bank, W2, D), jnp.float32), s((bank, W2, D), jnp.float32),
            s((2, 48), jnp.int32), s((2,), jnp.int32))


def _restore(s):
    return (lambda win, src, omap, tiles: fused_restore(
        win, src, omap, 8, 2, reuse_tiles=tiles, interpret=False),
        s((2, 48, W2, D), jnp.float32), s((2, NR * DD), jnp.int32),
        s((2, NR * DD), jnp.int32), s((2, NR, DD, W2, D), jnp.float32))


def _int8(s):
    return (lambda xq, wq, sx, sw: int8_matmul(xq, wq, sx, sw,
                                               interpret=False),
            s((4096, D), jnp.int8), s((D, D), jnp.int8),
            s((4096,), jnp.float32), s((D,), jnp.float32))


CASES = {
    "window_flagged_bw64": _window,
    "flash_4096": _flash,
    "flash_4096_b4": _flash_b4,
    "flash_masked_1536": _flash_masked_1536,
    "flash_masked_3072_b4": _flash_masked_3072_b4,
    "avg_pool_rgb_8x1024": _pool,
    "avg_pool_patches_d1024": _pool_patches,
    "nn_upsample_d1024": _upsample,
    "fused_pack_pos_d1024": _pack,
    "fused_restore_d1024": _restore,
    "int8_gemm_4096x1024x1024": _int8,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *args = CASES[case](struct)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_named_scopes_keep_kernel_instruction_names(one_chip):
    """The device scopes (repro.spans.SCOPES) are HLO metadata only: the
    attention kernels keep the instruction names the benchmark's roofline
    readers match (``_flash_attention*``, ``_window_attention*``)."""
    q = jax.ShapeDtypeStruct((1, T, H, DH), jnp.float32, sharding=one_chip)

    def step(q, scoped):
        def scope(name):
            return jax.named_scope(name) if scoped else nullcontext()
        with scope("vit.pre_beta"):
            a = window_attention(q, q, q, W2, interpret=False)
        with scope("vit.post_beta"):
            return flash_attention(a, a, a, interpret=False)

    names = {}
    for scoped in (True, False):
        text = jax.jit(lambda q: step(q, scoped)).lower(q).compile().as_text()
        names[scoped] = sorted(re.findall(r"%(_(?:flash|window)_attention"
                                          r"[.\w]*) = ", text))
        assert ("vit.post_beta" in text) is scoped
    assert names[True] == names[False]
    assert [n.split(".")[0] for n in names[True]] == ["_flash_attention",
                                                     "_window_attention"]
