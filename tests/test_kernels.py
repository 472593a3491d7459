"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
ref.py pure-jnp oracle (kernels run in interpret=True mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mixed_res_pool.ops import avg_pool_2d, nn_upsample_2d
from repro.kernels.mixed_res_pool.ref import (avg_pool_2d_ref,
                                              nn_upsample_2d_ref)
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.window_attention.ops import window_attention
from repro.kernels.window_attention.ref import window_attention_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _check(out, ref, dtype):
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (2, 256, 256, 4, 2, 64),      # GQA, block-aligned
    (1, 300, 300, 8, 8, 64),      # MHA, ragged T (padding path)
    (2, 128, 384, 4, 1, 32),      # MQA, cross lengths
    (1, 100, 260, 6, 2, 128),     # ragged both, Dh = 128
    (1, 2048, 2048, 2, 2, 64),    # non-causal: 2 x 2 blocks of 1024
])
def test_flash_attention(shape, causal, dtype):
    B, T, S, H, KV, Dh = shape
    ks = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    q = _rand(ks[0], (B, T, H, Dh), dtype)
    k = _rand(ks[1], (B, S, KV, Dh), dtype)
    v = _rand(ks[2], (B, S, KV, Dh), dtype)
    out = flash_attention(q, k, v, causal=causal)
    ref = flash_attention_ref(q, k, v, causal=causal)
    _check(out, ref, dtype)


def test_flash_attention_matches_model_sdpa():
    """The kernel and the model's XLA sdpa must agree (same semantics)."""
    from repro.models.attention import sdpa
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (2, 256, 8, 64), jnp.float32)
    k = _rand(ks[1], (2, 256, 2, 64), jnp.float32)
    v = _rand(ks[2], (2, 256, 2, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(sdpa(q, k, v, causal=True)), rtol=3e-5, atol=3e-5)


def _flash_call(eqns):
    """The pallas_call equation under ``eqns`` (through jit and vjp)."""
    for e in eqns:
        if e.primitive.name == "pallas_call":
            return e
        for val in e.params.values():
            inner = getattr(val, "jaxpr", val)
            if isinstance(inner, jax.extend.core.Jaxpr):
                found = _flash_call(inner.eqns)
                if found is not None:
                    return found
    return None


@pytest.mark.parametrize("T,S,causal,blocks,padded", [
    (4096, 4096, False, (1024, 1024), (4096, 4096)),   # ViTDet global
    (300, 260, False, (128, 128), (384, 384)),         # ragged both
    (1000, 260, False, (512, 128), (1024, 384)),
    (100, 260, False, (104, 128), (104, 384)),
    (4096, 4096, True, (128, 128), (4096, 4096)),      # prefill keeps 128
    (20, 12, False, (24, 16), (24, 16)),               # tiny: _round8
])
def test_flash_default_blocks(T, S, causal, blocks, padded, monkeypatch):
    """Untuned, the blocks come from the shape, and pad T and S to the
    lengths 128-blocks give."""
    monkeypatch.setattr(autotune, "_ENABLED", False)
    q = jax.ShapeDtypeStruct((1, T, 2, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, causal=causal, interpret=True))(q, k)
    call = _flash_call(jaxpr.jaxpr.eqns)
    q_map, k_map = call.params["grid_mapping"].block_mappings[:2]
    got = tuple(m.block_shape[2].block_size for m in (q_map, k_map))
    assert got == blocks
    assert tuple(v.aval.shape[2] for v in call.invars[:2]) == padded
    for n, p in zip((T, S), padded):
        r8 = max(8, -(-n // 8) * 8)
        assert p == -(-n // min(128, r8)) * min(128, r8)


@pytest.mark.parametrize("T,kv_len,blocks", [
    (4096, False, (1024, 1024)),    # post-restoration global blocks
    (1536, True, (768, 768)),       # padded pre-restoration, bucket 24
    (3072, True, (1024, 1024)),     # bucket 48
])
def test_flash_scalar_prefetch_only_with_kv_len(T, kv_len, blocks,
                                                monkeypatch):
    """A call without ``kv_len`` builds the pallas_call with no
    scalar-prefetch operand; a call with one passes the lengths as the
    only one, at the padded shape's default blocks."""
    monkeypatch.setattr(autotune, "_ENABLED", False)
    q = jax.ShapeDtypeStruct((2, T, 2, 64), jnp.float32)
    n = jax.ShapeDtypeStruct((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, n: flash_attention(
        q, q, q, kv_len=n if kv_len else None, interpret=True))(q, n)
    call = _flash_call(jaxpr.jaxpr.eqns)
    gm = call.params["grid_mapping"]
    assert gm.num_index_operands == int(kv_len)
    assert len(call.invars) == 3 + int(kv_len)
    if kv_len:
        assert call.invars[0].aval.shape == (2,)
        assert call.invars[0].aval.dtype == jnp.int32
    got = tuple(m.block_shape[2].block_size
                for m in gm.block_mappings[:2])
    assert got == blocks


def _masked_case(lens, H=4, KV=2, T=512, Dh=32):
    ks = jax.random.split(jax.random.PRNGKey(sum(lens)), 3)
    q = _rand(ks[0], (len(lens), T, H, Dh), jnp.float32)
    k = _rand(ks[1], (len(lens), T, KV, Dh), jnp.float32)
    v = _rand(ks[2], (len(lens), T, KV, Dh), jnp.float32)
    return q, k, v, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("lens", [
    (1, 512),      # one key; the full length
    (200, 256),    # a partial block; an exact block boundary
    (100, 384),    # three tail blocks skipped; one
])
def test_flash_kv_len_matches_masked_sdpa(lens):
    """Per-row lengths (forced 128-blocks, 4 x 4 of them): every valid
    query row matches XLA's masked softmax, and the rows past a length
    come out zero."""
    from repro.models.attention import _sdpa_dense
    q, k, v, kv_len = _masked_case(lens)
    out = flash_attention(q, k, v, kv_len=kv_len, bq=128, bk=128)
    ref = _sdpa_dense(q, k, v, kv_len=kv_len)
    for b, n in enumerate(lens):
        _check(out[b, :n], ref[b, :n], jnp.float32)
        assert not np.asarray(out[b, n:]).any()


def test_flash_kv_len_zero_emits_zeros():
    q, k, v, kv_len = _masked_case((0, 300))
    out = np.asarray(flash_attention(q, k, v, kv_len=kv_len, bq=128,
                                     bk=128))
    assert not out[0].any() and not out[1, 300:].any()
    assert np.abs(out[1, :300]).min(axis=-1).max() > 0


def test_flash_kv_len_grad_matches_xla():
    """jax.grad through the kernel's custom VJP with per-row lengths
    equals the gradient of XLA's masked sdpa, over a loss on the valid
    query rows."""
    from repro.models.attention import sdpa
    q, k, v, kv_len = _masked_case((200, 384))
    valid = (jnp.arange(q.shape[1])[None, :]
             < kv_len[:, None])[:, :, None, None]

    def grads(f):
        def loss(q, k, v):
            o = f(q, k, v)
            return jnp.sum(jnp.where(valid, o * jnp.cos(o), 0.0))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(q, k, v, kv_len=kv_len,
                                                bq=128, bk=128))
    ref = grads(lambda q, k, v: sdpa(q, k, v, kv_len=kv_len,
                                     backend="xla"))
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# window attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 4, 64, 4, 4, 64),     # w = 8 ViTDet window, MHA
    (1, 9, 81, 8, 8, 32),     # w = 9 (the paper's fine-tuned window), pads
    (2, 3, 49, 4, 2, 64),     # GQA + ragged window count
    (1, 16, 64, 16, 16, 64),  # ViTDet-L head count
])
def test_window_attention(shape, dtype):
    B, W, win, H, KV, Dh = shape
    T = W * win
    ks = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 3)
    q = _rand(ks[0], (B, T, H, Dh), dtype)
    k = _rand(ks[1], (B, T, KV, Dh), dtype)
    v = _rand(ks[2], (B, T, KV, Dh), dtype)
    out = window_attention(q, k, v, win)
    ref = window_attention_ref(q, k, v, win)
    _check(out, ref, dtype)


@pytest.mark.parametrize("valid", [0, 3, 8])
def test_window_attention_win_valid_boundaries(valid):
    """Pad-window zeroing at the boundaries: no valid windows, a count
    that ends mid-tile (wb does not divide it), and all windows valid —
    parity vs the masked XLA oracle."""
    B, W, win, H, Dh = 2, 8, 16, 4, 32
    T = W * win
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = _rand(ks[0], (B, T, H, Dh), jnp.float32)
    k = _rand(ks[1], (B, T, H, Dh), jnp.float32)
    v = _rand(ks[2], (B, T, H, Dh), jnp.float32)
    wv = jnp.asarray([valid, max(valid - 1, 0)], jnp.int32)
    out = window_attention(q, k, v, win, win_valid=wv, wb=8)
    ref = window_attention_ref(q, k, v, win)
    keep = (jnp.arange(W)[None, :] < wv[:, None]).astype(ref.dtype)
    ref = ref * jnp.repeat(keep, win, axis=1)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # pad windows emit exact zeros
    np.testing.assert_array_equal(
        np.asarray(out.reshape(B, W, win, H, Dh)[0, valid:]), 0.0)


def test_window_attention_matches_model_window_sdpa():
    from repro.models.attention import window_sdpa
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (2, 256, 8, 64), jnp.float32)
    k = _rand(ks[1], (2, 256, 8, 64), jnp.float32)
    v = _rand(ks[2], (2, 256, 8, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(window_attention(q, k, v, 64)),
        np.asarray(window_sdpa(q, k, v, 64)), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 1024, 8, 2, 64),
    (4, 777, 32, 8, 128),     # ragged cache length
    (1, 4096, 4, 4, 64),      # MHA (G = 1 -> pads group rows)
    (2, 300, 16, 1, 32),      # MQA
])
def test_decode_attention(shape, dtype):
    B, S, H, KV, Dh = shape
    ks = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31), 4)
    q = _rand(ks[0], (B, 1, H, Dh), dtype)
    k = _rand(ks[1], (B, S, KV, Dh), dtype)
    v = _rand(ks[2], (B, S, KV, Dh), dtype)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, k, v, kv_len)
    ref = decode_attention_ref(q, k, v, kv_len)
    _check(out, ref, dtype)


@pytest.mark.parametrize("kv_len_val", [1, 511, 512])
def test_decode_attention_kv_len_edges(kv_len_val):
    """Edge lengths: single valid key, one short of a block, full cache."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, S, H, KV, Dh = 2, 512, 8, 4, 64
    q = _rand(ks[0], (B, 1, H, Dh), jnp.float32)
    k = _rand(ks[1], (B, S, KV, Dh), jnp.float32)
    v = _rand(ks[2], (B, S, KV, Dh), jnp.float32)
    kv_len = jnp.full((B,), kv_len_val, jnp.int32)
    out = decode_attention(q, k, v, kv_len)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G", [1, 2, 4])
def test_decode_attention_gqa_groups(G):
    """GQA group sizes 1/2/4 with a cache length that is NOT a multiple
    of the kv block (S = 300, bs = 128 -> ragged final block)."""
    B, S, KV, Dh = 2, 300, 4, 64
    H = KV * G
    ks = jax.random.split(jax.random.PRNGKey(31 + G), 4)
    q = _rand(ks[0], (B, 1, H, Dh), jnp.float32)
    k = _rand(ks[1], (B, S, KV, Dh), jnp.float32)
    v = _rand(ks[2], (B, S, KV, Dh), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, k, v, kv_len, bs=128)
    ref = decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_model_sdpa():
    from repro.models.attention import sdpa
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, S, H, KV, Dh = 3, 640, 16, 4, 64
    q = _rand(ks[0], (B, 1, H, Dh), jnp.float32)
    k = _rand(ks[1], (B, S, KV, Dh), jnp.float32)
    v = _rand(ks[2], (B, S, KV, Dh), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    np.testing.assert_allclose(
        np.asarray(decode_attention(q, k, v, kv_len)),
        np.asarray(sdpa(q, k, v, kv_len=kv_len)), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# SSD scan


def _ssd_inputs(key, b, T, H, G, N, P, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    x = _rand(ks[0], (b, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = (_rand(ks[3], (b, T, G, N), dtype) * 0.3).astype(dtype)
    Cm = (_rand(ks[4], (b, T, G, N), dtype) * 0.3).astype(dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("shape", [
    (2, 128, 8, 1, 32, 16, 32),
    (1, 200, 16, 2, 64, 32, 64),   # ragged T (chunk padding)
    (2, 64, 4, 4, 16, 64, 32),     # one head per group
    (1, 96, 8, 1, 128, 64, 96),    # full-size state dims, single chunk
])
def test_ssd_scan(shape):
    b, T, H, G, N, P, chunk = shape
    x, dt, A, Bm, Cm = _ssd_inputs(
        jax.random.PRNGKey(hash(shape) % 2**31), b, T, H, G, N, P)
    y, s_fin = ssd(x, dt, A, Bm, Cm, chunk, return_final_state=True)
    y_ref, s_ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_bf16_inputs():
    b, T, H, G, N, P, chunk = 2, 128, 8, 1, 32, 16, 64
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(0), b, T, H, G, N, P,
                                   jnp.bfloat16)
    y = ssd(x, dt, A, Bm, Cm, chunk)
    y_ref, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-2, atol=3e-2)


def test_ssd_state_handoff_chains():
    """Scanning two halves with state handoff == scanning the whole —
    the invariant the sequence-parallel sharding relies on."""
    b, T, H, G, N, P, chunk = 1, 128, 4, 1, 16, 16, 32
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(1), b, T, H, G, N, P)
    y_full, s_full = ssd(x, dt, A, Bm, Cm, chunk, return_final_state=True)
    h = T // 2
    y1, s1 = ssd(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], chunk,
                 return_final_state=True)
    y2, s2 = ssd(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], chunk,
                 init_state=s1, return_final_state=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)


def test_mamba2_forward_kernel_flag_matches():
    """mamba2_forward(use_kernel=True) must equal the jnp path."""
    from repro.configs import get_reduced
    from repro.models import mamba2
    cfg = get_reduced("mamba2-370m")
    params = mamba2.init_mamba2(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = _rand(jax.random.PRNGKey(1), (2, 64, cfg.d_model), jnp.float32)
    out_jnp = mamba2.mamba2_forward(cfg, params, x, use_kernel=False)
    out_krn = mamba2.mamba2_forward(cfg, params, x, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out_krn), np.asarray(out_jnp),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# mixed-res pool


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 32, 32, 64, 2),
    (1, 48, 48, 100, 4),      # non-128 channels (padding path)
    (2, 16, 24, 128, 2),      # rectangular
    (1, 8, 8, 3, 2),          # RGB pixels
])
def test_avg_pool(shape, dtype):
    B, H, W, C, d = shape
    x = _rand(jax.random.PRNGKey(hash(shape) % 2**31), (B, H, W, C), dtype)
    _check(avg_pool_2d(x, d), avg_pool_2d_ref(x, d), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 16, 16, 64, 2), (1, 12, 12, 100, 4), (1, 4, 6, 3, 2),
])
def test_nn_upsample(shape, dtype):
    B, H, W, C, d = shape
    x = _rand(jax.random.PRNGKey(hash(shape) % 2**31), (B, H, W, C), dtype)
    out = nn_upsample_2d(x, d)
    ref = nn_upsample_2d_ref(x, d)
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pool_matches_mixed_res_downsample():
    """The kernel is a drop-in for core.mixed_res.downsample_grid."""
    from repro.core.mixed_res import downsample_grid
    x = _rand(jax.random.PRNGKey(2), (2, 32, 32, 48), jnp.float32)
    np.testing.assert_allclose(np.asarray(avg_pool_2d(x, 2)),
                               np.asarray(downsample_grid(x, 2)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# custom VJPs: the Pallas entry points are differentiable, and their
# gradients match jax.grad through the pure-XLA oracles (the contract
# that lets dispatch route training graphs to the Pallas lane)

GRAD_TOL = dict(rtol=2e-4, atol=2e-4)


def _grad_check(f_pallas, f_ref, args):
    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))
    g_pal = jax.grad(loss(f_pallas), argnums=tuple(range(len(args))))(*args)
    g_ref = jax.grad(loss(f_ref), argnums=tuple(range(len(args))))(*args)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_vjp_matches_xla(causal):
    ks = jax.random.split(jax.random.PRNGKey(41), 3)
    q = _rand(ks[0], (2, 48, 8, 32), jnp.float32)
    k = _rand(ks[1], (2, 48, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 48, 2, 32), jnp.float32)
    _grad_check(lambda q, k, v: flash_attention(q, k, v, causal=causal),
                lambda q, k, v: flash_attention_ref(q, k, v, causal=causal),
                (q, k, v))


def test_window_attention_vjp_matches_xla():
    ks = jax.random.split(jax.random.PRNGKey(43), 3)
    win, W = 16, 4
    q = _rand(ks[0], (2, W * win, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, W * win, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, W * win, 2, 32), jnp.float32)
    _grad_check(lambda q, k, v: window_attention(q, k, v, win),
                lambda q, k, v: window_attention_ref(q, k, v, win),
                (q, k, v))


def test_window_attention_vjp_with_win_valid():
    """Gradients respect the pad-window mask: pad windows contribute
    zero cotangent everywhere."""
    ks = jax.random.split(jax.random.PRNGKey(47), 3)
    win, W, B = 16, 4, 2
    wv = jnp.asarray([3, 2], jnp.int32)
    q = _rand(ks[0], (B, W * win, 4, 32), jnp.float32)
    k = _rand(ks[1], (B, W * win, 4, 32), jnp.float32)
    v = _rand(ks[2], (B, W * win, 4, 32), jnp.float32)

    def ref(q, k, v):
        o = window_attention_ref(q, k, v, win)
        keep = (jnp.arange(W)[None, :] < wv[:, None]).astype(o.dtype)
        return o * jnp.repeat(keep, win, axis=1)[:, :, None, None]

    _grad_check(lambda q, k, v: window_attention(q, k, v, win,
                                                 win_valid=wv),
                ref, (q, k, v))


def test_pool_vjps_match_xla():
    x = _rand(jax.random.PRNGKey(53), (2, 16, 16, 8), jnp.float32)
    _grad_check(lambda x: avg_pool_2d(x, 2),
                lambda x: avg_pool_2d_ref(x, 2), (x,))
    _grad_check(lambda x: nn_upsample_2d(x, 2),
                lambda x: nn_upsample_2d_ref(x, 2), (x,))


def test_vjp_survives_jit():
    """jax.jit around a custom-VJP entry keeps the analytic backward
    (the launch/train.py path: grads through a jitted training step)."""
    ks = jax.random.split(jax.random.PRNGKey(59), 3)
    q = _rand(ks[0], (1, 32, 4, 16), jnp.float32)
    k = _rand(ks[1], (1, 32, 4, 16), jnp.float32)
    v = _rand(ks[2], (1, 32, 4, 16), jnp.float32)

    @jax.jit
    def step(q, k, v):
        return jax.grad(
            lambda q: jnp.sum(flash_attention(q, k, v, causal=True)))(q)

    ref = jax.grad(
        lambda q: jnp.sum(flash_attention_ref(q, k, v, causal=True)))(q)
    np.testing.assert_allclose(np.asarray(step(q, k, v)), np.asarray(ref),
                               **GRAD_TOL)
