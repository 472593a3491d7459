"""Kernel backend dispatch layer (kernels.dispatch) — parity between the
``"pallas"`` (interpret mode on CPU) and ``"xla"`` backbone paths, the
fused-QKV bit-compatibility guarantee, and the packed-position cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vitdet_l import SIM
from repro.core import partition as pt
from repro.core import vit_backbone as vb
from repro.kernels import dispatch
from repro.models import attention as attn
from repro.models import registry
from repro.models.config import ModelConfig

TOL = dict(rtol=5e-5, atol=5e-5)


@pytest.fixture(scope="module")
def setup():
    params = registry.init_params(SIM, jax.random.PRNGKey(0))
    img = jax.random.uniform(jax.random.PRNGKey(1), (1, *SIM.vit.img_size, 3))
    part = vb.vit_partition(SIM)
    return params, img, part


def _ids(part, n_low):
    mask = np.zeros(part.n_regions, np.int32)
    mask[:n_low] = 1
    fi, li = pt.mask_to_region_ids(mask, n_low)
    return jnp.asarray(fi), jnp.asarray(li)


# ---------------------------------------------------------------------------
# backend resolution


def _no_env(monkeypatch):
    """Neutralise any ambient REPRO_BACKEND (the CI parity lane runs
    this file WITH it set) for the duration of a resolution test."""
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    dispatch.refresh_from_env()


def _restore_env(monkeypatch):
    monkeypatch.undo()
    dispatch.refresh_from_env()
    dispatch.set_backend(None)


def test_resolve_backends(monkeypatch):
    try:
        _no_env(monkeypatch)
        assert dispatch.resolve("xla") == "xla"
        assert dispatch.resolve("pallas") == "pallas"
        # auto never picks interpret-mode pallas for the hot path off-TPU
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert dispatch.resolve("auto") == expect
        # bare default == "auto" (the kernels are grad-capable via custom
        # VJPs now, so None no longer has to force XLA for grad safety)
        assert dispatch.resolve(None) == expect
        with pytest.raises(ValueError):
            dispatch.resolve("cuda")
    finally:
        _restore_env(monkeypatch)


def test_resolve_env_override(monkeypatch):
    """REPRO_BACKEND is read ONCE per process (refresh_from_env for
    tests) and outranks every per-call and process-default choice."""
    try:
        monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
        dispatch.refresh_from_env()
        assert dispatch.resolve("xla") == "pallas"
        dispatch.set_backend("xla")
        assert dispatch.resolve(None) == "pallas"   # env > set_backend
        monkeypatch.setenv(dispatch.ENV_VAR, "xla")
        assert dispatch.resolve("pallas") == "pallas"  # stale until refresh
        dispatch.refresh_from_env()
        assert dispatch.resolve("pallas") == "xla"
    finally:
        _restore_env(monkeypatch)


def test_set_backend_process_default(monkeypatch):
    """set_backend() replaces the "auto" fallback for backend=None calls
    only; explicit per-call choices still win."""
    try:
        _no_env(monkeypatch)
        dispatch.set_backend("pallas")
        assert dispatch.resolve(None) == "pallas"
        assert dispatch.resolve("xla") == "xla"
        dispatch.set_backend(None)
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert dispatch.resolve(None) == expect
        with pytest.raises(ValueError):
            dispatch.set_backend("cuda")
    finally:
        _restore_env(monkeypatch)


def test_backend_scope_pins_trace(monkeypatch):
    """backend_scope() pins None-backend dispatch sites for its dynamic
    extent — the ServeEngine wraps jit traces with it."""
    try:
        _no_env(monkeypatch)
        with dispatch.backend_scope("pallas"):
            assert dispatch.resolve(None) == "pallas"
            with dispatch.backend_scope("xla"):
                assert dispatch.resolve(None) == "xla"
            assert dispatch.resolve(None) == "pallas"
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert dispatch.resolve(None) == expect
        # a None scope is a no-op: the current default stays in force
        with dispatch.backend_scope(None):
            assert dispatch.resolve(None) == expect
    finally:
        _restore_env(monkeypatch)


# ---------------------------------------------------------------------------
# forward_features parity: pallas (interpret) vs xla, full-res and mixed


def test_forward_features_full_res_parity(setup):
    params, img, _ = setup
    ref = vb.forward_features(SIM, params, img, backend="xla")
    out = vb.forward_features(SIM, params, img, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("n_low", [8, 16])
@pytest.mark.parametrize("beta", [0, 1, SIM.vit.n_subsets])
def test_forward_features_mixed_parity(setup, beta, n_low):
    params, img, part = setup
    fi, li = _ids(part, n_low)
    ref = vb.forward_features(SIM, params, img, fi, li, beta, backend="xla")
    out = vb.forward_features(SIM, params, img, fi, li, beta,
                              backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_forward_features_jit_parity(setup):
    """The dispatch choice survives jit (the ServerModel path)."""
    params, img, part = setup
    fi, li = _ids(part, 8)

    def f(backend):
        fn = jax.jit(lambda p, i, a, b: vb.forward_features(
            SIM, p, i, a, b, 2, backend=backend))
        return fn(params, img, fi, li)

    np.testing.assert_allclose(np.asarray(f("pallas")),
                               np.asarray(f("xla")), **TOL)


# ---------------------------------------------------------------------------
# fused QKV: one concatenated GEMM must be BIT-compatible with the old
# three-GEMM path (each output column touches only its own weight column)


def _three_gemm_qkv(cfg, p, x, positions, rope):
    """The pre-fusion reference implementation of _project_qkv."""
    from repro.models.layers import apply_rope, rms_norm
    B, T, _ = x.shape
    q = x @ p["w_q"]
    k = x @ p["w_k"]
    v = x @ p["w_v"]
    if cfg.attention_bias:
        q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta,
                       cfg.partial_rotary_factor)
        k = apply_rope(k, positions, cfg.rope_theta,
                       cfg.partial_rotary_factor)
    return q, k, v


@pytest.mark.parametrize("cfg", [
    SIM,                                            # bias, MHA (ViT)
    ModelConfig(n_heads=8, n_kv_heads=2, head_dim=16, d_model=64),  # GQA
], ids=["vit-sim", "gqa"])
def test_fused_qkv_bit_compatible(cfg):
    p = attn.init_attention(cfg, jax.random.PRNGKey(3), jnp.float32)
    for T in (16, 1):         # prefill (fused) and decode (three-GEMM)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, T, cfg.d_model))
        positions = jnp.arange(T)[None].repeat(2, 0)
        for rope in (False, True):
            fused = attn._project_qkv(cfg, p, x, positions, rope)
            ref = _three_gemm_qkv(cfg, p, x, positions, rope)
            for a, b, name in zip(fused, ref, "qkv"):
                assert jnp.array_equal(a, b), \
                    f"{name} not bit-identical (T={T})"


# ---------------------------------------------------------------------------
# sdpa / window_sdpa routing guards


def test_sdpa_decode_routes_to_decode_kernel():
    """The one-token kv_len shape (q_len 1, no offset, non-causal) now
    routes to the Pallas GQA decode kernel; parity with XLA holds."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    k = jax.random.normal(ks[1], (2, 32, 4, 16))
    v = jax.random.normal(ks[2], (2, 32, 4, 16))
    kv_len = jnp.array([7, 32])
    ref = attn.sdpa(q, k, v, kv_len=kv_len, backend="xla")
    out = attn.sdpa(q, k, v, kv_len=kv_len, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_sdpa_unsupported_shapes_stay_on_xla(monkeypatch):
    """Multi-token queries with a (B,) kv_len over their own keys (the
    padded ViT's pre-restoration global blocks) route to the flash
    kernel's per-row lengths, in parity with XLA on every valid query
    row (the kernel zeroes the rows past a length).  Shapes no kernel
    supports — nonzero q_offset, kv_len over keys of another length —
    fall back to XLA, not mis-route."""
    calls = []
    real = dispatch.flash_attention

    def spy(*a, **kw):
        calls.append(kw.get("kv_len") is not None)
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, "flash_attention", spy)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    k = jax.random.normal(ks[1], (2, 32, 4, 16))
    v = jax.random.normal(ks[2], (2, 32, 4, 16))
    kv_len = jnp.array([7, 32])
    # multi-token self-attention + kv_len mask: the flash route
    qm = jax.random.normal(ks[0], (2, 32, 4, 16))
    out = np.asarray(attn.sdpa(qm, k, v, kv_len=kv_len, backend="pallas"))
    assert calls == [True]
    ref = np.asarray(attn.sdpa(qm, k, v, kv_len=kv_len, backend="xla"))
    assert calls == [True]
    for b, n in enumerate([7, 32]):
        np.testing.assert_allclose(out[b, :n], ref[b, :n], **TOL)
        assert not out[b, n:].any()
    # multi-token queries over keys of another length stay on XLA
    qx = qm[:, :8]
    np.testing.assert_array_equal(
        np.asarray(attn.sdpa(qx, k, v, kv_len=kv_len, backend="pallas")),
        np.asarray(attn.sdpa(qx, k, v, kv_len=kv_len, backend="xla")))
    # one-token causal decode with an explicit query offset
    q1 = jax.random.normal(ks[0], (2, 1, 4, 16))
    np.testing.assert_allclose(
        np.asarray(attn.sdpa(q1, k, v, kv_len=kv_len, causal=True,
                             q_offset=16, backend="pallas")),
        np.asarray(attn.sdpa(q1, k, v, kv_len=kv_len, causal=True,
                             q_offset=16, backend="xla")), **TOL)
    assert calls == [True]


def test_window_sdpa_backend_parity():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 4, 16))
    v = jax.random.normal(ks[2], (2, 64, 4, 16))
    np.testing.assert_allclose(
        np.asarray(attn.window_sdpa(q, k, v, 4, backend="pallas")),
        np.asarray(attn.window_sdpa(q, k, v, 4, backend="xla")), **TOL)


# ---------------------------------------------------------------------------
# packed positional-embedding cache


def test_packed_positions_cache_hit(setup):
    params, _, part = setup
    pos = params["pos_emb"]
    fi, li = _ids(part, 8)
    vb._POS_CACHE.clear()
    a = vb.packed_positions(pos, part, fi, li)
    b = vb.packed_positions(pos, part, fi, li)
    assert b is a                       # second eager call is a cache hit
    np.testing.assert_array_equal(
        np.asarray(a),
        np.asarray(__import__("repro.core.mixed_res", fromlist=["x"])
                   .pack_positions(pos, part, fi, li)))
    # different region choice with the same n_low -> different entry
    mask = np.zeros(part.n_regions, np.int32)
    mask[8:] = 1
    fi2, li2 = (jnp.asarray(x) for x in pt.mask_to_region_ids(mask, 8))
    c = vb.packed_positions(pos, part, fi2, li2)
    assert c is not a
    assert not np.array_equal(np.asarray(c), np.asarray(a))


def test_packed_positions_tracer_bypass(setup):
    params, _, part = setup
    pos = params["pos_emb"]
    fi, li = _ids(part, 8)
    vb._POS_CACHE.clear()
    out = jax.jit(lambda p, a, b: vb.packed_positions(p, part, a, b))(
        pos, fi, li)
    assert not vb._POS_CACHE            # traced call must not populate
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(vb.packed_positions(pos, part, fi, li)),
        **TOL)
