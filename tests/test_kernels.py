"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (the TPU lowering path is identical)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("B,H,K,S,T,E,causal,window", [
    (2, 8, 4, 256, 256, 32, True, 0),
    (1, 4, 4, 256, 256, 64, True, 64),
    (1, 6, 2, 128, 384, 32, True, 0),
    (1, 4, 4, 128, 128, 32, False, 0),
    (2, 4, 1, 128, 256, 16, True, 0),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, K, S, T, E, causal, window, dtype):
    ks = jax.random.split(jax.random.key(S + T + E + H), 3)
    q = jax.random.normal(ks[0], (B, H, S, E), dtype)
    k = jax.random.normal(ks[1], (B, K, T, E), dtype)
    v = jax.random.normal(ks[2], (B, K, T, E), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,K,T,E,L", [
    (2, 8, 4, 512, 64, 300),
    (1, 16, 2, 1024, 32, 1024),
    (3, 4, 4, 256, 128, 1),
    (1, 8, 8, 256, 64, 255),               # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, K, T, E, L, dtype):
    ks = jax.random.split(jax.random.key(T + E + L), 3)
    q = jax.random.normal(ks[0], (B, H, E), dtype)
    k = jax.random.normal(ks[1], (B, T, K, E), dtype)
    v = jax.random.normal(ks[2], (B, T, K, E), dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(L), block_k=128)
    r = ref.decode_attention_ref(q, k, v, L)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,NC,c,P,N", [
    (2, 4, 4, 32, 16, 16),
    (1, 2, 8, 64, 32, 32),
    (1, 1, 16, 128, 64, 64),               # production tile shape
])
def test_ssm_chunk_scan(B, H, NC, c, P, N):
    ks = jax.random.split(jax.random.key(c + P + NC), 4)
    xb = jax.random.normal(ks[0], (B, H, NC, c, P))
    Bc = jax.random.normal(ks[1], (B, NC, c, N))
    Cc = jax.random.normal(ks[2], (B, NC, c, N))
    cum = -jnp.cumsum(
        jax.nn.softplus(jax.random.normal(ks[3], (B, H, NC, c))), -1) * 0.1
    y, st = ops.ssm_chunk_scan(xb, Bc, Cc, cum)
    yr, sr = ref.ssm_chunk_scan_ref(xb, Bc, Cc, cum)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st, sr, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,D,V", [(64, 128, 512), (32, 64, 256),
                                   (256, 256, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_early_exit_head(T, D, V, dtype):
    ks = jax.random.split(jax.random.key(T + D + V), 3)
    h = jax.random.normal(ks[0], (T, D), dtype)
    nw = (jnp.abs(jax.random.normal(ks[1], (D,))) + 0.5).astype(dtype)
    W = jax.random.normal(ks[2], (D, V), dtype)
    tok, conf = ops.early_exit_head(h, nw, W, block_t=32, block_v=128)
    tr, cr = ref.early_exit_head_ref(h, nw, W)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tr))
        np.testing.assert_allclose(conf, cr, atol=1e-5, rtol=1e-5)
    else:
        # bf16: ties may flip the argmax; confidences must still agree
        agree = np.mean(np.asarray(tok) == np.asarray(tr))
        assert agree > 0.95
        np.testing.assert_allclose(np.asarray(conf, np.float32),
                                   np.asarray(cr, np.float32),
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("E,C,D,F", [
    (4, 64, 128, 256), (8, 128, 512, 256), (2, 32, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(E, C, D, F, dtype):
    ks = jax.random.split(jax.random.key(E + C + D), 2)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    out = ops.moe_gmm(x, w, block_c=32, block_f=64, block_d=64)
    r = ref.moe_gmm_ref(x, w)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# explicit interpret=True: every kernel module must honour the flag directly
# (the auto-select path above infers it from the platform; CI pins it so a
# TPU-hosted run still exercises the interpreter-validated semantics)
# ---------------------------------------------------------------------------

def test_flash_attention_interpret_explicit():
    from repro.kernels import flash_attention as _fa
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    out = _fa.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    r = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out, r, atol=2e-5, rtol=2e-5)


def test_decode_attention_interpret_explicit():
    from repro.kernels import decode_attention as _dec
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (1, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = _dec.decode_attention(q, k, v, jnp.int32(100), block_k=128,
                                interpret=True)
    r = ref.decode_attention_ref(q, k, v, 100)
    np.testing.assert_allclose(out, r, atol=2e-5, rtol=2e-5)


def test_ssm_chunk_scan_interpret_explicit():
    from repro.kernels import ssm_scan as _ssm
    ks = jax.random.split(jax.random.key(9), 4)
    xb = jax.random.normal(ks[0], (1, 2, 4, 32, 16))
    Bc = jax.random.normal(ks[1], (1, 4, 32, 16))
    Cc = jax.random.normal(ks[2], (1, 4, 32, 16))
    cum = -jnp.cumsum(
        jax.nn.softplus(jax.random.normal(ks[3], (1, 2, 4, 32))), -1) * 0.1
    y, st = _ssm.ssm_chunk_scan(xb, Bc, Cc, cum, interpret=True)
    yr, sr = ref.ssm_chunk_scan_ref(xb, Bc, Cc, cum)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st, sr, atol=1e-4, rtol=1e-4)


def test_early_exit_head_interpret_explicit():
    from repro.kernels import early_exit as _ee
    ks = jax.random.split(jax.random.key(10), 3)
    h = jax.random.normal(ks[0], (32, 64))
    nw = jnp.abs(jax.random.normal(ks[1], (64,))) + 0.5
    W = jax.random.normal(ks[2], (64, 256))
    tok, conf = _ee.early_exit_head(h, nw, W, block_t=32, block_v=128,
                                    interpret=True)
    tr, cr = ref.early_exit_head_ref(h, nw, W)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tr))
    np.testing.assert_allclose(conf, cr, atol=1e-5, rtol=1e-5)


def test_moe_gmm_interpret_explicit():
    from repro.kernels import moe_gmm as _gmm
    ks = jax.random.split(jax.random.key(11), 2)
    x = jax.random.normal(ks[0], (2, 32, 64))
    w = jax.random.normal(ks[1], (2, 64, 64))
    out = _gmm.moe_gmm(x, w, block_c=32, block_f=64, block_d=64,
                       interpret=True)
    r = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(out, r, atol=2e-4, rtol=2e-4)


def test_flash_matches_model_attention():
    """The kernel agrees with the model's blocked-attention path."""
    from repro.models.flash import flash_attention as model_flash
    ks = jax.random.split(jax.random.key(0), 3)
    B, S, H, K, E = 2, 256, 8, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, E))
    k = jax.random.normal(ks[1], (B, S, K, E))
    v = jax.random.normal(ks[2], (B, S, K, E))
    m = model_flash(q, k, v, True, 0, 0, 64, 64)
    p = ops.flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), block_q=64, block_k=64)
    np.testing.assert_allclose(m, p.transpose(0, 2, 1, 3),
                               atol=2e-5, rtol=2e-5)
