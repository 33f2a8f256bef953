"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp
oracle (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(0, scale, shape), dtype)


# ------------------------------------------------------------------ #
# flash attention
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("B,H,L,hd", [
    (1, 1, 128, 64), (2, 4, 256, 64), (1, 2, 512, 32), (2, 1, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, L, hd, causal, window, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_reference
    q, k, v = (_arr((B, H, L, hd), dtype) for _ in range(3))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=64, bk=64, interpret=True)
    ref = attention_reference(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_gqa_wrapper():
    from repro.kernels.flash_attention.ops import gqa_flash
    from repro.models.layers import gqa_attention
    B, L, Hq, Hkv, hd = 2, 128, 8, 2, 64
    q = _arr((B, L, Hq, hd))
    k = _arr((B, L, Hkv, hd))
    v = _arr((B, L, Hkv, hd))
    out = gqa_flash(q, k, v, causal=True, use_pallas=True, bq=64, bk=64)
    ref = gqa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,Hq,Hkv,L,hd", [
    (2, 8, 2, 256, 64), (1, 4, 1, 128, 32), (2, 6, 3, 128, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 48),
                                           (False, 0)])
def test_flash_attention_gqa_native_kernel(B, Hq, Hkv, L, hd, causal,
                                           window):
    """GQA-native kernel (KV tiles staged once per group) vs expanded
    reference."""
    from repro.kernels.flash_attention.kernel import (
        flash_attention_gqa_pallas)
    from repro.kernels.flash_attention.ref import attention_reference
    q = _arr((B, Hq, L, hd))
    k = _arr((B, Hkv, L, hd))
    v = _arr((B, Hkv, L, hd))
    out = flash_attention_gqa_pallas(q, k, v, causal=causal, window=window,
                                     bq=64, bk=64, interpret=True)
    rep = Hq // Hkv
    ref = attention_reference(q, jnp.repeat(k, rep, 1),
                              jnp.repeat(v, rep, 1), causal=causal,
                              window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ #
# decode attention
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (2, 8, 2, 256, 64), (1, 4, 4, 128, 32), (2, 4, 1, 512, 64),
])
@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Hq, Hkv, S, hd, window, dtype):
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    from repro.kernels.decode_attention.ref import (
        decode_attention_reference)
    step = S - S // 3
    q = _arr((B, Hq, hd), dtype)
    k = _arr((B, Hkv, S, hd), dtype)
    v = _arr((B, Hkv, S, hd), dtype)
    pos = np.full((B, S), -1, np.int32)
    for b in range(B):
        n = min(step + 1, S)
        ps = np.arange(step + 1 - n, step + 1)
        pos[b, ps % S] = ps
    pos = jnp.asarray(pos)
    qp = jnp.full((B,), step, jnp.int32)
    out = decode_attention_pallas(q, k, v, pos, qp, window=window, bk=64,
                                  interpret=True)
    ref = decode_attention_reference(q, k, v, pos, qp, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("T,B,Hq,Hkv,S,hd", [
    (4, 2, 8, 2, 256, 64), (5, 1, 4, 4, 128, 32),
])
@pytest.mark.parametrize("window", [0, 64])
def test_decode_attention_multi_query(T, B, Hq, Hkv, S, hd, window):
    """Multi-query rows (speculative verify / chunked-prefill extend):
    T query tokens per row, each masked at its own absolute position,
    against the same per-slot cache region."""
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    from repro.kernels.decode_attention.ref import (
        decode_attention_reference)
    step = S - S // 3
    q = _arr((B, T, Hq, hd))
    k = _arr((B, Hkv, S, hd))
    v = _arr((B, Hkv, S, hd))
    pos = np.full((B, S), -1, np.int32)
    for b in range(B):
        n = min(step + T, S)
        ps = np.arange(step + T - n, step + T)
        pos[b, ps % S] = ps
    pos = jnp.asarray(pos)
    qp = jnp.broadcast_to(step + jnp.arange(T, dtype=jnp.int32), (B, T))
    out = decode_attention_pallas(q, k, v, pos, qp, window=window, bk=64,
                                  interpret=True)
    ref = decode_attention_reference(q, k, v, pos, qp, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_cached_decode_attention_multi_query_matches_gqa():
    """The ops wrapper's (B, T) form == the model's gqa_attention with
    per-row query positions (what extend_into_cache routes through when
    cfg.use_decode_kernel is set)."""
    from repro.kernels.decode_attention.ops import cached_decode_attention
    from repro.models.layers import gqa_attention
    B, T, S, Hq, Hkv, hd = 2, 3, 64, 4, 2, 32
    base = S - 8
    q = _arr((B, T, Hq, hd))
    k_cache = _arr((B, S, Hkv, hd))
    v_cache = _arr((B, S, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q_pos = base + jnp.arange(T, dtype=jnp.int32)[None] \
        + jnp.zeros((B, 1), jnp.int32)
    out = cached_decode_attention(q, k_cache, v_cache, pos, q_pos,
                                  use_pallas=True, bk=32)
    ref = gqa_attention(q, k_cache, v_cache, q_positions=q_pos,
                        k_positions=pos, causal=True, k_valid=pos >= 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # (B,) base-position form == explicit per-query positions
    out2 = cached_decode_attention(q, k_cache, v_cache, pos,
                                   jnp.full((B,), base, jnp.int32),
                                   use_pallas=True, bk=32)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=1e-6, atol=1e-6)


def test_decode_attention_matches_model_path():
    """Kernel == the model's gqa_attention on a populated cache."""
    from repro.kernels.decode_attention.ops import cached_decode_attention
    from repro.models.layers import gqa_attention
    B, S, Hq, Hkv, hd = 2, 64, 4, 2, 32
    q = _arr((B, 1, Hq, hd))
    k_cache = _arr((B, S, Hkv, hd))
    v_cache = _arr((B, S, Hkv, hd))
    step = jnp.full((B,), S - 1, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out = cached_decode_attention(q, k_cache, v_cache, pos, step,
                                  use_pallas=True, bk=32)
    ref = gqa_attention(q, k_cache, v_cache,
                        q_positions=step[:, None], k_positions=pos,
                        causal=True, k_valid=pos >= 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [0, 16])
def test_paged_decode_attention_kernel(window):
    """Paged kernel: K/V pages fetched through the block table (shuffled
    page order, one partly filled sequence) == the gathered oracle."""
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_pallas)
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_reference)
    B, T, Hq, Hkv, hd, ps, NB = 2, 3, 8, 2, 32, 16, 4
    S = NB * ps
    q = _arr((B, T, Hq, hd))
    k_pool = _arr((B * NB + 1, ps, Hkv, hd))
    v_pool = _arr((B * NB + 1, ps, Hkv, hd))
    bt = jnp.asarray(rng.permutation(B * NB).reshape(B, NB), jnp.int32)
    pos = np.full((B, S), -1, np.int32)
    pos[0, :40] = np.arange(40)
    pos[1] = np.arange(S)
    q_pos = jnp.asarray([[37, 38, 39], [S - 3, S - 2, S - 1]], jnp.int32)
    out = paged_decode_attention_pallas(q, k_pool, v_pool, bt,
                                        jnp.asarray(pos), q_pos,
                                        window=window, interpret=True)
    ref = paged_decode_attention_reference(q, k_pool, v_pool, bt,
                                           jnp.asarray(pos), q_pos,
                                           window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ #
# SSD scan
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (2, 128, 4, 32, 1, 32, 32), (1, 256, 8, 64, 2, 128, 64),
    (2, 64, 2, 16, 2, 16, 16), (1, 128, 6, 32, 3, 64, 64),
])
def test_ssd_scan(b, l, h, p, g, n, chunk):
    from repro.kernels.ssd_scan.kernel import ssd_pallas
    from repro.kernels.ssd_scan.ref import ssd_reference
    x = _arr((b, l, h, p))
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (b, l, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = _arr((b, l, g, n))
    Cm = _arr((b, l, g, n))
    D = _arr((h,))
    y, s = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b,T,h,p,g,n", [
    (2, 5, 4, 16, 2, 32), (1, 256, 4, 32, 1, 64), (1, 384, 2, 64, 1, 128),
])
def test_ssd_extend_kernel(b, T, h, p, g, n):
    """Sequential extend from a carried state: one token block per grid
    step (T = 384 spans three 128-token blocks), state carried across
    blocks in VMEM scratch."""
    from repro.kernels.ssd_scan.kernel import ssd_extend_pallas
    from repro.kernels.ssd_scan.ref import ssd_extend_reference
    state = _arr((b, h, p, n))
    x = _arr((b, T, h, p))
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (b, T, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = _arr((b, T, g, n))
    Cm = _arr((b, T, g, n))
    D = _arr((h,))
    y, s = ssd_extend_pallas(state, x, dt, A, Bm, Cm, D, interpret=True)
    yr, sr = ssd_extend_reference(state, x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,blocks,g,tail", [
    (1, 1, 1, "full"), (2, 2, 2, "full"), (1, 4, 1, "full"),
    (2, 2, 1, "dt0_tail"), (1, 4, 2, "dt0_tail"),
    (2, 1, 2, "length0"), (2, 4, 1, "length0"),
])
def test_ssd_extend_chunked_kernel(b, blocks, g, tail):
    """The chunked dual form from a carried state, T = blocks x the
    dispatch's block, against the token-serial reference at the serial
    kernel's tolerances. ``dt0_tail``: row 0 stops 37 tokens into its
    last block (the engine's mask, dt = 0 past a row's length);
    ``length0``: the last row has no valid token and must keep its state
    bit for bit."""
    from repro.kernels.ssd_scan.kernel import ssd_extend_chunked_pallas
    from repro.kernels.ssd_scan.ops import EXTEND_CHUNK as Q
    from repro.kernels.ssd_scan.ref import ssd_extend_reference
    T, h, p, n = blocks * Q, 4, 32, 64
    state = _arr((b, h, p, n))
    x = _arr((b, T, h, p))
    dt = rng.uniform(1e-3, 0.1, (b, T, h))
    if tail == "dt0_tail":
        dt[0, T - Q + 37:] = 0.0
    if tail == "length0":
        dt[-1] = 0.0
    dt = jnp.asarray(dt, jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = _arr((b, T, g, n))
    Cm = _arr((b, T, g, n))
    D = _arr((h,))
    y, s = ssd_extend_chunked_pallas(state, x, dt, A, Bm, Cm, D, chunk=Q,
                                     interpret=True)
    yr, sr = ssd_extend_reference(state, x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)
    if tail == "length0":
        np.testing.assert_array_equal(np.asarray(s[-1]),
                                      np.asarray(state[-1]))


@pytest.mark.parametrize("T,form", [
    (1, "ssd_extend"),             # decode rows of a mixed step
    (5, "ssd_extend"),             # a verify window, gamma 4
    (None, "ssd_extend"),          # a block and a bit: not a multiple
    (512, "ssd_extend_chunked"),   # the admission chunk
])
def test_ssd_extend_dispatch_by_length(T, form):
    """``ops.ssd_extend`` picks its Pallas form from T alone, as the
    names of the Pallas calls in its traced program show."""
    from repro.kernels.ssd_scan import ops as ssd_ops
    T = T or ssd_ops.EXTEND_CHUNK + 8
    b, h, p, g, n = 2, 2, 16, 1, 32
    args = (jnp.zeros((b, h, p, n)), jnp.zeros((b, T, h, p)),
            jnp.zeros((b, T, h)), jnp.zeros((h,)), jnp.zeros((b, T, g, n)),
            jnp.zeros((b, T, g, n)), jnp.zeros((h,)))
    fn = lambda *a: ssd_ops.ssd_extend(*a, use_pallas=True)
    calls = [eqn.params["name"] for eqn in jax.make_jaxpr(fn)(*args).eqns
             if eqn.primitive.name == "pallas_call"]
    assert calls == [form]


def test_ssd_decode_step_matches_scan():
    """Recurrent decode steps reproduce the chunked scan outputs."""
    from repro.kernels.ssd_scan.ref import ssd_decode_step, ssd_reference
    b, l, h, p, g, n = 1, 32, 2, 16, 1, 16
    x = _arr((b, l, h, p))
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (b, l, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = _arr((b, l, g, n))
    Cm = _arr((b, l, g, n))
    y_scan, s_scan = ssd_reference(x, dt, A, Bm, Cm, None, chunk=16)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    for t in range(l):
        y_t, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                     Bm[:, t], Cm[:, t], None)
        np.testing.assert_allclose(np.asarray(y_t),
                                   np.asarray(y_scan[:, t]),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=f"t={t}")
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_scan),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------ #
# fused dequantize-matmul
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("M,K,N,bm,bn", [
    (128, 256, 128, 64, 64), (64, 128, 256, 64, 128), (128, 64, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_int8_kernel(M, K, N, bm, bn, dtype):
    from repro.kernels.quant_matmul.kernel import quant_matmul_int8_pallas
    from repro.kernels.quant_matmul.ref import quant_matmul_int8_reference
    from repro.quant import quantize_tensor
    x = _arr((M, K), dtype, scale=0.5)
    qt = quantize_tensor(_arr((K, N), scale=0.05), bits=8)
    out = quant_matmul_int8_pallas(x, qt["q"], qt["scale"], bm=bm, bn=bn,
                                   interpret=True)
    ref = quant_matmul_int8_reference(x, qt["q"], qt["scale"])
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("M,K,N,gs", [
    (64, 128, 128, 32), (128, 256, 64, 64), (64, 64, 128, 16),
])
def test_quant_matmul_int4_kernel(M, K, N, gs):
    from repro.kernels.quant_matmul.kernel import quant_matmul_int4_pallas
    from repro.kernels.quant_matmul.ref import quant_matmul_int4_reference
    from repro.quant import quantize_tensor
    x = _arr((M, K), scale=0.5)
    qt = quantize_tensor(_arr((K, N), scale=0.05), bits=4, group_size=gs)
    out = quant_matmul_int4_pallas(x, qt["q4"], qt["scale"], bm=64, bn=64,
                                   interpret=True)
    ref = quant_matmul_int4_reference(x, qt["q4"], qt["scale"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quant_matmul_matches_dense_dequant():
    """The fused op == dense matmul against the dequantized weight — the
    dispatch path models/layers.linear takes for quantized projections."""
    from repro.kernels.quant_matmul.ops import quant_matmul
    from repro.quant import dequantize_tensor, quantize_tensor
    x = _arr((2, 16, 96), scale=0.5)                  # rank-3 activations
    for bits in (8, 4):
        qt = quantize_tensor(_arr((96, 64), scale=0.05), bits=bits,
                             group_size=32)
        out = quant_matmul(x, qt)
        ref = x @ dequantize_tensor(qt)
        assert out.shape == (2, 16, 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_quant_matmul_pallas_path_matches_ref():
    from repro.kernels.quant_matmul.ops import quant_matmul
    from repro.quant import quantize_tensor
    x = _arr((128, 128), scale=0.5)
    for bits in (8, 4):
        qt = quantize_tensor(_arr((128, 128), scale=0.05), bits=bits,
                             group_size=32)
        out_p = quant_matmul(x, qt, use_pallas=True, interpret=True)
        out_r = quant_matmul(x, qt, use_pallas=False)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ #
# fused rmsnorm
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("N,d,bn", [(256, 128, 128), (128, 512, 64),
                                    (64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rmsnorm(N, d, bn, dtype):
    from repro.kernels.rmsnorm.ops import fused_rmsnorm
    x = _arr((N, d), dtype)
    r = _arr((N, d), dtype)
    s = _arr((d,))
    yp, rp = fused_rmsnorm(x, r, s, use_pallas=True, bn=bn)
    yr, rr = fused_rmsnorm(x, r, s, use_pallas=False)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(yp, np.float32),
                               np.asarray(yr, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(rp, np.float32),
                               np.asarray(rr, np.float32), rtol=tol,
                               atol=tol)
