"""Pallas kernel sweeps: shapes × dtypes, assert_allclose against the
pure-jnp ref.py oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import (decode_attn, paged_decode_attn,
                                           paged_prefill_attn)
from repro.kernels.decode_attn.ref import (decode_attn_ref,
                                           paged_decode_attn_ref,
                                           paged_prefill_attn_ref)
from repro.kernels.fused_score.ops import fused_score
from repro.kernels.fused_score.ref import fused_score_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


# ------------------------------------------------------------ fused_score

@pytest.mark.parametrize("B,V", [(1, 128), (5, 1000), (8, 4096), (16, 2048),
                                 (3, 50257), (2, 151936)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_score_sweep(B, V, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * V))
    logits = (jax.random.normal(k1, (B, V)) * 3).astype(dtype)
    log_q = jax.nn.log_softmax(jax.random.normal(k2, (V,)))
    out = fused_score(logits, log_q)
    ref = fused_score_ref(logits, log_q)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for o, r, name in zip(out, ref, ["kl", "conf", "ent"]):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=tol, atol=tol, err_msg=name)


def test_fused_score_extreme_logits():
    """Large-magnitude logits must not overflow the online softmax."""
    logits = jnp.array([[1e4, 0.0, -1e4] + [0.0] * 125,
                        [-1e4] * 64 + [1e4] * 64])
    log_q = jax.nn.log_softmax(jnp.zeros(128))
    kl, conf, ent = fused_score(logits, log_q)
    rkl, rconf, rent = fused_score_ref(logits, log_q)
    assert np.all(np.isfinite(np.asarray(kl)))
    np.testing.assert_allclose(np.asarray(conf), np.asarray(rconf), rtol=1e-4)


def test_fused_score_odd_vocab_padding():
    """Non-tile-multiple vocab (e.g. granite's 49155) pads correctly."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    logits = jax.random.normal(k1, (4, 49155))
    log_q = jax.nn.log_softmax(jax.random.normal(k2, (49155,)))
    out = fused_score(logits, log_q)
    ref = fused_score_ref(logits, log_q)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-4,
                                   atol=1e-5)


# ------------------------------------------------------------ decode_attn

@pytest.mark.parametrize("B,H,KV,hd,S,pos,window,ring", [
    (2, 8, 2, 64, 256, 100, 0, False),       # GQA, early pos
    (1, 4, 4, 32, 512, 511, 0, False),       # MHA, cache full
    (2, 6, 3, 128, 300, 299, 64, False),     # sliding window, odd S
    (2, 4, 1, 64, 128, 500, 128, True),      # MQA ring buffer, wrapped
    (1, 16, 2, 64, 1024, 700, 256, True),    # ring, window < ring size
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_sweep(B, H, KV, hd, S, pos, window, ring, dtype):
    ks = jax.random.split(jax.random.PRNGKey(hash((B, H, S, pos)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    out = decode_attn(q, k, v, pos, window=window, ring=ring)
    ref = decode_attn_ref(q, k, v, pos, window=window, ring=ring)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,KV,hd,ps,MP,P", [
    (2, 8, 2, 64, 16, 4, 12),     # GQA
    (1, 4, 4, 32, 8, 8, 10),      # MHA, many small pages
    (3, 6, 3, 128, 32, 2, 8),     # odd head count, 2 logical pages
    (2, 4, 1, 64, 64, 3, 7),      # MQA, page = S-tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attn_sweep(B, H, KV, hd, ps, MP, P, dtype):
    """Paged kernel vs the pure-jnp paged oracle, scrambled block tables
    and per-row positions (trash-aliased tails included)."""
    rng = np.random.RandomState(B * H + ps)
    ks = jax.random.split(jax.random.PRNGKey(hash((B, H, ps, MP)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, KV, ps, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, KV, ps, hd)).astype(dtype)
    # each row: random position, owned pages drawn without replacement,
    # unowned entries alias the last physical page (trash convention)
    pos = rng.randint(0, MP * ps, size=B).astype(np.int32)
    bt = np.full((B, MP), P - 1, np.int32)
    for b in range(B):
        owned = pos[b] // ps + 1
        bt[b, :owned] = rng.choice(P - 1, size=owned, replace=False)
    out = paged_decode_attn(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    ref = paged_decode_attn_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def _quantize_pages(x):
    """Per-(page, kv-head, slot) absmax int8 quantization of head-major
    (P, KV, ps, hd) pages; scales come back (P, KV, 1, ps) — the same
    layout the serving cache uses for its ``k_s``/``v_s`` scale leaves."""
    x = np.asarray(x, np.float32)
    s = np.maximum(np.abs(x).max(axis=-1), 1e-8) / 127.0
    q = np.clip(np.round(x / s[..., None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(s[:, :, None, :].astype(np.float32))


def _scrambled_tables(rng, B, MP, P, ps, pos):
    """Owned pages drawn without replacement, tails alias the trash page
    (index P-1) — same convention as the fp sweep above."""
    bt = np.full((B, MP), P - 1, np.int32)
    for b in range(B):
        owned = int(pos[b]) // ps + 1
        bt[b, :owned] = rng.choice(P - 1, size=owned, replace=False)
    return bt


@pytest.mark.parametrize("B,H,KV,hd,ps,MP,P", [
    (2, 8, 2, 64, 16, 4, 12),     # GQA
    (1, 4, 4, 32, 8, 8, 10),      # MHA, many small pages
    (2, 4, 1, 64, 64, 3, 7),      # MQA, page = S-tile
])
def test_paged_decode_attn_int8_sweep(B, H, KV, hd, ps, MP, P):
    """Int8 paged kernel vs the int8-aware oracle: both dequantize the
    same int8 pages with the same scales, so the comparison is tight.
    A loose check against the unquantized oracle bounds the actual
    quantization error."""
    rng = np.random.RandomState(B * H + ps + 1)
    ks = jax.random.split(jax.random.PRNGKey(hash((B, H, ps, MP, 8)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, KV, ps, hd))
    vp = jax.random.normal(ks[2], (P, KV, ps, hd))
    kq, ksc = _quantize_pages(kp)
    vq, vsc = _quantize_pages(vp)
    pos = rng.randint(0, MP * ps, size=B).astype(np.int32)
    bt = _scrambled_tables(rng, B, MP, P, ps, pos)
    out = paged_decode_attn(q, kq, vq, jnp.asarray(bt), jnp.asarray(pos),
                            k_scales=ksc, v_scales=vsc)
    ref = paged_decode_attn_ref(q, kq, vq, jnp.asarray(bt), jnp.asarray(pos),
                                k_scales=ksc, v_scales=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    full = paged_decode_attn_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("B,C,H,KV,hd,ps,MP,P", [
    (2, 4, 8, 2, 64, 16, 4, 12),  # GQA, mid-size chunk
    (1, 7, 4, 4, 32, 8, 8, 10),   # MHA, chunk not a page multiple
    (2, 1, 4, 1, 64, 16, 3, 7),   # MQA, single-token chunk (= decode)
    (1, 16, 6, 3, 128, 32, 2, 8), # odd head count, chunk = half a page
])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_prefill_attn_sweep(B, C, H, KV, hd, ps, MP, P, quant):
    """Paged chunk-prefill kernel vs the pure-jnp causal oracle: random
    chunk offsets ``pos0`` (chunk straddles page boundaries), scrambled
    block tables, fp32 and int8 pages."""
    rng = np.random.RandomState(B * C + ps)
    ks = jax.random.split(jax.random.PRNGKey(hash((B, C, H, ps)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd))
    kp = jax.random.normal(ks[1], (P, KV, ps, hd))
    vp = jax.random.normal(ks[2], (P, KV, ps, hd))
    # pos0 = position of the chunk's FIRST token; last token must fit
    pos0 = rng.randint(0, MP * ps - C + 1, size=B).astype(np.int32)
    last = pos0 + C - 1
    bt = _scrambled_tables(rng, B, MP, P, ps, last)
    if quant:
        kp, ksc = _quantize_pages(kp)
        vp, vsc = _quantize_pages(vp)
    else:
        ksc = vsc = None
    out = paged_prefill_attn(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos0),
                             k_scales=ksc, v_scales=vsc)
    ref = paged_prefill_attn_ref(q, kp, vp, jnp.asarray(bt),
                                 jnp.asarray(pos0),
                                 k_scales=ksc, v_scales=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_prefill_single_token_matches_decode():
    """A one-token chunk through the prefill entry equals the decode
    entry bitwise — they share one kernel body."""
    B, H, KV, hd, ps, MP, P = 2, 8, 2, 64, 16, 4, 12
    rng = np.random.RandomState(0)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, KV, ps, hd))
    vp = jax.random.normal(ks[2], (P, KV, ps, hd))
    pos = rng.randint(0, MP * ps, size=B).astype(np.int32)
    bt = _scrambled_tables(rng, B, MP, P, ps, pos)
    d = paged_decode_attn(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    p = paged_prefill_attn(q[:, None], kp, vp, jnp.asarray(bt),
                           jnp.asarray(pos))
    assert np.array_equal(np.asarray(d), np.asarray(p[:, 0]))


def test_paged_decode_attn_matches_contiguous_kernel():
    """Gathering a row's pages into a contiguous cache and running the
    existing flash-decode kernel gives the same answer — the paged kernel
    only changes *where* the S-tiles come from."""
    B, H, KV, hd, ps, MP, P = 3, 8, 2, 64, 16, 4, 14
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, KV, ps, hd))
    vp = jax.random.normal(ks[2], (P, KV, ps, hd))
    rng = np.random.RandomState(1)
    bt = np.stack([rng.choice(P, size=MP, replace=False) for _ in range(B)])
    pos = np.array([5, 63, 40], np.int32)
    out = paged_decode_attn(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    for b in range(B):
        kc = kp[jnp.asarray(bt[b])].transpose(0, 2, 1, 3).reshape(
            1, MP * ps, KV, hd)
        vc = vp[jnp.asarray(bt[b])].transpose(0, 2, 1, 3).reshape(
            1, MP * ps, KV, hd)
        oc = decode_attn(q[b:b + 1], kc, vc, int(pos[b]))
        np.testing.assert_allclose(np.asarray(out[b:b + 1]), np.asarray(oc),
                                   rtol=2e-5, atol=2e-5)


def test_decode_attn_pos_zero():
    """Only slot 0 valid — attention must equal v[:, 0]."""
    B, H, KV, hd, S = 1, 2, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = decode_attn(q, k, v, 0)
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(v[0, 0, 0]),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- rwkv6_scan

@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 64, 3, 32, 16), (1, 128, 2, 64, 32), (2, 50, 2, 16, 32),
    (1, 33, 4, 64, 16),
])
@pytest.mark.parametrize("with_s0", [False, True])
def test_rwkv6_scan_sweep(B, T, H, hd, chunk, with_s0):
    ks = jax.random.split(jax.random.PRNGKey(T * hd + with_s0), 6)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (H, hd)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1 if with_s0 else None
    y, sf = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    s0_ref = s0 if s0 is not None else jnp.zeros((B, H, hd, hd))
    yr, sr = rwkv6_scan_ref(r, k, v, w, u, s0_ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), rtol=3e-4, atol=3e-4)


def test_rwkv6_scan_tiny_decays():
    """Near-zero decay (strong forgetting) must stay finite in the
    log-space chunked form."""
    B, T, H, hd = 1, 32, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jnp.full((B, T, H, hd), 1e-6)
    u = jnp.zeros((H, hd))
    y, sf = rwkv6_scan(r, k, v, w, u, chunk=16)
    yr, sr = rwkv6_scan_ref(r, k, v, w, u, jnp.zeros((B, H, hd, hd)))
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-3, atol=1e-3)


def test_rwkv6_chunk_boundary_equivalence():
    """Different chunk sizes give identical results (associativity)."""
    B, T, H, hd = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd)))
    u = jnp.ones((H, hd)) * 0.3
    y16, s16 = rwkv6_scan(r, k, v, w, u, chunk=16)
    y32, s32 = rwkv6_scan(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s32), rtol=2e-4,
                               atol=2e-4)
