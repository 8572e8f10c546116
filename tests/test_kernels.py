"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Sweeps shapes/dtypes and asserts allclose against ``repro.kernels.ref`` —
the contract demanded for every Pallas kernel in this repo.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encodings
from repro.kernels import ops, ref
from repro.kernels.flash_decode import dequantize_kv, quantize_kv
from repro.kernels.se2_project import se2_fourier_project


def rand_qkv(rng, b, hq, hkv, sq, sk, d, dv=None, dtype=jnp.float32):
    dv = dv or d
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype=dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, d)), dtype=dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, dv)), dtype=dtype)
    return q, k, v


def tol_for(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-4)


SHAPE_SWEEP = [
    # b, hq, hkv, sq, sk, d, dv, block
    (1, 1, 1, 32, 32, 32, 32, 16),
    (2, 4, 4, 64, 64, 64, 64, 32),
    (1, 4, 2, 48, 80, 32, 32, 16),     # GQA + ragged (padding path)
    (2, 8, 1, 33, 65, 16, 16, 16),     # MQA + unaligned seq lens
    (1, 2, 2, 64, 64, 24, 40, 32),     # dv != d, unaligned head dims
]


@pytest.mark.parametrize("shape", SHAPE_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(shape, dtype):
    b, hq, hkv, sq, sk, d, dv, blk = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q, k, v = rand_qkv(rng, b, hq, hkv, sq, sk, d, dv, dtype)
    got = ops.flash_attention(q, k, v, block_q=blk, block_k=blk,
                              interpret=True)
    want = ref.mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol_for(dtype))


@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None),
    (False, 24, None),
    (True, 16, None),
    (False, None, 30.0),
    (True, None, 50.0),
])
def test_flash_mask_variants(causal, window, softcap):
    rng = np.random.default_rng(0)
    q, k, v = rand_qkv(rng, 2, 4, 2, 64, 64, 32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=16, block_k=16,
                              interpret=True)
    want = ref.mha_reference(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_flash_segment_ids():
    rng = np.random.default_rng(1)
    b, sq = 2, 64
    q, k, v = rand_qkv(rng, b, 2, 2, sq, sq, 32)
    seg = jnp.asarray(rng.integers(0, 3, size=(b, sq)), jnp.int32)
    got = ops.flash_attention(q, k, v, q_segment_ids=seg, k_segment_ids=seg,
                              block_q=16, block_k=16, interpret=True)
    want = ref.mha_reference(q, k, v, q_segment_ids=seg, k_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_flash_gradients_match_ref():
    """Default (Pallas) backward vs autodiff through the O(S^2) oracle."""
    rng = np.random.default_rng(2)
    q, k, v = rand_qkv(rng, 1, 2, 1, 32, 48, 16)

    def loss_flash(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                                interpret=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.1))

    def loss_ref(q, k, v):
        o = ref.mha_reference(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape) * 0.1))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-3)


def test_flash_gradients_gqa_softcap():
    rng = np.random.default_rng(3)
    q, k, v = rand_qkv(rng, 1, 4, 2, 32, 32, 16)

    def mk(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o ** 2)
        return loss

    flash = mk(lambda q, k, v: ops.flash_attention(
        q, k, v, softcap=20.0, block_q=16, block_k=16, interpret=True))
    oracle = mk(lambda q, k, v: ref.mha_reference(q, k, v, softcap=20.0))
    g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# Pallas backward kernels: parity against the reference gradients and the
# blocked-XLA recurrence across the full feature matrix.
# ---------------------------------------------------------------------------

def _flash_grads(q, k, v, g, bwd_impl, *, block=16, **kwargs):
    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, block_q=block, block_k=block,
                                interpret=True, bwd_impl=bwd_impl, **kwargs)
        return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


GRAD_CASES = {
    "plain": dict(),
    "causal": dict(causal=True),
    "window": dict(window=24),
    "causal_window": dict(causal=True, window=16),
    "softcap": dict(softcap=20.0),
    "causal_softcap": dict(causal=True, softcap=30.0),
}


@pytest.mark.parametrize("case", sorted(GRAD_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_bwd_pallas_feature_matrix(case, dtype):
    """Pallas backward vs reference gradients vs the XLA-recurrence backward."""
    kwargs = GRAD_CASES[case]
    # str hash is randomized per process; seed deterministically instead.
    rng = np.random.default_rng(sorted(GRAD_CASES).index(case))
    q, k, v = rand_qkv(rng, 2, 4, 2, 64, 64, 32, dtype=dtype)   # GQA
    g = jnp.asarray(rng.normal(size=(2, 4, 64, 32)), dtype)
    got = _flash_grads(q, k, v, g, "pallas", **kwargs)
    want = ref.mha_grads_reference(q, k, v, g, **kwargs)
    xla = _flash_grads(q, k, v, g, "xla", **kwargs)
    # bf16: both sides quantize their outputs to bf16, so the envelope is a
    # bf16 ulp of the gradient magnitude (sums over 64 keys), not 1e-2 alone.
    tol = dict(atol=1e-2, rtol=4e-2) if dtype == jnp.bfloat16 else dict(
        atol=1e-5, rtol=1e-3)
    for name, a, w, x in zip("dq dk dv".split(), got, want, xla):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=f"{name} pallas-vs-ref", **tol)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(x, np.float32),
                                   err_msg=f"{name} pallas-vs-xla", **tol)


@pytest.mark.parametrize("shape", SHAPE_SWEEP)
def test_flash_bwd_pallas_shape_sweep(shape):
    """Backward parity at every forward sweep shape (padding, GQA, dv != d)."""
    b, hq, hkv, sq, sk, d, dv, blk = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q, k, v = rand_qkv(rng, b, hq, hkv, sq, sk, d, dv)
    g = jnp.asarray(rng.normal(size=(b, hq, sq, dv)), jnp.float32)
    got = _flash_grads(q, k, v, g, "pallas", block=blk, causal=True)
    want = ref.mha_grads_reference(q, k, v, g, causal=True)
    for name, a, w in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5, rtol=1e-3,
                                   err_msg=f"{name} @ {shape}")


def test_flash_bwd_pallas_segment_ids():
    rng = np.random.default_rng(11)
    b, s = 2, 64
    q, k, v = rand_qkv(rng, b, 2, 2, s, s, 32)
    g = jnp.asarray(rng.normal(size=(b, 2, s, 32)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, 3, size=(b, s)), jnp.int32)
    kw = dict(q_segment_ids=seg, k_segment_ids=seg)
    got = _flash_grads(q, k, v, g, "pallas", **kw)
    want = ref.mha_grads_reference(q, k, v, g, **kw)
    for name, a, w in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5, rtol=1e-3, err_msg=name)


def test_flash_bwd_pallas_times():
    """Block-causal over explicit per-token times (agent-sim scenes)."""
    rng = np.random.default_rng(12)
    b, s = 2, 64
    q, k, v = rand_qkv(rng, b, 2, 2, s, s, 32)
    g = jnp.asarray(rng.normal(size=(b, 2, s, 32)), jnp.float32)
    times = jnp.asarray(np.sort(rng.integers(0, 8, size=(b, s)), axis=-1),
                        jnp.int32)
    kw = dict(causal=True, q_times=times, k_times=times)
    got = _flash_grads(q, k, v, g, "pallas", **kw)
    want = ref.mha_grads_reference(q, k, v, g, **kw)
    for name, a, w in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5, rtol=1e-3, err_msg=name)


def test_flash_fwd_lse_matches_reference():
    """The forward kernel's saved LSE rows equal the O(S^2) logsumexp."""
    from repro.kernels import flash_attention as fa
    rng = np.random.default_rng(13)
    q, k, v = rand_qkv(rng, 2, 4, 2, 64, 64, 32)
    _, lse = fa.flash_attention_fwd(q, k, v, causal=True, block_q=16,
                                    block_k=16, interpret=True,
                                    return_lse=True)
    want = ref.lse_reference(q, k, causal=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_flash_bwd_default_dispatches_pallas(monkeypatch):
    """jax.grad through ops.flash_attention runs the Pallas backward by
    default: poison the XLA fallback and check gradients still flow."""
    monkeypatch.setattr(ops, "_bwd_chunked",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            AssertionError("XLA backward should not run")))
    # Pin the default so an ambient REPRO_FLASH_BWD override cannot skew
    # what this test checks (that bwd_impl=None resolves to Pallas).
    monkeypatch.setattr(ops, "DEFAULT_BWD_IMPL", "pallas")
    rng = np.random.default_rng(14)
    q, k, v = rand_qkv(rng, 1, 2, 2, 32, 32, 16)
    g = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.float32)
    got = _flash_grads(q, k, v, g, None, causal=True)
    want = ref.mha_grads_reference(q, k, v, g, causal=True)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5, rtol=1e-3)


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 32)])
def test_chunked_matches_ref(causal, window):
    rng = np.random.default_rng(4)
    q, k, v = rand_qkv(rng, 2, 4, 2, 96, 96, 32)
    got = ref.mha_chunked(q, k, v, causal=causal, window=window,
                          chunk_size=32)
    want = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_chunked_q_offset_decode():
    """Decode semantics: queries are a suffix of the key sequence."""
    rng = np.random.default_rng(5)
    q, k, v = rand_qkv(rng, 1, 2, 2, 4, 64, 32)
    got = ref.mha_chunked(q, k, v, causal=True, q_offset=60, chunk_size=16)
    want = ref.mha_reference(q, k, v, causal=True, q_offset=60)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# Small-q decode path: q_len << block_q (the incremental rollout shape),
# cursor-based masking via kv_length, and the _pad_all padding edge cases.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq", [1, 2, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_small_q_decode_matches_ref(sq, dtype):
    """Tiny query counts over a long K/V cache with per-row cursors (GQA).

    Exercises ``_pad_all``'s q_len < block_q path: the auto-shrunk decode
    block is 16 rows, so every sq here gets zero-padded query rows that
    must be sliced off without contaminating live rows.
    """
    rng = np.random.default_rng(100 + sq)
    q, k, v = rand_qkv(rng, 2, 4, 2, sq, 96, 16, dtype=dtype)
    kvl = jnp.asarray([70, 96], jnp.int32)
    got = ops.flash_attention(q, k, v, kv_length=kvl, block_k=32,
                              interpret=True)
    want = ref.mha_reference(q, k, v, kv_length=kvl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol_for(dtype))


def test_flash_small_q_unaligned_kv():
    """Both _pad_all branches at once: q_len < block_q AND sk % block_k != 0
    (padded key rows must stay masked behind segment id -1)."""
    rng = np.random.default_rng(9)
    q, k, v = rand_qkv(rng, 2, 2, 1, 3, 65, 24, 40)     # MQA + dv != d
    kvl = jnp.asarray([50, 65], jnp.int32)
    got = ops.flash_attention(q, k, v, kv_length=kvl, block_q=32, block_k=32,
                              interpret=True)
    want = ref.mha_reference(q, k, v, kv_length=kvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_pad_all_q_lt_block_q_direct():
    """_pad_all with q_len < block_q, driven through the padded forward at
    an explicit 32-row block (bypasses the auto-shrink)."""
    rng = np.random.default_rng(10)
    q, k, v = rand_qkv(rng, 1, 2, 2, 5, 64, 16)
    out, lse = ops._flash_fwd_padded(q, k, v, None, None, None, None,
                                     causal=False, window=None, softcap=None,
                                     scale=None, block_q=32, block_k=32,
                                     interpret=True)
    want = ref.mha_reference(q, k, v)
    assert out.shape == (1, 2, 5, 16) and lse.shape == (1, 2, 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(ref.lse_reference(q, k)),
                               atol=1e-5, rtol=1e-5)


def test_flash_small_q_times_block_causal_decode():
    """The agent-sim decode shape: new tokens at one sim step attending a
    block-causal times cache plus segment ids plus cursor masking."""
    rng = np.random.default_rng(11)
    b, sk, n = 2, 64, 4
    q, k, v = rand_qkv(rng, b, 2, 2, n, sk, 16)
    k_times = jnp.asarray(np.sort(rng.integers(0, 8, size=(b, sk)), -1),
                          jnp.int32)
    q_times = jnp.full((b, n), 5, jnp.int32)
    seg = jnp.asarray(rng.integers(0, 2, size=(b, sk)), jnp.int32)
    qseg = jnp.zeros((b, n), jnp.int32)
    kvl = jnp.asarray([40, 64], jnp.int32)
    kw = dict(causal=True, q_times=q_times, k_times=k_times,
              q_segment_ids=qseg, k_segment_ids=seg, kv_length=kvl)
    got = ops.flash_attention(q, k, v, block_k=16, interpret=True, **kw)
    want = ref.mha_reference(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_decode_block_q_auto_shrink():
    assert ops._decode_block_q(1, 128) == 16
    assert ops._decode_block_q(5, 128) == 16
    assert ops._decode_block_q(17, 128) == 32
    assert ops._decode_block_q(128, 128) == 128
    assert ops._decode_block_q(64, 16) == 16        # never grows the block


def test_flash_kv_length_one_sided_segment_ids():
    """kv_length must survive a caller passing only ONE segment-id side
    (regression: the fold used to leave q_seg None — which disables the
    kernel's segment mask entirely — or clobber a provided q_seg)."""
    rng = np.random.default_rng(13)
    b, sq, sk = 2, 4, 64
    q, k, v = rand_qkv(rng, b, 2, 2, sq, sk, 16)
    kvl = jnp.asarray([40, 64], jnp.int32)
    kseg = jnp.asarray(rng.integers(0, 2, size=(b, sk)), jnp.int32)
    got = ops.flash_attention(q, k, v, k_segment_ids=kseg, kv_length=kvl,
                              block_k=16, interpret=True)
    want = ref.mha_reference(q, k, v, q_segment_ids=jnp.zeros((b, sq),
                                                              jnp.int32),
                             k_segment_ids=kseg, kv_length=kvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4, err_msg="k-side only")
    qseg = jnp.asarray(rng.integers(-1, 1, size=(b, sq)), jnp.int32)
    got = ops.flash_attention(q, k, v, q_segment_ids=qseg, kv_length=kvl,
                              block_k=16, interpret=True)
    want = ref.mha_reference(q, k, v, q_segment_ids=qseg,
                             k_segment_ids=jnp.zeros((b, sk), jnp.int32),
                             kv_length=kvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4, err_msg="q-side only")


@pytest.mark.parametrize("impl", ["ref", "chunked"])
def test_kv_length_scalar_and_vector(impl):
    """Scalar cursors behave like broadcast vectors in the XLA impls."""
    rng = np.random.default_rng(12)
    q, k, v = rand_qkv(rng, 2, 2, 2, 4, 48, 16)
    a = ops.attention(q, k, v, impl=impl, kv_length=33, chunk_size=16)
    b_ = ops.attention(q, k, v, impl=impl,
                       kv_length=jnp.asarray([33, 33], jnp.int32),
                       chunk_size=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)
    want = ref.mha_reference(q, k[:, :, :33], v[:, :, :33])
    np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# SE(2) Fourier projection kernel vs the encodings oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("head_dim,num_terms,tokens,block_t", [
    (6, 8, 16, 8),
    (12, 18, 100, 32),     # unaligned token count (padding path)
    (24, 12, 64, 64),
])
@pytest.mark.parametrize("mode", ["q", "k"])
def test_se2_project_matches_oracle(head_dim, num_terms, tokens, block_t, mode):
    enc = encodings.SE2Fourier(head_dim=head_dim, num_terms=num_terms)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(tokens, head_dim)), dtype=jnp.float32)
    pose = jnp.asarray(
        np.concatenate([rng.uniform(-3, 3, (tokens, 2)),
                        rng.uniform(-np.pi, np.pi, (tokens, 1))], -1),
        dtype=jnp.float32)
    got = se2_fourier_project(x, pose, enc, mode, block_t=block_t,
                              interpret=True)
    want = enc.transform_q(x, pose) if mode == "q" else enc.transform_k(x, pose)
    assert got.shape == want.shape == (tokens, enc.expanded_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_se2_project_dtypes(dtype):
    enc = encodings.SE2Fourier(head_dim=12, num_terms=10)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(32, 12)), dtype=dtype)
    pose = jnp.asarray(rng.uniform(-2, 2, (32, 3)), dtype=jnp.float32)
    got = se2_fourier_project(x, pose, enc, "k", block_t=16, interpret=True)
    want = enc.transform_k(x, pose)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_flash_then_se2_project_end_to_end():
    """Alg. 2 with both Pallas kernels == quadratic oracle (Alg. 1)."""
    from repro.core import attention as core_attn
    enc = encodings.SE2Fourier(head_dim=12, num_terms=20)
    rng = np.random.default_rng(8)
    n = 32
    q = jnp.asarray(rng.normal(size=(n, 12)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(n, 12)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, 12)), dtype=jnp.float32)
    pose = jnp.asarray(
        np.concatenate([rng.uniform(-2, 2, (n, 2)),
                        rng.uniform(-np.pi, np.pi, (n, 1))], -1),
        dtype=jnp.float32)
    qt = se2_fourier_project(q, pose, enc, "q", block_t=16, interpret=True)
    kt = se2_fourier_project(k, pose, enc, "k", block_t=16, interpret=True)
    vt = se2_fourier_project(v, pose, enc, "k", block_t=16, interpret=True)
    ot = ops.flash_attention(qt[None, None], kt[None, None], vt[None, None],
                             scale=1.0 / np.sqrt(12), block_q=16, block_k=16,
                             interpret=True)[0, 0]
    out = enc.untransform_out(ot, pose)
    want = core_attn.relative_attention_quadratic(enc, q, k, v, pose, pose)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# Layer-stacked, feature-major decode cache: (L, B, H, c, S)
# ---------------------------------------------------------------------------

#: as tests/test_decode.py's DECODE_TOL: every path reads the same cache
#: values; bf16 is looser because the generic fallback rounds its output
#: to the cache dtype
STACKED_TOL = {"float32": dict(atol=2e-5, rtol=2e-4),
               "bfloat16": dict(atol=8e-3, rtol=8e-3),
               "int8": dict(atol=2e-4, rtol=2e-3)}


def _stacked_case(c, cache_dtype, seed, *, layers=3, layer=1):
    """A stacked cache built from row-major (L, B, Hkv, S, c) rows, with
    rows past each slot's cursor poisoned with NaN (in the scales of an
    int8 cache); returns the kernels' operands and the oracle's clean
    layer ``layer`` in (B, Hkv, S, c)."""
    rng = np.random.default_rng(seed)
    b, hq, hkv, sq, s = 3, 4, 2, 4, 64
    kvl = np.asarray([s - 5, 17, 33], np.int32)       # ragged cursors
    q = jnp.asarray(rng.normal(size=(b, hq, sq, c)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(layers, b, hkv, s, c)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(layers, b, hkv, s, c)), jnp.float32)
    kw = dict(
        q_times=jnp.full((b, sq), 6, jnp.int32),
        k_times=jnp.asarray(np.sort(rng.integers(0, 8, size=(b, s)), -1),
                            jnp.int32),
        q_segment_ids=jnp.asarray(rng.integers(0, 2, size=(b, sq)),
                                  jnp.int32),
        k_segment_ids=jnp.asarray(rng.integers(-1, 2, size=(b, s)),
                                  jnp.int32))
    dead = jnp.asarray(np.arange(s)[None, :] >= kvl[:, None])   # (B, S)
    dead_rows = dead[None, :, None, :, None]
    k_scale = v_scale = None
    if cache_dtype == "int8":
        k, k_scale = quantize_kv(k)
        v, v_scale = quantize_kv(v)
        k_oracle = dequantize_kv(k[layer], k_scale[layer])
        v_oracle = dequantize_kv(v[layer], v_scale[layer])
        k_scale = jnp.where(dead[None, :, None], jnp.nan, k_scale)
        v_scale = jnp.where(dead[None, :, None], jnp.nan, v_scale)
    else:
        k = k.astype(cache_dtype)
        v = v.astype(cache_dtype)
        k_oracle = k[layer].astype(jnp.float32)
        v_oracle = v[layer].astype(jnp.float32)
        k = jnp.where(dead_rows, jnp.nan, k).astype(cache_dtype)
        v = jnp.where(dead_rows, jnp.nan, v).astype(cache_dtype)
    k, v = jnp.swapaxes(k, -1, -2), jnp.swapaxes(v, -1, -2)    # (L,B,H,c,S)
    stacked = dict(kv_length=jnp.asarray(kvl), k_scale=k_scale,
                   v_scale=v_scale, layer=layer, **kw)
    return q, k, v, stacked, k_oracle, v_oracle, kw


@pytest.mark.parametrize("cache_dtype", sorted(STACKED_TOL))
@pytest.mark.parametrize("c", [24, 200])
def test_stacked_feature_major_decode_parity(c, cache_dtype):
    """flash_decode (interpret) == decode_ragged_xla == the ref fallback
    on a feature-major stacked cache, == ref.mha_reference over the
    row-major layer it was built from: row widths of both serving
    encodings, ragged cursors, times, segments, GQA, and NaN rows past
    every cursor."""
    q, k, v, stacked, k_oracle, v_oracle, kw = _stacked_case(
        c, cache_dtype, seed=c)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mha_reference(
            q, k_oracle, v_oracle, causal=True,
            kv_length=stacked["kv_length"], **kw), np.float32)
    got = {
        "flash_decode": ops.decode_attention(
            q, k, v, impl="flash_decode", block_k=16, num_splits=2,
            interpret=True, **stacked),
        "xla": ops.decode_attention(q, k, v, impl="xla", block_k=16,
                                    **stacked),
        "ref": ops.decode_attention(q, k, v, impl="ref", **stacked),
    }
    for name, g in got.items():
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all(), name
        np.testing.assert_allclose(g, want, **STACKED_TOL[cache_dtype],
                                   err_msg=f"{name} c={c} {cache_dtype}")
