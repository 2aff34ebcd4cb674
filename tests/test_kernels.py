"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode, which every call here asks for explicitly. Their TPU compiles are
checked in ``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = jax.random.PRNGKey(0)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,S,H,KV,D,causal",
    [
        (2, 128, 128, 4, 2, 64, True),
        (1, 256, 256, 8, 8, 128, True),
        (2, 100, 100, 4, 1, 64, False),    # non-aligned edge blocks
        (1, 64, 192, 4, 4, 64, False),     # cross attention T != S
        (1, 128, 128, 4, 4, 256, True),    # recurrentgemma head_dim
    ],
)
def test_flash_attention_matches_reference(B, T, S, H, KV, D, causal, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    got = ops.flash_attention(
        q, k, v, causal=causal, block_q=64, block_kv=64, interpret=True
    )
    want = ref.reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,D,page,P,pps",
    [
        (4, 8, 4, 64, 16, 32, 6),
        (2, 4, 1, 128, 8, 16, 4),     # MQA
        (3, 6, 6, 64, 32, 12, 3),     # MHA
    ],
)
def test_paged_attention_matches_reference(B, H, KV, D, page, P, pps, dtype):
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (KV, P, page, D), dtype)
    vp = jax.random.normal(ks[2], (KV, P, page, D), dtype)
    bt = jax.random.randint(ks[3], (B, pps), 0, P)
    max_ctx = pps * page
    cl = jax.random.randint(ks[4], (B,), 1, max_ctx + 1)
    got = ops.paged_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.reference_paged_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=_tol(dtype)
    )


def test_paged_attention_shared_pages_are_consistent():
    """Two sequences pointing at the SAME physical pages (a shared
    prefix) must see identical attention over that prefix."""
    B, H, KV, D, page, P = 2, 4, 2, 64, 8, 8
    ks = jax.random.split(RNG, 4)
    q = jnp.tile(jax.random.normal(ks[0], (1, H, D)), (B, 1, 1))
    kp = jax.random.normal(ks[1], (KV, P, page, D))
    vp = jax.random.normal(ks[2], (KV, P, page, D))
    bt = jnp.array([[0, 1, 2], [0, 1, 2]], jnp.int32)  # same physical pages
    cl = jnp.array([24, 24], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, cl, interpret=True)
    np.testing.assert_allclose(out[0], out[1], atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "P,M,H,KV,D,S",
    [
        (3, 4, 8, 4, 64, 64),
        (2, 2, 4, 1, 128, 100),   # MQA + non-aligned prefix blocks
        (1, 8, 4, 4, 64, 256),
    ],
)
def test_shared_prefix_attention_matches_reference(P, M, H, KV, D, S, dtype):
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (P, M, H, D), dtype)
    pk = jax.random.normal(ks[1], (P, S, KV, D), dtype)
    pv = jax.random.normal(ks[2], (P, S, KV, D), dtype)
    plens = jax.random.randint(ks[3], (P,), 1, S + 1)
    got_o, got_l = ops.shared_prefix_attention(
        q, pk, pv, plens, block_s=32, interpret=True
    )
    want_o, want_l = ref.reference_shared_prefix_attention(q, pk, pv, plens)
    np.testing.assert_allclose(
        got_o.astype(jnp.float32), want_o, atol=_tol(dtype)
    )
    np.testing.assert_allclose(got_l, want_l, atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "P,M,H,KV,D,S,Ss",
    [
        (2, 3, 8, 4, 64, 100, 24),   # non-aligned prefix blocks
        (1, 4, 4, 1, 128, 256, 16),  # MQA
    ],
)
def test_shared_prefix_decode_matches_attention_over_prefix_and_suffix(
    P, M, H, KV, D, S, Ss, dtype
):
    ks = jax.random.split(RNG, 7)
    q = jax.random.normal(ks[0], (P, M, H, D), dtype)
    pk = jax.random.normal(ks[1], (P, S, KV, D), dtype)
    pv = jax.random.normal(ks[2], (P, S, KV, D), dtype)
    sk = jax.random.normal(ks[3], (P, M, Ss, KV, D), dtype)
    sv = jax.random.normal(ks[4], (P, M, Ss, KV, D), dtype)
    plens = jax.random.randint(ks[5], (P,), 1, S + 1)
    slens = jax.random.randint(ks[6], (P, M), 1, Ss + 1)
    got = ops.shared_prefix_decode(
        q, pk, pv, plens, sk, sv, slens, interpret=True
    )
    want = ref.reference_shared_prefix_decode(q, pk, pv, plens, sk, sv, slens)
    assert got.shape == q.shape and got.dtype == dtype
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=_tol(dtype)
    )


def test_lse_merge_equals_joint_attention():
    """Merging prefix + suffix partials == attention over concatenated KV."""
    B, T, H, KV, D, S1, S2 = 2, 1, 4, 2, 32, 24, 16
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k1 = jax.random.normal(ks[1], (B, S1, KV, D))
    v1 = jax.random.normal(ks[2], (B, S1, KV, D))
    k2 = jax.random.normal(ks[3], (B, S2, KV, D))
    v2 = jax.random.normal(ks[4], (B, S2, KV, D))
    o1, l1 = ref.reference_attention_with_lse(q, k1, v1)
    o2, l2 = ref.reference_attention_with_lse(q, k2, v2)
    merged = ref.lse_merge(o1, l1, o2, l2)
    joint = ref.reference_attention(
        q, jnp.concatenate([k1, k2], 1), jnp.concatenate([v1, v2], 1),
        causal=False,
    )
    np.testing.assert_allclose(merged, joint, atol=1e-5)


def test_model_chunked_attention_grads_match_reference():
    """The model's flash custom-VJP backward vs autodiff of the oracle."""
    from repro.models.attention import chunked_attention

    B, T, H, KV, D = 2, 32, 4, 2, 16
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, KV, D))
    v = jax.random.normal(ks[2], (B, T, KV, D))

    def f(q, k, v):
        return (chunked_attention(q, k, v, causal=True, kv_chunk=8) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.reference_attention(q, k, v, causal=True) ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-4)
