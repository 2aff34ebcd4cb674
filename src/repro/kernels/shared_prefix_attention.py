"""Shared-prefix ("object sharing") attention as a Pallas TPU kernel.

The paper's core idea at kernel granularity: when requests share a cached
object (a common prompt prefix / RAG chunk / few-shot block), its KV is
stored **once** and should be *read and computed* once per group rather
than once per request. This kernel batches all M queries of a prefix
group against the group's single physical prefix KV:

* MXU efficiency: the score matmul has M*G rows instead of G — decode
  attention against a popular prefix becomes a dense (M*G x d) x
  (d x block) matmul (Hydragen-style), turning a memory-bound gather
  into compute-bound reuse. One HBM read of the shared object is
  amortized over the whole group — the compute-side analogue of the
  paper's ``l_n/|P(n)|`` storage sharing;
* the kernel emits (out, logsumexp) so the caller LSE-merges with
  per-request suffix attention (``ops.shared_prefix_decode`` /
  ``ref.lse_merge``).

Grid: (prefix, kv_head, prefix_blocks); online-softmax scratch carries
across blocks. Validated against
``ref.reference_shared_prefix_attention`` in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefix_kernel(
    prefix_lens_ref,             # scalar prefetch
    q_ref, k_ref, v_ref,
    o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *,
    block_s: int,
    sm_scale: float,
):
    p_idx = pl.program_id(0)
    i = pl.program_id(2)
    n_blocks = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = prefix_lens_ref[p_idx]
    s_start = i * block_s

    @pl.when(s_start < valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale    # (M*G, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (block_s, d)
        v = v_ref[0, 0].astype(jnp.float32)
        # zero padded edge-block rows (undefined memory; NaN in interpret)
        row = s_start + jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0)
        v = jnp.where(row < valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # (M*G, block_s)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


def shared_prefix_attention(
    q: jnp.ndarray,            # (P, M, H, D) queries grouped by prefix id
    prefix_k: jnp.ndarray,     # (P, S, KV, D) one physical copy per prefix
    prefix_v: jnp.ndarray,     # (P, S, KV, D)
    prefix_lens: jnp.ndarray,  # (P,) int32
    *,
    sm_scale: float | None = None,
    block_s: int = 128,
    interpret: bool = False,
):
    """Returns (out (P, M, H, D), lse (P, M, H)) for LSE merging."""
    P, M, H, D = q.shape
    S, KV = prefix_k.shape[1], prefix_k.shape[2]
    assert H % KV == 0
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_s = min(block_s, S)
    n_blocks = pl.cdiv(S, block_s)

    # rows = all grouped queries for one kv head: (P, KV, M*G, D)
    qr = jnp.moveaxis(q.reshape(P, M, KV, G, D), 2, 1).reshape(P, KV, M * G, D)
    kh = jnp.moveaxis(prefix_k, 2, 1)   # (P, KV, S, D)
    vh = jnp.moveaxis(prefix_v, 2, 1)

    kernel = functools.partial(
        _prefix_kernel, block_s=block_s, sm_scale=sm_scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, KV, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, M * G, D), lambda p, h, i, pls: (p, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda p, h, i, pls: (p, h, i, 0)),
            pl.BlockSpec((1, 1, block_s, D), lambda p, h, i, pls: (p, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, M * G, D), lambda p, h, i, pls: (p, h, 0, 0)),
            # a unit axis keeps the block's last two dims (1, M*G) equal
            # to the array's, as the TPU lowering requires of a block
            # whose second-minor dim is neither 8-aligned nor full
            pl.BlockSpec((1, 1, 1, M * G), lambda p, h, i, pls: (p, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((M * G, D), jnp.float32),
            pltpu.VMEM((M * G,), jnp.float32),
            pltpu.VMEM((M * G,), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, KV, M * G, D), q.dtype),
            jax.ShapeDtypeStruct((P, KV, 1, M * G), jnp.float32),
        ],
        interpret=interpret,
    )(prefix_lens, qr, kh, vh)
    out = jnp.moveaxis(out.reshape(P, KV, M, G, D), 1, 2).reshape(P, M, H, D)
    lse = jnp.moveaxis(lse.reshape(P, KV, M, G), 1, 2).reshape(P, M, H)
    return out, lse
