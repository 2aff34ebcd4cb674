"""Flash attention (training/prefill) as a Pallas TPU kernel.

FA2-style tiling for the MXU/VMEM hierarchy:

* grid = (batch, q_heads, q_blocks, kv_blocks), kv innermost — the TPU
  grid executes sequentially per core, so the (m, l, acc) VMEM scratch
  carries the online softmax across the kv steps of one q block;
* BlockSpecs stage (block_q x head_dim) q tiles and (block_kv x head_dim)
  k/v tiles HBM->VMEM; matmul dims are MXU-aligned for the assigned
  head_dims (64/128/256; 80 is lane-padded by Mosaic);
* causal masking skips fully-masked kv blocks via ``pl.when`` — this is
  the 2x FLOP saving over the XLA reference path, which computes the
  full T x S score matrix and masks (see EXPERIMENTS.md §Perf);
* GQA: kv tiles are indexed by ``q_head // group_size``, so grouped query
  heads reuse the same staged KV tile.

Validated against ``ref.reference_attention`` in interpret mode on the
host; ``tests/test_tpu_compile.py`` compiles it for a TPU v5e.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,        # VMEM tiles
    o_ref,                      # output tile
    acc_ref, m_ref, l_ref,      # VMEM scratch carried across kv steps
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_kv: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    kv_start = ki * block_kv
    run = (kv_start <= q_start + block_q - 1) if causal else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale   # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        # zero out-of-range kv rows: edge blocks are padded with
        # undefined values (NaN in interpret mode) and 0 * NaN = NaN in
        # the p @ v product even under a fully-masked softmax.
        kv_row = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0
        )
        v = jnp.where(kv_row < kv_len, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # (bq, bkv)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        mask = kv_pos < kv_len
        if causal:
            mask &= kv_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

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

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,   # (B, T, H, D)
    k: jnp.ndarray,   # (B, S, KV, D)
    v: jnp.ndarray,   # (B, S, KV, D)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, T, H, D) attention output."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, "q heads must be a multiple of kv heads"
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, T)
    block_kv = min(block_kv, S)
    n_q = pl.cdiv(T, block_q)
    n_kv = pl.cdiv(S, block_kv)

    qh = jnp.moveaxis(q, 2, 1)   # (B, H, T, D)
    kh = jnp.moveaxis(k, 2, 1)   # (B, KV, S, D)
    vh = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=S,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D), lambda b, h, qi, ki: (b, h // G, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D), lambda b, h, qi, ki: (b, h // G, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.moveaxis(out, 1, 2)  # (B, T, H, D)
