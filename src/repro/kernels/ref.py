"""Pure-jnp oracles for every Pallas kernel. These are the ground truth
the interpret-mode kernels are asserted against (shape/dtype sweeps in
``tests/test_kernels_*.py``), and double as documentation of the exact
semantics."""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def reference_attention(
    q: jnp.ndarray,   # (B, T, H, D)
    k: jnp.ndarray,   # (B, S, KV, D)
    v: jnp.ndarray,   # (B, S, KV, Dv)
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,   # (B,)
) -> jnp.ndarray:
    """Dense masked softmax attention with GQA broadcast."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, D) * sm_scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->btkgs", qf, kf)
    mask = jnp.ones((B, T, S), bool)
    if causal:
        mask &= jnp.arange(S)[None, None, :] <= jnp.arange(T)[None, :, None]
    if kv_valid_len is not None:
        mask &= jnp.arange(S)[None, None, :] < kv_valid_len[:, None, None]
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskv->btkgv", p, vf)
    return out.reshape(B, T, H, -1).astype(q.dtype)


def reference_attention_with_lse(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, sm_scale: Optional[float] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Non-causal attention returning (out, logsumexp) — the merge
    primitive for shared-prefix attention. lse: (B, T, H)."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, D) * sm_scale
    s = jnp.einsum("btkgd,bskd->btkgs", qf, k.astype(jnp.float32))
    if kv_valid_len is not None:
        mask = jnp.arange(S)[None, :] < kv_valid_len[:, None]
        s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)                 # (B,T,KV,G)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("btkgs,bskv->btkgv", p, v.astype(jnp.float32))
    return (
        out.reshape(B, T, H, -1),
        lse.reshape(B, T, H),
    )


def lse_merge(
    out_a: jnp.ndarray, lse_a: jnp.ndarray,
    out_b: jnp.ndarray, lse_b: jnp.ndarray,
) -> jnp.ndarray:
    """Merge two attention partials over disjoint KV sets."""
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)[..., None]
    wb = jnp.exp(lse_b - m)[..., None]
    return (out_a * wa + out_b * wb) / (wa + wb)


def reference_paged_attention(
    q: jnp.ndarray,            # (B, H, D)
    k_pages: jnp.ndarray,      # (KV, P, page, D)
    v_pages: jnp.ndarray,      # (KV, P, page, D)
    block_tables: jnp.ndarray, # (B, pages_per_seq) int32
    context_lens: jnp.ndarray, # (B,) int32
    *,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Decode attention over a paged physical KV pool (the device-side
    "shared cache"): each sequence reads its logical pages through its
    block table; physical pages may be shared across sequences."""
    B, H, D = q.shape
    KV, P, page, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    S = pages_per_seq * page
    # gather logical KV: (B, KV, S, D)
    k = jnp.moveaxis(k_pages[:, block_tables], 0, 1).reshape(B, KV, S, D)
    v = jnp.moveaxis(v_pages[:, block_tables], 0, 1).reshape(B, KV, S, D)
    out = reference_attention(
        q[:, None],                        # (B, 1, H, D)
        jnp.moveaxis(k, 1, 2),             # (B, S, KV, D)
        jnp.moveaxis(v, 1, 2),
        causal=False,
        sm_scale=sm_scale,
        kv_valid_len=context_lens,
    )
    return out[:, 0]


def reference_shared_prefix_attention(
    q: jnp.ndarray,            # (P, M, H, D) queries grouped by prefix
    prefix_k: jnp.ndarray,     # (P, S, KV, D) one physical copy per prefix
    prefix_v: jnp.ndarray,     # (P, S, KV, D)
    prefix_lens: jnp.ndarray,  # (P,) valid length of each prefix
    *,
    sm_scale: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped shared-prefix attention (the paper's object sharing at the
    kernel level): all M queries of a group attend the group's single
    physical prefix KV. Returns (out (P,M,H,Dv), lse (P,M,H)) for LSE
    merging with per-request suffix attention."""
    return reference_attention_with_lse(
        q, prefix_k, prefix_v, sm_scale=sm_scale, kv_valid_len=prefix_lens
    )


def reference_shared_prefix_decode(
    q: jnp.ndarray,            # (P, M, H, D) queries grouped by prefix
    prefix_k: jnp.ndarray,     # (P, S, KV, D) one physical copy per prefix
    prefix_v: jnp.ndarray,     # (P, S, KV, D)
    prefix_lens: jnp.ndarray,  # (P,)
    suffix_k: jnp.ndarray,     # (P, M, Ss, KV, D) per-request suffixes
    suffix_v: jnp.ndarray,     # (P, M, Ss, KV, D)
    suffix_lens: jnp.ndarray,  # (P, M)
    *,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Shared-prefix decode as ONE dense attention per request over its
    prefix's valid KV followed by its own valid suffix KV — what the
    kernel computes in two LSE-merged parts. Returns (P, M, H, D)."""
    P, M, H, D = q.shape
    S, KV = prefix_k.shape[1], prefix_k.shape[2]
    Ss = suffix_k.shape[2]
    # position j of the concatenation reads prefix row j below the prefix
    # length and suffix row j - prefix_len from there on
    j = jnp.arange(S + Ss)[None, None, :]
    plen = prefix_lens[:, None, None]
    src = jnp.where(j < plen, j, S + j - plen)
    src = jnp.broadcast_to(jnp.minimum(src, S + Ss - 1), (P, M, S + Ss))

    def concat(prefix, suffix):
        both = jnp.concatenate(
            [jnp.broadcast_to(prefix[:, None], (P, M, S, KV, D)), suffix], 2
        )
        rows = jnp.take_along_axis(both, src[..., None, None], axis=2)
        return rows.reshape(P * M, S + Ss, KV, D)

    out = reference_attention(
        q.reshape(P * M, 1, H, D),
        concat(prefix_k, suffix_k),
        concat(prefix_v, suffix_v),
        causal=False,
        sm_scale=sm_scale,
        kv_valid_len=(plen[:, :, 0] + suffix_lens).reshape(P * M),
    )
    return out.reshape(P, M, H, D)
