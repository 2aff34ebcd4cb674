"""Jit'd public wrappers for the Pallas kernels.

The kernels compile to Mosaic for the TPU. ``interpret=True`` runs the
kernel bodies in the Pallas interpreter instead, which is how they are
checked on a host without a TPU; it is never chosen implicitly, so a
kernel that the TPU compiler refuses fails loudly rather than running
interpreted.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash
from .paged_attention import paged_attention as _paged
from .shared_prefix_attention import shared_prefix_attention as _shared_prefix


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv", "interpret"))
def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 128, block_kv: int = 128,
    interpret: bool = False,
):
    """(B,T,H,D) x (B,S,KV,D) -> (B,T,H,D); FA2 tiling, causal block skip."""
    return _flash(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q, k_pages, v_pages, block_tables, context_lens, *,
    interpret: bool = False,
):
    """Decode attention through block tables over the shared physical
    KV pool. (B,H,D) -> (B,H,D)."""
    return _paged(
        q, k_pages, v_pages, block_tables, context_lens, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def shared_prefix_attention(
    q, prefix_k, prefix_v, prefix_lens, *, block_s: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped attention of all requests sharing a prefix against its one
    physical KV copy. Returns (out, lse) for merging."""
    return _shared_prefix(
        q, prefix_k, prefix_v, prefix_lens, block_s=block_s, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def shared_prefix_decode(
    q,                 # (P, M, H, D) grouped queries
    prefix_k, prefix_v, prefix_lens,       # shared objects (one copy each)
    suffix_k, suffix_v,                    # (P, M, Ss, KV, D) per-request
    suffix_lens,                           # (P, M)
    *,
    interpret: bool = False,
):
    """Full object-sharing decode: shared-prefix kernel + per-request
    suffix attention, LSE-merged. The physical prefix KV is read once per
    GROUP (not once per request) — the compute analogue of the paper's
    l_n/|P(n)| apportioning."""
    out_a, lse_a = _shared_prefix(
        q, prefix_k, prefix_v, prefix_lens, interpret=interpret
    )
    P, M, H, D = q.shape
    qf = q.reshape(P * M, 1, H, D)
    Ss, KV = suffix_k.shape[2], suffix_k.shape[3]
    out_b, lse_b = ref.reference_attention_with_lse(
        qf,
        suffix_k.reshape(P * M, Ss, KV, D),
        suffix_v.reshape(P * M, Ss, KV, D),
        kv_valid_len=suffix_lens.reshape(P * M),
    )
    out_b = out_b.reshape(P, M, H, D)
    lse_b = lse_b.reshape(P, M, H)
    return ref.lse_merge(
        out_a.astype(jnp.float32), lse_a, out_b, lse_b
    ).astype(q.dtype)
