"""jit'd public wrapper: model layout [B,T,H,D] <-> kernel layout."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_hm
from repro.utils.backend import pallas_interpret


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, T, H, D]; k, v: [B, S, K, D] (GQA: H = K * group).

    On CPU the kernel body runs in interpret mode (CPU
    validation); on TPU it lowers to Mosaic.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, S, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, S, D)
    out = flash_attention_hm(qh, kh, vh, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)
