"""Pallas-TPU segment-means reduction (PRISM Eq. 1).

Tiling: grid (B, L, D/TD); each program reduces one [seg, TD] tile of one
segment in VMEM (f32 accumulation on the VPU) and writes a [1, TD] row.
The wrapper views x as [B, L, seg, D] and the output as [B, L, 1, D], so
every block's last two dims are (seg, TD) / (1, TD): ``seg`` is the full
dim (any segment length tiles, e.g. ViT's 50 or 3) and ``TD`` is a lane
multiple (128). The compute is a pure reduction — the kernel's value is
avoiding an HBM round-trip of the [B, L, seg, D] reshape view the jnp path
materializes inside fusions, and fusing the mean with the (1/seg) scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    x = x_ref[0, 0].astype(jnp.float32)                     # [seg, TD]
    o_ref[0, 0] = (jnp.sum(x, axis=0, keepdims=True)
                   / x.shape[0]).astype(o_ref.dtype)        # [1, TD]


@functools.partial(jax.jit, static_argnames=("L", "block_d", "interpret"))
def segment_means_pallas(x: jnp.ndarray, L: int, *, block_d: int = 512,
                         interpret: bool = False) -> jnp.ndarray:
    """[B, N, D] → [B, L, D]; requires N % L == 0 and D % block_d == 0
    (callers pad D to a lane multiple; ops.py picks block_d)."""
    B, N, D = x.shape
    seg = N // L
    td = min(block_d, D)
    assert D % td == 0, (D, td)
    out = pl.pallas_call(
        _kernel,
        grid=(B, L, D // td),
        in_specs=[pl.BlockSpec((1, 1, seg, td),
                               lambda b, l, d: (b, l, 0, d))],
        out_specs=pl.BlockSpec((1, 1, 1, td), lambda b, l, d: (b, l, 0, d)),
        out_shape=jax.ShapeDtypeStruct((B, L, 1, D), x.dtype),
        interpret=interpret,
    )(x.reshape(B, L, seg, D))
    return out.reshape(B, L, D)
