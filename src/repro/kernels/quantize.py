"""Fused symmetric int8 quantization Pallas TPU kernel.

The device-side half of the compressed VFL exchange: before a member's
embeddings cross the pod boundary, each (rows-block x d) tile is absmax-
reduced and cast to int8 in ONE pass through VMEM — the un-fused jnp
version reads the tensor twice (absmax, then scale+round) from HBM.

Grid: (padded_rows / block_r,). Per-row scales (row = token) are emitted
alongside the int8 payload as a ``(rows, 1)`` column: Mosaic refuses a
1-D ``(block_r,)`` output block once the grid has more than one step
(its layout tiles 1-D arrays by 1024, the block by ``block_r``). Any
row count works: rows are zero-padded up to a multiple of a block that
is itself a multiple of 8 sublanes, and the padding is sliced off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                    # (block_r, d)
    absmax = jnp.maximum(jnp.abs(x).max(axis=1, keepdims=True), 1e-12)
    scale = absmax / 127.0                                # (block_r, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def quantize_int8(x: jax.Array, *, block_r: int = 256,
                  interpret: bool = False):
    """x: (rows, d) -> (q int8 (rows, d), scale f32 (rows,))."""
    rows, d = x.shape
    block_r = min(block_r, -(-rows // 8) * 8)
    assert block_r % 8 == 0, block_r
    padded = -(-rows // block_r) * block_r
    xp = jnp.pad(x, ((0, padded - rows), (0, 0))) if padded != rows else x
    q, scale = pl.pallas_call(
        _kernel,
        grid=(padded // block_r,),
        in_specs=[pl.BlockSpec((block_r, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, d), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return q[:rows], scale[:rows, 0]
