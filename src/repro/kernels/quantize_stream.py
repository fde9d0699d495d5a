"""Streaming int8 quantization — the Streaming Compute block's in-flight
gradient compression kernel (DESIGN.md §2: SC = transform bytes in flight).

Data is processed in packet-sized chunks, exactly how the SC block sees
AXI4-Stream beats: grid over chunks, each chunk quantized independently
with its own fp32 scale (max-abs / 127). The chunked layout means a
gradient bucket can be compressed as it streams into a collective without
a global reduction over the tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


#: scale = amax * (1/127) as an EXPLICIT fp32 multiply: XLA rewrites a
#: division by the constant 127 into a reciprocal multiply under jit but
#: not eagerly (1 ULP apart), so spelling the multiply out keeps the
#: kernel bit-identical to ``ref.ref_quantize`` in every compilation
#: mode — the byte contract the streaming quantize handler is gated on.
#: (A plain Python float of the exact fp32 reciprocal: Pallas kernels
#: cannot capture traced array constants.)
INV_QMAX = float(np.float32(1.0) / np.float32(127.0))


#: rows per grid step are a multiple of this: the sublane tiling of the
#: int8 values (f32 needs 8 rows, bf16 16, int8 32), so Mosaic accepts
#: every block of every operand.
ROW_TILE = 32
#: f32 input bytes per grid step. Bounds the VMEM a step holds (input,
#: int8 values and lane-padded scales, each double-buffered) whatever the
#: row count.
BLOCK_BYTES = 1 << 18


def block_rows(n: int, chunk: int) -> int:
    """Rows per grid step for ``n`` chunks of ``chunk`` lanes."""
    cap = max(ROW_TILE, BLOCK_BYTES // (4 * chunk) // ROW_TILE * ROW_TILE)
    return min(cap, -(-n // ROW_TILE) * ROW_TILE)


def _pad_rows(a: jax.Array, rows: int, value) -> jax.Array:
    pad = rows - a.shape[0]
    return a if pad == 0 else jnp.pad(a, ((0, pad), (0, 0)),
                                      constant_values=value)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                 # (rows, chunk)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax * INV_QMAX)  # (rows, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(out_dtype)


def quantize_stream(x: jax.Array, *, chunk: int = 1024,
                    interpret: bool = False):
    """x: (n, chunk) -> (int8 values (n, chunk), scales (n, 1)).

    Each row quantizes on its own, so the rows padded up to a whole
    number of blocks never change a live row's bytes.
    ``ops.compress`` handles padding/reshape of arbitrary pytrees.
    """
    assert x.ndim == 2 and x.shape[1] == chunk, x.shape
    n = x.shape[0]
    br = block_rows(n, chunk)
    rows = -(-n // br) * br
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, chunk), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_pad_rows(x, rows, 0))
    return q[:n], s[:n]


def dequantize_stream(q: jax.Array, scales: jax.Array, *,
                      out_dtype=jnp.float32, interpret: bool = False):
    """(int8 (n, chunk), scales (n, 1)) -> (n, chunk) ``out_dtype``."""
    n, chunk = q.shape
    br = block_rows(n, chunk)
    rows = -(-n // br) * br
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=out_dtype),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), out_dtype),
        interpret=interpret,
    )(_pad_rows(q, rows, 0), _pad_rows(scales, rows, 1.0))
    return out[:n]
