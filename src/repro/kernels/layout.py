"""TPU memory-layout helpers shared by the kernels and the VMEM audit.

One place for what the (8, 128) tiling means to this repo: how many VMEM
bytes an array really takes (`tile_bytes`, read by the sweep guard in
`kernels.ops` and by `analysis.pallas_footprint`), how the batch is laid
on 128 lanes (`lane_pad`, `pad_lanes`), and which MXU precision keeps an
f32 kernel at f32 accuracy (`mxu_precision`).  The Block-ELL row-panel
layout the SpMV kernel consumes is built with the structure itself
(`core.graph.block_panels`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def tile_bytes(shape, dtype) -> int:
    """VMEM bytes of one array under the TPU (8, 128) memory tiling: the
    minor dim pads to 128 lanes, the second-minor to 8 rows (Mosaic tiles
    16-bit refs (8, 128) in memory too, packing row pairs)."""
    itemsize = np.dtype(dtype).itemsize
    dims = [int(d) for d in shape] or [1]
    minor = dims.pop()
    sub = dims.pop() if dims else 1
    n = (-(-minor // 128) * 128) * (-(-sub // 8) * 8)
    for d in dims:
        n *= d
    return n * itemsize


def vmem_bytes(buffers) -> int:
    """Total tiled VMEM bytes of ``[(shape, dtype), ...]``."""
    return sum(tile_bytes(shape, dt) for shape, dt in buffers)


def lane_pad(batch: int) -> int:
    """The batch rounded up to whole 128-lane vregs (VMEM holds whole
    tiles either way, and Mosaic slices a ref only at 128-lane
    granularity)."""
    return -(-batch // 128) * 128


def pad_lanes(a, width: int):
    """Zero-pad the minor (lane) axis of a 2-D array to `width`."""
    return jnp.pad(a, ((0, 0), (0, width - a.shape[-1])))


def mxu_precision(dtype):
    """Full-precision (multi-pass) MXU products for f32 operands, so the
    kernels match an f32 reference; bf16 operands take the native pass."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)
