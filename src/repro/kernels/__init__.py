"""Pallas TPU kernels (validated in interpret mode on CPU) + jnp oracles."""
from . import ops, ref
from .bcsr_spmv import block_ell_spmv_batched
from .cheb_step import cheb_step
from .cheb_sweep import cheb_sweep, jacobi_sweep
from .flash_attention import flash_attention
from .soft_threshold import ista_shrink

__all__ = [
    "ops", "ref", "block_ell_spmv_batched", "cheb_step", "cheb_sweep",
    "jacobi_sweep", "flash_attention", "ista_shrink",
]
