"""Pallas TPU kernel: fused Lanczos oracle pair  (Z @ x, Zᵀ @ y).

Every Lanczos bidiagonalization iteration issues the two oracle products
back-to-back (paper §3 'SVD Component'). Z^p is the big operand (R x K̂) and
both products are memory-bound: done naively, Z is streamed from HBM twice
per iteration. Fusing them reads Z once — a straight 2x cut of the dominant
HBM term for the SVD phase.

Design: 1-D grid over 128-row blocks of Z. Per step:
    xo[rb]  = Z_blk @ x          (MXU, 128 x K̂ · K̂)
    yo_acc += Z_blkᵀ @ y[rb]     (MXU, K̂ x 128 · 128)
``yo`` uses a grid-constant output index, so the accumulator tile stays in
VMEM across all steps (canonical safe accumulation pattern). x stays resident
(constant index); y/xo stream block-by-block.

VMEM per step: Z block (128·K̂·4B) + x (K̂·4B) + yo (K̂·4B)  — tiny.
Validated against ref.oracle_pair_ref in interpret mode; TPU-targeted tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["oracle_pair"]

ROW_BLOCK = 128


def _kernel(z_ref, x_ref, y_ref, xo_ref, yo_ref):
    i = pl.program_id(0)
    Z = z_ref[...]  # (128, Khat)
    x = x_ref[...]  # (Khat, s) — s = panel width (1 for the vector oracle)
    y = y_ref[...]  # (128, s)
    xo_ref[...] = jax.lax.dot_general(
        Z, x, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (128, s)
    zty = jax.lax.dot_general(
        Z, y, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (Khat, s)

    @pl.when(i == 0)
    def _init():
        yo_ref[...] = jnp.zeros_like(yo_ref)

    yo_ref[...] += zty


@functools.partial(jax.jit, static_argnames=("interpret",))
def oracle_pair(
    Z: jnp.ndarray,  # (R, Khat) float32
    x: jnp.ndarray,  # (Khat,) or (Khat, s) — panel of right-space directions
    y: jnp.ndarray,  # (R,) or (R, s) — panel of left-space directions
    *,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (Z @ x, Zᵀ @ y) with one pass over Z.

    ``x``/``y`` may be vectors (the classic oracle) or width-``s`` panels
    (block Lanczos): the same grid-constant accumulator services all ``s``
    directions per row block, so the pass count over Z is independent of
    ``s``. Both operands must share the panel width.
    """
    vec_in = x.ndim == 1
    if vec_in:
        x = x[:, None]
        y = y[:, None]
    R, Khat = Z.shape
    s = x.shape[1]
    R_pad = max(-(-R // ROW_BLOCK) * ROW_BLOCK, ROW_BLOCK)
    K_pad = max(-(-Khat // 128) * 128, 128)
    Zp = jnp.pad(Z, ((0, R_pad - R), (0, K_pad - Khat)))
    xp = jnp.pad(x, ((0, K_pad - Khat), (0, 0)))
    yp = jnp.pad(y, ((0, R_pad - R), (0, 0)))
    n_rb = R_pad // ROW_BLOCK

    xo, yo = pl.pallas_call(
        _kernel,
        grid=(n_rb,),
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, K_pad), lambda i: (i, 0)),  # Z
            pl.BlockSpec((K_pad, s), lambda i: (0, 0)),  # x (resident)
            pl.BlockSpec((ROW_BLOCK, s), lambda i: (i, 0)),  # y
        ],
        out_specs=[
            pl.BlockSpec((ROW_BLOCK, s), lambda i: (i, 0)),  # xo
            pl.BlockSpec((K_pad, s), lambda i: (0, 0)),  # yo (accumulator)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R_pad, s), jnp.float32),
            jax.ShapeDtypeStruct((K_pad, s), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(Zp, xp, yp)
    if vec_in:
        return xo[:R, 0], yo[:Khat, 0]
    return xo[:R, :], yo[:Khat, :]
