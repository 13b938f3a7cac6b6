"""Pallas TPU kernel: fused Kronecker-contribution + segment-sum (TTM build).

This is the compute hot spot of HOOI (paper §4.3): for every non-zero element
e, accumulate ``val(e) * kron(a_e, b_e)`` into row ``rows[e]`` of the local
penultimate matrix Z^p. On a GPU/CPU this is a scatter-add; scatter-add is
hostile to the TPU's systolic MXU, so we *reformulate segment-sum as a
one-hot matmul* (the TPU-native adaptation, see DESIGN.md §2):

    Z[rb*128 : rb*128+128, :] += onehot(rows)ᵀ @ C,   C = kron(a_blk, b_blk)

Key structural facts exploited:

  * elements are sorted by dense-renumbered local row id, so one block of
    ``block_e`` elements touches at most ``span = block_e//128 + 2``
    consecutive 128-row blocks (proof: interior rows of a sorted dense-id
    block are fully contained in it);
  * the whole (R_pad, Ka, Kb_blk) Z tile is held in VMEM with a grid-constant
    output index over the inner (element-block) grid dimension, so
    accumulation across grid steps is the canonical safe Pallas pattern
    (no aliasing, no revisits after eviction);
  * the one-hot matmul (128 x block_e) @ (block_e x Ka*Kb_blk) runs on the
    MXU with hardware-aligned dims (128 rows, block_e and Kb_blk multiples
    of 128).

Grid: (n_kb, n_eb) — Kb blocks outer (Z tile changes rarely), element blocks
inner (Z tile constant, stays resident). Scalar-prefetched ``first_rb`` gives
each element block its first row-block so only ``span`` row windows are
updated per step (total MXU work ≈ span·128/block_e ≈ 1.5x the minimal
E·K̂ MACs, versus a fully dense one-hot matmul's R_pad/128 x blowup).

VMEM per launch: the double-buffered Z tile plus element-block temporaries
(``TileGeometry.vmem_bytes``); ops.py admits launches within its budget and
falls back to the jnp reference beyond that (large-R cases are sharded
across devices anyway — Lite's R_max <= ceil(L/P)+2 bound is precisely what
keeps R_pad small per device), and the kernel asks Mosaic for a matching
scoped-VMEM limit.

Validated against ref.kron_segsum_ref in interpret mode (CPU) across shape/
dtype sweeps, and compiled for a described v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["kron_segsum", "kron_segsum_oracle", "tile_geometry",
           "TileGeometry", "ROW_BLOCK"]

ROW_BLOCK = 128
LANE = 128  # minor-dimension tile: narrower blocks pad up to it in VMEM
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024  # Mosaic's limit when none is given


@dataclasses.dataclass(frozen=True)
class TileGeometry:
    """Padded tile shapes + VMEM footprint of one kernel launch.

    Single source of truth for the padding math: the VMEM admission gate
    (``ops.kernel_fits_vmem``) and the kernel itself both derive their shapes
    from here, so the gate can never drift from what the kernel allocates.
    """

    Ka: int
    block_e: int
    span: int  # 128-row windows one element block can touch
    R_pad: int  # Z-tile rows (num_rows rounded up + span slack)
    kb_blk: int  # Kb block held per grid step
    Kb_pad: int  # Kb rounded up to a multiple of kb_blk
    itemsize: int = 4  # bytes per kron-contribution element (2 under bf16)
    oracle_s: int = 0  # fused oracle panel width (0 = plain build)

    @property
    def vmem_bytes(self) -> int:
        """VMEM one launch allocates, as Mosaic lays it out:

        * the f32 Z tile — a pipelined output block, so double-buffered;
        * the element block: its double-buffered inputs (rows, a, b, each
          padded to 128 lanes), the f32 kron product, C at the
          contribution itemsize, and three f32 (128, Ka*kb_blk) row-window
          temporaries (the one-hot update, the Z read and their sum);
        * with the fused oracle: the double-buffered X slab and (R_pad, s)
          accumulator (s padded to 128 lanes) and one row block's product.

        For the nell2-s mode-0 launch (R_pad 1792, Ka 10, kb_blk 128) this
        is 22.6 MiB; a v5e compile allocated 22.3 MiB.
        """
        width = self.Ka * self.kb_blk
        z_tile = 2 * self.R_pad * width * 4
        inputs = 2 * self.block_e * (LANE + _lanes(self.Ka) + self.kb_blk) * 4
        elems = (self.block_e * width * (4 + self.itemsize)
                 + 3 * ROW_BLOCK * width * 4)
        oracle = 0
        if self.oracle_s:
            s = _lanes(self.oracle_s)
            oracle = (2 * width * s * 4 + 2 * self.R_pad * s * 4
                      + ROW_BLOCK * (width + s) * 4)
        return z_tile + inputs + elems + oracle

    @property
    def vmem_limit_bytes(self) -> int:
        """Scoped-VMEM limit asked of Mosaic: the footprint with a quarter
        of headroom, never below Mosaic's own default."""
        return max(DEFAULT_SCOPED_VMEM, self.vmem_bytes * 5 // 4)


def _lanes(n: int) -> int:
    return -(-n // LANE) * LANE


def tile_geometry(num_rows: int, Ka: int, Kb: int,
                  block_e: int = 256, kb_block: int | None = None,
                  itemsize: int = 4, oracle_s: int = 0
                  ) -> TileGeometry:
    span = block_e // ROW_BLOCK + 2
    kb_blk = kb_block or min(max(-(-Kb // 128) * 128, 128), 512)
    return TileGeometry(
        Ka=Ka,
        block_e=block_e,
        span=span,
        R_pad=-(-num_rows // ROW_BLOCK) * ROW_BLOCK + span * ROW_BLOCK,
        kb_blk=kb_blk,
        Kb_pad=-(-Kb // kb_blk) * kb_blk,
        itemsize=itemsize,
        oracle_s=oracle_s,
    )


def _mxu_precision(dtype):
    """f32 operands contract at full f32 precision (the MXU default is one
    bf16 pass); bf16 operands, the opt-in mixed-precision contract, at the
    default — Mosaic refuses an fp32 contraction of bf16 operands."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _contrib_block(a_ref, b_ref, block_e: int, Ka: int, kb_blk: int):
    """C[e, ka*kb_blk + kb] = a[e, ka] * b[e, kb]  (C-order kron).

    Built in f32 and rounded to the operands' dtype afterwards: Mosaic
    refuses the (block_e, Ka, kb_blk) -> (block_e, Ka*kb_blk) shape cast
    on packed bf16 vectors. A product of two bf16 values is exact in f32,
    so the rounded C equals the bf16 product.
    """
    a = a_ref[...].astype(jnp.float32)  # (block_e, Ka)
    b = b_ref[...].astype(jnp.float32)  # (block_e, kb_blk)
    C = (a[:, :, None] * b[:, None, :]).reshape(block_e, Ka * kb_blk)
    return C.astype(a_ref.dtype)


def _kernel(first_rb_ref, rows_ref, a_ref, b_ref, z_ref, *, span: int,
            block_e: int, Ka: int, kb_blk: int):
    k = pl.program_id(0)  # Kb-block index (outer)
    i = pl.program_id(1)  # element-block index (inner)
    del k  # b/z BlockSpecs already select the Kb block

    @pl.when(i == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    rows = rows_ref[...]  # (block_e, 1) int32, sorted, dense ids
    C = _contrib_block(a_ref, b_ref, block_e, Ka, kb_blk)

    row0 = first_rb_ref[i] * ROW_BLOCK
    local = rows[:, 0] - row0  # (block_e,) in [0, span*128) for real elements
    col = jax.lax.broadcasted_iota(jnp.int32, (block_e, ROW_BLOCK), 1)
    for s in range(span):  # statically unrolled: span is 3-6
        onehot = (local[:, None] == col + s * ROW_BLOCK).astype(C.dtype)
        upd = jax.lax.dot_general(
            onehot, C, (((0,), (0,)), ((), ())),
            precision=_mxu_precision(C.dtype),
            preferred_element_type=jnp.float32,
        )  # (128, Ka*kb_blk) on the MXU
        idx = (pl.dslice(row0 + s * ROW_BLOCK, ROW_BLOCK),
               slice(None), slice(None))
        z_ref[idx] = z_ref[idx] + upd.reshape(ROW_BLOCK, Ka, kb_blk)


def _cast_contrib_operands(a, b, precision):
    """bf16 mixed precision: the kron contribution operands (and hence the
    per-element products) are rounded to bf16; accumulation into the Z tile
    stays f32 via ``preferred_element_type`` on every MXU dot."""
    if precision == "bf16":
        return a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return a, b


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_e", "kb_block", "interpret",
                     "precision"),
)
def kron_segsum(
    rows: jnp.ndarray,  # (E,) int32 — dense local row ids, SORTED ascending
    a: jnp.ndarray,  # (E, Ka) float32 — values folded in
    b: jnp.ndarray,  # (E, Kb) float32
    num_rows: int,
    *,
    block_e: int = 256,
    kb_block: int | None = None,
    interpret: bool = True,
    precision: str = "f32",
) -> jnp.ndarray:
    """Z = segment_sum(kron(a,b), rows) of shape (num_rows, Ka*Kb).

    Requirements: ``rows`` sorted ascending with dense ids in [0, num_rows)
    (padding elements must have a==0 and any valid sorted row id; the wrapper
    in ops.py arranges all of this). ``precision="bf16"`` rounds the kron
    operands to bf16 (halving the C-block VMEM footprint) while the Z tile
    accumulates in f32.
    """
    E, Ka = a.shape
    Kb = b.shape[1]
    if E == 0:
        # an empty grid would never run the @pl.when(i == 0) zero-init, so
        # the output buffer would be uninitialized memory (and rows[-1]
        # below would index an empty array): the sum over no elements is 0
        return jnp.zeros((num_rows, Ka * Kb), jnp.float32)
    itemsize = 2 if precision == "bf16" else 4
    geom = tile_geometry(num_rows, Ka, Kb, block_e, kb_block,
                         itemsize=itemsize)
    span, kb_blk = geom.span, geom.kb_blk
    R_pad, Kb_pad = geom.R_pad, geom.Kb_pad
    a, b = _cast_contrib_operands(a, b, precision)

    # --- padding to hardware-aligned shapes -------------------------------
    E_pad = -(-E // block_e) * block_e

    if E_pad != E:
        pad = E_pad - E
        # pad rows with the *last* row id to keep sortedness; a=0 kills them
        rows = jnp.concatenate([rows, jnp.full((pad,), rows[-1], rows.dtype)])
        a = jnp.concatenate([a, jnp.zeros((pad, Ka), a.dtype)])
        b = jnp.concatenate([b, jnp.ones((pad, Kb), b.dtype)])
    if Kb_pad != Kb:
        b = jnp.pad(b, ((0, 0), (0, Kb_pad - Kb)))

    n_eb = E_pad // block_e
    n_kb = Kb_pad // kb_blk
    first_rb = rows[jnp.arange(n_eb) * block_e] // ROW_BLOCK  # (n_eb,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kb, n_eb),
        in_specs=[
            pl.BlockSpec((block_e, 1), lambda k, i, frb: (i, 0)),  # rows
            pl.BlockSpec((block_e, Ka), lambda k, i, frb: (i, 0)),  # a
            pl.BlockSpec((block_e, kb_blk), lambda k, i, frb: (i, k)),  # b
        ],
        out_specs=pl.BlockSpec(
            (R_pad, Ka, kb_blk), lambda k, i, frb: (0, 0, k)
        ),
    )
    kern = functools.partial(
        _kernel, span=span, block_e=block_e, Ka=Ka, kb_blk=kb_blk
    )
    z3 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R_pad, Ka, Kb_pad), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=geom.vmem_limit_bytes),
    )(first_rb.astype(jnp.int32), rows[:, None].astype(jnp.int32), a, b)
    return z3[:num_rows, :, :Kb].reshape(num_rows, Ka * Kb)


def _kernel_fused(first_rb_ref, rows_ref, a_ref, b_ref, x_ref, z_ref, zx_ref,
                  *, span: int, block_e: int, Ka: int, kb_blk: int,
                  R_pad: int, n_eb: int):
    """kron-segsum accumulation + first oracle panel product, one launch.

    Identical accumulation body to ``_kernel``; when the element-block loop
    finishes a Kb block (the Z tile for that block is complete and still
    VMEM-resident) the kernel immediately multiplies it into the resident X
    panel slab, so the first Lanczos matvec never re-reads Z from HBM. The
    (R_pad, s) accumulator ``zx`` is grid-constant over the whole grid and
    sums the per-Kb-block partial products.
    """
    k = pl.program_id(0)  # Kb-block index (outer)
    i = pl.program_id(1)  # element-block index (inner)

    @pl.when(i == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    @pl.when((k == 0) & (i == 0))
    def _init_zx():
        zx_ref[...] = jnp.zeros_like(zx_ref)

    rows = rows_ref[...]  # (block_e, 1) int32, sorted, dense ids
    C = _contrib_block(a_ref, b_ref, block_e, Ka, kb_blk)

    row0 = first_rb_ref[i] * ROW_BLOCK
    local = rows[:, 0] - row0
    col = jax.lax.broadcasted_iota(jnp.int32, (block_e, ROW_BLOCK), 1)
    for s in range(span):
        onehot = (local[:, None] == col + s * ROW_BLOCK).astype(C.dtype)
        upd = jax.lax.dot_general(
            onehot, C, (((0,), (0,)), ((), ())),
            precision=_mxu_precision(C.dtype),
            preferred_element_type=jnp.float32,
        )
        idx = (pl.dslice(row0 + s * ROW_BLOCK, ROW_BLOCK),
               slice(None), slice(None))
        z_ref[idx] = z_ref[idx] + upd.reshape(ROW_BLOCK, Ka, kb_blk)

    @pl.when(i == n_eb - 1)
    def _oracle():
        # Z tile for Kb block k is final here; contract it with the matching
        # X slab while it is still resident, one 128-row block at a time (a
        # whole-tile value would be one more tile of VMEM). kb_blk is a
        # multiple of 128, so the (128, Ka, kb_blk) -> (128, Ka*kb_blk)
        # reshape is layout-preserving.
        Xf = x_ref[...].reshape(Ka * kb_blk, x_ref.shape[-1])

        def row_block(r, carry):
            rows = pl.ds(pl.multiple_of(r * ROW_BLOCK, ROW_BLOCK), ROW_BLOCK)
            Zb = z_ref[rows, :, :].reshape(ROW_BLOCK, Ka * kb_blk)
            zx_ref[rows, :] += jax.lax.dot_general(
                Zb, Xf, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            return carry

        jax.lax.fori_loop(0, R_pad // ROW_BLOCK, row_block, 0)


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_e", "kb_block", "interpret",
                     "precision"),
)
def kron_segsum_oracle(
    rows: jnp.ndarray,  # (E,) int32 — dense local row ids, SORTED ascending
    a: jnp.ndarray,  # (E, Ka) float32 — values folded in
    b: jnp.ndarray,  # (E, Kb) float32
    num_rows: int,
    X: jnp.ndarray,  # (Ka*Kb, s) float32 — first oracle panel V_1
    *,
    block_e: int = 256,
    kb_block: int | None = None,
    interpret: bool = True,
    precision: str = "f32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Z build + first oracle product: returns ``(Z, Z @ X)``.

    Same contract as ``kron_segsum`` plus the panel ``X``; the product is
    computed from the VMEM-resident Z tile at the end of each Kb block, so
    the first Lanczos pass over Z costs no extra HBM read of Z.
    """
    E, Ka = a.shape
    Kb = b.shape[1]
    s = X.shape[1]
    if E == 0:
        return (jnp.zeros((num_rows, Ka * Kb), jnp.float32),
                jnp.zeros((num_rows, s), jnp.float32))
    itemsize = 2 if precision == "bf16" else 4
    geom = tile_geometry(num_rows, Ka, Kb, block_e, kb_block,
                         itemsize=itemsize, oracle_s=s)
    span, kb_blk = geom.span, geom.kb_blk
    R_pad, Kb_pad = geom.R_pad, geom.Kb_pad
    a, b = _cast_contrib_operands(a, b, precision)

    E_pad = -(-E // block_e) * block_e
    if E_pad != E:
        pad = E_pad - E
        rows = jnp.concatenate([rows, jnp.full((pad,), rows[-1], rows.dtype)])
        a = jnp.concatenate([a, jnp.zeros((pad, Ka), a.dtype)])
        b = jnp.concatenate([b, jnp.ones((pad, Kb), b.dtype)])

    # X enters as (Ka*Kb, s) in C-order (b fastest); regroup per Kb block and
    # zero-pad the Kb tail so pad columns of Z contract against zeros
    X3 = X.astype(jnp.float32).reshape(Ka, Kb, s)
    if Kb_pad != Kb:
        b = jnp.pad(b, ((0, 0), (0, Kb_pad - Kb)))
        X3 = jnp.pad(X3, ((0, 0), (0, Kb_pad - Kb), (0, 0)))

    n_eb = E_pad // block_e
    n_kb = Kb_pad // kb_blk
    first_rb = rows[jnp.arange(n_eb) * block_e] // ROW_BLOCK

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kb, n_eb),
        in_specs=[
            pl.BlockSpec((block_e, 1), lambda k, i, frb: (i, 0)),  # rows
            pl.BlockSpec((block_e, Ka), lambda k, i, frb: (i, 0)),  # a
            pl.BlockSpec((block_e, kb_blk), lambda k, i, frb: (i, k)),  # b
            pl.BlockSpec((Ka, kb_blk, s), lambda k, i, frb: (0, k, 0)),  # X
        ],
        out_specs=[
            pl.BlockSpec((R_pad, Ka, kb_blk), lambda k, i, frb: (0, 0, k)),
            pl.BlockSpec((R_pad, s), lambda k, i, frb: (0, 0)),  # zx acc
        ],
    )
    kern = functools.partial(
        _kernel_fused, span=span, block_e=block_e, Ka=Ka, kb_blk=kb_blk,
        R_pad=R_pad, n_eb=n_eb,
    )
    z3, zx = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R_pad, Ka, Kb_pad), jnp.float32),
            jax.ShapeDtypeStruct((R_pad, s), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=geom.vmem_limit_bytes),
    )(first_rb.astype(jnp.int32), rows[:, None].astype(jnp.int32), a, b, X3)
    return (z3[:num_rows, :, :Kb].reshape(num_rows, Ka * Kb),
            zx[:num_rows, :])
