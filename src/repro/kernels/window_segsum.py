"""Pallas TPU kernel: the sorted-rows Z build, accumulated window by window.

For elements sorted by dense local row id, ``Z[rows[e]] += kron(a[e], b[e])``
is computed without a scatter and without holding Z in VMEM. Z stays in
HBM; the kernel visits it in aligned windows of ``W = 128`` rows:

    Z[w·W : w·W+W, :] += onehot(rows − w·W)ᵀ @ C,   C[e, i·Kb + j] = a[e, i] · b[e, j]

(the one-hot as ``(W, block_e)`` and C held as Cᵀ, ``(K̂, block_e)``).

Grid: one step per (element block, row window) pair in which the block of
``block_e`` elements has an element in the window. Sorted rows make the
pairs one sequence that is non-decreasing in both block and window, at
most ``ceil(E / block_e) + ceil(R / W) − 1`` long; it is computed on the
device once per mode (``mode_pairs``), sliced per chunk (``chunk_pairs``)
and passed by scalar prefetch, and the grid is as long as the chunk's
pairs are (a dynamic grid bound). The output block is the
pair's ``(W, K̂)`` window, so consecutive pairs on one window keep it
resident in VMEM and Pallas writes it back once, when the window changes.
Z is an aliased input: a window is read on its first visit and added to,
and a window no pair visits keeps what Z held (zero on the first chunk of
a mode, the earlier chunks' sum after that). VMEM per launch depends on
``block_e`` and K̂ only (``WindowGeometry.vmem_bytes``), not on R.

Where a ``(W, K̂)`` window and the K̂-row expansion matrices would not fit
VMEM (K̂ = 10⁴ at five modes of K = 10), the build runs over column panels
of Z: one launch per panel, each the launch above for ``panel`` columns of
Z, the ``panel / Kb`` rows of ``aᵀ`` that make them and expansion matrices
of ``panel`` rows. A panel's width is a multiple of 128 lanes and of
``8·Kb`` (whole, sublane-aligned rows of ``aᵀ``); the last panel may
overhang K̂, and its rows of ``aᵀ`` past Ka read as zero. Z stays one
``(R, K̂)`` array, aliased through the launches.

C is built K̂ = Ka·Kb rows deep, element by element in lanes (``Cᵀ``), with
no padding of Kb: the kernel takes ``a`` and ``b`` as ``(Ka, E)`` and
``(Kb, E)``, elements along lanes, so that the XLA gathers that make them
write dense rows instead of 10 used lanes in 128. ``a`` and ``b`` enter
in f32 and are cut there into bfloat16 pieces (``hi, mid, lo``, each the
next 8 significant bits of the remainder, cut by masking the f32 bit
pattern, so that their sum is the value exactly whatever a compiler does
with a float round trip). One matmul per piece against a constant 0/1
expansion matrix sums the pieces of ``a[i, e]`` into every row ``i·Kb +
j`` (and of ``b[j, e]`` likewise): each row receives exact products, so
the expansions are exact and Cᵀ is the f32 product ``a[i, e]·b[j, e]``,
bit for bit as ``ref.kron_segsum_ref`` forms it. Cᵀ is cut again into
pieces for the one-hot matmul, which contracts the elements of both
operands: the one-hot is exact in bfloat16, so each piece's product is
exact and the MXU sums them in f32. Under ``precision="bf16"`` ``a``,
``b`` and C are each rounded once to bfloat16: the mixed-precision
contract of ``kron_segsum``.

Validated against ``ref.kron_segsum_ref`` in interpret mode (CPU) and
compiled for a described v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kron_segsum import DEFAULT_SCOPED_VMEM, LANE, ROW_BLOCK, _lanes

__all__ = ["window_segsum", "window_geometry", "panel_widths",
           "window_pairs", "mode_pairs", "chunk_pairs", "pair_count", "split_pieces",
           "expansion_matrices", "packed_ct", "WindowGeometry", "WINDOW"]

WINDOW = ROW_BLOCK  # rows of Z one grid step updates
# elements per block times K̂'s lanes: sizes the per-step C and its
# temporaries (~16 B per entry) to a few MiB of VMEM whatever K̂ is
_BLOCK_ENTRIES = 1 << 18
_BLOCK_MIN, _BLOCK_MAX = 128, 2048


def _sublanes(n: int, itemsize: int) -> int:
    tile = 8 * (4 // itemsize)  # 8 rows of f32, 16 of bf16
    return -(-n // tile) * tile


@dataclasses.dataclass(frozen=True)
class WindowGeometry:
    """Block size, column panel and VMEM footprint of one windowed launch.

    The admission gate (``ops.windowed_geometry``) and the kernel derive
    their shapes from here. Nothing depends on the number of rows.
    """

    Ka: int
    Kb: int
    block_e: int
    pieces: int  # bf16 pieces per f32 operand: 3 (f32), 1 (bf16)
    panel: int  # columns of Z one launch builds (K̂: a single launch)

    @property
    def khat(self) -> int:
        return self.Ka * self.Kb

    @property
    def panels(self) -> int:
        """Launches per chunk: column panels covering K̂."""
        return -(-self.khat // self.panel)

    @property
    def panel_rows(self) -> int:
        """Rows of ``aᵀ`` one launch reads (Ka for a single launch)."""
        return self.panel // self.Kb

    @property
    def vmem_bytes(self) -> int:
        """VMEM one launch allocates, as Mosaic lays it out:

        * double-buffered inputs: the rows block (one row, 8 sublanes),
          the f32 ``aᵀ`` and ``bᵀ`` blocks, the two bf16 expansion
          matrices, the Z window read;
        * the double-buffered Z window written;
        * the Cᵀ pieces (bf16 scratch);
        * temporaries: the operand pieces, the two f32 expansions, Cᵀ and
          its remainder, the one-hot and its compare, the window update.
        """
        B, W, p = self.block_e, self.panel, self.pieces
        kl = _lanes(W)
        ab_rows = _sublanes(self.panel_rows, 4) + _sublanes(self.Kb, 4)
        inputs = 2 * (8 * B * 4 + ab_rows * B * 4
                      + _sublanes(W, 2)
                      * (_lanes(self.panel_rows) + _lanes(self.Kb)) * 2)
        z_windows = 2 * 2 * WINDOW * kl * 4
        c_pieces = p * _sublanes(W, 2) * B * 2
        temps = (ab_rows * B * (4 + 2 * 3) + 4 * _sublanes(W, 4) * B * 4
                 + WINDOW * B * (4 + 2) + 2 * WINDOW * kl * 4)
        return inputs + z_windows + c_pieces + temps

    @property
    def vmem_limit_bytes(self) -> int:
        """Scoped-VMEM limit asked of Mosaic: the footprint with a quarter
        of headroom, never below Mosaic's own default."""
        return max(DEFAULT_SCOPED_VMEM, self.vmem_bytes * 5 // 4)


def window_geometry(Ka: int, Kb: int, precision: str = "f32",
                    panel: int | None = None) -> WindowGeometry:
    """The launch geometry for K̂ = Ka·Kb built ``panel`` columns a launch
    (None: all K̂ in one). ``block_e`` is the power of two in [128, 2048]
    nearest below ``2**18 / lanes(panel)`` (2,048 elements at K̂ = 100,
    256 at K̂ = 1,000)."""
    panel = Ka * Kb if panel is None else int(panel)
    if panel != Ka * Kb and (panel % math.lcm(LANE, 8 * Kb)
                             or panel >= Ka * Kb):
        raise ValueError(f"a panel of {panel} columns is no multiple of "
                         f"lcm(128, 8·{Kb}) below K̂ = {Ka * Kb}")
    block_e = _BLOCK_MAX
    while block_e > _BLOCK_MIN and block_e * _lanes(panel) > _BLOCK_ENTRIES:
        block_e //= 2
    return WindowGeometry(Ka=Ka, Kb=Kb, block_e=block_e,
                          pieces=1 if precision == "bf16" else 3,
                          panel=panel)


def panel_widths(Ka: int, Kb: int) -> list[int]:
    """The column panels narrower than K̂ a launch can take, widest first:
    multiples of ``lcm(128, 8·Kb)`` whose ``aᵀ`` rows fill at most one
    128-deep MXU pass, so that the expansion of ``aᵀ`` costs no more than
    the one-hot product."""
    unit = math.lcm(LANE, 8 * Kb)
    widest = LANE * Kb // unit * unit
    return [w for w in range(widest, 0, -unit) if w < Ka * Kb]


def _truncate_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` (f32) with the low 16 bits of its pattern cleared: the leading
    8 significant bits, a value bfloat16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_pieces(x: jnp.ndarray, pieces: int) -> list[jnp.ndarray]:
    """``x`` (f32) as ``pieces`` bfloat16 arrays of its shape.

    Three pieces are ``hi, mid, lo``, each the leading 8 significant bits
    of what the earlier ones leave, so ``hi + mid + lo == x`` exactly for
    normal f32 values. The pieces are cut by masking the bit pattern,
    never by a float round trip through bfloat16, which a compiler may
    drop as excess precision. One piece is ``x`` rounded to bfloat16.
    """
    x = x.astype(jnp.float32)
    if pieces == 1:
        return [x.astype(jnp.bfloat16)]
    out, rest = [], x
    for k in range(pieces):
        piece = _truncate_bf16(rest) if k + 1 < pieces else rest
        out.append(piece.astype(jnp.bfloat16))
        rest = rest - piece
    return out


def expansion_matrices(Ka: int, Kb: int) -> tuple[np.ndarray, np.ndarray]:
    """0/1 bfloat16 matrices ``(K̂, Ka)`` and ``(K̂, Kb)``: row ``i·Kb + j``
    of the first has its one in column ``i``, of the second in column
    ``j``. ``Ea @ aᵀ`` repeats each row of ``aᵀ`` Kb times; ``Eb @ bᵀ``
    tiles ``bᵀ`` Ka times. Host constants, so a traced build holds them as
    literals."""
    row = np.arange(Ka * Kb)[:, None]
    ea = row // Kb == np.arange(Ka)[None, :]
    eb = row % Kb == np.arange(Kb)[None, :]
    return ea.astype(jnp.bfloat16), eb.astype(jnp.bfloat16)


def _dot(x, y, contract=((1,), (0,))):
    """bf16 operands, f32 sums: one MXU pass, whatever default matmul
    precision the caller traces under (Mosaic refuses an fp32 contraction
    of bf16 operands)."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _expand(e, xt, pieces: int):
    """``e @ xt`` for a 0/1 ``e`` and f32 ``xt``, exact: one matmul per
    piece of ``xt``; each output entry sums one entry's pieces."""
    parts = split_pieces(xt, pieces)
    out = _dot(e, parts[0])
    for part in parts[1:]:
        out = out + _dot(e, part)
    return out


def packed_ct(at, bt, ea, eb, pieces: int = 3) -> jnp.ndarray:
    """Cᵀ[i·Kb + j, e] = a[i, e]·b[j, e] in f32 (``pieces`` = 3) from f32
    ``aᵀ`` (Ka, E) and ``bᵀ`` (Kb, E): the kernel's Cᵀ, usable outside a
    kernel too. With one piece, ``a`` and ``b`` are rounded to bfloat16
    first."""
    return _expand(ea, at, pieces) * _expand(eb, bt, pieces)


def mode_pairs(rows: jnp.ndarray, block_e: int, num_rows: int):
    """The (element block, row window) pairs of all of a mode's sorted
    ``rows``, computed once for every chunk of the build (``chunk_pairs``).

    Blocks are the consecutive ``block_e``-element blocks of ``rows``; a
    last partial block is padded with its last row, as the kernel pads it.
    Every window between a block's first and last row is a pair; with dense
    row ids each of them holds an element of the block. Returns ``(blk,
    win, begins, ends)``: the pairs in order (block ascending, then window)
    followed by repeats of the last pair, ``ceil(E / block_e) + 2·ceil(R /
    W)`` entries, and each block's first and past-the-last pair index.
    """
    E = rows.shape[0]
    nb = -(-E // block_e)
    nw = max(-(-num_rows // WINDOW), 1)
    first = rows[::block_e] // WINDOW
    last = rows[np.minimum(np.arange(nb) * block_e + block_e - 1,
                           E - 1)] // WINDOW
    count = last - first + 1
    ends = jnp.cumsum(count)
    p = jnp.arange(nb + 2 * nw, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(ends, p, side="right"), nb - 1)
    win = first[blk] + p - (ends[blk] - count[blk])
    real = p < ends[-1]
    blk = jnp.where(real, blk, nb - 1).astype(jnp.int32)
    win = jnp.where(real, win, last[-1]).astype(jnp.int32)
    return blk, win, (ends - count).astype(jnp.int32), ends.astype(jnp.int32)


def chunk_pairs(pairs, start, size: int, block_e: int, num_rows: int):
    """The pairs of the ``size`` elements from element ``start`` (a
    multiple of ``block_e``; traced in a loop) out of ``mode_pairs``.

    Returns ``(blk, win, n)``: block indices within the chunk and windows,
    two int32 arrays of the static bound ``ceil(size / block_e) + ceil(R /
    W) − 1`` (what a chunk's pairs can number), and the count ``n`` of the
    chunk's pairs, which lead the arrays.
    """
    blk, win, begins, ends = pairs
    nbc = -(-size // block_e)
    bound = nbc + max(-(-num_rows // WINDOW), 1) - 1
    b0 = start // block_e
    p0 = begins[b0]
    n = ends[b0 + nbc - 1] - p0
    blk = jnp.clip(jax.lax.dynamic_slice_in_dim(blk, p0, bound) - b0,
                   0, nbc - 1)
    return blk, jax.lax.dynamic_slice_in_dim(win, p0, bound), n


def window_pairs(rows: jnp.ndarray, block_e: int, num_rows: int):
    """``chunk_pairs`` of ``rows`` taken as one chunk: ``(blk, win, n)``,
    with the entries past ``n`` repeating the last pair."""
    return chunk_pairs(mode_pairs(rows, block_e, num_rows), 0,
                       rows.shape[0], block_e, num_rows)


def pair_count(rows, block_e: int) -> int:
    """Host count of the pairs ``mode_pairs`` gives for sorted ``rows``
    (numpy)."""
    rows = np.asarray(rows)
    if rows.size == 0:
        return 0
    first = rows[::block_e] // WINDOW
    last_idx = np.minimum(np.arange(len(first)) * block_e + block_e - 1,
                          rows.size - 1)
    return int((rows[last_idx] // WINDOW - first + 1).sum())


_ELEMENTS = ((1,), (1,))  # one-hot (W, B) against Cᵀ (K̂, B): contract B


def _kernel(blk_ref, win_ref, rows_ref, at_ref, bt_ref, ea_ref, eb_ref,
            zin_ref, zout_ref, c_ref, *, pieces: int, block_e: int,
            a_rows: int | None):
    p = pl.program_id(0)
    prev = jnp.maximum(p - 1, 0)
    win = win_ref[p]

    @pl.when((p == 0) | (blk_ref[p] != blk_ref[prev]))
    def _build_c():
        at = at_ref[...]
        if a_rows is not None:  # a last panel past Ka: those rows are zero
            at = jnp.where(jax.lax.broadcasted_iota(jnp.int32, at.shape, 0)
                           < a_rows, at, 0.0)
        ct = packed_ct(at, bt_ref[...], ea_ref[...], eb_ref[...], pieces)
        for k, piece in enumerate(split_pieces(ct, pieces)):
            c_ref[k] = piece

    @pl.when((p == 0) | (win != win_ref[prev]))
    def _first_visit():
        zout_ref[...] = zin_ref[...]

    local = rows_ref[...] - win * WINDOW  # (1, block_e)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (WINDOW, block_e), 0)
              == local).astype(jnp.bfloat16)
    upd = _dot(onehot, c_ref[0], _ELEMENTS)
    for k in range(1, pieces):
        upd = upd + _dot(onehot, c_ref[k], _ELEMENTS)
    zout_ref[...] += upd


@functools.partial(jax.jit,
                   static_argnames=("interpret", "precision", "panel"))
def window_segsum(
    rows: jnp.ndarray,  # (E,) int32 — dense local row ids, SORTED ascending
    a: jnp.ndarray,  # (E, Ka) float32 — values folded in
    b: jnp.ndarray,  # (E, Kb) float32
    z: jnp.ndarray,  # (R, Ka*Kb) float32 — accumulated into
    pairs: tuple | None = None,  # chunk_pairs of these rows, if computed
    *,
    interpret: bool = True,
    precision: str = "f32",
    panel: int | None = None,
) -> jnp.ndarray:
    """``z + segment_sum(kron(a, b), rows)``, shape (R, Ka·Kb).

    ``rows`` sorted ascending in [0, R); padding elements carry a == 0 and
    the last real row id (the partition contract). ``z`` is the kernel's
    aliased output, so XLA updates it in place where its buffer is free
    to reuse (a loop carry). ``pairs`` are the rows' ``chunk_pairs`` where
    a chunked build computed them for the whole mode (``window_pairs`` of
    the rows otherwise). ``a`` and ``b`` reach the kernel transposed, so a
    caller that gathers them lets XLA lay the gathers out element-major.
    ``panel`` is the columns of Z one launch builds (``panel_widths``;
    None: all of them in one launch).
    """
    E, Ka = a.shape
    Kb = b.shape[1]
    R, khat = z.shape
    if E == 0:
        return z
    geom = window_geometry(Ka, Kb, precision, panel)
    B, pieces, W, rows_a = (geom.block_e, geom.pieces, geom.panel,
                            geom.panel_rows)

    E_pad = -(-E // B) * B
    at, bt = a.astype(jnp.float32).T, b.astype(jnp.float32).T
    if E_pad != E:
        pad = E_pad - E
        rows = jnp.concatenate([rows, jnp.full((pad,), rows[-1], rows.dtype)])
        at = jnp.pad(at, ((0, 0), (0, pad)))
        bt = jnp.pad(bt, ((0, 0), (0, pad)))
    rows = rows.astype(jnp.int32)
    blk, win, n_pairs = window_pairs(rows, B, R) if pairs is None else pairs
    # every panel starts on a row of aᵀ, so all take the same matrices
    ea, eb = expansion_matrices(rows_a, Kb)
    z = z.astype(jnp.float32)
    for c in range(geom.panels):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_pairs,),
            in_specs=[
                pl.BlockSpec((1, B), lambda p, blk, win: (0, blk[p])),  # rows
                pl.BlockSpec((rows_a, B),
                             lambda p, blk, win, c=c: (c, blk[p])),
                pl.BlockSpec((Kb, B), lambda p, blk, win: (0, blk[p])),
                pl.BlockSpec((W, rows_a), lambda p, blk, win: (0, 0)),
                pl.BlockSpec((W, Kb), lambda p, blk, win: (0, 0)),
                pl.BlockSpec((WINDOW, W),
                             lambda p, blk, win, c=c: (win[p], c)),
            ],
            out_specs=pl.BlockSpec((WINDOW, W),
                                   lambda p, blk, win, c=c: (win[p], c)),
            scratch_shapes=[pltpu.VMEM((pieces, W, B), jnp.bfloat16)],
        )
        a_rows = Ka - c * rows_a
        z = pl.pallas_call(
            functools.partial(_kernel, pieces=pieces, block_e=B,
                              a_rows=a_rows if a_rows < rows_a else None),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((R, khat), jnp.float32),
            input_output_aliases={7: 0},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=geom.vmem_limit_bytes),
            name="window_segsum",
        )(blk, win, rows[None, :], at, bt, ea, eb, z)
    return z
