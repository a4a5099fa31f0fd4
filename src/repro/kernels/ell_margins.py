"""Pallas TPU kernel: the loss probe's margins A·x from a layout of the
static matrix built once, whose every shape follows from the matrix's
shapes alone.

The probe's ``ell_matvec`` is one XLA gather of every ELL entry from x
and a row sum; on a v5e XLA runs that gather one element at a time.
Here the entries are regrouped once, by ``margin_layout``, so that each
(8, 128) block of them reads x from one window of ``window_rows`` x 128
columns and adds into one tile of 128 rows per sublane:

  lane-row   up to 128 entries of one row tile (128 rows) whose columns
             fall in one window, in row order, then pads
  chunk      8 lane-rows of one window: one (8, 128) vreg of entries

For each chunk the kernel gathers x in registers (the window's rows
one at a time, broadcast to the vreg, a lane gather each), multiplies
by the values, sums each row's run of lanes with a segmented scan of
seven lane rolls, finds each row's last lane by a binary search over
the sorted row ids (eight lane gathers), picks the row's sum from it,
and adds the 8 lane-rows into their tiles of the margins, which stay in
VMEM with the whole of x. Products and sums are float32; only the order
of the sums differs from ``ell_matvec``.

Every array shape, the grid and each loop bound follow from (rows, n,
width) and the batch of blocks: the capacity is the most lane-rows any
matrix of those shapes can need, ``ceil(rows·width/128)`` plus one per
(tile, window) pair plus 7 per window. Where entries start and end is
data: the chunks in use and the window and row tile of each lane-row are
values, read from SMEM. So every matrix of one shape shares one
compiled program.

``margin_layout`` returns None off a TPU (the probe keeps
``ell_matvec`` there, bitwise as before) and where x would not fit in
VMEM.
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

from repro.kernels.ell_gram import default_interpret

LANES, SUBLANES = 128, 8
_PAD_ROW = 255  # above every row of a tile: pads sort last and join no run
_STEPS = tuple(1 << i for i in range(7))  # lane distances: 1, 2, …, 64
_X_VMEM_BYTES = 64 << 20  # the most of x the kernel holds in VMEM
_INTERLEAVE = 2  # chunks a loop iteration takes, for the scheduler to overlap


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The layout's shapes, from the matrix's shapes alone."""

    rows: int
    n: int
    width: int

    @property
    def window_rows(self) -> int:
        """x rows (of 128 columns) a chunk reads: 16 where a row tile
        would meet fewer than about 93 entries in 1,024 columns, else 8."""
        return 16 if self.n > 2048 * self.width else 8

    @property
    def windows(self) -> int:
        return -(-self.n // (self.window_rows * LANES))

    @property
    def tiles(self) -> int:
        """Row tiles of 128 rows, padded to whole (8, 128) blocks."""
        return -(-self.rows // (LANES * SUBLANES)) * SUBLANES

    @property
    def _lane_rows(self) -> int:
        entries = self.rows * self.width
        pairs = -(-self.rows // LANES) * self.windows
        return -(-entries // LANES) + min(pairs, entries) + (SUBLANES - 1) * self.windows

    @property
    def chunks_per_step(self) -> int:
        """128 chunks a grid step (a rank-1 SMEM block is whole lanes of
        lanes), or all of them, in whole loop iterations."""
        chunks = -(-self._lane_rows // SUBLANES)
        return min(LANES, -(-chunks // _INTERLEAVE) * _INTERLEAVE)

    @property
    def lane_rows(self) -> int:
        """Capacity: the most lane-rows any matrix of these shapes needs,
        rounded up to whole grid steps."""
        step = SUBLANES * self.chunks_per_step
        return -(-self._lane_rows // step) * step

    @property
    def x_bytes(self) -> int:
        return self.windows * self.window_rows * LANES * 4

    @property
    def fits(self) -> bool:
        """x fits in VMEM, and a lane-row's place in its 16 + 15 bits."""
        return self.x_bytes <= _X_VMEM_BYTES and self.tiles < 1 << 16


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MarginLayout:
    """The entries of one ELL block (or a batch of them, in the leading
    axes) regrouped for ``ell_margins``.

    meta:   (..., lane_rows, 128) int32 per slot: column lane (bits
            0-6), x row in the window (7-11), row in the tile (12-19,
            255 in a pad)
    values: (..., lane_rows, 128) float32, 0 in pads
    places: (..., lane_rows) int32 per lane-row: its row tile (bits
            0-15) and its window (16-30)
    chunks: (..., 1) int32, the chunks in use
    """

    meta: jnp.ndarray
    values: jnp.ndarray
    places: jnp.ndarray
    chunks: jnp.ndarray
    rows: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    width: int = dataclasses.field(metadata=dict(static=True))

    @property
    def geometry(self) -> Geometry:
        return Geometry(self.rows, self.n, self.width)


def margin_layout(indices, values, n: int) -> MarginLayout | None:
    """The layout the loss probe reads on a TPU, as host arrays; None
    off a TPU, or where x would not fit in VMEM."""
    *_, rows, width = np.shape(indices)
    if default_interpret() or not Geometry(rows, n, width).fits:
        return None
    return build_margin_layout(indices, values, n)


def build_margin_layout(indices, values, n: int) -> MarginLayout:
    """Regroup ELL blocks ``(..., rows, width)`` (column ids local to
    each block, pads of value 0) into a ``MarginLayout`` of host arrays.
    Vectorized over all entries of all blocks: one stable counting sort
    by (block, window) keeps each window's entries in row order, so each
    row tile's entries of a window follow one another, rows in order."""
    idx = np.asarray(indices)
    val = np.asarray(values, np.float32)
    *lead, rows, width = idx.shape
    geo = Geometry(rows, n, width)
    blocks, wr, cap = math.prod(lead), geo.window_rows, geo.lane_rows
    shift = wr.bit_length() + 6  # log2 of a window's columns

    # real entries, block- then row-major
    mask = val.reshape(blocks * rows, width) != 0
    per_row = mask.sum(axis=1)
    col = idx.reshape(blocks * rows, width)[mask]
    v = val.reshape(blocks * rows, width)[mask]
    row = np.tile(np.arange(rows, dtype=np.int32), blocks)
    tile = np.repeat((row >> 7).astype(np.uint16), per_row)
    entry = np.repeat((row & (LANES - 1)) << 12, per_row)
    entry |= col & ((wr << 7) - 1)  # lane, and x row in the window
    group = col >> shift  # (block, window)
    if blocks > 1:
        group += np.repeat(np.arange(blocks, dtype=np.int32) * geo.windows,
                           per_row.reshape(blocks, rows).sum(axis=1))
    groups = blocks * geo.windows
    order = np.argsort(group.astype(np.uint16) if groups <= 1 << 16 else group, kind="stable")
    entry, v, tile = entry[order], v[order], tile[order]

    # pairs: one tile's entries of one group; each takes whole lane-rows,
    # each group whole chunks, and each block's groups follow one another
    gend = np.cumsum(np.bincount(group, minlength=groups))
    head = np.empty(len(entry), bool)
    head[:1] = True
    np.not_equal(tile[1:], tile[:-1], out=head[1:])
    head[gend[:-1][gend[:-1] < len(entry)]] = True
    starts = np.flatnonzero(head)
    counts = np.diff(np.append(starts, len(entry)))
    pgroup = np.searchsorted(gend, starts, side="right")
    prows = -(-counts // LANES)  # lane-rows of each pair
    grows = np.bincount(pgroup, prows, minlength=groups).astype(np.int64)
    grows = (-(-grows // SUBLANES) * SUBLANES).reshape(blocks, geo.windows)
    gfirst = (np.cumsum(grows, axis=1) - grows).reshape(-1)  # first lane-row of each group
    pfirst = np.cumsum(prows) - prows
    pfirst += gfirst[pgroup] - pfirst[np.searchsorted(pgroup, pgroup)]
    pslot = ((pgroup // geo.windows) * cap + pfirst) * LANES  # first slot of each pair
    slot = np.int32 if blocks * cap * LANES < 1 << 31 else np.int64
    dest = np.arange(len(entry), dtype=slot)
    dest += np.repeat((pslot - starts).astype(slot), counts)

    meta = np.full(blocks * cap * LANES, _PAD_ROW << 12, np.int32)
    vals = np.zeros(blocks * cap * LANES, np.float32)
    meta[dest] = entry
    vals[dest] = v
    places = np.zeros(blocks * cap, np.int32)
    lane_rows = np.repeat(pslot // LANES - np.cumsum(prows) + prows, prows) + np.arange(prows.sum())
    places[lane_rows] = np.repeat(tile[starts] | (pgroup % geo.windows) << 16, prows)
    chunks = (grows.sum(axis=1) // SUBLANES).astype(np.int32)

    def shaped(a, *tail):
        return a.reshape(*lead, *tail)

    return MarginLayout(
        meta=shaped(meta, cap, LANES), values=shaped(vals, cap, LANES),
        places=shaped(places, cap), chunks=shaped(chunks, 1), rows=rows, n=n, width=width,
    )


def _margins_kernel(chunks_ref, x_ref, meta_ref, val_ref, place_ref, y_ref, *,
                    window_rows: int, chunks_per_step: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)

    def chunk(c):
        r0 = pl.multiple_of(c * SUBLANES, SUBLANES)
        meta = meta_ref[pl.ds(r0, SUBLANES), :]
        base = pl.multiple_of((place_ref[r0] >> 16) * window_rows, SUBLANES)
        xw = x_ref[pl.ds(base, window_rows), :]
        col, xrow = meta & (LANES - 1), (meta >> 7) & 31
        gathered = jnp.zeros((SUBLANES, LANES), jnp.float32)
        for k in range(window_rows):
            table = jnp.broadcast_to(xw[k : k + 1, :], (SUBLANES, LANES))
            hit = jnp.take_along_axis(table, col, axis=1, mode="promise_in_bounds")
            gathered = jnp.where(xrow == k, hit, gathered)
        p = val_ref[pl.ds(r0, SUBLANES), :] * gathered
        row = (meta >> 12) & 255
        for d in _STEPS:  # inclusive segmented scan over each row's run
            same = (pltpu.roll(row, d, 1) == row) & (lane >= d)
            p = p + jnp.where(same, pltpu.roll(p, d, 1), 0.0)
        upto = jnp.zeros((SUBLANES, LANES), jnp.int32)  # entries of rows ≤ lane
        for d in (*reversed(_STEPS), 1):  # binary search of the sorted row ids
            probe = jnp.take_along_axis(row, upto + (d - 1), axis=1, mode="promise_in_bounds")
            upto = jnp.where(probe <= lane, upto + d, upto)
        last = jnp.maximum(upto - 1, 0)
        hit = jnp.take_along_axis(row, last, axis=1, mode="promise_in_bounds") == lane
        sums = jnp.take_along_axis(p, last, axis=1, mode="promise_in_bounds")
        sums = jnp.where(hit & (upto > 0), sums, 0.0)
        for i in range(SUBLANES):
            t = place_ref[r0 + i] & 0xFFFF
            y_ref[pl.ds(t, 1), :] += sums[i : i + 1, :]

    def chunks(c, carry):  # a chunk past those in use is all pads: it adds 0
        for k in range(_INTERLEAVE):
            chunk(c * _INTERLEAVE + k)
        return carry

    live = jnp.clip(chunks_ref[0] - step * chunks_per_step, 0, chunks_per_step)
    jax.lax.fori_loop(0, (live + _INTERLEAVE - 1) // _INTERLEAVE, chunks, 0)


def ell_margins(layout: MarginLayout, x: jnp.ndarray, *, interpret: bool | None = None):
    """A·x, (rows,), for one block's layout (no leading axes) and its
    (n,) x. ``interpret=None`` takes the platform's mode."""
    geo = layout.geometry
    interpret = default_interpret() if interpret is None else interpret
    wr, cb = geo.window_rows, geo.chunks_per_step
    xw = jnp.pad(x.astype(jnp.float32), (0, geo.windows * wr * LANES - geo.n))
    xw = xw.reshape(geo.windows * wr, LANES)

    def block(s, chunks):  # steps past the chunks in use keep the last block
        return jnp.minimum(s, jnp.maximum((chunks[0] + cb - 1) // cb - 1, 0))

    entries = pl.BlockSpec((cb * SUBLANES, LANES), lambda s, c: (block(s, c), 0))
    y = pl.pallas_call(
        functools.partial(_margins_kernel, window_rows=wr, chunks_per_step=cb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(geo.lane_rows // (SUBLANES * cb),),
            in_specs=[
                pl.BlockSpec(xw.shape, lambda s, c: (0, 0)),
                entries,
                entries,
                pl.BlockSpec((cb * SUBLANES,), lambda s, c: (block(s, c),),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((geo.tiles, LANES), lambda s, c: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((geo.tiles, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=geo.x_bytes + 2 * geo.tiles * LANES * 4
            + 4 * cb * SUBLANES * LANES * 4 + (16 << 20),
        ),
        interpret=interpret,
        name="ell_margins",
    )(layout.chunks, xw, layout.meta, layout.values, layout.places)
    return y.reshape(-1)[: geo.rows]
