"""Row update kernel: ``table[rows[i]] += delta[i]`` for sorted row ids,
in place, on the TPU.

XLA:TPU's row scatter-add does one read-modify-write of a row at a time
(105-115 ns a row of 1536 bytes on a v5e, colliding ids or not), and its
``indices_are_sorted`` emitter walks the whole operand (15 ms for a 4.29 GiB
table, whatever the batch; PERF.md section 6, PR 31).  This kernel keeps
hundreds of rows in flight instead.

The table stays in HBM and is aliased input to output, so a donated buffer
is updated in place.  Mosaic cannot slice one row out of an ``(8, 128)``
tiled array in HBM, so the unit is the tile-aligned **group of 8 rows**
that holds the row (``8 * cols * 4`` contiguous bytes).  The ids come
sorted, which puts the ids of one group next to each other: a *run*.  Per
block of ids the kernel starts one read DMA a run, adds each delta to its
row of its group in VMEM, in order, and starts one write DMA a run; the
next block's reads are in flight under this block's adds and the block
before's writes.  Two hazards, both about runs:

* a run that crosses a block boundary is carried into the next block: its
  group tile is copied forward in VMEM, neither written nor read again;
* two ids of one group are never two read-modify-writes in flight: sorted
  ids make a group's ids one run, and a run is one read and one write.

What a run is, which tile of the block's buffer it takes and which runs a
block reads and writes is integer work on the sorted ids alone; ``_plan``
does it in XLA.  The kernel's scalar core is what bounds it (a DMA costs it
about 17 ns to start and wait for, an add 11 ns), so its loops go over
runs, eight a trip, and never test an id.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["row_update", "usable"]

GROUP = 8       # rows of one float32 (8, 128) tile: the unit of a DMA
LANES = 128
_BLOCKS = (256, 128, 64, 32, 16, 8)     # ids a block, the largest that fits
_SLOTS = 3      # group buffers: one read into, one added to, one written from
_UNROLL = 8     # DMAs started, or waited for, a trip of a loop
# Ids a call: two int32 an id rest in the core's 1 MiB of scalar memory.
_MAX_IDS = 1 << 16
_VMEM_BYTES = 12 << 20                  # of the 16 MiB a kernel may take
# A plan word: the id's slot among its block's group tiles (``block`` itself
# is the tile of dropped ids), and above it the row's place in its group.
_ROW = 9
_SLOT_MASK = (1 << _ROW) - 1


def _block(n: int, cols: int):
    """Ids a block: the largest that divides ``n`` and whose buffers
    (``_SLOTS`` of ``block + 1`` group tiles, two blocks of deltas) fit."""
    for block in _BLOCKS:
        held = (_SLOTS * (block + 1) * GROUP + 2 * block) * cols * 4
        if n % block == 0 and held <= _VMEM_BYTES:
            return block
    return None


def usable(table, rows, delta) -> bool:
    """Whether the kernel can take this update: float32 rows a whole number
    of lanes wide, a whole number of groups in the table and of blocks in
    the batch.  (One device is the caller's to check.)"""
    return (table.ndim == 2 and table.dtype == jnp.float32
            and delta.dtype == jnp.float32 and rows.ndim == 1
            and table.shape[1] % LANES == 0 and table.shape[0] % GROUP == 0
            and rows.shape[0] > 0 and rows.shape[0] % GROUP == 0
            and _block(GROUP, table.shape[1]) is not None)


def _plan(rows, num_rows: int, block: int):
    """From sorted ids (every id in ``[0, num_rows]``, ``num_rows`` itself
    meaning "dropped"), all ``[n]`` int32 but the last:

    - ``word``: an id's slot in its block (runs count from 0 in every
      block) and its row's place in the group;
    - ``run_group``: block ``b``'s runs' groups, in order, from ``b *
      block`` (what follows them there is not read).  A second sort packs
      them: the kernel's loops then go over runs, with no test an id; a
      test an id is a branch on a value just loaded, about 25 ns each on a
      v5e (PERF.md section 6, PR 31);
    - ``counts``, flat ``[blocks * 3]``: a block's first run to read (1 if
      its first run came over from the block before, whose tile is carried),
      its runs, and its runs to write (one fewer if the last goes on).
    """
    n = rows.shape[0]
    blocks = n // block
    valid = rows < num_rows
    group = rows // GROUP
    index = jnp.arange(n, dtype=jnp.int32)
    at = index % block
    same_prev = jnp.concatenate(
        [jnp.zeros((1,), bool), group[1:] == group[:-1]])
    same_next = jnp.concatenate([same_prev[1:], jnp.zeros((1,), bool)])
    opens = ~same_prev | (at == 0)
    slot = jnp.cumsum(opens.astype(jnp.int32).reshape(blocks, block),
                      axis=1).reshape(n) - 1
    # A dropped id adds into a tile of its own past the block's last slot.
    word = jnp.where(valid, slot, block) | ((rows % GROUP) << _ROW)
    opens &= valid
    # Openers first within each block, each kind in its order.
    key = (index - at) * 2 + jnp.where(opens, at, block + at)
    _, run_group = jax.lax.sort((key, group), num_keys=1)
    per_block = lambda x: x.reshape(blocks, block)
    carried_in = per_block(valid & same_prev)[:, 0]
    carried_out = per_block(valid & same_next)[:, -1]
    runs = per_block(opens).sum(1, dtype=jnp.int32)
    counts = jnp.stack([carried_in.astype(jnp.int32), runs,
                        runs - carried_out.astype(jnp.int32)], axis=1)
    return word, run_group, counts.reshape(-1)  # flat: SMEM pads a last axis


def _kernel(word_ref, run_ref, counts_ref, delta_hbm, table_in, table_out,
            groups, deltas, read_sem, write_sem, delta_sem, *,
            block: int, blocks: int, first: int):
    """``table_*``: ``[groups, 8, cols]`` in HBM, one buffer (aliased);
    ``delta_hbm``: ``[ids / 8, 8, cols]``, this call's from ``first``;
    ``groups``: ``[_SLOTS * (block + 1), 8, cols]``, buffer ``s``'s tiles
    from ``s * (block + 1)``; ``deltas``: two blocks of ``[block / 8, 8,
    cols]``."""
    del table_in
    tiles = block + 1
    eighths = block // GROUP

    def unrolled(lo, hi, body):
        """``body(j)`` for ``j`` in ``[lo, hi)``, ``_UNROLL`` a trip: the
        scalar core starts a DMA in the shadow of the one before."""
        trips = (hi - lo) // _UNROLL

        def trip(t, _):
            for u in range(_UNROLL):
                body(lo + t * _UNROLL + u)
            return 0
        jax.lax.fori_loop(0, trips, trip, 0)
        jax.lax.fori_loop(lo + trips * _UNROLL, hi,
                          lambda j, _: body(j) or 0, 0)

    def start_reads(b):
        s = b % _SLOTS
        unrolled(counts_ref[3 * b], counts_ref[3 * b + 1],
                 lambda j: pltpu.make_async_copy(
                     table_out.at[run_ref[b * block + j]],
                     groups.at[s * tiles + j], read_sem.at[s]).start())
        pltpu.make_async_copy(
            delta_hbm.at[pl.ds(first // GROUP + b * eighths, eighths)],
            deltas.at[pl.ds((b % 2) * eighths, eighths)],
            delta_sem.at[b % 2]).start()

    def wait_tiles(sem, count):
        # Every group DMA moves one tile: any tile-shaped copy waits for one.
        unrolled(0, count, lambda _: pltpu.make_async_copy(
            groups.at[0], groups.at[0], sem).wait())

    def add(b):
        s = b % _SLOTS

        def trip(o, _):             # eight ids a trip, ``u`` static
            for u in range(GROUP):
                word = word_ref[b * block + o * GROUP + u]
                at = (s * tiles + (word & _SLOT_MASK),
                      pl.ds(word >> _ROW, 1), slice(None))
                groups[at] = groups[at] + deltas[(b % 2) * eighths + o,
                                                 u:u + 1, :]
            return 0
        jax.lax.fori_loop(0, eighths, trip, 0)

    def start_writes(b):
        s = b % _SLOTS
        unrolled(0, counts_ref[3 * b + 2],
                 lambda j: pltpu.make_async_copy(
                     groups.at[s * tiles + j],
                     table_out.at[run_ref[b * block + j]],
                     write_sem.at[s]).start())

    start_reads(0)

    def step(b, _):
        @pl.when(b + 1 < blocks)
        def _():
            start_reads(b + 1)

        wait_tiles(read_sem.at[b % _SLOTS],
                   counts_ref[3 * b + 1] - counts_ref[3 * b])
        pltpu.make_async_copy(
            delta_hbm.at[pl.ds(0, eighths)], deltas.at[pl.ds(0, eighths)],
            delta_sem.at[b % 2]).wait()
        add(b)

        @pl.when(counts_ref[3 * b + 2] < counts_ref[3 * b + 1])
        def _():
            # The last run goes on: its tile is the next block's slot 0.
            groups[((b + 1) % _SLOTS) * tiles] = groups[
                (b % _SLOTS) * tiles + counts_ref[3 * b + 2]]

        start_writes(b)

        @pl.when(b > 0)
        def _():
            wait_tiles(write_sem.at[(b - 1) % _SLOTS], counts_ref[3 * b - 1])
        return 0

    jax.lax.fori_loop(0, blocks, step, 0)
    wait_tiles(write_sem.at[(blocks - 1) % _SLOTS],
               counts_ref[3 * blocks - 1])


def _update(table, rows, delta, first: int, interpret: bool):
    """One call of the kernel: ``rows`` (at most ``_MAX_IDS``) with the
    deltas ``delta[first // 8:]``, both tables as ``[groups, 8, cols]``."""
    num_rows, cols = table.shape[0] * GROUP, table.shape[2]
    n = rows.shape[0]
    block = _block(n, cols)
    word, run_group, counts = _plan(rows, num_rows, block)
    kernel = functools.partial(_kernel, block=block, blocks=n // block,
                               first=first)
    return pl.pallas_call(
        kernel,
        name="row_update",
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS * (block + 1), GROUP, cols), jnp.float32),
                pltpu.VMEM((2 * block // GROUP, GROUP, cols), jnp.float32),
                pltpu.SemaphoreType.DMA((_SLOTS,)),
                pltpu.SemaphoreType.DMA((_SLOTS,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        # Operand 4 of the call (after the three prefetched) is the table.
        input_output_aliases={4: 0},
        interpret=interpret,
    )(word, run_group, counts, delta, table)


def row_update(table, rows, delta, interpret: bool = False):
    """``table.at[rows].add(delta, mode="drop")`` for int32 ``rows`` sorted
    ascending and not negative (``table.shape[0]`` or more: dropped).
    Duplicates add in the order they come.  ``usable(table, rows, delta)``
    must hold."""
    num_rows, cols = table.shape
    n = rows.shape[0]
    # Both views are the arrays' own bytes: a tile holds 8 rows.
    out = table.reshape(num_rows // GROUP, GROUP, cols)
    delta = delta.reshape(n // GROUP, GROUP, cols)
    for first in range(0, n, _MAX_IDS):         # sorted: one after the other
        out = _update(out, rows[first:first + _MAX_IDS], delta, first,
                      interpret)
    return out.reshape(num_rows, cols)
