"""Rows set in place into tables far larger than what a step touches.

XLA's scatter on a TPU streams its whole operand through VMEM and back
whatever the updates: 6.25 ms a ``f32[2^29]`` table on a v5e to set 16 floats.
:func:`scatter_rows_inplace` is a read-modify-write of the 4 KB tiles the keys
name and of no other: the table stays in HBM (``pl.ANY``, aliased to its
output), and the kernel copies each distinct tile into VMEM, patches it for
every key it holds, and copies it back.  :func:`scatter_rows` is what a caller
uses: the kernel where the table is long against the keys, XLA's scatter
elsewhere (a small table's pass costs less than a copy a key).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_segment import pallas_interpret

#: the kernel's name in a compiled program and in a device trace
SCATTER_ROWS_KERNEL = "_scatter_rows_inplace_pallas"

#: floats of a table's tile: 8 sublanes of 128 lanes, the unit of the
#: ``T(1024)`` layout XLA gives a long 1-D float32 array, so that the view
#: ``[F / 1024, 8, 128]`` is a bitcast
_TILE_SHIFT = 10
TILE = 1 << _TILE_SHIFT
#: keys a grid step decodes, and tiles of each table it may hold in VMEM
#: (XLA lays ``s32[lanes]`` out in ``T(1024)``: an SMEM block cannot be less)
CHUNK = 1024
#: copies one wait takes off a semaphore
_WAIT = 64

#: floats of table a lane of keys must stand against for the kernel to be the
#: cheaper visit.  A v5e's readings (PERF.md section 5, chip runs, PR 34): XLA's
#: scatter 6.37 ms a ``f32[2^29]`` table (0.0119 ns a float of table) and 4.9 ns a
#: lane of keys; the kernel 12.4 ns a key a table (a tile in, a patch, a tile
#: out) and nothing a lane past the keys.  With every lane a key the kernel
#: wins from 7.5 / 0.0119 = 632 floats a lane
CROSSOVER_FLOATS_PER_LANE = 640


def _kernel_holds(dtype, width: int) -> bool:
    """32-bit elements; rows of one element, or of whole sublane groups."""
    return jnp.dtype(dtype).itemsize == 4 and (width == 1 or width % 8 == 0)


def _visit_shape(table, keys) -> tuple:
    """``engages``' arguments for a visit of ``keys`` to ``table``."""
    return (table.shape[0], keys.shape[0], table.dtype, *table.shape[1:])


def engages(length: int, lanes: int, dtype, width: int = 1) -> bool:
    """Whether a visit of ``lanes`` keys to tables of ``length`` rows of
    ``width`` floats takes the kernel: read off static shapes and the backend
    (compiled kernels run on a TPU alone), nothing else."""
    return (not pallas_interpret() and _kernel_holds(dtype, width)
            and length % TILE == 0
            and lanes % CHUNK == 0
            and length * width >= lanes * CROSSOVER_FLOATS_PER_LANE)


def _kernel(count_ref, keys_ref, *refs, n_tables: int):
    rows_refs = refs[:n_tables]
    # refs[n_tables:2 * n_tables] are the tables as inputs: the same memory
    # as the outputs, which is what the copies below read and write
    tables = refs[2 * n_tables:3 * n_tables]
    tiles_ref = refs[3 * n_tables]
    bufs = refs[3 * n_tables + 1:4 * n_tables + 1]
    last_tile, sems = refs[4 * n_tables + 1:]
    step = pl.program_id(0)
    count = count_ref[0]

    @pl.when(step == 0)
    def _():
        tiles_ref[0] = 0
        last_tile[0] = -1

    def copy_in(k, tile, slot):
        return pltpu.make_async_copy(tables[k].at[tile], bufs[k].at[slot],
                                     sems.at[k])

    def copy_out(k, tile, slot):
        return pltpu.make_async_copy(bufs[k].at[slot], tables[k].at[tile],
                                     sems.at[k])

    def drain(slots):
        """Wait for ``slots`` copies a table, in or out.  A wait takes a
        copy's bytes off its semaphore, whichever copy brought them: so
        ``_WAIT`` tiles at a time, then the rest one by one."""
        def wait(copy):
            def body(_, carry):
                for k in range(n_tables):
                    copy(k).wait()
                return carry
            return body
        jax.lax.fori_loop(0, slots // _WAIT, wait(
            lambda k: pltpu.make_async_copy(
                tables[k].at[pl.ds(0, _WAIT)], bufs[k].at[pl.ds(0, _WAIT)],
                sems.at[k])), 0)
        jax.lax.fori_loop(0, slots % _WAIT, wait(
            lambda k: copy_in(k, 0, 0)), 0)

    @pl.when(step * CHUNK < count)
    def _():
        here = jnp.minimum(count - step * CHUNK, CHUNK)

        # every distinct tile of the chunk, once: keys that share a tile are
        # neighbours, so a tile is new where it differs from the key before
        def fetch(j, carry):
            slots, prev = carry
            tile = keys_ref[j] >> _TILE_SHIFT
            new = tile != prev

            @pl.when(new)
            def _():
                for k in range(n_tables):
                    copy_in(k, tile, slots).start()
            return slots + new.astype(jnp.int32), tile

        first = keys_ref[0] >> _TILE_SHIFT
        slots, last = jax.lax.fori_loop(0, here, fetch,
                                        (jnp.int32(0), jnp.int32(-1)))
        drain(slots)

        where = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128
                 + jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1))

        def patch(j, carry):
            slot, prev = carry
            key = keys_ref[j]
            tile = key >> _TILE_SHIFT
            slot = slot + (tile != prev).astype(jnp.int32)
            named = where == (key & (TILE - 1))
            for k in range(n_tables):
                bufs[k][slot] = jnp.where(named, rows_refs[k][j], bufs[k][slot])
            # the last key of its tile sends the tile back
            after = keys_ref[jnp.minimum(j + 1, CHUNK - 1)] >> _TILE_SHIFT

            @pl.when((after != tile) | (j == here - 1))
            def _():
                for k in range(n_tables):
                    copy_out(k, tile, slot).start()
            return slot, tile

        jax.lax.fori_loop(0, here, patch, (jnp.int32(-1), jnp.int32(-1)))
        # no copy to or from a tile outlives its chunk: the tile that
        # straddles into the next chunk is read there as written here
        drain(slots)
        # ... and counted once
        straddles = (first == last_tile[0]).astype(jnp.int32)
        tiles_ref[0] += n_tables * (slots - straddles)
        last_tile[0] = last


def scatter_rows_inplace(tables, keys, rows, count) -> tuple:
    """``t.at[keys].set(r, mode="drop")`` for each of ``tables`` and its
    ``rows``, writing only the tiles the keys name.  ``tables``: 32-bit
    tables ``[F]`` (or ``[F, K]``: ``_scatter_wide_inplace``, below) of one
    shape, ``F % 1024 == 0``, each aliased to its output (donate them, or
    XLA copies them first); ``keys``: ``s32[lanes]``, ``lanes % 1024 == 0``,
    the ``count`` distinct keys first and ascending, whatever follows them
    (ids ``>= F``) never read; ``rows``: one ``[lanes]`` (``[lanes, K]``) a
    table.  Returns ``(tables, tiles)``: ``tiles`` the 4 KB tiles written."""
    if tables[0].ndim == 2:
        return _scatter_wide_inplace(tables, keys, rows, count)
    n, length, lanes = len(tables), tables[0].shape[0], keys.shape[0]
    chunks = lanes // CHUNK
    tiled = [t.reshape(length // TILE, 8, 128) for t in tables]
    # the kernel's copies are not bounds-checked (a check costs more than
    # the copy's issue): a count past the keys in range stops at them
    count = jnp.minimum(count.astype(jnp.int32), jnp.sum(
        (keys >= 0) & (keys < length), dtype=jnp.int32))

    def chunk_of(i, count_ref):
        # steps past the keys ask for the block they hold: nothing is copied
        return (jnp.minimum(i, jnp.maximum(count_ref[0] - 1, 0) // CHUNK),)

    smem_chunk = pl.BlockSpec((CHUNK,), chunk_of, memory_space=pltpu.SMEM)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    *out, tiles = pl.pallas_call(
        functools.partial(_kernel, n_tables=n),
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiled]
        + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks,),
            in_specs=[smem_chunk] * (1 + n) + [anywhere] * n,
            out_specs=[anywhere] * n
            + [pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((CHUNK, 8, 128), rows[0].dtype)] * n + [
                pltpu.SMEM((1,), jnp.int32), pltpu.SemaphoreType.DMA((n,))]),
        # table k is operand 2 + n + k, the count and the keys before the rows
        input_output_aliases={2 + n + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True,
            vmem_limit_bytes=n * CHUNK * TILE * 4 + (8 << 20)),
        interpret=pltpu.InterpretParams() if pallas_interpret() else False,
        name=SCATTER_ROWS_KERNEL,
    )(count.reshape(1), keys, *rows, *tiled)
    return tuple(t.reshape(length) for t in out), tiles[0]


def scatter_rows(tables, keys, rows, count) -> tuple:
    """Rows set at sorted distinct ``keys`` into ``tables``: the kernel
    where :func:`engages` says so, XLA's scatter elsewhere (rows ``[A, K]``
    too).  Returns ``(tables, tiles)``, ``tiles`` 0 on XLA's path."""
    if tables[0].ndim < 3 and engages(*_visit_shape(tables[0], keys)):
        return scatter_rows_inplace(tables, keys, rows, count)
    return tuple(t.at[keys].set(r, mode="drop", unique_indices=True,
                                indices_are_sorted=True)
                 for t, r in zip(tables, rows)), jnp.zeros((), jnp.int32)


# ---- rows of K floats -------------------------------------------------------
# XLA lays a ``f32[F, K]`` table of small K out with F on the lanes
# (``{0,1:T(8,128)}``: no padding of K to 128), so its transpose ``[K, F]`` is
# the same bytes and a key's K floats sit in ONE lane of K / 8 tiles: a block
# of 128 neighbouring keys is ``[K, 128]``, what the kernel below copies in,
# patches a lane of and copies back.  (A flat row-major view would cost a
# relayout of the whole table, 34 GB for ``f32[2^26, 16]``.)
_BLOCK_SHIFT = 7
BLOCK = 1 << _BLOCK_SHIFT


def _kernel_wide(count_ref, keys_ref, *refs, n_tables: int):
    """:func:`_kernel` for tables ``[K, F]``: a block of 128 keys where that
    has a tile of 1,024, a key's row rolled along the lanes to the key's own
    and selected into its block."""
    rows_refs = refs[:n_tables]                     # [K, CHUNK] in VMEM
    tables = refs[2 * n_tables:3 * n_tables]        # [K, F], the outputs
    tiles_ref = refs[3 * n_tables]
    bufs = refs[3 * n_tables + 1:4 * n_tables + 1]  # [CHUNK, K, 128]
    last_block, sems = refs[4 * n_tables + 1:]
    step = pl.program_id(0)
    count = count_ref[0]
    width = bufs[0].shape[1]

    @pl.when(step == 0)
    def _():
        tiles_ref[0] = 0
        last_block[0] = -1

    def block_of(k, block):
        return tables[k].at[:, pl.ds(pl.multiple_of(block * BLOCK, BLOCK),
                                     BLOCK)]

    def copy_in(k, block, slot):
        return pltpu.make_async_copy(block_of(k, block), bufs[k].at[slot],
                                     sems.at[k])

    def copy_out(k, block, slot):
        return pltpu.make_async_copy(bufs[k].at[slot], block_of(k, block),
                                     sems.at[k])

    def drain(slots):
        """As :func:`_kernel`'s: a wait takes its destination's bytes off
        the semaphore, so ``_WAIT`` blocks at a time, then one by one."""
        def wait(many):
            def body(_, carry):
                for k in range(n_tables):
                    held = bufs[k].at[pl.ds(0, many)]
                    pltpu.make_async_copy(held, held, sems.at[k]).wait()
                return carry
            return body
        jax.lax.fori_loop(0, slots // _WAIT, wait(_WAIT), 0)
        jax.lax.fori_loop(0, slots % _WAIT, wait(1), 0)

    @pl.when(step * CHUNK < count)
    def _():
        here = jnp.minimum(count - step * CHUNK, CHUNK)

        def fetch(j, carry):
            slots, prev = carry
            block = keys_ref[j] >> _BLOCK_SHIFT
            new = block != prev

            @pl.when(new)
            def _():
                for k in range(n_tables):
                    copy_in(k, block, slots).start()
            return slots + new.astype(jnp.int32), block

        first = keys_ref[0] >> _BLOCK_SHIFT
        slots, last = jax.lax.fori_loop(0, here, fetch,
                                        (jnp.int32(0), jnp.int32(-1)))
        drain(slots)

        lane = jax.lax.broadcasted_iota(jnp.int32, (width, BLOCK), 1)

        def patch(j, carry):
            slot, prev = carry
            key = keys_ref[j]
            block = key >> _BLOCK_SHIFT
            slot = slot + (block != prev).astype(jnp.int32)
            at = key & (BLOCK - 1)
            named = lane == at
            # the 128 rows that hold row j, turned so that it lies on lane
            # ``at``; the select takes that lane alone
            group = pl.multiple_of((j >> _BLOCK_SHIFT) * BLOCK, BLOCK)
            turn = (at - j) & (BLOCK - 1)
            for k in range(n_tables):
                row = pltpu.roll(rows_refs[k][:, pl.ds(group, BLOCK)], turn, 1)
                bufs[k][slot] = jnp.where(named, row, bufs[k][slot])
            after = keys_ref[jnp.minimum(j + 1, CHUNK - 1)] >> _BLOCK_SHIFT

            @pl.when((after != block) | (j == here - 1))
            def _():
                for k in range(n_tables):
                    copy_out(k, block, slot).start()
            return slot, block

        jax.lax.fori_loop(0, here, patch, (jnp.int32(-1), jnp.int32(-1)))
        drain(slots)
        straddles = (first == last_block[0]).astype(jnp.int32)
        tiles_ref[0] += n_tables * -(-width // 8) * (slots - straddles)
        last_block[0] = last


def _scatter_wide_inplace(tables, keys, rows, count) -> tuple:
    """:func:`scatter_rows_inplace` for tables ``[F, K]`` and rows ``[lanes,
    K]``, ``K % 8 == 0`` on a chip (any K interpreted): the blocks of 128
    keys the keys name, ``K / 8`` tiles each, read, patched and written."""
    n, lanes = len(tables), keys.shape[0]
    length, width = tables[0].shape
    across = [t.T for t in tables]
    count = jnp.minimum(count.astype(jnp.int32), jnp.sum(
        (keys >= 0) & (keys < length), dtype=jnp.int32))

    def chunk_of(i, count_ref):
        return jnp.minimum(i, jnp.maximum(count_ref[0] - 1, 0) // CHUNK)

    smem_chunk = pl.BlockSpec((CHUNK,), lambda i, c: (chunk_of(i, c),),
                              memory_space=pltpu.SMEM)
    rows_chunk = pl.BlockSpec((width, CHUNK), lambda i, c: (0, chunk_of(i, c)))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    *out, tiles = pl.pallas_call(
        functools.partial(_kernel_wide, n_tables=n),
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in across]
        + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes // CHUNK,),
            in_specs=[smem_chunk] + [rows_chunk] * n + [anywhere] * n,
            out_specs=[anywhere] * n
            + [pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((CHUNK, width, BLOCK), rows[0].dtype)
                            ] * n + [pltpu.SMEM((1,), jnp.int32),
                                     pltpu.SemaphoreType.DMA((n,))]),
        input_output_aliases={2 + n + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True,
            vmem_limit_bytes=n * CHUNK * width * BLOCK * 4 + (8 << 20)),
        interpret=pltpu.InterpretParams() if pallas_interpret() else False,
        name=SCATTER_ROWS_KERNEL,
    )(count.reshape(1), keys, *(r.T for r in rows), *across)
    return tuple(t.T for t in out), tiles[0]
