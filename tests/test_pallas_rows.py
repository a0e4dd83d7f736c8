"""``ops/pallas_rows.py``: the in-place read-modify-write of the tiles a
step's keys name, on the CPU through the TPU interpreter, held bit for bit
against XLA's scatter; and the touched-rows step with the kernel forced on at
a small table against the same step on XLA's path."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.data.staging import PaddedBatch
from dmlc_core_tpu.models.common import FTRL
from dmlc_core_tpu.models.linear import SparseLinearModel
from dmlc_core_tpu.ops import pallas_rows
from dmlc_core_tpu.ops.pallas_rows import CHUNK, TILE

F = 32 * TILE
LANES = 2 * CHUNK
LAST = F - TILE  # the first float of the last tile


def spread(count: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).choice(F, count, replace=False)


# name -> (keys, tables in the call): the keys in any order, sorted below
CASES = {
    "count_0": ([], 1),
    "count_1": ([5 * TILE + 77], 1),
    "a_chunk_less_one": (spread(CHUNK - 1, 1), 1),
    "a_chunk": (spread(CHUNK, 2), 1),
    "a_chunk_and_one": (spread(CHUNK + 1, 3), 1),
    "all_lanes": (spread(LANES, 4), 1),
    "many_keys_in_one_tile": (
        np.concatenate([7 * TILE + np.arange(0, TILE, 3), [3, 9 * TILE + 1]]),
        1),
    # the keys of ranks CHUNK - 2 .. CHUNK + 2 share tile 20: the tile is
    # read, patched and written by two grid steps
    "a_tile_straddling_two_chunks": (
        np.concatenate([np.arange(CHUNK - 2) * 19,
                        20 * TILE + np.array([0, 1, 127, 128, 1023]),
                        21 * TILE + np.arange(40) * 7]), 1),
    "first_and_last_tile": (
        [0, 1, TILE - 1, LAST, LAST + 511, F - 1], 1),
    # 1,024 keys are every float of tile 11, all of one chunk: the lanes
    # before them fill the chunk before
    "every_key_of_a_chunk_in_one_tile": (
        np.concatenate([np.arange(CHUNK) * 5, 11 * TILE + np.arange(TILE)]),
        1),
    "three_tables_in_one_call": (spread(CHUNK + 300, 5), 3),
    "three_tables_straddling": (
        np.concatenate([np.arange(CHUNK - 1) * 17,
                        25 * TILE + np.array([4, 5, 6])]), 3),
}


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_xla_scatter_bit_for_bit(case):
    """Tables that hold a NaN with a payload, ``-0.0``, a denormal and an
    infinity in every tile (beside the floats patched, and among the rows
    set): every bit of every table as XLA's scatter leaves it; padding ids
    ``>= F`` after the keys are never read; the tiles counted are the
    distinct ones, once a table."""
    picked, n = CASES[case]
    keys = np.sort(np.asarray(picked, np.int64)).astype(np.int32)
    assert len(np.unique(keys)) == len(keys) <= LANES
    rng = np.random.default_rng(len(keys))
    odd = np.array([0x7FC12345, 0x80000000, 0x00000007, 0xFF800000],
                   np.uint32).view(np.float32)
    tables = []
    for _ in range(n):
        t = rng.normal(size=F).astype(np.float32)
        t[rng.integers(0, F, F // 8)] = np.tile(odd, F // 32)
        tables.append(jnp.asarray(t))
    rows = []
    for _ in range(n):
        r = rng.normal(size=LANES).astype(np.float32)
        r[::5] = np.resize(odd, len(r[::5]))
        rows.append(jnp.asarray(r))
    padded = jnp.asarray(np.concatenate(
        [keys, F + np.arange(LANES - len(keys), dtype=np.int32)]))
    want = [t.at[padded].set(r, mode="drop", unique_indices=True,
                             indices_are_sorted=True)
            for t, r in zip(tables, rows)]
    got, tiles = pallas_rows.scatter_rows_inplace(
        tables, padded, rows, jnp.int32(len(keys)))
    assert len(got) == n
    for g, w in zip(got, want):
        assert g.shape == (F,) and g.dtype == jnp.float32
        np.testing.assert_array_equal(bits(g), bits(w))
    assert int(tiles) == n * len(np.unique(keys // TILE))


@pytest.mark.parametrize("length,lanes,dtype,tpu,takes", [
    (1 << 29, 1 << 17, jnp.float32, True, True),
    (1 << 29, 655360, jnp.float32, True, True),    # a visit of every lane
    (1 << 27, 1 << 17, jnp.float32, True, True),
    (1 << 27, 1 << 18, jnp.float32, True, False),  # 512 floats a lane
    (1 << 20, 1 << 16, jnp.float32, True, False),  # a pass costs 0.01 ms
    (1 << 29, 1 << 17, jnp.float32, False, False),
    (1 << 29, 1 << 17, jnp.bfloat16, True, False),
    ((1 << 29) + 8, 1 << 17, jnp.float32, True, False),
    (1 << 29, (1 << 17) + 8, jnp.float32, True, False),
])
def test_the_crossover_reads_static_shapes_and_the_backend(
        monkeypatch, length, lanes, dtype, tpu, takes):
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: not tpu)
    assert pallas_rows.engages(length, lanes, dtype) is takes


def test_a_count_past_the_keys_in_range_stops_at_them():
    """The kernel's copies are not bounds-checked: a ``count`` that reaches
    into the padding reads no id ``>= F``."""
    keys = jnp.asarray(np.concatenate(
        [[7, TILE + 1], F + np.arange(LANES - 2)]).astype(np.int32))
    t = jnp.zeros(F, jnp.float32)
    (out,), tiles = pallas_rows.scatter_rows_inplace(
        (t,), keys, (jnp.ones(LANES, jnp.float32),), jnp.int32(LANES))
    assert int(tiles) == 2
    assert np.flatnonzero(np.asarray(out)).tolist() == [7, TILE + 1]


def test_xla_path_counts_no_tile():
    t = jnp.arange(4096, dtype=jnp.float32)
    keys = jnp.asarray([3, 4000, 4096, 4097], jnp.int32)
    (out,), tiles = pallas_rows.scatter_rows(
        (t,), keys, (jnp.full(4, -1.0),), jnp.int32(2))
    assert int(tiles) == 0
    assert np.flatnonzero(np.asarray(out) == -1).tolist() == [3, 4000]


def test_touched_rows_step_with_the_kernel_equals_the_xla_path(monkeypatch):
    """Three FTRL steps of ``SparseLinearModel`` over 4,096 buckets, the
    second and third on live state: with the kernel forced on (this table is
    far under the crossover) ``(w, z, n)`` and the bias equal XLA's path
    exactly, and ``sgd.scatter_tiles`` counts the distinct tiles of each
    batch's live keys, three tables each."""
    features, rows, per_row = 4 * TILE, 64, 16
    rng = np.random.default_rng(11)
    batches, distinct, tiles = [], 0, 0
    for _ in range(3):
        index = rng.integers(0, features, rows * per_row).astype(np.int32)
        value = rng.choice([0.0, 1.0, 2.0, -0.5], rows * per_row)
        live = np.unique(index[value != 0])
        distinct += len(live)
        tiles += 3 * len(np.unique(live // TILE))
        batches.append(PaddedBatch(
            label=jnp.asarray(rng.integers(0, 2, rows), jnp.float32),
            weight=jnp.ones(rows, jnp.float32),
            row_ptr=jnp.asarray(np.arange(rows + 1) * per_row, jnp.int32),
            index=jnp.asarray(index), value=jnp.asarray(value, jnp.float32),
            num_rows=jnp.asarray(np.int32(rows))))

    def three_steps():
        model = SparseLinearModel(features, optimizer=FTRL(l1=0.05))
        before = {c: telemetry.counter_get(c) for c in
                  ("sgd.scatter_tiles", "sgd.touched_rows", "sgd.steps")}
        params = model.init()
        for batch in batches:
            params, _loss = model.train_step(params, batch)
        model.flush_step_counters()
        return params, {c: telemetry.counter_get(c) - v
                        for c, v in before.items()}

    plain, counted = three_steps()
    assert counted == {"sgd.scatter_tiles": 0, "sgd.touched_rows": distinct,
                       "sgd.steps": 3}
    monkeypatch.setattr(pallas_rows, "engages", lambda *_: True)
    kernel, counted = three_steps()
    assert counted == {"sgd.scatter_tiles": tiles,
                       "sgd.touched_rows": distinct, "sgd.steps": 3}
    assert float(jnp.sum(jnp.abs(kernel["w"]))) > 0
    for a, b in zip(jax.tree.leaves(kernel), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(bits(a), bits(b))
