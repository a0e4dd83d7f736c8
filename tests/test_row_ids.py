"""`csr_row_ids` (one mark a row, one running sum) gives the row ids the
binary search it replaced gave (PR 27), lane for lane."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.data.staging import PaddedBatch, csr_row_ids


def by_search(row_ptr, nnz_pad):
    """`PaddedBatch.row_ids` as it stood before PR 27."""
    batch = row_ptr.shape[0] - 1
    k = jnp.arange(nnz_pad, dtype=row_ptr.dtype)
    r = jnp.searchsorted(row_ptr, k, side="right") - 1
    return jnp.minimum(r, batch - 1).astype(jnp.int32)


CASES = {
    "even": ([3] * 8, 0),
    "padding_lanes": ([2, 5, 1, 4], 7),
    "empty_rows": ([0, 3, 0, 0, 2, 0], 3),
    "empty_first_and_last": ([0, 0, 4, 0], 0),
    "padding_rows_after": ([5, 2, 0, 0, 0], 9),
    "no_entry_at_all": ([0, 0, 0], 8),
    "one_row": ([6], 2),
    "many": (list(np.random.default_rng(0).integers(0, 9, 500)), 37),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_row_ids_equal_the_binary_search(name):
    counts, pad = CASES[name]
    row_ptr = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    nnz_pad = int(row_ptr[-1]) + pad
    got = jax.jit(csr_row_ids, static_argnums=1)(row_ptr, nnz_pad)
    want = by_search(row_ptr, nnz_pad)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_batchs_row_ids_are_the_functions():
    counts = [2, 0, 3]
    batch = PaddedBatch(
        label=jnp.zeros(3), weight=jnp.ones(3),
        row_ptr=jnp.asarray(np.concatenate([[0], np.cumsum(counts)]),
                            jnp.int32),
        index=jnp.zeros(8, jnp.int32), value=jnp.ones(8),
        num_rows=jnp.asarray(np.int32(3)))
    assert batch.row_ids().tolist() == [0, 0, 2, 2, 2, 2, 2, 2]
