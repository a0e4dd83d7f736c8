"""`GBDT.fit_batch` held against the `bosch-gbdt` configuration's plain
reference (numpy float64, dense reading of the sparsity-aware split) at a
small size on the CPU: the forest's numbers lie inside tight limits, and a
default direction stored the wrong way round, a histogram rounded through
bfloat16 and a fit that drops entries each fail."""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402
from dmlc_core_tpu.data.staging import PaddedBatch  # noqa: E402
from dmlc_core_tpu.models import GBDT, QuantileBinner  # noqa: E402

ROWS, FEATURES, STATIONS, BINS, DEPTH = 4096, 64, 8, 32, 3
SIZES = {"num_features": FEATURES, "num_bins": BINS, "max_depth": DEPTH,
         "learning_rate": 0.1, "lambda": 1.0, "min_child_weight": 1.0,
         "objective": "logistic", "missing_aware": True}
# about three times what sound fits of two trees read at this size, the
# largest over the seeds (XLA scatter-add in entry order: 9.5e-8, 9.0e-6,
# 3.4e-5, 3.7e-5, 6e-16; the kernel: 3.6e-7, 5.9e-6, 2.4e-6); the bfloat16
# control reads 1e-3 and more
LIMITS = {"base_abs_err": 1e-6, "gain_rel_err": 3e-5, "cover_rel_err": 1e-4,
          "leaf_rel_err": 1e-4, "split_regret": 1e-6, "trees_missing": 0,
          # a nearest-rank cut reads 0.5 at most, by its definition
          "cuts_rank_err": 1.5}
REGRET = [[0, 0], [0, 2], [1, 1]]


@pytest.fixture(scope="module")
def reference():
    return run.load_module("references", "bosch-gbdt")


def drawn(seed: int):
    """The traffic generator's rows as a resident batch, with its cuts."""
    data = run.load_module("traffic", "sparse_fit").draw_rows(
        seed, ROWS, FEATURES, STATIONS, 0.19, 0.05)
    value = (data["q"] / 1000.0).astype(np.float32)
    entries = len(value)
    pad = -entries % 1024
    batch = PaddedBatch(
        label=jnp.asarray(data["label"], jnp.float32),
        weight=jnp.ones(ROWS, jnp.float32),
        row_ptr=jnp.asarray(data["row_ptr"], jnp.int32),
        index=jnp.asarray(np.pad(data["fi"].astype(np.int32), (0, pad))),
        value=jnp.asarray(np.pad(value, (0, pad))),
        num_rows=jnp.asarray(np.int32(ROWS)))
    binner = QuantileBinner(num_bins=BINS, missing_aware=True)
    binner.fit_sparse(data["fi"], value, FEATURES)
    data["value"] = value
    return data, batch, binner


def model(histogram: str, cls=GBDT, trees: int = 2):
    return cls(num_features=FEATURES, num_trees=trees, max_depth=DEPTH,
               num_bins=BINS, learning_rate=0.1, lambda_=1.0,
               min_child_weight=1.0, missing_aware=True, histogram=histogram)


def compared(reference, data, binner, forest, trees: int = 2,
             control: bool = False) -> dict:
    forest = {k: np.asarray(v) for k, v in forest.items()}
    out = reference.compare(data["row_ptr"], data["fi"], data["value"],
                            np.asarray(binner.cuts), data["label"], forest,
                            SIZES, trees, REGRET, ROWS, control=control)
    return {c["name"]: c["value"] for c in out}


def failed(numbers: dict) -> list:
    return [k for k, v in numbers.items()
            if not k.startswith("control.") and v > LIMITS[k]]


@pytest.mark.parametrize("histogram", ["xla", "pallas"])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 27, 123456789])
def test_fit_batch_agrees_with_the_reference(reference, seed, histogram):
    data, batch, binner = drawn(seed)
    forest = model(histogram).fit_batch(batch, binner)
    numbers = compared(reference, data, binner, forest, control=True)
    assert failed(numbers) == [], numbers
    assert np.any(np.asarray(forest["default_right"]) == 1)
    assert np.any(np.asarray(forest["threshold"]) < BINS)
    # the reference one precision down is not inside them
    assert numbers["control.gain_rel_err"] > LIMITS["gain_rel_err"]
    assert numbers["control.cover_rel_err"] > LIMITS["cover_rel_err"]
    # nor is the root's default direction stored the other way round, nor
    # the cuts rounded through bfloat16
    assert numbers["control.split_regret"] > 1e3 * LIMITS["split_regret"]
    assert numbers["control.cuts_rank_err"] > 3 * LIMITS["cuts_rank_err"]


def test_cuts_from_another_sample_fail(reference):
    """The reference sorts the binner's sample itself: cuts that are not
    that sample's nearest-rank quantiles (here those of its first half) are
    not inside the limit, though a forest fitted under them is sound in
    every other number."""
    data, batch, binner = drawn(5)
    half = int(data["row_ptr"][ROWS // 2])
    other = QuantileBinner(num_bins=BINS, missing_aware=True).fit_sparse(
        data["fi"][:half], data["value"][:half], FEATURES)
    forest = model("xla").fit_batch(batch, other)
    assert failed(compared(reference, data, other, forest)) == [
        "cuts_rank_err"]


def test_a_flipped_default_direction_fails(reference):
    data, batch, binner = drawn(5)
    forest = dict(model("pallas").fit_batch(batch, binner))
    assert failed(compared(reference, data, binner, forest)) == []
    split = np.asarray(forest["threshold"]) < BINS
    # one node, the root of the first tree: its absent rows sent the other way
    flipped = np.asarray(forest["default_right"]).copy()
    assert split[0, 0]
    flipped[0, 0] = 1 - flipped[0, 0]
    forest["default_right"] = flipped
    wrong = failed(compared(reference, data, binner, forest))
    assert "gain_rel_err" in wrong and "split_regret" in wrong, wrong


class Bf16Histogram(GBDT):
    """Split finding handed the level's sums rounded through bfloat16, as a
    DEFAULT-precision contraction would leave them."""

    def _level_splits_from_hist(self, hist, gh_node, *rest):
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return GBDT._level_splits_from_hist(self, low(hist), low(gh_node),
                                            *rest)


@pytest.mark.parametrize("histogram", ["xla", "pallas"])
def test_a_bfloat16_histogram_fails(reference, histogram):
    data, batch, binner = drawn(5)
    forest = model(histogram, Bf16Histogram).fit_batch(batch, binner)
    wrong = failed(compared(reference, data, binner, forest))
    assert "gain_rel_err" in wrong, wrong


def test_a_fit_that_drops_a_features_entries_fails(reference):
    """The absent mass is the node total less the present sum: entries lost
    on the way (here every entry of the root's split feature, read as 0 and
    so as absent) move both."""
    import dataclasses
    data, batch, binner = drawn(5)
    sound = model("pallas", trees=1).fit_batch(batch, binner)
    lost = int(np.asarray(sound["feature"])[0, 0])
    fewer = dataclasses.replace(
        batch, value=jnp.where(batch.index == lost, 0.0, batch.value))
    forest = model("pallas", trees=1).fit_batch(fewer, binner)
    wrong = failed(compared(reference, data, binner, forest, trees=1))
    assert wrong, wrong


def test_the_reference_bins_as_the_program_does(reference):
    data, batch, binner = drawn(5)
    codes = np.asarray(binner.transform_entries(
        jnp.asarray(data["fi"].astype(np.int32)), jnp.asarray(data["value"])))
    dense_t = reference.bin_dense(data["row_ptr"], data["fi"], data["value"],
                                  np.asarray(binner.cuts), ROWS, FEATURES)
    rid = np.repeat(np.arange(ROWS), np.diff(data["row_ptr"]))
    assert np.array_equal(dense_t[data["fi"], rid], codes)
    assert int((dense_t > 0).sum()) == len(codes)


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmark" / "references" / "bosch-gbdt.py").read_text()
    assert "dmlc_core_tpu" not in text.split('"""', 2)[2]
