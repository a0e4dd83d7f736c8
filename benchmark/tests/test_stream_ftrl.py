"""The ``stream_ftrl`` generator and the ``criteo-tb-ftrl`` reference at the
cell's rehearsal size: the walk is sound, its control is not, a state
rounded through bfloat16 fails, a batch delivered twice is noticed, and the
file is what the seed says."""
import ml_dtypes
import numpy as np
import pytest

from test_references import SEED, control_fails, verdict, walk

CELL = "criteo-tb-ftrl.stream-train"


@pytest.mark.parametrize("seed", (SEED, 99))
def test_ftrl_reference_agrees_and_its_control_does_not(tmp_path, seed):
    cell, generator, reference, state = walk(CELL, tmp_path, seed)
    assert state["compared"][0].shape == (
        cell.params["sample_features"], 3)
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    assert {"control.z_rel_err", "control.n_rel_err", "control.live_z_rel_err",
            "control.live_n_rel_err"} <= set(control_fails(cell, sound)), sound
    # a compared loss is a reading, held to no limit
    assert not [c for c in sound if "loss" in c["name"]]
    generator.teardown(state)


def test_a_live_step_that_writes_nothing_back_fails(tmp_path):
    """The step after the window is compared at every distinct id of its
    minibatch, against one step of the reference from the same state."""
    import jax.numpy as jnp
    cell, generator, reference, state = walk(CELL, tmp_path)
    state["model"].train_step = lambda params, batch: (params,
                                                       jnp.float32(0.6))
    got = verdict(cell, generator.check(state, reference))
    assert not got["live_z_rel_err"] and not got["live_n_rel_err"]
    assert got["z_rel_err"] and got["untouched_changed"]
    assert got["delivery_mismatch"]
    generator.teardown(state)


def test_state_rounded_through_bfloat16_fails(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    rows, bias = state["compared"]
    state["compared"] = (rows.astype(ml_dtypes.bfloat16).astype(np.float32),
                         bias)
    got = verdict(cell, generator.check(state, reference))
    assert not got["z_rel_err"] and not got["n_rel_err"]
    assert got["delivery_mismatch"] and got["untouched_changed"]
    generator.teardown(state)


def test_a_batch_delivered_twice_is_a_delivery_mismatch(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    import jax.numpy as jnp
    state["tally"] = dict(state["tally"],
                          rows=state["tally"]["rows"] + jnp.uint32(256))
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["delivery_mismatch"] == 1
    generator.teardown(state)


def test_the_file_is_the_seeds_and_is_found_again(tmp_path):
    cell, generator, _reference, state = walk(CELL, tmp_path)
    path, made = state["path"], state["made"]
    s = cell.sizes
    assert path.stat().st_size == made["bytes"] == generator.libsvm_bytes(
        cell.params["file_rows"], s["entries_per_row"], s["num_features"])
    assert made["written"]
    first = path.read_text().splitlines()[0].split()
    assert int(first[0]) == made["label"][0]
    assert [int(e.split(":")[0]) for e in first[1:]] == list(made["index"][0])
    again = generator.make_file(cell, path, np.arange(8), 16)
    assert not again["written"]
    assert np.array_equal(again["ids"], made["ids"])
    # ids no row names really are in no row
    ids = np.loadtxt(path, dtype=str)[:, 1:]
    named = {int(e.split(":")[0]) for e in ids.reshape(-1)}
    assert not named & set(state["untouched_ids"].tolist())
    assert set(state["sample_ids"].tolist()) <= named
    generator.teardown(state)
