"""Each reference agrees with the program at a tiny size, fails when the
program's result is rounded through bfloat16, and its lower-precision control
comes out as not correct.  The cells' rehearsal sizes are the tiny sizes."""
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from benchmark import harness, run

HERE = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 77


def through_bf16(a):
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def walk(cell_name: str, tmp_path, seed: int = SEED):
    """Set a cell up at its rehearsal size, run a short window, and return
    (cell, generator, reference, state)."""
    cell = harness.load_cell(HERE, cell_name, seed, rehearse=True)
    cell.cache_dir = tmp_path
    generator = run.load_module("traffic", cell.generator)
    reference = run.load_module("references", cell.reference)
    spans = harness.Spans()
    state = generator.setup(cell, spans)
    generator.window(state, 0.2, spans)
    return cell, generator, reference, state


def verdict(cell, comparisons) -> dict:
    limits = cell.config["tolerance"]["limits"]
    return {c["name"]: c["value"] <= limits[c["name"]] for c in comparisons
            if not c["name"].startswith("control.")}


def control_fails(cell, comparisons) -> list:
    limits = cell.config["tolerance"]["limits"]
    return [c["name"] for c in comparisons if c["name"].startswith("control.")
            and c["value"] > limits[c["name"][len("control."):]]]


@pytest.mark.parametrize("seed", (SEED, 99, 123456789))
def test_gbdt_reference_agrees_and_catches_bf16(tmp_path, seed):
    cell, generator, reference, state = walk(
        "higgs-gbdt.fit-resident", tmp_path, seed)
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    # the two numbers the control fails at the cell's own size too, under
    # the cell's own limits (the rehearsal overrides neither)
    assert {"control.gain_rel_err", "control.cover_rel_err"} <= set(
        control_fails(cell, sound)), sound
    # the program's own result, rounded through bfloat16 where it is stored
    forest = dict(state["forest"])
    for key in ("split_gain", "split_cover", "leaf"):
        forest[key] = through_bf16(forest[key])
    state["forest"] = forest
    rounded = verdict(cell, generator.check(state, reference))
    assert not all(rounded.values()), rounded
    generator.teardown(state)


def test_ffm_reference_agrees_and_catches_bf16(tmp_path):
    cell, generator, reference, state = walk(
        "criteo-ffm.stream-train", tmp_path)
    kept = {k: state[k] for k in ("losses", "first_grad", "change",
                                  "sample_change")}
    steps, params = state["steps"], state["params"]
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    assert control_fails(cell, sound), sound
    state.update(kept, steps=steps, params=params)
    state["sample_change"] = {k: through_bf16(v)
                              for k, v in kept["sample_change"].items()}
    rounded = verdict(cell, generator.check(state, reference))
    assert not rounded["delta_sample_diff"], rounded
    generator.teardown(state)


def test_ffm_delivery_tally_sees_a_dropped_batch(tmp_path):
    cell, generator, reference, state = walk(
        "criteo-ffm.stream-train", tmp_path)
    next(state["batches"])          # a batch the consumer never saw
    generator.step(state, harness.Spans())
    out = {c["name"]: c["value"]
           for c in generator.check(state, reference)}
    assert out["delivery_mismatch"] > 0
    generator.teardown(state)


@pytest.mark.parametrize("config", sorted(
    p.stem for p in (HERE / "configs").glob("*.json")))
def test_every_limit_is_a_number_the_reference_reports(config):
    limits = json.loads((HERE / "configs" / f"{config}.json").read_text())[
        "tolerance"]["limits"]
    text = (HERE / "references" / f"{config}.py").read_text()
    text += "".join(p.read_text() for p in (HERE / "traffic").glob("*.py"))
    for name in limits:
        assert f'"{name}"' in text, name
