"""A whole run, the look for a chip replaced by the rehearsal's, with the timed
path broken underneath: ``correct`` has to come out false.  And sound runs come
out true, with exactly the keys the driver reads."""
import json

import pytest

from benchmark import run

SEED = 2 ** 31 + 5


def run_cell(capsys, cell: str, trace: int = 0) -> dict:
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.3", "--trace", str(trace), "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def patched_loader(monkeypatch, kind_name: tuple, patch):
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == kind_name:
            patch(module)
        return module
    monkeypatch.setattr(run, "load_module", load)


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", ("higgs-gbdt.fit-resident",
                                  "criteo-ffm.stream-train"))
def test_sound_run_is_correct_and_prints_the_contract_keys(capsys, cell,
                                                           trace):
    line = run_cell(capsys, cell, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["metrics"] == {}      # a rehearsal reports no number
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_fit_that_returns_its_state_unchanged_is_not_correct(capsys,
                                                             monkeypatch):
    def patch(module):
        def unchanged(state):
            state["forest"] = state["model"].init()
        module.fit_once = unchanged
    patched_loader(monkeypatch, ("traffic", "resident_fit"), patch)
    assert run_cell(capsys, "higgs-gbdt.fit-resident")["correct"] is False


def test_fit_that_leaves_out_half_the_rows_is_not_correct(capsys,
                                                          monkeypatch):
    def patch(module):
        import jax

        def half(state):
            n = state["rows"] // 2
            state["forest"] = jax.block_until_ready(state["model"].fit(
                state["bins"][:n], state["label"][:n]))
        module.fit_once = half
    patched_loader(monkeypatch, ("traffic", "resident_fit"), patch)
    assert run_cell(capsys, "higgs-gbdt.fit-resident")["correct"] is False


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    def patch(module):
        real = module.make_model

        def make(cell):
            import jax
            import jax.numpy as jnp
            model = real(cell)
            step = model.train_step     # donates what it is given: a copy
            model.train_step = lambda params, batch: (
                params, step(jax.tree.map(jnp.copy, params), batch)[1])
            return model
        module.make_model = make
    patched_loader(monkeypatch, ("traffic", "stream_epochs"), patch)
    assert run_cell(capsys, "criteo-ffm.stream-train")["correct"] is False


def test_without_the_flag_no_chip_is_a_failure(capsys):
    rc = run.main(["--workload", "higgs-gbdt.fit-resident", "--seed", "1",
                   "--seconds", "0.1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""
