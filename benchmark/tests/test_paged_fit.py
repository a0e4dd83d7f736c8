"""The ``criteo-xgb-extmem`` configuration and its cell
``criteo-xgb-extmem.fit-paged``: the names resolve to their files, the
generator's pages are a function of (seed, page) with the source's three
kinds of columns, the binner's sample is rows the fit sees, the roofline's
work is the algorithm's whatever the pages, the rehearsal walks,
``check`` breaks, by the limit that names the fault, when the paged fit is
broken, and the cell's entries in ``BENCHMARK.json`` are there in their order
(membership and order, never that they are the last or a cell's only ones)."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, opcount, opcount_paged_histogram, run
from test_names import cell_entries

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG, CELL = "criteo-xgb-extmem", "criteo-xgb-extmem.fit-paged"
SEED = 2 ** 31 + 48
# the readings the resident tree has too, under its names
SHARED = ["hist_ms_per_round", "round_device_ms", "margin_ms_per_round"]
MINE = ["paged_hist_roofline", "paged_route_ms_per_round",
        "paged_accumulate_ms_per_round", "paged_boost_ms_per_round",
        "page_h2d_us_per_page", "page_h2d_bytes_per_round",
        "page_wait_pct.train", "page_passes_per_round"]
LIMITS = {"base_abs_err", "gain_rel_err", "cover_rel_err", "leaf_rel_err",
          "root_cover_rel_err", "split_regret", "trees_missing",
          "rows_streamed_mismatch", "page_bytes_mismatch",
          "pages_resident_max"}


def paged_fit():
    return run.load_module("traffic", "paged_fit")


def test_the_cell_and_its_configuration_resolve():
    """Membership and order, never "mine are the last": the next cell goes
    after this one."""
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(CELL) > cells.index(
        "criteo-tb-difacto-ps4.stream-train-mesh4")
    assert configs.index(CONFIG) > configs.index("criteo-tb-difacto-ps4")
    cell = BENCH["workloads"][cells.index(CELL)]
    config = BENCH["configs"][configs.index(CONFIG)]
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG] == [CELL]
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    assert CELL in rate["workloads"]
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "criteo-tb-difacto-ps4.stream-train-mesh4")
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "paged_fit" and spec["reference"] == CONFIG
    p = spec["params"]
    assert (p["rows"], p["page_rows"], p["prefetch_pages"], p["num_trees"],
            p["histogram"]) == (2 ** 28, 2 ** 22, 2, 1, "auto")
    data = json.loads((ROOT / config["file"]).read_text())
    sizes = data["sizes"]
    # the source's shapes, none of them cut; the rows cut, and said so
    assert (sizes["num_features"], sizes["num_bins"], sizes["max_depth"],
            sizes["learning_rate"], sizes["lambda"]) == (67, 256, 8, 0.1, 1.0)
    assert sizes["train_rows"] == p["rows"] and sizes["page_rows"] == p["page_rows"]
    assert config["reduced"] == ["rows"] == data["reduced"]
    assert data["published"]["train_rows"] == 1_700_000_000
    assert data["architecture"] is None
    # the rows are more than a v5e holds, so the paging is forced
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    assert p["rows"] * sizes["num_features"] == 17_985_175_552
    assert all(p["rows"] * sizes["num_features"] > d.get("hbm_bytes", 0)
               for d in peaks.values())
    assert set(data["tolerance"]["limits"]) == LIMITS
    assert data["tolerance"]["limits"]["pages_resident_max"] == (
        p["prefetch_pages"] + 1)
    assert len(data["guarantees"]) == 4 and "deployment" in data


def test_every_new_layer_metric_has_its_file_and_reader():
    for m in cell_entries(CELL, MINE, after="route_push_per_round"):
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
    for m in cell_entries(CELL, SHARED):
        # joined, after the resident cell whose names they are
        assert m["workloads"].index(CELL) > m["workloads"].index(
            "higgs-gbdt.fit-resident")
    roof = json.loads(
        (HERE / "layer_metrics" / "paged_hist_roofline.json").read_text())
    assert roof["args"]["pattern"] == "^%_histogram_gh_pallas"
    module, function = roof["args"]["opcount"].split(":")
    assert (HERE / f"{module}.py").is_file()
    assert callable(getattr(opcount_paged_histogram, function))


def test_the_rooflines_work_is_the_algorithms_whatever_the_pages():
    counts = {"data_rows": 2 ** 28, "features": 67, "levels": 8, "pages": 64,
              "passes": 9, "rounds": 1}
    work = opcount_paged_histogram.paged_dense_histogram(counts)
    assert work == opcount.dense_histogram(counts)
    assert work["bytes"] == 2 ** 28 * (67 + 12) * 8
    assert work == opcount_paged_histogram.paged_dense_histogram(
        dict(counts, pages=1))
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    for peak in peaks.values():
        assert opcount.least_seconds(work, peak)[1] == "bytes"


def test_pages_are_a_function_of_seed_and_page_with_the_sources_columns():
    import jax
    g = paged_fit()
    cols, score = jax.jit(lambda k: g.draw_block(k, 1 << 16))(
        jax.random.PRNGKey(3))
    a = np.asarray(cols)
    assert a.shape == (67, 1 << 16)
    ints, ctrs, counts = a[:13], a[13:39], a[39:]
    missing = np.isnan(ints).mean(axis=1)
    assert np.allclose(missing, g.INT_MISSING, atol=0.01)
    assert not np.isnan(a[13:]).any()
    assert np.all(np.nan_to_num(ints) == np.floor(np.nan_to_num(ints)))
    assert ctrs.min() > 0 and ctrs.max() < 1
    assert abs(np.median(ctrs) - g.CTR_MEDIAN) < 5e-4
    assert np.all(counts == np.floor(counts)) and np.median(counts) == 20
    assert np.percentile(counts, 99) > 50 * np.median(counts)  # heavy tail
    rate = float((np.asarray(score) > g.SCORE_CUT).mean())
    assert abs(rate - 0.03) < 0.004
    # the binner's shortcut is QuantileBinner.transform, absent cells too
    from dmlc_core_tpu.models import QuantileBinner
    binner = QuantileBinner(num_bins=256, missing_aware=True).fit(
        a[:, :8192].T)
    codes = np.asarray(g.bin_columns(cols, binner.cuts))
    assert codes.dtype == np.uint8 and codes.shape == (1 << 16, 67)
    assert np.array_equal(codes, np.asarray(binner.transform(cols.T)))
    assert np.array_equal(codes[:, :13] == 0, np.isnan(ints).T)

    first, make = g.page_maker(SEED, page_rows=1024, block_rows=256)
    page, label = make(np.int32(2), binner.cuts)
    again, _ = make(np.int32(2), binner.cuts)
    other, _ = make(np.int32(3), binner.cuts)
    assert np.array_equal(np.asarray(page), np.asarray(again))
    assert not np.array_equal(np.asarray(page), np.asarray(other))
    _, make2 = g.page_maker(SEED + 1, page_rows=1024, block_rows=256)
    assert not np.array_equal(np.asarray(page),
                              np.asarray(make2(np.int32(2), binner.cuts)[0]))
    # the binner's sample is the page's own first block
    sample = np.asarray(g.bin_columns(first(np.int32(2)), binner.cuts))
    assert np.array_equal(sample, np.asarray(page)[:256])
    assert label.shape == (1024,)


def walk(tmp_path, seed=SEED):
    cell = harness.load_cell(HERE, CELL, seed, rehearse=True)
    cell.cache_dir = tmp_path
    generator = paged_fit()
    reference = run.load_module("references", cell.reference)
    spans = harness.Spans()
    state = generator.setup(cell, spans)
    measured = generator.window(state, 0.2, spans)
    return cell, generator, reference, state, measured


@pytest.mark.parametrize("seed", (SEED, 99))
def test_the_rehearsal_walks_and_the_control_fails(tmp_path, seed):
    cell, generator, reference, state, measured = walk(tmp_path, seed)
    assert measured["attempted"] >= 1 and measured["failed"] == 0
    counts = measured["counts"]
    assert set(counts) == {"rows", "rounds", "levels", "passes", "data_rows",
                           "pages", "features", "window_us"}
    assert counts["pages"] == 4 and counts["data_rows"] == 7000
    assert counts["passes"] == counts["rounds"] * 4     # depth 3 + 1
    assert counts["rows"] == 7000 * counts["rounds"]
    # a short last page on the host, whole pages before it
    assert [p.shape for p in state["pages"]] == [(2048, 67)] * 3 + [(856, 67)]
    assert all(isinstance(p, np.ndarray) and p.dtype == np.uint8
               for p in state["pages"])
    assert state["label"].shape == (7000,)
    assert state["observed"]["page.resident_max"] == 2
    got = generator.check(state, reference, control=1)
    limits = cell.config["tolerance"]["limits"]
    sound = {c["name"]: c["value"] <= limits[c["name"]] for c in got
             if not c["name"].startswith("control.")}
    assert set(sound) == set(limits) == LIMITS and all(sound.values()), got
    failed = [c["name"] for c in got if c["name"].startswith("control.")
              and c["value"] > limits[c["name"][len("control."):]]]
    assert len(failed) >= 2, got
    generator.teardown(state)


def run_cell(capsys, trace: int = 0):
    """The cell's rehearsal through ``run.main``: its result line, and the
    names of the limits it failed (from the log)."""
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "0.3", "--trace", str(trace), "--rehearse-cpu"])
    assert rc == 0
    io = capsys.readouterr()
    failed = set(re.findall(r"compared (\w+): .* FAILED", io.err))
    return json.loads(io.out.strip().splitlines()[-1]), failed


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct_and_prints_the_contract_keys(capsys, trace):
    line, failed = run_cell(capsys, trace)
    assert line["correct"] is True and line["failed"] == 0 and not failed
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["metrics"] == {}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}


def patched(monkeypatch, patch):
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == ("traffic", "paged_fit"):
            patch(module)
        return module
    monkeypatch.setattr(run, "load_module", load)


def visiting(monkeypatch, visit):
    """Put ``visit(real, model, depth, hist, node, page, grad, hess, offset,
    prev)`` in `GBDT._page_visit`'s place."""
    from dmlc_core_tpu.models import GBDT
    real = GBDT._page_visit
    monkeypatch.setattr(
        GBDT, "_page_visit",
        lambda model, *args: visit(real, model, *args))


def test_fit_that_returns_its_state_unchanged_is_not_correct(capsys,
                                                             monkeypatch):
    def patch(module):
        def unchanged(state):
            real(state)
            state["forest"] = state["model"].init()
        real, module.fit_once = module.fit_once, unchanged
    patched(monkeypatch, patch)
    line, failed = run_cell(capsys)
    assert line["correct"] is False
    assert {"trees_missing", "base_abs_err"} <= failed


def test_a_page_skipped_fails_the_counts_and_the_roots_cover(capsys,
                                                             monkeypatch):
    """The fit never sees the last page (nor its labels)."""
    def patch(module):
        def short(state):
            whole = dict(state)
            held = sum(p.shape[0] for p in state["pages"][:-1])
            state.update(pages=state["pages"][:-1],
                         label=state["label"][:held])
            try:
                real(state)
            finally:
                state.update(pages=whole["pages"], label=whole["label"])
        real, module.fit_once = module.fit_once, short
    patched(monkeypatch, patch)
    line, failed = run_cell(capsys)
    assert line["correct"] is False
    assert {"rows_streamed_mismatch", "page_bytes_mismatch",
            "root_cover_rel_err"} <= failed


def test_a_page_visited_twice_fails_the_roots_cover(capsys, monkeypatch):
    def twice(real, model, depth, hist, node, page, *rest):
        hist, node, tick = real(model, depth, hist, node, page, *rest)
        if int(rest[2]) == 2048 and depth < model.max_depth:
            hist, node, tick = real(model, depth, hist, node, page, *rest)
        return hist, node, tick
    visiting(monkeypatch, twice)
    line, failed = run_cell(capsys)
    assert line["correct"] is False
    assert "root_cover_rel_err" in failed and "cover_rel_err" in failed
    assert not {"rows_streamed_mismatch", "page_bytes_mismatch"} & failed


def test_a_page_routed_with_its_neighbours_rows_fails_gain_and_cover(
        capsys, monkeypatch):
    """Page 1's bins against page 0's slice of node, grad and hess."""
    def neighbour(real, model, depth, hist, node, page, grad, hess, offset,
                  prev):
        if int(offset) == 2048:
            offset = np.int32(0)
        return real(model, depth, hist, node, page, grad, hess, offset, prev)
    visiting(monkeypatch, neighbour)
    line, failed = run_cell(capsys)
    assert line["correct"] is False
    assert {"gain_rel_err", "cover_rel_err"} <= failed


def test_three_pages_resident_where_two_are_allowed_fails_its_limit(
        capsys, monkeypatch):
    def patch(module):
        def greedy(state):
            state["prefetch_pages"] = 2     # the rehearsal's file says 1
            real(state)
        real, module.fit_once = module.fit_once, greedy
    patched(monkeypatch, patch)
    line, failed = run_cell(capsys)
    assert line["correct"] is False and failed == {"pages_resident_max"}


def test_every_fit_runs_under_the_guard_that_refuses_a_fetch(capsys,
                                                             monkeypatch):
    """Row state fetched to the host inside a fit: the generator runs every
    fit under ``jax.transfer_guard_device_to_host("disallow")``, under which
    a TPU's ``np.asarray(node)`` raises and the run prints no line.  The
    CPU's arrays ARE host memory and no guard sees their reads, so here the
    visits read the guard itself: on in every visit, off outside the fit."""
    import jax
    seen = []

    def look(real, model, *args):
        seen.append(jax.config.jax_transfer_guard_device_to_host)
        return real(model, *args)
    visiting(monkeypatch, look)
    line, failed = run_cell(capsys)
    assert line["correct"] is True and not failed
    assert seen and set(seen) == {"disallow"}
    assert jax.config.jax_transfer_guard_device_to_host != "disallow"
