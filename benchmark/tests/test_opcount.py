"""opcount.py against shapes worked by hand."""
import json
from pathlib import Path

import pytest

from benchmark import opcount

PEAKS = json.loads((Path(__file__).resolve().parents[1]
                    / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_dense_histogram_is_rows_times_f_plus_12_bytes_a_level():
    work = opcount.dense_histogram(
        {"data_rows": 1000, "features": 28, "levels": 6})
    assert work["bytes"] == 1000 * 40 * 6
    assert work["flops"] == 2 * 1000 * 28 * 6


def test_least_seconds_names_the_bound():
    work = opcount.dense_histogram(
        {"data_rows": 10_500_000, "features": 28, "levels": 1})
    seconds, bound = opcount.least_seconds(work, PEAKS)
    assert bound == "bytes"
    assert seconds == pytest.approx(10_500_000 * 40 / 819e9)
    seconds, bound = opcount.least_seconds({"flops": 197e12, "bytes": 1.0},
                                           PEAKS)
    assert (seconds, bound) == (pytest.approx(1.0), "flops")
