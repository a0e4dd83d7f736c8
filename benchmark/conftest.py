"""Two tests of ``tests/test_mesh_fit.py`` (PR 31) hold only while
``airline-gbdt.fit-mesh4`` is the benchmark's NEWEST cell: one asserts that
its name is the last of ``train_rows_per_s``'s ``workloads``, the other that
its nine metrics are the last nine of ``per_layer``.  New entries go at the
end of ``BENCHMARK.json``'s lists (one put first or in the middle reads as a
change to what was there), so the first cell added after it, PR 33's
``criteo-tb-ftrl.stream-train``, breaks both, and a PR that is not a
``benchmark`` PR may not edit that file.

Until one turns the two assertions into tests of membership and order, they
are expected to fail, strictly: the day they pass this file has to go.
``tests/test_stream_ftrl.py`` runs both tests' bodies, every assertion of
them, against ``BENCHMARK.json`` less the entries that came after PR 31's, so
nothing they guard goes unguarded meanwhile.
"""
import pytest

NEWEST_CELL_ONLY = (
    "test_mesh_fit.py::test_the_cell_and_its_configuration_resolve",
    "test_mesh_fit.py::test_every_new_layer_metric_has_its_file_and_reader",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(NEWEST_CELL_ONLY):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts that airline-gbdt.fit-mesh4 is "
                "the benchmark's newest cell; run by test_stream_ftrl.py "
                "against the benchmark as PR 31 left it"))
