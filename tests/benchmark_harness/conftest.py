"""One test of this directory holds what a later cell has to undo:
``test_driver_interval_passes.py`` asks that ``exome_intervals`` be the
last cell of ``BENCHMARK.json`` and the last name of fifteen metrics'
``workloads``, and PR 48 appended ``longread_read`` after it.  The file
is the accepted benchmark's and only a ``benchmark`` PR may edit it, so
the test is expected to fail here, strictly: once that PR has made it
``CELL in ...`` this file fails the run and goes.  Until then
``test_driver_longread_passes.py`` runs the test's body against the
manifest with what PR 48 appended taken off, so nothing it holds lapses.
"""

import pytest

STALE = ("test_driver_interval_passes.py::"
         "test_the_cell_is_the_issues_tables_letter_for_letter")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asks that exome_intervals be the "
                "last cell; longread_read (PR 48) is appended after it"))
