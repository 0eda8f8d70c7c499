"""One expected failure, until a ``benchmark`` PR edits the test itself.

``test_input_readers.py::test_the_benchmark_names_the_readers_and_their_layers``
(PR 24) asserts that its eight metrics are the LAST entries of
``per_layer``.  The benchmark's contract has every later PR append its
entries at the end of the list and edit no file that is there, so the
clause fails from the first such PR on (PR 26: ten entries for
``dense-ps-async-1chip``).  Everything else that test says is also held
by ``test_dense_ps.py::
test_the_new_entries_are_appended_behind_the_ones_that_were_there``.
``strict``: when the clause is dropped the test passes, this hook fails
the run, and it is deleted with it.
"""

import pytest

HELD_TO_THE_END = ("test_input_readers.py::"
                   "test_the_benchmark_names_the_readers_and_their_layers")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(HELD_TO_THE_END):
            item.add_marker(pytest.mark.xfail(
                raises=AssertionError, strict=True,
                reason="holds PR 24's entries to the end of per_layer, "
                       "where later PRs must append (PERF.md section 7)"))
