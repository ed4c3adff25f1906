"""The benchmark's own tests of its per-layer table (benchmarks/tests/
test_table.py: one metric is one file and one `per_layer` entry, every pair
PR 55 had still reads the same formula), run in tier-1: no jax, about a
second.  A PR that adds a metric file finds out here, not on the chip."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.tests.test_table import *  # noqa: E402,F401,F403
from benchmarks.tests.test_table import ENTRIES, MOST_ENTRIES, OLD  # noqa: E402


def test_the_old_table_was_full_and_this_one_has_room():
    """As the benchmark's, but for its `len(ENTRIES) <= 109`: that pins PR
    56's count in a file only a `benchmark` PR may edit, and PR 57 added six
    entries.  What the driver's contract allows is MOST_ENTRIES."""
    assert len(OLD) == MOST_ENTRIES
    assert len(ENTRIES) <= MOST_ENTRIES
    assert sum(len(m["workloads"]) for m in ENTRIES) >= \
        sum(len(o["cells"]) for o in OLD)
