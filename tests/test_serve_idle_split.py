"""Tier-1's run of the benchmark's idle-split readers (PR 40): the cases
live with the readers, in ``benchmark/tests/test_idle_split_readers.py``,
and are imported, not copied. A known clock skew is recovered, the four
classes add to the idle total, ticks and units are kept apart, unmatched
modules and launches are counted, and crossed bounds, no launch, no idle
and a missing counter each read None."""
from benchmark.tests.test_idle_split_readers import (  # noqa: F401
    test_decode_feed_mb, test_idle_split)
