"""Driver for ``kind: closed_loop_held``: ``closed_loop_counted`` for a
family whose expert layers HOLD a share of the experts their router
ranks.

It RUNS ``closed_loop_counted.run`` and copies none of it.  The one
thing that differs: the program counts, beside the rows its experts
computed (``moe.rows``), the rows it sent to experts held on another
chip (``moe.rows_elsewhere``), and ``closed_loop_counted.COUNTERS`` is a
fixed tuple; for the length of the run it is this module's, one counter
longer, so that the new counter's deltas reach ``run.counters`` and
``records["counters_in_trace"]`` the way the others do.
"""
from __future__ import annotations

import closed_loop_counted

COMPARES = closed_loop_counted.COMPARES
COUNTERS = closed_loop_counted.COUNTERS + ("moe.rows_elsewhere",)


def run(r) -> None:
    theirs = closed_loop_counted.COUNTERS
    closed_loop_counted.COUNTERS = COUNTERS
    try:
        closed_loop_counted.run(r)
    finally:
        closed_loop_counted.COUNTERS = theirs
