"""Share of the routed (token, expert) rows that fell on the experts this
chip HOLDS, in the window: ``moe.rows / (moe.rows + moe.rows_elsewhere)``
from the program's counters, in percent.  A chip that holds 64 of the 128
experts its router ranks reads 50 within a point under an even routing;
another reading says the cut is not the one the configuration states (or
the routing leans to one half).  Rows sent elsewhere are computed by no
one here: the deployment's other chip would."""


def read(run):
    held = run.counters.get("moe.rows")
    away = run.counters.get("moe.rows_elsewhere")
    if not held or away is None:
        return None
    return 100.0 * held / (held + away)
