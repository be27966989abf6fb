"""D2H: how full the sparse transfer budgets were over the window: the
larger of blocks used / budget and values used / budget, x 100, from
the growth of the four `sparse_*` counters. Over 100 the waves went
dense (the value count is then a lower bound: it is taken after the
block budget cut). Not measured where the program has no such counters
or none of them moved."""

from tvtbench import evidence


def read(ev):
    fills = []
    for what in ("blocks", "values"):
        budget = evidence.stage_delta(ev, f"sparse_{what}_budget")
        if budget > 0:
            fills.append(100.0 * evidence.stage_delta(
                ev, f"sparse_{what}_used") / budget)
    return max(fills) if fills else None
