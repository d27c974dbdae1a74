"""Pipeline worker processes started per tuner tick: the program's
`workers_started` counter's increase in the traced window (marked on
the trace at each start) over the window's ticks (`spans.py`)."""
import spans


def read(rec):
    red = rec["spans"]
    if red is None:
        return None
    return spans.per_tick(red, red["counters"].get("workers_started", 0))
