"""Pipeline worker processes started per tuner tick: the program's
`workers_started` counter's increase in the traced window (marked on
the trace at each start) over the window's ticks (`spans.py`)."""


def read(rec):
    import spans
    red = None if rec["trace"] is None else spans.window()
    if red is None:
        return None
    return spans.per_tick(red, red["counters"].get("workers_started", 0))
