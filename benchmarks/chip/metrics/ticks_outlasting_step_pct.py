"""Share of the traced window's tuner ticks that outlasted the step
dispatched before them: the program's `ticks_outlasting_step` counter
(`train_on_feed`: the step's loss was ready when the tick ended, so the
device idled for the rest of the tick) over the window's ticks, in %
(`spans.py`)."""
import spans


def read(rec):
    red = rec["spans"]
    if red is None:
        return None
    share = spans.per_tick(red, red["counters"].get("ticks_outlasting_step", 0))
    return None if share is None else 100.0 * share
