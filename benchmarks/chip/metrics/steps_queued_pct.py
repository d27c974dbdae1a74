"""Share of the traced window's steps whose dispatch found the step
before still running on the device, so that the new step queued behind
it: the program's `steps_queued` counter (`train_on_feed`) over the
window's steps, in % (`spans.py`). None where the window holds no such
counter: a program that does not count it, or one in which no dispatch
found the device busy."""


def read(rec):
    red = rec["spans"]
    if red is None or not red["steps"] or "steps_queued" not in red["counters"]:
        return None
    return 100.0 * red["counters"]["steps_queued"] / red["steps"]
