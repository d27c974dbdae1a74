"""Rows the toy looked up per traced step: its `toy_rows_looked_up`
counter over the window's steps."""


def read(rec):
    red = rec["spans"]
    if red is None or not red["steps"]:
        return None
    return red["counters"].get("toy_rows_looked_up", 0) / red["steps"]
