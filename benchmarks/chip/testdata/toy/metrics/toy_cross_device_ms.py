"""Device time per train-step call under the toy's `toy_cross` scope."""


def read(rec):
    red = rec["spans"]
    ms = red and red["scope_ms"].get("toy_cross")
    return ms or None
