"""Host time of one tuner tick: the program's `tuner_tick` span
(`Session.step()` in the served loop, `repro.common.tracing`) on the
loop's thread in the traced window, seconds over count (`spans.py`)."""
import spans


def read(rec):
    red = rec["spans"]
    t = red and red["spans"].get("tuner_tick")
    return 1e3 * spans.per_tick(red, t["s"]) if t else None
