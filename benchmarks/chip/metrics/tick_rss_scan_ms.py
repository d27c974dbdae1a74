"""Host time the loop's thread spends reading the pipeline workers'
resident memory per tuner tick: the program's `rss_scan` spans on the
loop's thread in the traced window (the tick's `measure` and each new
worker's baseline; the sampler thread's scans are left out), over the
window's ticks (`spans.py`)."""
import spans


def read(rec):
    red = rec["spans"]
    t = red and red["spans"].get("rss_scan")
    return 1e3 * spans.per_tick(red, t["s"]) if t else None
