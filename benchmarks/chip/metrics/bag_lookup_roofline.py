"""Share of its roofline that the `bag_lookup` scope reaches per train
step: the operations and HBM bytes the multi-hot lookup needs as the
algorithm needs them (`archs/<model>.scope_work`, which counts batch x
sum(hot_sizes) looked-up rows) against the scope's traced device time
per call (`spans.roofline_pct`). None unless the program's
`ids_looked_up` counter shows that each step dispatched in the window
looked up that many ids: the count the bytes assume."""
import spans


def read(rec):
    red, cfg = rec["spans"], rec["config"]
    if red is None or not red["steps"]:
        return None
    per_step = cfg["batch"] * sum(cfg["hot_sizes"])
    ids = red["counters"].get("ids_looked_up", 0)
    # the window's counter holds its whole steps and, at most, the step
    # dispatched as it closed
    if ids % per_step or not red["steps"] <= ids // per_step <= red["steps"] + 1:
        return None
    return spans.roofline_pct(rec, "bag_lookup")
