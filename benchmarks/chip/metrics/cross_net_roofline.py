"""Share of its roofline that the `cross_net` scope reaches per train
step: the cross layers' products forward and backward and the bytes
they need (`archs/<model>.scope_work`; bound by the operations, against
the bf16 peak) over the scope's traced device time per call
(`spans.roofline_pct`)."""
import spans


def read(rec):
    return spans.roofline_pct(rec, "cross_net")
