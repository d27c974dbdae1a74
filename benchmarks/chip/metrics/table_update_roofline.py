"""Share of its roofline that the `table_update` scope reaches per
train step: the operations and HBM bytes the row-wise Adagrad update of
the looked-up rows needs as the algorithm needs them
(`archs/<model>.scope_work`) against the scope's traced device time per
call (`spans.roofline_pct`)."""
import spans


def read(rec):
    return spans.roofline_pct(rec, "table_update")
