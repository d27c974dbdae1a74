"""Share of its roofline that the `embedding_lookup` scope reaches per
train step: the operations and HBM bytes the lookup needs as the
algorithm needs them (`archs/<model>.scope_work`) against the scope's
traced device time per call (`spans.roofline_pct`)."""
import spans


def read(rec):
    return spans.roofline_pct(rec, "embedding_lookup")
