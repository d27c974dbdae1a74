"""Device time per train-step call under the `embedding_lookup` scope
(`models/dlrm.forward`): the gather of the looked-up rows and, through
the scope its gradient keeps, their scatter into the dense table
gradient. Each instant of a traced `jit_train_step` call goes to the
innermost operation's scope (`spans.py`)."""


def read(rec):
    red = rec["spans"]
    ms = red and red["scope_ms"].get("embedding_lookup")
    return ms or None
