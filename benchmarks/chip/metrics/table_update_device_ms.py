"""Device time per train-step call under the `table_update` scope
(`train/optim.rowwise_adagrad`: the row-wise Adagrad update of the
embedding tables). Each instant of a traced `jit_train_step` call goes
to the innermost operation's scope (`spans.py`)."""


def read(rec):
    red = rec["spans"]
    ms = red and red["scope_ms"].get("table_update")
    return ms or None
