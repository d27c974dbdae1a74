"""Device time per train-step call under the `cross_net` scope
(`models/dlrm.cross_net`, inside `interaction`): the low-rank cross
layers forward and, through the scope their gradients keep, backward.
Each instant of a traced `jit_train_step` call goes to the innermost
operation's scope (`spans.py`)."""


def read(rec):
    red = rec["spans"]
    ms = red and red["scope_ms"].get("cross_net")
    return ms or None
