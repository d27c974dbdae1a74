"""Device time per train-step call under the `bag_lookup` scope
(`models/embedding.packed_multifeature_bag`, inside `embedding_lookup`):
the gather of every looked-up row from the packed table, each bag's sum
and, through the scope its gradient keeps, the scatter of their
gradient into the table gradient. Each instant of a traced
`jit_train_step` call goes to the innermost operation's scope
(`spans.py`)."""


def read(rec):
    red = rec["spans"]
    ms = red and red["scope_ms"].get("bag_lookup")
    return ms or None
