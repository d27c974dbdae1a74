"""The numbers that decide `correct`, and the check of the fed batches.

Training (three fed steps replayed by the float32 reference, after the
four warm-up steps the program takes on its fixed warm batch):

  loss_gap     largest |program loss - reference loss| / |reference
               loss| over the three fed steps;
  grad_gap     the run's first gradient (the first warm-up step's, on
               the seed's weights), as the optimizer took it (read from
               its Adagrad accumulators after that step), by the worst leaf:
               |program norm - reference norm| over the larger of the
               reference's norm of that leaf and of the median leaf;
  change_gap   the same for the parameters' change from the seed's
               weights to those the fourth fed step starts from.

Leaves whose reference gradient is under a thousandth of the median
leaf's would move by round-off alone; none is left out at these widths,
but the rule stands in `reference.gaps`.

The fed batches (what the pipeline's featurization produced) cannot be
regenerated: the pipeline's workers seed their record draws from their
process ids. They are held to what featurization guarantees: shapes and
dtypes (the architecture's, `archs/<model>.feed_faults`), ids inside
their tables, labels of 0 or 1, dense features finite and standardised
per batch, and rows that all differ.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import reference


def training_gaps(program: Dict, ref: Dict, checked: int) -> Dict[str, float]:
    fed = len(ref["loss"]) - checked
    loss = max(abs(p - r) / abs(r) for p, r in zip(program["loss"], ref["loss"][fed:]))
    first = ref["grad"][0]
    grad, _ = reference.gaps(program["grad"], first, first)
    change, _ = reference.gaps(program["change"], ref["change"], first)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def worst_leaves(program: Dict, ref: Dict) -> Dict[str, str]:
    first = ref["grad"][0]
    return {"grad_gap": reference.gaps(program["grad"], first, first)[1],
            "change_gap": reference.gaps(program["change"], ref["change"], first)[1]}


def feed_faults(batches: List[Dict[str, np.ndarray]],
                want: Dict[str, Tuple[Tuple[int, ...], type]], ids: str,
                rows) -> List[str]:
    """What the fed batches break of featurization's guarantees: each
    key of `want` of its (shape, dtype); the ids under key `ids` in [0,
    rows) (a count, or one per id broadcast over its trailing axes);
    labels of 0 or 1; `dense` finite and standardised; and no row (all
    keys but the label) repeated."""
    b = want["label"][0][0]
    faults: List[str] = []
    rows_seen = []
    for n, batch in enumerate(batches):
        bad = [f"batch {n}: {k} is not {dtype.__name__}{shape}"
               for k, (shape, dtype) in want.items()
               if k not in batch or batch[k].shape != shape or batch[k].dtype != dtype]
        faults += bad
        if bad:
            continue
        dense, label = batch["dense"], batch["label"]
        if batch[ids].min() < 0 or (batch[ids] >= rows).any():
            where = f"[0, {rows})" if np.ndim(rows) == 0 else "their tables"
            faults.append(f"batch {n}: ids outside {where}")
        if not np.isin(label, (0.0, 1.0)).all():
            faults.append(f"batch {n}: labels other than 0 and 1")
        if not np.isfinite(dense).all():
            faults.append(f"batch {n}: dense features not finite")
        elif (np.abs(dense.mean(0)).max() > 1e-3
              or np.abs(dense.std(0) - 1.0).max() > 1e-3):
            faults.append(f"batch {n}: dense features not standardised")
        rows_seen.append(np.concatenate([batch[k].reshape(b, -1).view(np.uint32)
                                         for k in want if k != "label"], axis=1))
    if rows_seen:
        allrows = np.ascontiguousarray(np.concatenate(rows_seen))
        distinct = np.unique(allrows.view(np.dtype((np.void, allrows.shape[1] * 4))))
        if distinct.size != allrows.shape[0]:
            faults.append(f"{allrows.shape[0] - distinct.size} fed rows repeat")
    return faults
