"""Bytes a query's search has to read from device memory, whatever
implements it: the block summaries of the live blocks of its probed
lists (routing), and the forward rows of the documents it scored
(``docs_evaluated``). They are the streamed-row terms of the program's
fuse-level-2 work model (``repro.retrieval.workmodel``), copied here so
that the yardstick does not move with the program, and sized from the
index's own arrays. Query rows, outputs and intermediates are left out:
an implementation can keep them on chip.
"""
from __future__ import annotations

import numpy as np


def summary_row_bytes(index) -> int:
    """One block summary: ``S`` coordinates and quantized values, and
    the block's dequantization constants."""
    s = index.sum_coords.shape[-1]
    return (s * (index.sum_coords.dtype.itemsize
                 + index.sum_q.dtype.itemsize)
            + index.sum_scale.dtype.itemsize + index.sum_zero.dtype.itemsize)


def forward_row_bytes(index) -> int:
    """One document's forward row (and its dequantization constants on
    a quantized plane)."""
    nnz = index.fwd.coords.shape[-1]
    b = nnz * (index.fwd.coords.dtype.itemsize
               + index.fwd.vals.dtype.itemsize)
    if index.fwd_scale is not None:
        b += index.fwd_scale.dtype.itemsize + index.fwd_zero.dtype.itemsize
    return b


def live_blocks_probed(live_per_list: np.ndarray,
                       probed: np.ndarray) -> np.ndarray:
    """Live blocks each query routes over: ``probed`` [Q, cut] list ids
    (repeats count once), ``live_per_list`` [L] live-block counts."""
    out = np.zeros(probed.shape[0], np.int64)
    for i, row in enumerate(probed):
        out[i] = live_per_list[np.unique(row)].sum()
    return out


def necessary_bytes(live_blocks: np.ndarray, docs_evaluated: np.ndarray,
                    summary_row_b: int, forward_row_b: int) -> np.ndarray:
    """Per query: summary rows of its live probed blocks plus forward
    rows of the documents it scored."""
    return (np.asarray(live_blocks, np.int64) * summary_row_b
            + np.asarray(docs_evaluated, np.int64) * forward_row_b)
