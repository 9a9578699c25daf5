"""Operations and bytes of a DLRM-family request, counted from shapes: the
parts every architecture (``archs/*.py``) shares.

These are the work the model defines, not what an implementation happens
to do: padding rows of a batch, padding tables, DMA'd blocks and
recomputation do not count, so a share computed from them reads the same
whatever implements a layer.  Bias adds, ReLUs and the sigmoid are left out
(under 0.1% of the total).

Pooling does one add per element of a valid row: valid indices * s FLOPs.
Its useful bytes, per request: each valid index reads one row
(s * itemsize) plus its index (4 B) and its weight (4 B), and each real
table writes one pooled row (s * itemsize).
"""
from __future__ import annotations

import numpy as np


def _mlp_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def pooling_flops(cfg: dict, valid: np.ndarray) -> np.ndarray:
    """FLOPs of pooling requests with ``valid`` indices each."""
    return np.asarray(valid, np.int64) * cfg["embed_dim"]


def pooling_bytes(cfg: dict, valid: np.ndarray) -> np.ndarray:
    """Useful HBM bytes of pooling requests with ``valid`` indices each."""
    s = cfg["embed_dim"]
    item = np.dtype(cfg["dtype"]).itemsize
    valid = np.asarray(valid, np.int64)
    return valid * (s * item + 8) + len(cfg["table_sizes"]) * s * item
