"""The necessary work of one training step, and the chip's peaks.

Counted from the algorithm and the batch alone: the distinct table rows
touched ``u``, the rows of the batch, its non-zeros, ``V_dim`` and the
item size of the type the configuration stores V in. Never from the
stored row width or from what the program materialises on the way, so a
better layout reads as the same work done faster.
"""

from __future__ import annotations

import json
import os

# FTRL keeps w, z and sqrt_g for a row, and the activation rule its
# count: four float32 scalars beside the embedding and its AdaGrad state
ROW_SCALARS = 4
SCALAR_BYTES = 4
INDEX_BYTES = 4
LABEL_BYTES = 4


def item_size(config: dict) -> int:
    """Bytes an item of V and Vg takes in the type the configuration
    stores them in: ``slot_dtype`` int8 or fp8 1, bf16 2, and otherwise
    (``slot_dtype`` fp32, or none) what ``V_dtype`` says."""
    slot = str(config.get("slot_dtype", "fp32"))
    if slot in ("int8", "fp8"):
        return 1
    if slot == "bf16" or str(config.get("V_dtype", "float32")) == "bfloat16":
        return 2
    return 4


def step_work(u: float, rows: float, nnz: float, V_dim: int,
              itemsize: int, valued: bool = False) -> dict:
    """{"bytes", "flops"} that one step cannot do without.

    Bytes: every touched row's V and Vg (2 * V_dim items) and scalars are
    read once and written once; the batch's indices, labels (and values,
    where features carry them) are read once.
    Flops: forward, per non-zero and factor, one add into X.V and a
    multiply-add into the sum of squares (3), then per row and factor the
    square, the difference and the sum (3); backward, per row and factor
    p * XV (1), per non-zero and factor one add into the feature's sum
    (1), per touched row and factor the gradient's second term and the
    mask (3) and AdaGrad's update (8); FTRL takes about 16 a touched row;
    the linear term one add a non-zero each way (2)."""
    row_bytes = 2 * V_dim * itemsize + ROW_SCALARS * SCALAR_BYTES
    byts = (2 * u * row_bytes + nnz * INDEX_BYTES + rows * LABEL_BYTES
            + (nnz * 4 if valued else 0))
    flops = (4 * nnz * V_dim + 4 * rows * V_dim + 11 * u * V_dim
             + 16 * u + 2 * nnz)
    return {"bytes": float(byts), "flops": float(flops)}


def load_peaks(device_kind: str, path: str = None) -> dict:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, not a default."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {path}: add the "
            "chip with its source before measuring on it")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict, chips: int = 1) -> dict:
    """The least time ``chips`` chips could take for ``work``, and which
    peak bounds it."""
    t_mem = work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    t_flop = work["flops"] / (peaks["flops_per_s"] * chips)
    return {"seconds": max(t_mem, t_flop),
            "bound": "hbm" if t_mem >= t_flop else "flops",
            "hbm_seconds": t_mem, "flop_seconds": t_flop}
