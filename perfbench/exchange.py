"""The necessary work of the row exchange of a feature-sharded step, and
the interconnect's peak.

Beside ``work.py`` and counted the same way: from the algorithm and the
batch alone. The table's rows are sharded by key range over ``fs`` chips
and the batch is replicated, so every chip computes the whole step and
needs every row the batch touches; it owns a share ``1/fs`` of them (the
hash spreads rows evenly over the key ranges). So each chip must take in
the ``u * (1 - 1/fs)`` rows it does not own, ``2 * V_dim`` items of the
storage type and four float32 scalars a row, once a step. Nothing on the
write side: each chip writes the rows it owns from the update it
computed itself. Never from what the program moves (today GSPMD
all-reduces the padded, replicated ``row cap x lanes`` operand: the
program's ``store_exchange_bytes_total`` counts that), so a leaner
exchange reads as the same work done faster.
"""

from __future__ import annotations

import json
import os

from perfbench import work

HERE = os.path.dirname(os.path.abspath(__file__))


def step_exchange(u: float, V_dim: int, itemsize: int, fs: int) -> dict:
    """{"bytes_per_chip"}: what one chip cannot do without taking in."""
    row_bytes = (2 * V_dim * itemsize
                 + work.ROW_SCALARS * work.SCALAR_BYTES)
    return {"bytes_per_chip": float(u * (1.0 - 1.0 / fs) * row_bytes)}


def load_ici_peak(device_kind: str, path: str = None) -> float:
    """Bytes a second one chip of ``device_kind`` can take in over its
    interconnect; a device that is not in the table is an error."""
    path = path or os.path.join(HERE, "peaks_ici.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no interconnect peak for device_kind "
                       f"{device_kind!r} in {path}")
    return float(table[device_kind]["ici_bytes_per_s"])


def uniq_of(least: dict, peaks: dict, chips: int, rows: float, nnz: float,
            V_dim: int, itemsize: int) -> float:
    """The batch's distinct features a step, from the step's least time.

    ``run.py`` hands a reader ``work.least_seconds`` of the step and not
    the count it was made from; ``work.step_work`` is linear in ``u``,
    so the count comes back exactly."""
    byts = least["hbm_seconds"] * peaks["hbm_bytes_per_s"] * chips
    batch = work.step_work(0.0, rows, nnz, V_dim, itemsize)["bytes"]
    per_u = work.step_work(1.0, 0.0, 0.0, V_dim, itemsize)["bytes"]
    return (byts - batch) / per_u


# ------------------------------------------------------------- readers
def _cell(metric: str) -> tuple:
    """(configuration, traffic) of the one cell that lists ``metric``:
    a reader is handed neither the cell's name nor its files."""
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = next(m for m in bench["per_layer"]
                 if m["name"] == metric)["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic",
                           cell["traffic"] + ".json")) as f:
        return config, json.load(f)


def collective_pct(ctx):
    """Time under a collective operation over the busy time, both of the
    fullest chip."""
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s_fullest") \
            or tr.get("collective_s_fullest") is None:
        return None
    return 100.0 * tr["collective_s_fullest"] / tr["busy_s_fullest"]


def least_exchange_seconds(least: dict, peaks: dict, ici: float,
                           chips: int, rows: float, width: int,
                           config: dict) -> float:
    """The least time one chip's interconnect could take for the rows
    that chip must take in, in a step of ``rows`` rows of ``width``
    features whose least time ``work.least_seconds`` gave as ``least``."""
    V_dim = int(config["V_dim"])
    itemsize = work.item_size(config)
    u = uniq_of(least, peaks, chips, rows, rows * width, V_dim, itemsize)
    need = step_exchange(u, V_dim, itemsize, int(config["mesh_fs"]))
    return need["bytes_per_chip"] / ici


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def exchange_roofline(ctx, metric: str = "exchange_roofline.replay"):
    """That least time over the collective time a step took on the
    fullest chip."""
    tr, least = ctx.get("trace"), ctx.get("least")
    if not tr or not least or not ctx.get("steps") \
            or not tr.get("collective_s_fullest"):
        return None
    from perfbench import gen
    config, traffic = _cell(metric)
    kind = device_kind()
    t_least = least_exchange_seconds(
        least, work.load_peaks(kind), load_ici_peak(kind), ctx["chips"],
        ctx["res"]["window_rows"] / ctx["steps"],
        gen.Spec(**traffic["generator"]).width, config)
    return 100.0 * t_least / (tr["collective_s_fullest"] / ctx["steps"])
