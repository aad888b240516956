"""Assembling a run's last line: the per-layer readers, found by name, and
the contract's keys."""

from __future__ import annotations

import importlib
import json
import sys


def note(**fields) -> None:
    """An earlier line of stdout: everything that is not the contract's
    (pre-flight choice, geometry, sample counts, p99, series)."""
    print(json.dumps(fields, default=str), flush=True)


def read_per_layer(cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell through ``metrics/<name>.py``'s
    ``read(ctx)``. A reader that finds nothing returns ``None`` and the
    metric is left out; so is one whose end-to-end metric the cell lacks."""
    reported = {m["name"] for m in cell.end_to_end}
    out = {}
    for metric in cell.per_layer:
        if metric["moves"] not in reported:
            continue
        reader = importlib.import_module(f"perfbench.metrics.{metric['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def end_to_end(cell, values: dict) -> dict:
    out = {}
    for metric in cell.end_to_end:
        if values.get(metric["name"]) is not None:
            out[metric["name"]] = {"value": float(values[metric["name"]]),
                                   "unit": metric["unit"]}
    return out


def last_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
              device: dict, breakdown=None) -> None:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
