"""The device a run is measured on: what JAX reports, the peaks table, and
the refusal to measure without the chips a cell asks for. Copies of
``bench.py:_device_record`` / ``_chip_peak_tflops`` (listed in PERF.md)."""

from __future__ import annotations

import json

from .manifest import BENCH_DIR


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for: exit non-zero
    and print no result."""


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(chips: int, *, rehearse: bool) -> dict:
    """The device record, or exit: nothing falls back to the CPU. A
    rehearsal (tests only) runs wherever it is started."""
    record = device_record()
    if rehearse:
        return record
    if record["platform"] != "tpu":
        raise NoChip(f"perfbench: no TPU here (jax sees {record}); "
                     f"nothing is measured on a {record['platform']}")
    if record["count"] < chips:
        raise NoChip(f"perfbench: the cell needs {chips} chip(s), jax sees "
                     f"{record['count']}")
    return record


def peaks(kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``. An unknown kind is an
    error, never a default."""
    with open(BENCH_DIR / "peaks.json") as fh:
        table = json.load(fh)
    if kind not in table:
        raise RuntimeError(
            f"no peaks on record for device_kind {kind!r}: add a row with "
            f"its source to perfbench/peaks.json")
    return table[kind]


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes held on the fullest of the first ``n_devices`` chips:
    ``peak_bytes_in_use`` (live arrays: parameters, optimizer state, batches)
    plus ``peak_bytes_reserved`` (what the runtime set aside for the largest
    program's temporaries, which this runtime keeps out of ``bytes_in_use``:
    1.59 GB + 12.41 GB against a pre-flight projection of 14.22 GB in
    ``base-train-full512``, PR 22)."""
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats() -> dict:
    """The first chip's allocator statistics as the runtime reports them."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def bytes_limit() -> int:
    return int(memory_stats().get("bytes_limit", 0))
