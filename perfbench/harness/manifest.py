"""``BENCHMARK.json`` and the data files it names. No jax here."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent      # <checkout>/perfbench
ROOT = BENCH_DIR.parent                                 # the checkout
CACHE_DIR = BENCH_DIR / ".cache"                        # in .gitignore


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict        # perfbench/configs/<config>.json
    traffic: dict       # perfbench/traffic/<traffic>.json
    end_to_end: tuple   # manifest entries of the metrics this cell reports
    per_layer: tuple

    @property
    def runner(self) -> str:
        return self.traffic["runner"]

    def job(self, rehearse: bool) -> dict:
        """The traffic file's ``job`` (departures from the shipped config file
        and the runner's own parameters), with its ``rehearsal`` part laid
        over it for the tests' CPU run."""
        job = dict(self.traffic["job"])
        if rehearse:
            job.update(self.traffic.get("rehearsal", {}))
        return job


def load_manifest(root: Path = ROOT, manifest=None) -> dict:
    """``<root>/BENCHMARK.json``, or the file ``manifest`` names (tests: a
    manifest with more cells over the same files)."""
    with open(manifest if manifest else root / "BENCHMARK.json") as fh:
        return json.load(fh)


def _read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _in_cell(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT, manifest=None) -> Cell:
    manifest = load_manifest(root, manifest)
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json (have: "
            f"{', '.join(sorted(entries))})")
    entry = entries[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    bench = root / manifest["paths"][0]
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_read_json(root / configs[entry["config"]]["file"]),
        traffic=_read_json(bench / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=tuple(
            m for m in manifest["end_to_end"] if _in_cell(m, workload)),
        per_layer=tuple(
            m for m in manifest["per_layer"] if _in_cell(m, workload)),
    )


def write_cfg(shipped: Path, values: dict, path: Path) -> Path:
    """The program's shipped config file with ``values`` laid over it
    (``None`` drops a key), as ``chip_smoke.py:write_train_config`` does."""
    merged = {}
    for line in shipped.read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#") and "=" in line:
            key, value = line.split("=", 1)
            merged[key.strip()] = value.strip()
    merged.update(values)
    path.write_text("".join(
        f"{k}={v}\n" for k, v in merged.items() if v is not None))
    return path
