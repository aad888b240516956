"""``python3 perfbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

The last line of stdout is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``);
everything else goes on earlier lines. Without the chips the cell asks for it
exits non-zero and prints no result: nothing falls back to the CPU.
``--rehearse`` is the tests' CPU run at the tiny size the traffic file names;
it prints no number under a metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tests only: tiny size, any backend, no metric")
    parser.add_argument("--manifest", default=None,
                        help="tests only: another manifest over the same files")
    args = parser.parse_args(argv)

    from perfbench.harness.manifest import load_cell, load_manifest

    cell = load_cell(args.workload, manifest=args.manifest)
    seconds = (args.seconds if args.seconds is not None
               else float(load_manifest(manifest=args.manifest)["run_seconds"]))
    runner = importlib.import_module(f"perfbench.runners.{cell.runner}")
    return runner.run(cell, seed=args.seed, seconds=seconds,
                      trace=bool(args.trace), rehearse=args.rehearse,
                      t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
