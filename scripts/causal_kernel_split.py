"""Device time of the two-width causal kernels, one row a kernel.

``perfbench``'s ``mla_attn_ms_step`` is one sum over every ``%flash_causal_*``
custom call. This reads the traced stretch a ``--trace 1`` run of
``joyai-ep16-train-seq4096`` left under ``perfbench/.cache/trace/<cell>/`` and
splits it by the kernel's name (``flash_causal_fwd``, ``flash_causal_bwd``,
``flash_causal_bwd_dq``, ``flash_causal_bwd_dkv``): ms a step (one executed
``jit_train_step`` module) and chip, and calls a step.

    python scripts/causal_kernel_split.py [--cell NAME] [--out FILE]

Reads a file; touches no device.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KERNEL = re.compile(r"^%(flash_causal_\w+?)(?:\.\d+)?$")


def split(path: str, program: str = "jit_train_step") -> dict:
    from perfbench.harness.joyai_trace import load_named

    ops, modules = load_named(path)
    rows: dict = {}
    steps = 0
    for chip, events in ops.items():
        spans = [(s, e) for name, s, e in modules.get(chip, [])
                 if name.startswith(program)]
        steps += len(spans)
        for name, s, e in events:
            m = KERNEL.match(name)
            if m and any(lo <= s and e <= hi for lo, hi in spans):
                row = rows.setdefault(m.group(1), {"ns": 0, "calls": 0})
                row["ns"] += e - s
                row["calls"] += 1
    if not steps:
        return {"steps": 0}
    out = {"steps": steps, "trace": os.path.relpath(path, ROOT)}
    for name, row in sorted(rows.items()):
        out[name] = {"ms_step": row["ns"] * 1e-6 / steps,
                     "calls_step": row["calls"] / steps}
    out["sum_ms_step"] = sum(r["ns"] for r in rows.values()) * 1e-6 / steps
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="joyai-ep16-train-seq4096")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    found = sorted(glob.glob(os.path.join(
        ROOT, "perfbench", ".cache", "trace", args.cell, "plugins", "profile",
        "*", "*.xplane.pb")))
    if not found:
        sys.exit(f"no trace of {args.cell} under perfbench/.cache/trace")
    line = json.dumps(split(found[-1]))
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
