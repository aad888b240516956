"""One run of a training cell of the benchmark with the training loss of
every step kept: ``perfbench/run.py``'s arguments plus ``--losses FILE``.

The benchmark's result line holds no loss of a training step (its comparison
runs outside the window), so a change that touches only a backward kernel is
invisible to ``correct``. This wraps the runner's own per-step tap (which
already reads the epoch meter's running mean loss to see that it is finite)
and writes what it read, one value a step, beside the result line: two trees
on one seed then give two trajectories to compare. After a ``--trace 1`` run
the file also holds ``metrics.trace.causal_backward_calls`` of every program
the run registered (the traced run has asked for their scope maps by then)
and, for an expert-routed trunk, what the run's ``TrainTelemetry`` registry
holds of the routing counters (``train_moe_*``: steps observed, mean, least
and largest), and the trainer's INFO lines go to stderr. No file of
``perfbench/`` changes; the run is the cell's own.

    python3 scripts/cell_loss_trajectory.py --workload joyai-ep16-train-seq4096 \\
        --seed N --seconds 30 --trace 0 --losses chiprun_out/losses.json
"""

from __future__ import annotations

import json
import logging
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    argv = sys.argv[1:]
    at = argv.index("--losses")
    out = argv[at + 1]
    del argv[at:at + 2]

    from ml_recipe_tpu.train.telemetry import TrainTelemetry
    from perfbench import run
    from perfbench.runners import train

    losses, telemetries = [], []
    new_telemetry = TrainTelemetry.__init__

    def kept(self, *args, **kwargs):
        new_telemetry(self, *args, **kwargs)
        telemetries.append(self)

    TrainTelemetry.__init__ = kept
    build = train.build_trainer

    def build_with_tap(cell, job, work, seed, n_epochs, finite_tap):
        def tap(meters, *, step):
            losses.append(float(meters["loss"]()))
            return finite_tap(meters, step=step)
        return build(cell, job, work, seed, n_epochs, tap)

    train.build_trainer = build_with_tap
    package_log = logging.getLogger("ml_recipe_tpu")
    package_log.addHandler(logging.StreamHandler())
    package_log.setLevel(logging.INFO)
    try:
        return run.main(argv)
    finally:
        report = {"running_mean_loss_by_step": losses}
        if "--trace" in argv and argv[argv.index("--trace") + 1] == "1":
            from ml_recipe_tpu.metrics import trace
            if hasattr(trace, "causal_backward_calls"):   # not on the parent
                report["causal_backward_calls"] = {
                    name: trace.causal_backward_calls(name)
                    for name in trace.registered_programs()}
        if telemetries:
            report["telemetry_moe"] = {
                series.name: {"steps": series.count, "mean": series.mean,
                              "min": series.quantile(0.0),
                              "max": series.quantile(1.0)}
                for series in telemetries[-1].m_moe.values() if series.count}
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f)


if __name__ == "__main__":
    sys.exit(main())
