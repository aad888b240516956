"""Measure whether the LayerNorm/GELU-bwd elementwise segment is HBM-bound
at its floor (VERDICT r3 #2a).

Profiles a few steady-state training steps of the bench configuration with
``jax.profiler.trace``, parses the xplane op_profile, and reports for every
non-matmul, non-custom-call fusion: self time, bytes accessed, and achieved
HBM bandwidth vs the chip's peak. If the elementwise fusions run at or near
peak bandwidth, the segment is at
its floor and no kernel can shrink it without removing bytes; if they run
well below peak, the gap is collectable and this report says where.

Run on the real chip:

    python scripts/perf_elementwise_floor.py [--steps 3] [--peak_gbps 819]

Prints ONE JSON line with the per-category totals and the top fusions.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _collect_op_profile(trace_dir: str):
    """Parse the xplane dump into op rows via xprof (the tensorboard_plugin
    copy is protobuf-incompatible with this image — use xprof.convert)."""
    from xprof.convert import raw_to_tool_data

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert paths, f"no xplane.pb under {trace_dir}"
    data, _ = raw_to_tool_data.xspace_to_tool_data(paths, "op_profile", {})
    return json.loads(data) if isinstance(data, (str, bytes)) else data


_CAPTURE_META = "capture_meta.json"


def main() -> int:
    p = argparse.ArgumentParser()
    # default resolved below: 3 when capturing, the trace dir's recorded
    # step count when replaying (ADVICE r5 #4: a replay divided by a
    # DIFFERENT default step count silently reports wrong per-step numbers)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--global_batch", type=int, default=256)
    p.add_argument("--batch_split", type=int, default=4)
    p.add_argument("--model", default="bert-base-uncased")
    # v5e HBM peak ~819 GB/s (16 GB HBM2); override per chip generation
    p.add_argument("--peak_gbps", type=float, default=819.0)
    p.add_argument("--ln_impl", default="xla", choices=["xla", "fused"])
    # re-parse a saved trace (no chip needed) instead of capturing a new one
    p.add_argument("--trace_dir", default=None)
    args = p.parse_args()

    if args.trace_dir:
        # replay: the step count MUST match the capture's, or every
        # per-step number divides by the wrong N. Prefer the count the
        # capture persisted; an old trace dir without one requires an
        # explicit --steps.
        meta_path = os.path.join(args.trace_dir, _CAPTURE_META)
        if os.path.exists(meta_path):
            with open(meta_path) as fh:
                recorded = int(json.load(fh)["steps"])
            if args.steps is not None and args.steps != recorded:
                p.error(
                    f"--steps {args.steps} contradicts the capture's "
                    f"recorded step count {recorded} ({meta_path})"
                )
            args.steps = recorded
        elif args.steps is None:
            p.error(
                "--trace_dir replay needs --steps: this trace dir has no "
                f"{_CAPTURE_META} (captured before step counts were "
                "persisted), and the default would silently divide by the "
                "wrong step count"
            )
        return _report(args, args.trace_dir)
    if args.steps is None:
        args.steps = 3

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "needs a real TPU backend",
                          "backend": jax.default_backend()}))
        return 1

    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.train import Trainer
    from ml_recipe_tpu.train.optim import build_optimizer

    mesh = build_mesh()
    cfg = MODEL_PRESETS[args.model]
    model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="auto",
                    ln_impl=args.ln_impl)

    class TP:
        loss = "smooth"; smooth_alpha = 0.01; focal_alpha = 1; focal_gamma = 2
        w_start = 1; w_end = 1; w_start_reg = 1; w_end_reg = 1; w_cls = 1
        lr = 1e-5; weight_decay = 1e-4; warmup_coef = 0.0
        optimizer = "adam"; finetune = False

    rng = np.random.default_rng(0)
    B, L, G = args.global_batch, args.seq_len, args.batch_split
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
    )["params"]
    trainer = Trainer(model=model, params=params, loss=build_loss(TP()),
                      collate_fun=None, trainer_params=None, mesh=mesh,
                      batch_split=G, seed=0)
    trainer.optimizer, trainer.scheduler, trainer._schedule_count = (
        build_optimizer(TP(), trainer.params, num_training_steps=10_000,
                        max_grad_norm=None, warmup_coef=0.0))
    trainer.init_opt_state()
    step_fn = trainer._build_train_step()

    host_inputs = {
        "input_ids": rng.integers(
            1, cfg.vocab_size, (G, B // G, L)).astype(np.int32),
        "attention_mask": np.ones((G, B // G, L), dtype=np.int32),
        "token_type_ids": np.zeros((G, B // G, L), dtype=np.int32),
    }
    host_labels = {
        "start_class": rng.integers(0, L, (G, B // G)).astype(np.int32),
        "end_class": rng.integers(0, L, (G, B // G)).astype(np.int32),
        "start_reg": rng.random((G, B // G)).astype(np.float32),
        "end_reg": rng.random((G, B // G)).astype(np.float32),
        "cls": rng.integers(0, 5, (G, B // G)).astype(np.int32),
    }

    trace_dir = tempfile.mkdtemp(prefix="elementwise_floor_")
    # persist the capture's step count so a later --trace_dir replay can
    # recover the right per-step divisor without trusting a CLI default
    with open(os.path.join(trace_dir, _CAPTURE_META), "w") as fh:
        json.dump({"steps": args.steps}, fh)
    with mesh:
        inputs = trainer._global_batch(host_inputs, leading_accum=True)
        labels = trainer._global_batch(host_labels, leading_accum=True)
        params_d, opt_d = trainer.params, trainer.opt_state
        warmup = max(1, args.warmup)  # >=1: compile must precede the trace
        for i in range(warmup):
            params_d, opt_d, values = step_fn(params_d, opt_d, inputs,
                                              labels, i)
        jax.block_until_ready(values)
        with jax.profiler.trace(trace_dir):
            for i in range(args.steps):
                params_d, opt_d, values = step_fn(
                    params_d, opt_d, inputs, labels, warmup + i)
            jax.block_until_ready(values)

    return _report(args, trace_dir)


def _report(args, trace_dir: str) -> int:
    prof = _collect_op_profile(trace_dir)
    # xprof op_profile shape (verified on a real round-5 chip trace): no
    # byCategory on this version — programs live under byProgramExcludeIdle,
    # each program's CHILDREN are the XLA op categories ('convolution
    # fusion', 'custom-call', 'loop fusion', ...), and each category's
    # children are the individual fusions carrying rawTime (ps, summed over
    # traced steps) + rawBytesAccessedArray ([hbm, ...] bytes). Deeper
    # leaves are per-HLO rows with zero time — time is attributed at the
    # fusion level, so walk exactly program -> category -> fusion.
    root = prof.get("byProgramExcludeIdle") or prof.get("byProgram") or prof
    programs = root.get("children") or []

    def classify(category: str) -> str:
        lc = (category or "").lower()
        if "custom" in lc:  # 'custom-call' + 'custom fusion' = Pallas/attn
            return "attention_kernels"
        if "convolution" in lc:
            return "matmul"
        if "loop fusion" in lc or "elementwise" in lc:
            return "elementwise_fusion"
        return "other"

    cats: dict = {}
    fusion_rows = []
    for program in programs:
        for cat_node in program.get("children") or []:
            cat = classify(cat_node.get("name", ""))
            c = cats.setdefault(cat, {"time_ms": 0.0, "bytes": 0.0})
            for fusion in cat_node.get("children") or []:
                m = fusion.get("metrics") or {}
                t_ps = float(m.get("rawTime", 0.0))
                ba = m.get("rawBytesAccessedArray") or [0.0]
                bytes_acc = float(ba[0])  # index 0 = HBM space
                c["time_ms"] += t_ps / 1e9
                c["bytes"] += bytes_acc
                if cat == "elementwise_fusion" and t_ps > 0:
                    fusion_rows.append({
                        "name": fusion.get("name", "?")[:80],
                        "time_ms": round(t_ps / 1e9, 3),
                        "gbytes": round(bytes_acc / 1e9, 3),
                        "achieved_gbps": round(
                            bytes_acc / (t_ps / 1e12) / 1e9, 1),
                    })

    fusion_rows.sort(key=lambda r: -r["time_ms"])
    ew = cats.get("elementwise_fusion", {"time_ms": 0.0, "bytes": 0.0})
    achieved = (ew["bytes"] / (ew["time_ms"] / 1e3) / 1e9
                if ew["time_ms"] else None)
    print(json.dumps({
        "metric": "elementwise_bwd_floor",
        "ln_impl": args.ln_impl,
        "steps_traced": args.steps,
        "per_category_ms_per_step": {
            k: round(v["time_ms"] / args.steps, 2) for k, v in cats.items()
        },
        "elementwise_achieved_gbps": round(achieved, 1) if achieved else None,
        "peak_gbps": args.peak_gbps,
        "elementwise_bw_utilization": round(achieved / args.peak_gbps, 3)
        if achieved else None,
        "top_fusions": fusion_rows[:12],
        "trace_dir": trace_dir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
