"""Measure the inference pipeline's overlap decomposition on THIS backend.

How well the decoupled predictor loop overlaps host and device work is a
measurement, not a prediction. This script produces it on whatever backend
is active:

- ``loader_cps``   — ListDataloader alone (tokenize-on-read, collate, batch)
- ``device_cps``   — jitted forward alone on one pre-staged batch, outputs
  fetched with the same depth-2 lag the real loop uses
- ``e2e_cps``      — the shipped Predictor loop end-to-end
- ``overlap``      — e2e / min(loader, device): 1.0 = perfect overlap

Run (a CPU run shows the control flow and the loader's rate, never a device
rate):

    JAX_PLATFORMS=cpu python scripts/perf_infer_decomposition.py

Prints ONE JSON line. Flags mirror bench.py --mode infer where they overlap.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="bert-tiny")
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--global_batch", type=int, default=32)
    p.add_argument("--doc_stride", type=int, default=32)
    p.add_argument("--infer_docs", type=int, default=48)
    p.add_argument("--infer_doc_len", type=int, default=600)
    p.add_argument("--infer_jobs", type=int, default=4)
    p.add_argument("--passes", type=int, default=3,
                   help="timed passes per leg; median reported")
    args = p.parse_args()

    import jax

    import jax.numpy as jnp

    from ml_recipe_tpu.compose import init_collate_fun
    from ml_recipe_tpu.data import RawPreprocessor
    from ml_recipe_tpu.data.datasets import ChunkDataset
    from ml_recipe_tpu.data.loader import ListDataloader
    from ml_recipe_tpu.infer import Predictor
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import build_mesh, make_global_array
    from ml_recipe_tpu.tokenizer import Tokenizer
    from ml_recipe_tpu.utils.pipeline import LaggedConsumer

    mesh = build_mesh()
    L = args.seq_len

    tmp = Path(tempfile.mkdtemp(prefix="infer_decomp_"))
    try:
        words = [f"word{i:03d}" for i in range(256)]
        (tmp / "vocab.txt").write_text(
            "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
                       "<p>", "</p>", ".", "?", ","] + words) + "\n"
        )
        rng = np.random.default_rng(0)
        with open(tmp / "corpus.jsonl", "w") as fh:
            for i in range(args.infer_docs):
                doc = "<P> " + " ".join(
                    rng.choice(words, size=args.infer_doc_len)
                ) + " . </P>"
                line = {
                    "example_id": str(i),
                    "document_text": doc,
                    "question_text": " ".join(rng.choice(words, size=8)) + " ?",
                    "annotations": [{
                        "yes_no_answer": "NONE",
                        "long_answer": {"start_token": 0, "end_token": 12,
                                        "candidate_index": 0},
                        "short_answers": [{"start_token": 2, "end_token": 4}],
                    }],
                    "long_answer_candidates": [
                        {"start_token": 0, "end_token": 12, "top_level": True}
                    ],
                }
                fh.write(json.dumps(line) + "\n")

        tokenizer = Tokenizer("bert", str(tmp / "vocab.txt"), lowercase=True)
        preprocessor = RawPreprocessor(
            raw_json=tmp / "corpus.jsonl", out_dir=tmp / "proc"
        )
        _, _, (train_indexes, _, val_indexes, _) = preprocessor()
        indexes = np.concatenate([train_indexes, val_indexes])

        def make_dataset():
            return ChunkDataset(
                tmp / "proc", tokenizer, indexes,
                max_seq_len=L, max_question_len=16,
                doc_stride=args.doc_stride, split_by_sentence=False,
                cache_size=0,
            )

        cfg = MODEL_PRESETS[args.model]
        model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="auto")
        params = model.init(
            jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
        )["params"]
        collate = init_collate_fun(tokenizer, max_seq_len=L, return_items=True)

        predictor = Predictor(
            model, params, mesh=mesh, collate_fun=collate,
            batch_size=args.global_batch, n_jobs=args.infer_jobs,
        )

        # ---- leg 1: loader alone --------------------------------------
        def run_loader():
            n_chunks = 0
            dl = ListDataloader(
                make_dataset(), batch_size=args.global_batch,
                n_jobs=args.infer_jobs, collate_fun=collate,
                buffer_size=4096, shuffle=True,
            )
            t0 = time.perf_counter()
            for _, _, items in dl:
                n_chunks += len(items)
            return n_chunks / (time.perf_counter() - t0), n_chunks

        loader_rates = []
        for _ in range(args.passes):
            r, total_chunks = run_loader()
            loader_rates.append(r)
        loader_cps = float(np.median(loader_rates))

        # ---- leg 2: device forward alone ------------------------------
        # one pre-staged batch, every output fetched through the same
        # depth-2 lag as the real loop (fetch N-2 with N-1, N in flight)
        fwd = predictor._build_fwd()
        jit_fwd = jax.jit(fwd)
        n_batches = max(1, total_chunks // args.global_batch)
        if predictor._wire_ids_only:
            host = rng.integers(
                10, 10 + len(words), (args.global_batch, L)
            ).astype(np.uint16)
            staged = make_global_array(host, mesh)
        else:
            host = np.stack([
                rng.integers(10, 10 + len(words),
                             (args.global_batch, L)).astype(np.int32),
                np.ones((args.global_batch, L), np.int32),
                np.zeros((args.global_batch, L), np.int32),
            ])
            staged = make_global_array(host, mesh, batch_axis=1)
        with mesh:
            np.asarray(jit_fwd(params, staged))  # compile + settle

            def run_device():
                fetched = []
                lag = LaggedConsumer(
                    lambda out: fetched.append(np.asarray(out)), depth=2
                )
                t0 = time.perf_counter()
                for _ in range(n_batches):
                    lag.feed(jit_fwd(params, staged))
                lag.flush()
                return (n_batches * args.global_batch) / (
                    time.perf_counter() - t0
                )

            device_cps = float(np.median(
                [run_device() for _ in range(args.passes)]
            ))

        # ---- leg 3: the shipped loop ----------------------------------
        predictor(make_dataset())  # compile warm-up through the real path

        def run_e2e():
            predictor.scores.clear()
            predictor.candidates.clear()
            predictor.items.clear()
            t0 = time.perf_counter()
            predictor(make_dataset(), save_dump=True)
            elapsed = time.perf_counter() - t0
            chunks = sum(len(d[-1]) for d in predictor.dump)
            return chunks / elapsed

        e2e_cps = float(np.median([run_e2e() for _ in range(args.passes)]))

        cap = min(loader_cps, device_cps)
        # on a host whose cores are shared between the loader pool and XLA
        # (this box has ONE core), the overlap bound is the serial resource
        # model, not min(): both legs consume the same CPU
        serial_bound = 1.0 / (1.0 / loader_cps + 1.0 / device_cps)
        print(json.dumps({
            "metric": "infer_overlap_decomposition",
            "backend": jax.default_backend(),
            "loader_cps": round(loader_cps, 1),
            "device_cps": round(device_cps, 1),
            "e2e_cps": round(e2e_cps, 1),
            "cap_cps": round(cap, 1),
            "overlap": round(e2e_cps / cap, 3),
            "serial_bound_cps": round(serial_bound, 1),
            "vs_serial_bound": round(e2e_cps / serial_bound, 3),
            "batch_size": args.global_batch,
            "docs": int(len(indexes)),
            "chunks_per_pass": int(total_chunks),
        }))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
