"""Benchmark: bert-base QA fine-tune throughput (examples/sec/chip).

Measures the REAL training step the framework ships — the Trainer's jitted
SPMD step (forward + 5-head WeightedLoss + grad + clip + AdamW + schedule) at
the reference smoke-config shape (bert-base, seq 512, global batch 256,
config/test_bert.cfg parity) on whatever chips are visible.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "examples/sec/chip", "vs_baseline": N}

``vs_baseline`` is relative to a nominal single-V100 bert-base fine-tune
throughput (~100 ex/s at seq 384-512, fp16 — the reference publishes no
numbers; the driver's north star is >=3x single-V100).

``--mode infer`` benchmarks the OTHER hot loop (reference
predictor.py:106-131 + list_dataloader.py): chunks/sec through the real
inference path — ChunkDataset expansion in ListDataloader worker threads
(tokenization included), fixed-shape batching, the jitted forward with the
in-jit 1901.08634 answerability score, and the one-step-lag host gather.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

V100_EXAMPLES_PER_SEC_EST = 100.0  # nominal single-V100 bert-base QA fine-tune
# nominal single-V100 bert-base fp16 INFERENCE, ~3x its fine-tune rate (no
# backward, no optimizer) — same provenance caveat as the train estimate
V100_INFER_CHUNKS_PER_SEC_EST = 300.0

# Documented bf16 peaks per chip generation, for the MFU field. Matched
# against jax.devices()[0].device_kind substrings; a TPU kind that is not
# listed is an error, never a ratio against the wrong peak. Only the v5e row
# has been run by this repo; the table itself is ROADMAP S0's to rebuild.
TPU_BF16_PEAK_TFLOPS = (
    ("v5 lite", 197.0),  # v5e datasheet ("TPU v5 lite" device_kind)
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v6", 918.0),       # v6e/Trillium
    ("v4", 275.0),
)

# what a device metric reads when the run had no chip to measure it on
NOT_MEASURED = "not measured"


def _str2bool(value: str) -> bool:
    """Boolean-flag domain of ml_recipe_tpu.config.parser._str2bool, kept
    inline because importing the parser pulls jax in at argparse time and
    bench defers every heavy import until the arguments are parsed."""
    return str(value).strip().lower() in ("1", "true", "yes", "on")


def _cast_bytes(value) -> int:
    """Byte-budget domain of ml_recipe_tpu.config.parser.cast_bytes ('64M',
    '1g', plain ints), inline for the same deferred-import reason."""
    text = str(value).strip().lower()
    for suffix, mult in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30)):
        if text.endswith(suffix):
            return int(float(text[:-1]) * mult)
    return int(text)


def _device_record() -> dict:
    """The device every result line names (platform / kind / count)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _chip_peak_tflops(device: dict):
    """bf16 peak of the attached TPU generation. ``None`` without a chip
    (no utilization is computed from a CPU run); an unlisted TPU kind is an
    error."""
    if device["platform"] != "tpu":
        return None
    kind = device["kind"].lower()
    for sub, peak in TPU_BF16_PEAK_TFLOPS:
        if sub in kind:
            return peak
    raise RuntimeError(
        f"no bf16 peak on record for device_kind {device['kind']!r}: add it "
        f"to TPU_BF16_PEAK_TFLOPS with its source before reporting MFU"
    )


def _matmul_gflops_per_example(cfg, L: int, *, train: bool) -> float:
    """Model matmul FLOPs per example (multiply-add = 2 FLOPs), the
    numerator of the MFU field. Counts the encoder's dense matmuls (QKV/O
    projections, FFN) and the attention score/context dots; embeddings,
    pooler and the QA heads are <1% and omitted — stated so the number is
    auditable. Backward of a matmul costs 2x its forward (dX and dW dots):
    train = 3x forward."""
    C = cfg.hidden_size
    F = cfg.intermediate_size
    per_token = cfg.num_layers * (
        2 * 4 * C * C        # q/k/v/o projections
        + 2 * 2 * C * F      # FFN in/out
        + 4 * L * C          # QK^T + PV, summed over heads
    )
    fwd = per_token * L / 1e9
    return fwd * 3 if train else fwd


def _widen_positions(cfg, seq_len: int):
    """Widen the position table to the benched sequence length when it
    exceeds the preset's (Embeddings raises on out-of-table positions
    rather than clamping; long-context rows bench the widened-table model —
    the same model a real long-context run needs)."""
    if seq_len + cfg.position_offset > cfg.max_position_embeddings:
        import dataclasses

        return dataclasses.replace(
            cfg, max_position_embeddings=seq_len + cfg.position_offset
        )
    return cfg


def _mfu(gflops_per_example: float, examples_per_sec_per_chip: float,
         peak_tflops):
    """Model FLOPs utilization vs the documented peak of the ATTACHED chip
    generation (``_chip_peak_tflops``). Without a chip there is no device
    metric: the field reads ``NOT_MEASURED``, never a number."""
    if peak_tflops is None:
        return NOT_MEASURED
    achieved_tflops = gflops_per_example * examples_per_sec_per_chip / 1e3
    return round(achieved_tflops / peak_tflops, 4)


def _write_synthetic_nq_corpus(tmp, n_docs, doc_len_fn, rng) -> None:
    """``vocab.txt`` + ``corpus.jsonl`` in the NQ-jsonl schema (mirrors
    tests/helpers.py::nq_line — kept inline so the driver can run bench.py
    without the tests tree; update both if the preprocessor's expected
    schema ever changes). ``doc_len_fn(i)`` gives document i's token count —
    the one knob the infer and input modes differ on."""
    words = [f"word{i:03d}" for i in range(256)]
    (tmp / "vocab.txt").write_text(
        "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
                   "<p>", "</p>", ".", "?", ","] + words) + "\n"
    )
    with open(tmp / "corpus.jsonl", "w") as fh:
        for i in range(n_docs):
            doc = "<P> " + " ".join(
                rng.choice(words, size=doc_len_fn(i))
            ) + " . </P>"
            line = {
                "example_id": str(i),
                "document_text": doc,
                "question_text": " ".join(rng.choice(words, size=8)) + " ?",
                "annotations": [{
                    "yes_no_answer": "NONE",
                    "long_answer": {
                        "start_token": 0,
                        "end_token": 12,
                        "candidate_index": 0,
                    },
                    "short_answers": [{"start_token": 2, "end_token": 4}],
                }],
                "long_answer_candidates": [
                    {"start_token": 0, "end_token": 12, "top_level": True}
                ],
            }
            fh.write(json.dumps(line) + "\n")


# Deterministic per-index document-length cycle for --mode input: mostly
# short documents (one sub-max chunk) with a long tail — the shape of the
# NQ sliding-window chunk distribution the length bucketing targets. Kept a
# fixed cycle (not rng draws) so the reported padding-waste numbers are
# reproducible run to run.
INPUT_DOC_LEN_CYCLE = (40, 60, 80, 110, 150, 200, 260, 340, 450, 600, 900, 1800)


def bench_input(args) -> None:
    """Host-pipeline-only throughput: the TRAIN input path (dataset read ->
    chunking -> tokenization -> collate -> batching) with NO device work, so
    pipeline regressions are visible without a TPU and the padding
    accounting that motivates length bucketing is a number. Runs the
    pad-to-max loader and (unless --length_buckets off) the bucketed loader
    over the same synthetic NQ corpus and reports both sides'
    ``padding_waste_pct`` + nonpad-token throughput."""
    import shutil
    import tempfile
    from pathlib import Path

    from ml_recipe_tpu.compose import init_collate_fun
    from ml_recipe_tpu.data import RawPreprocessor
    from ml_recipe_tpu.data.bucketing import (
        BucketedDataLoader,
        parse_length_buckets,
    )
    from ml_recipe_tpu.data.datasets import SplitDataset
    from ml_recipe_tpu.data.loader import DataLoader, ShardedBatchSampler
    from ml_recipe_tpu.data.packing import (
        PackedDataLoader,
        parse_pack_splitting,
        parse_sequence_packing,
    )
    from ml_recipe_tpu.tokenizer import Tokenizer

    L = args.seq_len
    B = args.global_batch
    tmp = Path(tempfile.mkdtemp(prefix="bench_input_"))
    try:
        _write_synthetic_nq_corpus(
            tmp, args.input_docs,
            lambda i: min(
                INPUT_DOC_LEN_CYCLE[i % len(INPUT_DOC_LEN_CYCLE)],
                args.input_doc_len,
            ),
            np.random.default_rng(0),
        )
        tokenizer = Tokenizer("bert", str(tmp / "vocab.txt"), lowercase=True)
        preprocessor = RawPreprocessor(
            raw_json=tmp / "corpus.jsonl", out_dir=tmp / "proc"
        )
        _, _, (train_indexes, _, val_indexes, _) = preprocessor()
        indexes = np.concatenate([train_indexes, val_indexes])

        def make_dataset():
            return SplitDataset(
                tmp / "proc", tokenizer, indexes,
                max_seq_len=L, max_question_len=16,
                doc_stride=args.doc_stride, split_by_sentence=False,
                cache_size=0,  # every timed pass pays the real tokenize cost
                rng=np.random.default_rng(0),
            )

        def make_sampler():
            return ShardedBatchSampler(
                len(indexes), B, shuffle=True, drop_last=True, seed=0
            )

        collate = init_collate_fun(tokenizer, max_seq_len=L)

        # pass 1: pad-to-max loader (today's default path)
        loader = DataLoader(
            make_dataset(), make_sampler(), collate, n_jobs=args.infer_jobs
        )
        loader.set_epoch(1)
        real_tokens = padded_tokens = batches = rows = 0
        t0 = time.perf_counter()
        for inputs, _labels in loader:
            mask = np.asarray(inputs["attention_mask"])
            real_tokens += int(mask.sum())
            padded_tokens += int(mask.size)
            rows += int(mask.shape[0])
            batches += 1
        padmax_s = time.perf_counter() - t0
        padmax_waste = (
            100.0 * (1.0 - real_tokens / padded_tokens) if padded_tokens else 0.0
        )

        # pass 2: length-bucketed token-budget loader
        grid = parse_length_buckets(args.length_buckets, L)
        bucket_fields = {}
        if grid is not None:
            bloader = BucketedDataLoader(
                make_dataset(), make_sampler(), collate,
                seq_grid=grid, token_budget=B * grid[-1],
                n_jobs=args.infer_jobs,
            )
            bloader.set_epoch(1)
            t0 = time.perf_counter()
            for _batch in bloader:
                pass
            bucketed_s = time.perf_counter() - t0
            stats = bloader.epoch_stats
            waste = stats.get("padding_waste_pct")
            bucket_fields = {
                "padding_waste_pct": waste,
                # None ONLY when unmeasurable or the division is undefined:
                # a legitimate 0.0% bucketed waste (all lengths on bucket
                # edges) must not read as "missing"
                "waste_reduction_x": (
                    round(padmax_waste / waste, 2)
                    if waste is not None and waste > 0 else None
                ),
                "batches_bucketed": stats["batches"],
                "nonpad_tokens_per_sec": round(
                    stats["real_tokens"] / bucketed_s, 1
                ),
                "length_buckets": grid,
                "bucket_batches": {
                    str(k): v for k, v in bloader.batch_sizes.items()
                },
            }

        # pass 3: sequence-packed loader (packing supersedes bucketing —
        # the residual 12% bucketed waste is what it removes)
        packed_fields = {}
        if parse_sequence_packing(getattr(args, "sequence_packing", "on")):
            ploader = PackedDataLoader(
                make_dataset(), make_sampler(), tokenizer,
                max_seq_len=L, rows_per_batch=B,
                max_segments=getattr(args, "pack_max_segments", 8),
                n_jobs=args.infer_jobs,
            )
            ploader.set_epoch(1)
            t0 = time.perf_counter()
            for _batch in ploader:
                pass
            packed_s = time.perf_counter() - t0
            pstats = ploader.epoch_stats
            pwaste = pstats.get("padding_waste_pct")
            # reduction vs the BUCKETED waste when that pass ran (the
            # ISSUE-5 headline: the residual bucketed waste), else vs
            # pad-to-max; None only when the division is undefined
            ref_waste = bucket_fields.get("padding_waste_pct")
            if ref_waste is None:
                ref_waste = padmax_waste
            packed_fields = {
                "padding_waste_pct_packed": pwaste,
                "packing_efficiency": pstats.get("packing_efficiency"),
                "rows_per_sec_packed": round(pstats["rows"] / packed_s, 1),
                "nonpad_tokens_per_sec_packed": round(
                    pstats["real_tokens"] / packed_s, 1
                ),
                "batches_packed": pstats["batches"],
                "waste_reduction_x_packed": (
                    round(ref_waste / pwaste, 2)
                    if pwaste is not None and pwaste > 0 else None
                ),
                "pack_max_segments": getattr(args, "pack_max_segments", 8),
            }

        # pass 4: splitting packer (--pack_splitting fill) — the same
        # packed loader with hole-filling chunk fragments, reported as
        # before/after so the splitter's win over the non-splitting floor
        # is a number on every input-mode line
        split_fields = {}
        splitting = parse_pack_splitting(
            getattr(args, "pack_splitting", "fill")
        )
        if packed_fields and splitting != "off":
            min_fragment = int(getattr(args, "pack_min_fragment", 32))
            sloader = PackedDataLoader(
                make_dataset(), make_sampler(), tokenizer,
                max_seq_len=L, rows_per_batch=B,
                max_segments=getattr(args, "pack_max_segments", 8),
                splitting=splitting, min_fragment=min_fragment,
                n_jobs=args.infer_jobs,
            )
            sloader.set_epoch(1)
            t0 = time.perf_counter()
            for _batch in sloader:
                pass
            split_s = time.perf_counter() - t0
            sstats = sloader.epoch_stats
            swaste = sstats.get("padding_waste_pct")
            pwaste_before = packed_fields.get("padding_waste_pct_packed")
            split_fields = {
                "pack_splitting": splitting,
                "pack_min_fragment": min_fragment,
                "padding_waste_pct_split": swaste,
                "packing_efficiency_split": sstats.get("packing_efficiency"),
                "waste_before_split_pct": pwaste_before,
                "waste_after_split_pct": swaste,
                "split_count": sstats.get("split_count"),
                "fragment_rows": sstats.get("fragment_rows"),
                "fragment_size_hist": sstats.get("fragment_size_hist"),
                "batches_split": sstats["batches"],
                "rows_per_sec_split": round(sstats["rows"] / split_s, 1),
                "nonpad_tokens_per_sec_split": round(
                    sstats["real_tokens"] / split_s, 1
                ),
                "waste_reduction_x_split": (
                    round(pwaste_before / swaste, 2)
                    if pwaste_before is not None and swaste else None
                ),
            }

        headline = bucket_fields.get(
            "nonpad_tokens_per_sec", round(real_tokens / padmax_s, 1)
        )
        print(
            json.dumps(
                {
                    "metric": "input_pipeline_nonpad_tokens_per_sec",
                    "value": headline,
                    "unit": "nonpad_tokens/sec",
                    "vs_baseline": round(
                        headline / (real_tokens / padmax_s), 3
                    ) if real_tokens else None,
                    "padding_waste_pct_padmax": round(padmax_waste, 2),
                    "nonpad_tokens_per_sec_padmax": round(
                        real_tokens / padmax_s, 1
                    ),
                    "batches_padmax": batches,
                    "rows": rows,
                    "docs": int(len(indexes)),
                    "global_batch": B,
                    "seq_len": L,
                    **bucket_fields,
                    **packed_fields,
                    **split_fields,
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _quantize_for_bench(args, model, params, make_batches):
    """Shared int8 leg of bench_infer/bench_serve: convert the float pair,
    measure span parity vs the float path on ``make_batches()`` (built
    lazily — only the int8 path pays for it), and return the pair the
    benchmark should run plus the JSON ``quant_fields`` both modes emit
    (identical schema either way, so the two lines never diverge)."""
    quantize = getattr(args, "quantize", "off")
    quant_fields = {"quantize": quantize, "quant_mem_bytes": None,
                    "parity_span_agreement": None,
                    "parity_score_max_delta": None}
    if quantize == "int8":
        from ml_recipe_tpu.quant import quantize_model, span_parity

        qmodel, qparams, qreport = quantize_model(model, params)
        parity = span_parity(model, params, qmodel, qparams, make_batches())
        quant_fields.update(
            quant_mem_bytes=qreport["quant_bytes"],
            parity_span_agreement=parity["span_agreement"],
            parity_score_max_delta=parity["score_max_abs_delta"],
        )
        model, params = qmodel, qparams
    return model, params, quant_fields


def bench_infer(args) -> None:
    import shutil
    import tempfile
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.compose import init_collate_fun
    from ml_recipe_tpu.data import RawPreprocessor
    from ml_recipe_tpu.data.datasets import ChunkDataset
    from ml_recipe_tpu.infer import Predictor
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.tokenizer import Tokenizer

    n_chips = len(jax.devices())
    mesh = build_mesh()
    L = args.seq_len

    # synthetic NQ-schema corpus: long documents -> several chunks each
    tmp = Path(tempfile.mkdtemp(prefix="bench_infer_"))
    try:
        _write_synthetic_nq_corpus(
            tmp, args.infer_docs, lambda i: args.infer_doc_len,
            np.random.default_rng(0),
        )
        tokenizer = Tokenizer("bert", str(tmp / "vocab.txt"), lowercase=True)
        preprocessor = RawPreprocessor(
            raw_json=tmp / "corpus.jsonl", out_dir=tmp / "proc"
        )
        _, _, (train_indexes, _, val_indexes, _) = preprocessor()
        indexes = np.concatenate([train_indexes, val_indexes])

        def make_dataset(idx):
            return ChunkDataset(
                tmp / "proc", tokenizer, idx,
                max_seq_len=L, max_question_len=16, doc_stride=args.doc_stride,
                split_by_sentence=False,
                cache_size=0,  # no cross-pass token cache: every timed pass
                               # pays the real tokenize-on-read cost
            )

        cfg = _widen_positions(MODEL_PRESETS[args.model], L)
        model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="auto",
                        ln_impl=args.ln_impl)
        params = model.init(
            jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
        )["params"]
        collate = init_collate_fun(tokenizer, max_seq_len=L, return_items=True)

        # int8 path: convert, measure span parity vs the float path on a
        # sample of real collated chunks, then bench the QUANTIZED predictor
        def make_batches():
            sample_ds = make_dataset(indexes[:8])
            # dataset[i] is one DOCUMENT's chunk list — flatten to chunks
            sample = [
                chunk
                for i in range(min(len(sample_ds), 8))
                for chunk in sample_ds[i]
            ][:32]
            return [
                collate(sample[at: at + 8])[0]
                for at in range(0, len(sample), 8)
            ]

        model, params, quant_fields = _quantize_for_bench(
            args, model, params, make_batches)

        predictor = Predictor(
            model, params, mesh=mesh, collate_fun=collate,
            batch_size=args.global_batch, n_jobs=args.infer_jobs,
            fetch_every=args.fetch_every,
        )

        # compile warmup on a 2-doc slice (same static shapes)
        predictor(make_dataset(indexes[:2]))

        window_rates = []
        window_elapsed = []
        for _ in range(max(1, args.window)):
            predictor.scores.clear()
            predictor.candidates.clear()
            predictor.items.clear()
            t0 = time.perf_counter()
            predictor(make_dataset(indexes), save_dump=True)
            elapsed = time.perf_counter() - t0
            chunks = sum(len(d[-1]) for d in predictor.dump)
            window_rates.append(chunks / elapsed)
            window_elapsed.append(elapsed)

        # observability twins (train-mode JSON parity): pass-time
        # percentiles + the slow-step detector over the pass series
        from ml_recipe_tpu.metrics.anomaly import SlowStepDetector

        detector = SlowStepDetector(
            factor=3.0, window=max(2, len(window_elapsed)), warmup=0,
            min_steps=2)
        for i, s in enumerate(window_elapsed):
            detector.update(i, s, {"pass": s})
        # every document's chunks flowed through the loop (candidate VALIDITY
        # is score-dependent and not guaranteed under random-init params)
        seen_docs = {it.item_id for d in predictor.dump for it in d[-1]}
        assert len(seen_docs) == len(indexes), (len(seen_docs), len(indexes))

        per_chip = float(np.median(window_rates)) / n_chips
        infer_gflops = _matmul_gflops_per_example(cfg, L, train=False)
        device = _device_record()
        peak = _chip_peak_tflops(device)
        # padding accounting over the last pass's chunks (eval-side twin of
        # the train JSON fields): chunks pad to the static L, so the nonpad
        # token rate is what a bucketed eval path would actually deliver
        real_tokens = sum(
            len(it.input_ids) for d in predictor.dump for it in d[-1]
        )
        waste_pct = (
            100.0 * (1.0 - real_tokens / (chunks * L)) if chunks else 0.0
        )
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_qa_infer_seq{L}_chunks_per_sec_per_chip",
                    "value": round(per_chip, 2),
                    "unit": "chunks/sec/chip",
                    "vs_baseline": round(
                        per_chip / V100_INFER_CHUNKS_PER_SEC_EST, 3
                    ),
                    "model_gflops_per_example": round(infer_gflops, 2),
                    "device": device,
                    "mfu": _mfu(infer_gflops, per_chip, peak),
                    "peak_tflops_bf16": peak or NOT_MEASURED,
                    "padding_waste_pct": round(waste_pct, 2),
                    "packing_efficiency": round(
                        real_tokens / (chunks * L), 4
                    ) if chunks else None,
                    "rows_per_sec": round(float(np.median(window_rates)), 1),
                    "nonpad_tokens_per_sec_per_chip": round(
                        per_chip * (real_tokens / chunks), 1
                    ) if chunks else None,
                    "ln_impl": args.ln_impl,
                    **quant_fields,
                    "chunks": chunks,
                    "docs": int(len(indexes)),
                    "chunks_per_sec_windows": [round(r, 1) for r in window_rates],
                    "pass_time_s_p50": round(
                        float(np.percentile(window_elapsed, 50)), 3),
                    "pass_time_s_p95": round(
                        float(np.percentile(window_elapsed, 95)), 3),
                    "slow_pass_anomalies": detector.anomalies,
                    "batch_size": args.global_batch,
                    "fetch_every": args.fetch_every,
                    "n_chips": n_chips,
                    "backend": jax.default_backend(),
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serve(args) -> None:
    """Closed-loop latency benchmark of the online serving subsystem
    (``ml_recipe_tpu/serve/``): N client threads drive the QAEngine with
    synthetic question/document requests (``data/synthetic.py`` generator),
    each issuing its next request when the previous one answers. Emits
    p50/p95/p99 latency, throughput, and batch-occupancy in the JSON line —
    the serving counterparts of the train/infer headline numbers."""
    import dataclasses
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.data.synthetic import (
        make_learnable_line,
        write_learnable_vocab,
    )
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.ops import aot
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.serve.bucketing import BucketGrid
    from ml_recipe_tpu.serve.engine import QAEngine
    from ml_recipe_tpu.tokenizer import Tokenizer

    n_chips = len(jax.devices())
    mesh = build_mesh()
    grid = BucketGrid.from_spec(args.serve_buckets)

    # --aot_cold_warm_probe: point the program store at a FRESH directory
    # so the first engine's warmup is deterministically cold (compile +
    # persist) and the replacement engine built after the timed loop is
    # the measured warm restart (deserialize only)
    aot_probe_dir = None
    if getattr(args, "aot_cold_warm_probe", False):
        aot_probe_dir = tempfile.mkdtemp(prefix="bench_aot_probe_")
        aot.reset()
        aot.configure(enabled=True, cache_dir=aot_probe_dir)

    tmp = Path(tempfile.mkdtemp(prefix="bench_serve_"))
    try:
        tokenizer = Tokenizer(
            "bert", str(write_learnable_vocab(tmp)), lowercase=True
        )
        cfg = MODEL_PRESETS[args.model]
        # the synthetic corpus has a tiny closed vocab; positions must cover
        # the largest bucket
        cfg = dataclasses.replace(cfg, vocab_size=max(len(tokenizer), 128))
        cfg = _widen_positions(cfg, grid.max_seq)
        model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="auto",
                        ln_impl=args.ln_impl)
        params = model.init(
            jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
        )["params"]

        rng = np.random.default_rng(0)
        uniques = [
            make_learnable_line(i, rng) for i in range(args.serve_requests)
        ]
        # hot-set workload (ISSUE 7): with --serve_hot_fraction h, each
        # request slot draws a repeated (question, document) pair from a
        # small hot set with probability h (zipf-ish rank weights — rank r
        # drawn ∝ 1/r, the shape real document popularity takes), the rest
        # are unique. Repeats are tagged so the JSON can split hit-served
        # vs miss-served latency.
        hot_fraction = float(getattr(args, "serve_hot_fraction", 0.0) or 0.0)
        hot_docs = max(1, int(getattr(args, "serve_hot_docs", 4)))
        requests: list = []  # (line, is_hot)
        hot: list = []
        if hot_fraction > 0.0:
            hot = uniques[:hot_docs]
            zipf = 1.0 / np.arange(1, len(hot) + 1)
            zipf /= zipf.sum()
            cold = iter(uniques[hot_docs:])
            for _ in range(args.serve_requests):
                if rng.random() < hot_fraction:
                    line = hot[int(rng.choice(len(hot), p=zipf))]
                else:
                    line = next(cold, hot[0])
                requests.append((line, any(line is h for h in hot)))
        else:
            requests = [(line, False) for line in uniques]

        # int8 path: convert, measure span parity vs the float path on the
        # first requests' real chunks, then serve the QUANTIZED pair
        def make_batches():
            from ml_recipe_tpu.quant import make_parity_batches

            return make_parity_batches(
                tokenizer, uniques[:8], max_seq_len=grid.max_seq,
                max_question_len=16, doc_stride=args.doc_stride,
            )

        model, params, quant_fields = _quantize_for_bench(
            args, model, params, make_batches)
        quantize = quant_fields["quantize"]

        long_doc_tokens = int(
            getattr(args, "serve_long_doc_tokens", 0) or 0)
        engine = QAEngine(
            model, params, tokenizer, grid=grid, mesh=mesh,
            max_batch_delay_ms=args.max_batch_delay_ms,
            queue_size=args.serve_queue_size,
            max_question_len=16, doc_stride=args.doc_stride,
            quantize=quantize,
            serve_cache_bytes=int(getattr(args, "serve_cache_bytes", 0) or 0),
            doc_cache_bytes=int(getattr(args, "doc_cache_bytes", 0) or 0),
            # the long leg needs the scatter path on: any multi-chunk
            # request co-schedules; short-doc closed-loop traffic (single
            # chunk at these grids) is unaffected
            long_scatter_chunks=2 if long_doc_tokens else 0,
        )
        warm = engine.warmup(hbm_preflight=args.hbm_preflight)

        # priming pass (excluded from the timed loop): issue each hot line
        # once serially so every hot pick in the schedule is a true repeat —
        # the hit/miss latency split then measures steady-state cache
        # behavior, not first-touch fills racing their own repeats
        for line in hot:
            engine.submit(
                line["question_text"], line["document_text"]
            ).result(timeout=120)

        lock = threading.Lock()
        next_i = [0]
        latencies: list = []   # (seconds, is_hot)
        rejected = [0]
        failed = [0]

        def client() -> None:
            while True:
                with lock:
                    if next_i[0] >= len(requests):
                        return
                    line, is_hot = requests[next_i[0]]
                    next_i[0] += 1
                t_req = time.perf_counter()
                try:
                    ticket = engine.submit(
                        line["question_text"], line["document_text"]
                    )
                    ticket.result(timeout=120)
                except Exception as e:  # noqa: BLE001 - count, keep looping
                    with lock:
                        if "queue full" in str(e).lower():
                            rejected[0] += 1
                        else:
                            failed[0] += 1
                    continue
                dt = time.perf_counter() - t_req
                with lock:
                    latencies.append((dt, is_hot))

        threads = [
            threading.Thread(target=client, name=f"serve-client-{i}")
            for i in range(args.serve_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0

        # long-request leg (ISSUE 20): one synthetic document of
        # --serve_long_doc_tokens tokens through the long buckets; its
        # sliding-window chunks scatter chunk-parallel across dedicated
        # batches (engine long_scatter_chunks) instead of trickling
        # through deadline coalescing. Repeated --serve_long_requests
        # times for a latency sample; runs after the timed closed loop so
        # it never perturbs the headline numbers.
        longdoc = {
            "longdoc_tokens": long_doc_tokens or None,
            "longdoc_chunks": None,
            "longdoc_scatter_batches": None,
            "longdoc_p50_ms": None,
            "longdoc_p95_ms": None,
        }
        if long_doc_tokens:
            base = uniques[0]["document_text"]
            n_rep = max(1, -(-long_doc_tokens //
                             max(1, len(tokenizer.encode(base)))))
            long_document = " ".join([base] * n_rep)
            long_question = uniques[0]["question_text"]
            long_ms = []
            n_chunks = scatter_batches = 0
            for _ in range(max(1, int(
                    getattr(args, "serve_long_requests", 1) or 1))):
                t_req = time.perf_counter()
                ticket = engine.submit(long_question, long_document)
                ticket.result(timeout=600)
                long_ms.append((time.perf_counter() - t_req) * 1e3)
                n_chunks = ticket.n_chunks
                scatter_batches = ticket.scatter_batches
            longdoc.update(
                longdoc_chunks=n_chunks,
                longdoc_scatter_batches=scatter_batches,
                longdoc_p50_ms=round(
                    float(np.percentile(long_ms, 50)), 2),
                longdoc_p95_ms=round(
                    float(np.percentile(long_ms, 95)), 2),
            )

        engine.close()

        # rolling-restart leg of --aot_cold_warm_probe: a replacement
        # engine over the same model/grid warms up from the store the
        # first engine populated — its warmup should compile ZERO bucket
        # programs (misses == 0) and take a small fraction of the cold one
        aot_probe = None
        if getattr(args, "aot_cold_warm_probe", False):
            engine2 = QAEngine(
                model, params, tokenizer, grid=BucketGrid.from_spec(
                    args.serve_buckets),
                mesh=mesh,
                max_batch_delay_ms=args.max_batch_delay_ms,
                queue_size=args.serve_queue_size,
                max_question_len=16, doc_stride=args.doc_stride,
                quantize=quantize,
            )
            warm2 = engine2.warmup(hbm_preflight=args.hbm_preflight)
            engine2.close()
            cold_s = warm["warmup_seconds"]
            warm_s = warm2["warmup_seconds"]
            aot_probe = {
                "cold_compile_s": cold_s,
                "warm_load_s": warm_s,
                "speedup_x": (
                    round(cold_s / warm_s, 1) if warm_s else None),
                "hits": int(engine2.m_aot_hits.value),
                "misses": int(engine2.m_aot_misses.value),
            }
            shutil.rmtree(aot_probe_dir, ignore_errors=True)

        lat_ms = np.sort(np.asarray([d for d, _ in latencies])) * 1e3
        hot_ms = np.sort(np.asarray(
            [d for d, is_hot in latencies if is_hot])) * 1e3
        cold_ms = np.sort(np.asarray(
            [d for d, is_hot in latencies if not is_hot])) * 1e3
        pct = lambda q, a=None: (  # noqa: E731 - one-shot percentile accessor
            round(float(np.percentile(lat_ms if a is None else a, q)), 2)
            if (lat_ms if a is None else a).size else None
        )
        occ = engine.m_occupancy.mean
        waste = engine.m_padding_waste.mean
        cache = engine.cache_stats()

        def hit_rate(stats):
            if stats is None:
                return None
            n = stats["hits"] + stats["misses"]
            return round(stats["hits"] / n, 4) if n else None
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_qa_serve_p95_ms",
                    "value": pct(95),
                    "unit": "ms",
                    "p50_ms": pct(50),
                    "p95_ms": pct(95),
                    "p99_ms": pct(99),
                    "throughput_rps": round(len(latencies) / elapsed, 2)
                    if elapsed > 0 else None,
                    "requests": len(latencies),
                    "rejected_queue_full": rejected[0],
                    "failed": failed[0],
                    "clients": args.serve_clients,
                    "batches": int(engine.m_batches.value),
                    "batch_occupancy_mean": round(occ, 4) if occ else None,
                    "padding_waste_mean": round(waste, 4) if waste else None,
                    "buckets": [str(b) for b in grid],
                    # hot-set workload + serving-cache provenance (ISSUE 7):
                    # the hit/miss latency split is the cache's measured win
                    "hot_fraction": hot_fraction,
                    "hot_requests": int(hot_ms.size),
                    "p50_hit_ms": pct(50, hot_ms),
                    "p50_miss_ms": pct(50, cold_ms),
                    "p95_hit_ms": pct(95, hot_ms),
                    "p95_miss_ms": pct(95, cold_ms),
                    "chunk_cache_hit_rate": hit_rate(cache["chunk"]),
                    "doc_cache_hit_rate": hit_rate(cache["doc"]),
                    "chunk_cache": cache["chunk"],
                    "doc_cache": cache["doc"],
                    # long-request leg provenance (ISSUE 20): how the 16k+
                    # document scattered, and what it cost end to end
                    **longdoc,
                    **quant_fields,
                    "max_batch_delay_ms": args.max_batch_delay_ms,
                    "warmup_seconds": warm["warmup_seconds"],
                    "autotune_probes": warm["autotune"]["probes"],
                    # AOT program-store provenance of the benched engine's
                    # warmup + the optional rolling-restart measurement
                    "aot_cache": warm["aot"]["cache"],
                    "aot_hits": warm["aot"]["hits"],
                    "aot_misses": warm["aot"]["misses"],
                    "cold_vs_warm_compile_s": aot_probe,
                    "n_chips": n_chips,
                    "backend": jax.default_backend(),
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_fleet(args) -> None:
    """Closed-loop zipf benchmark of the serving FLEET (router tier +
    N engines, ``ml_recipe_tpu/fleet/``): the same workload is driven
    through the consistent-hash router and through a random-routing
    baseline (fresh engines each pass), and the JSON line reports the
    doc-cache hit-rate delta — the affinity win, measured — alongside
    p50/p95/p99 through the router and per-engine occupancy."""
    import dataclasses
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.data.synthetic import (
        make_learnable_line,
        write_learnable_vocab,
    )
    from ml_recipe_tpu.fleet import EngineEndpoint, FleetRouter
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.serve.bucketing import BucketGrid
    from ml_recipe_tpu.serve.engine import QAEngine
    from ml_recipe_tpu.serve.server import QAServer
    from ml_recipe_tpu.tokenizer import Tokenizer

    n_chips = len(jax.devices())
    mesh = build_mesh()
    n_engines = max(1, int(args.fleet_engines))
    # the affinity win is the TIER-1 doc cache's to show: fleet mode
    # defaults it on (1M per engine) when the shared flag is unset
    doc_cache_bytes = int(getattr(args, "doc_cache_bytes", 0) or 0) or (1 << 20)
    serve_cache_bytes = int(getattr(args, "serve_cache_bytes", 0) or 0)

    tmp = Path(tempfile.mkdtemp(prefix="bench_fleet_"))
    try:
        grid = BucketGrid.from_spec(args.serve_buckets)
        tokenizer = Tokenizer(
            "bert", str(write_learnable_vocab(tmp)), lowercase=True
        )
        cfg = MODEL_PRESETS[args.model]
        cfg = dataclasses.replace(cfg, vocab_size=max(len(tokenizer), 128))
        cfg = _widen_positions(cfg, grid.max_seq)
        model = QAModel(cfg, dtype=jnp.bfloat16, attention_impl="auto",
                        ln_impl=args.ln_impl)
        params = model.init(
            jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
        )["params"]

        # zipf document popularity (rank r drawn ∝ 1/r) over a fixed doc
        # set: the shape real repeat traffic takes, and exactly what the
        # ring's per-document affinity is built to exploit. One seeded
        # schedule, replayed by BOTH routing passes.
        rng = np.random.default_rng(0)
        docs = [make_learnable_line(i, rng) for i in range(args.fleet_docs)]
        zipf = 1.0 / np.arange(1, len(docs) + 1)
        zipf /= zipf.sum()
        schedule = [
            int(rng.choice(len(docs), p=zipf))
            for _ in range(args.serve_requests)
        ]

        def run_pass(routing: str) -> dict:
            """One tier (fresh engines + router) driving the schedule."""
            engines = []
            servers = []
            for _ in range(n_engines):
                engine = QAEngine(
                    model, params, tokenizer, grid=BucketGrid.from_spec(
                        args.serve_buckets),
                    mesh=mesh,
                    max_batch_delay_ms=args.max_batch_delay_ms,
                    queue_size=args.serve_queue_size,
                    max_question_len=16, doc_stride=args.doc_stride,
                    serve_cache_bytes=serve_cache_bytes,
                    doc_cache_bytes=doc_cache_bytes,
                )
                engine.warmup(hbm_preflight=args.hbm_preflight)
                server = QAServer(
                    engine, host="127.0.0.1", port=0,
                    request_timeout_s=120.0, drain_timeout_s=30.0,
                )
                server.start()
                engines.append(engine)
                servers.append(server)
            router = FleetRouter(
                [
                    EngineEndpoint(f"engine{i}", s.host, s.port)
                    for i, s in enumerate(servers)
                ],
                routing=routing, rng_seed=0, health_poll_s=0.5,
                request_timeout_s=120.0,
            ).start()

            lock = threading.Lock()
            next_i = [0]
            latencies: list = []
            failed = [0]
            url = f"http://{router.host}:{router.port}/v1/qa"

            def client() -> None:
                while True:
                    with lock:
                        if next_i[0] >= len(schedule):
                            return
                        line = docs[schedule[next_i[0]]]
                        next_i[0] += 1
                    body = json.dumps({
                        "question": line["question_text"],
                        "document": line["document_text"],
                    }).encode("utf-8")
                    req = urllib.request.Request(
                        url, data=body,
                        headers={"Content-Type": "application/json"})
                    t_req = time.perf_counter()
                    try:
                        with urllib.request.urlopen(req, timeout=120) as resp:
                            resp.read()
                            ok = resp.status == 200
                    except (urllib.error.URLError, OSError):
                        ok = False
                    dt = time.perf_counter() - t_req
                    with lock:
                        if ok:
                            latencies.append(dt)
                        else:
                            failed[0] += 1

            threads = [
                threading.Thread(target=client, name=f"fleet-client-{i}")
                for i in range(args.serve_clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0

            doc_hits = doc_misses = 0
            occupancy = []
            for engine in engines:
                stats = engine.cache_stats()["doc"]
                doc_hits += stats["hits"]
                doc_misses += stats["misses"]
                occupancy.append(
                    round(engine.m_occupancy.mean, 4)
                    if engine.m_occupancy.mean else None)
            per_engine = router.m_engine_requests.values()
            spilled = int(router.m_spilled.value)
            shed = int(router.m_shed.value)
            router.close()
            for server in servers:
                server.shutdown()
            lookups = doc_hits + doc_misses
            lat_ms = np.sort(np.asarray(latencies)) * 1e3
            pct = lambda q: (  # noqa: E731 - one-shot percentile accessor
                round(float(np.percentile(lat_ms, q)), 2)
                if lat_ms.size else None
            )
            return {
                "routing": routing,
                "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
                "throughput_rps": round(len(latencies) / elapsed, 2)
                if elapsed > 0 else None,
                "requests": len(latencies),
                "failed": failed[0],
                "doc_cache_hit_rate": round(doc_hits / lookups, 4)
                if lookups else None,
                "per_engine_requests": per_engine,
                "per_engine_occupancy": occupancy,
                "spilled": spilled,
                "shed": shed,
            }

        hash_pass = run_pass("hash")
        random_pass = run_pass("random")
        delta = None
        if hash_pass["doc_cache_hit_rate"] is not None \
                and random_pass["doc_cache_hit_rate"] is not None:
            delta = round(
                hash_pass["doc_cache_hit_rate"]
                - random_pass["doc_cache_hit_rate"], 4)
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_qa_fleet_p95_ms",
                    "value": hash_pass["p95_ms"],
                    "unit": "ms",
                    "engines": n_engines,
                    "clients": args.serve_clients,
                    "docs": args.fleet_docs,
                    "requests": args.serve_requests,
                    "buckets": [str(b) for b in grid],
                    "doc_cache_bytes": doc_cache_bytes,
                    # the affinity win: consistent-hash routing re-lands
                    # every repeat on the engine whose tier-1 cache holds
                    # the document; random routing pays a first-touch miss
                    # per engine per document
                    "doc_cache_hit_rate_delta": delta,
                    "hash": hash_pass,
                    "random": random_pass,
                    "n_chips": n_chips,
                    "backend": jax.default_backend(),
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_converge(args) -> None:
    """Train on-chip on the synthetic LEARNABLE corpus and emit the loss
    curve + final eval metrics (VERDICT r2 #1b: proof the framework learns,
    runnable by the driver on real hardware).

    The corpus (ml_recipe_tpu/data/synthetic.py) makes class and answer span
    derivable from the question/marker; a working optimizer drives mAP and
    cls-accuracy far above the 5-class chance floor (0.2) within a few
    hundred steps — a broken one cannot.
    """
    import math
    import shutil
    import tempfile
    from pathlib import Path

    import jax

    from ml_recipe_tpu.data import RawPreprocessor
    from ml_recipe_tpu.data.synthetic import make_convergence_trainer
    from ml_recipe_tpu.models import MODEL_PRESETS
    from ml_recipe_tpu.parallel import build_mesh
    from ml_recipe_tpu.train import AccuracyCallback, MAPCallback

    mesh = build_mesh()
    L = args.converge_seq
    B = args.converge_batch

    tmp = Path(tempfile.mkdtemp(prefix="bench_converge_"))
    try:
        # ~90% of the examples form the stratified train split
        steps_per_epoch = max(int(args.converge_examples * 0.9) // B, 1)
        n_epochs = max(1, math.ceil(args.converge_steps / steps_per_epoch))

        trainer = make_convergence_trainer(
            tmp,
            model_cfg=MODEL_PRESETS[args.model],
            mesh=mesh,
            lr=args.converge_lr,
            n_epochs=n_epochs,
            batch=B,
            seq_len=L,
            n_examples=args.converge_examples,
            test_size=0.1,
            n_jobs=args.infer_jobs,
            warmup_coef=args.converge_warmup,
        )

        # per-step running-average train loss, keyed by global step; the
        # last record of each epoch is that epoch's mean loss
        records: dict = {}

        def record(meters, *, step):
            if "loss" in meters:
                records[int(step)] = float(meters["loss"]())

        trainer.on_train_metrics = record

        callbacks = [
            MAPCallback(list(RawPreprocessor.labels2id.keys())),
            AccuracyCallback(),
        ]
        m0 = trainer.test(0, callbacks=callbacks)
        t0 = time.perf_counter()
        trainer.train()
        train_s = time.perf_counter() - t0
        mT = trainer.test(n_epochs + 1, callbacks=callbacks)

        spe = len(trainer.train_dataloader)
        loss_curve = [
            round(records[e * spe - 1], 4)
            for e in range(1, n_epochs + 1)
            if (e * spe - 1) in records
        ]
        # earliest recorded step, whatever its key — records.get(0, ...)
        # would silently fall back to an end-of-epoch mean if the trainer's
        # first recorded step key were ever nonzero (advisor r3)
        first_step_loss = records[min(records)] if records else None

        final_map = float(mT["map"])
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_qa_converge_seq{L}_final_map",
                    "value": round(final_map, 4),
                    "unit": "map",
                    # chance floor for 5 balanced classes is 0.2
                    "vs_baseline": round(final_map / 0.2, 3),
                    "loss_initial": round(first_step_loss, 4),
                    "loss_final": loss_curve[-1] if loss_curve else None,
                    "loss_curve_per_epoch": loss_curve,
                    "map_initial": round(float(m0["map"]), 4),
                    "c_acc": round(float(mT["c_acc"]), 4),
                    "s_acc": round(float(mT["s_acc"]), 4),
                    "e_acc": round(float(mT["e_acc"]), 4),
                    "steps": trainer.global_step,
                    "global_batch": B,
                    "train_seconds": round(train_s, 1),
                    "backend": jax.default_backend(),
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _goodput_json(summary: dict) -> dict:
    """Compact goodput summary for the bench JSON line: ratio + the
    nonzero badput categories, rounded. ``checkpoint_overlapped_s`` is an
    async save's background persist time — concurrent with training, so
    outside the badput partition by construction."""
    ratio = summary.get("goodput_ratio")
    out = {
        "goodput_ratio": round(ratio, 4) if ratio is not None else None,
        "total_wall_s": round(summary.get("total_wall_s", 0.0), 4),
        "productive_s": round(summary.get("productive_s", 0.0), 4),
        "badput_s": {
            k: round(v, 4)
            for k, v in summary.get("badput_s", {}).items()
            if v > 0.0005
        },
    }
    overlapped = summary.get("checkpoint_overlapped_s", 0.0)
    if overlapped > 0.0005:
        out["checkpoint_overlapped_s"] = round(overlapped, 4)
    return out


def _opt_bytes(trainer):
    """Measured per-chip optimizer-state bytes of a live trainer (one
    shard per leaf under zero1), or None before init."""
    from ml_recipe_tpu.parallel.sharding import opt_state_bytes_per_chip

    state, _ = trainer._split_ls()
    return opt_state_bytes_per_chip(state) if state is not None else None


def param_count_probe(args) -> None:
    """``--mode train --param_count_probe``: modeled replicated-vs-zero1
    optimizer bytes per chip WITHOUT running (or even compiling) a step —
    param and state shapes come from ``jax.eval_shape``, the ZeRO-1 layout
    from the same padding-aware per-leaf plan the trainer applies
    (parallel/sharding.zero1_state_bytes), so HBM planning for a pod shape
    works before a TPU window opens. ``--probe_devices N`` models any
    data-axis width; the default is the visible device count."""
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel.sharding import zero1_state_bytes
    from ml_recipe_tpu.train.optim import build_optimizer

    cfg = MODEL_PRESETS[args.model]
    cfg = _widen_positions(cfg, args.seq_len)
    model = QAModel(cfg, dtype=jnp.bfloat16)
    param_shapes = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0),
    )["params"]

    class TP:
        lr = 1e-5; weight_decay = 1e-4; warmup_coef = 0.0
        optimizer = args.optimizer; finetune = False

    tx, _, _ = build_optimizer(
        TP(), param_shapes, num_training_steps=1000, max_grad_norm=None,
        warmup_coef=0.0,
    )
    state_shapes = jax.eval_shape(tx.init, param_shapes)
    n = args.probe_devices or len(jax.devices())
    zero1 = zero1_state_bytes(
        state_shapes, data_size=n, min_size=args.zero_min_size
    )
    param_count = sum(
        int(np.prod(l.shape or (1,), dtype=np.int64))
        for l in jax.tree_util.tree_leaves(param_shapes)
    )
    print(
        json.dumps(
            {
                "mode": "param_count_probe",
                "model": args.model,
                "optimizer": args.optimizer,
                "param_count": param_count,
                "devices": int(n),
                "zero_min_size": int(args.zero_min_size),
                "opt_bytes_per_chip_replicated": zero1["replicated_bytes"],
                "opt_bytes_per_chip_zero1": zero1["zero1_bytes"],
                # the replicated footprint of exactly the leaves zero1
                # shards — the (N-1)/N savings base
                "opt_bytes_sharded_leaves": zero1["sharded_bytes"],
                "zero1_savings_pct": round(
                    100.0
                    * (1.0 - zero1["zero1_bytes"]
                       / max(zero1["replicated_bytes"], 1)),
                    2,
                ),
            }
        )
    )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode",
                        choices=("train", "infer", "converge", "serve",
                                 "fleet", "input"),
                        default="train")
    parser.add_argument("--seq_len", type=int, default=512)
    parser.add_argument("--global_batch", type=int, default=256)
    # micro-batch 64 (split 4) is the measured single-v5e sweet spot with the
    # fused attention kernel: 271 ex/s vs 237 (split 8) / 245 (split 2)
    parser.add_argument("--batch_split", type=int, default=4)
    # steps are timed in windows of --window; the reported number is the
    # MEDIAN window (one stalled window must not become the result)
    parser.add_argument("--steps", type=int, default=16,
                        help="train mode only; infer paces by --infer_docs")
    parser.add_argument("--window", type=int, default=4,
                        help="train: steps per timing window; infer: number "
                             "of timed full passes (median reported)")
    parser.add_argument("--warmup", type=int, default=2,
                        help="train mode only; infer warms up with one "
                             "2-doc compile pass")
    parser.add_argument("--model", type=str, default="bert-base-uncased")
    parser.add_argument("--ln_impl", type=str, default="xla",
                        choices=("xla", "fused", "auto", "interpret"),
                        help="LayerNorm implementation (ops/layer_norm.py). "
                             "Default stays 'xla': the round-5 on-chip A/B "
                             "measured the fused kernel a wash (732.2 vs "
                             "729.2 ms/step — it removes the elementwise "
                             "bytes but XLA already fused that work into "
                             "matmul epilogues; artifacts/r4/elementwise_"
                             "floor{,_lnfused}.json). interpret = CPU smoke "
                             "of the kernel path")
    parser.add_argument("--fetch_every", type=int, default=1,
                        help="infer mode: group output fetches over this many "
                             "batches (1 = per-batch). Default reverted to 1 "
                             "by the round-5 on-chip sweep: 423/408/394 "
                             "chunks/s at 1/4/8 (artifacts/r4/bench_infer_"
                             "fetch*.json) — grouping lost when the loop was "
                             "loader-bound, not fetch-bound")
    parser.add_argument("--remat", action="store_true",
                        help="train mode: rematerialize encoder layers "
                             "(activation-memory headroom for seq >= 8k)")
    # --mode infer knobs (192 docs x ~12 chunks = 9 batches/pass: enough to
    # reach the loader/device pipeline's steady state)
    parser.add_argument("--infer_docs", type=int, default=192)
    parser.add_argument("--infer_doc_len", type=int, default=3000)
    parser.add_argument("--infer_jobs", type=int, default=16)
    parser.add_argument("--doc_stride", type=int, default=256)
    # --mode input knobs: host-pipeline-only throughput + padding accounting
    # (no device work; runs the pad-to-max and bucketed loaders side by side)
    parser.add_argument("--input_docs", type=int, default=2048,
                        help="input mode: corpus size. Size it to several "
                             "bucket-batches per bucket (the bucketed pass "
                             "drops partial tails like drop_last — a corpus "
                             "much smaller than token_budget/avg_len steps "
                             "yields zero full buckets)")
    parser.add_argument("--input_doc_len", type=int, default=1800,
                        help="input mode: cap on the synthetic document "
                             "length cycle (INPUT_DOC_LEN_CYCLE)")
    parser.add_argument("--length_buckets", type=str, default="auto",
                        help="input mode: bucket grid for the bucketed pass "
                             "('off' skips it, 'auto' = evenly spaced grid "
                             "ending at --seq_len, or explicit edges "
                             "'128,256,384,512')")
    parser.add_argument("--sequence_packing", type=str, default="on",
                        help="input mode: run the sequence-packed loader "
                             "pass and report packing_efficiency / "
                             "padding_waste_pct_packed ('off' skips it)")
    parser.add_argument("--pack_max_segments", type=int, default=8,
                        help="input mode: max chunks per packed row")
    parser.add_argument("--pack_splitting", type=str, default="fill",
                        help="input mode: run the splitting-packer pass "
                             "(hole-filling chunk fragments) and report "
                             "splitter stats + waste before/after ('off' "
                             "skips it)")
    parser.add_argument("--pack_min_fragment", type=int, default=32,
                        help="input mode: splitting packer's minimum "
                             "fragment size in tokens")
    # --mode converge knobs (VERDICT r2 #1b). Defaults are the proven
    # from-scratch bert-base recipe (measured on a v5e chip: loss 8.61 ->
    # 0.0006, mAP 0.21 -> 1.00 in 2520 steps / ~9 min): post-LN depth
    # needs the long warmup — 0.05 plateaus at loss ~7.9. bert-tiny
    # converges in ~60 steps with --converge_lr 2e-3 --converge_steps 60.
    parser.add_argument("--converge_steps", type=int, default=2500)
    parser.add_argument("--converge_seq", type=int, default=128)
    parser.add_argument("--converge_batch", type=int, default=64)
    parser.add_argument("--converge_lr", type=float, default=1e-4)
    parser.add_argument("--converge_warmup", type=float, default=0.2)
    parser.add_argument("--converge_examples", type=int, default=2048)
    # --mode serve knobs (closed loop: each client issues its next request
    # when the previous one answers; occupancy comes from concurrency)
    parser.add_argument("--serve_buckets", type=str, default="8x128,32x128",
                        help="serve mode: bucket grid 'BATCHxSEQ,...'")
    parser.add_argument("--serve_clients", type=int, default=8)
    parser.add_argument("--serve_requests", type=int, default=128,
                        help="serve mode: total requests across clients")
    parser.add_argument("--serve_queue_size", type=int, default=256)
    parser.add_argument("--serve_hot_fraction", type=float, default=0.0,
                        help="serve mode: fraction of requests drawn as "
                             "repeats from a small hot set (zipf rank "
                             "weights) — the hot-set workload for the "
                             "serving caches; JSON gains the hit-vs-miss "
                             "latency split + cache hit rates")
    parser.add_argument("--serve_hot_docs", type=int, default=4,
                        help="serve mode: hot-set size (distinct repeated "
                             "question/document pairs)")
    parser.add_argument("--serve_cache_bytes", type=_cast_bytes, default=0,
                        help="serve mode: tier-2 chunk-result cache byte "
                             "budget (plain bytes or K/M/G suffix; 0 = "
                             "off)")
    parser.add_argument("--doc_cache_bytes", type=_cast_bytes, default=0,
                        help="serve mode: tier-1 document-preprocessing "
                             "cache byte budget (plain bytes or K/M/G "
                             "suffix; 0 = off)")
    parser.add_argument("--serve_long_doc_tokens", type=int, default=0,
                        help="serve mode: long-request leg (ISSUE 20) — "
                             "after the closed loop, drive one synthetic "
                             "document of this many tokens through the "
                             "long buckets; its sliding-window chunks "
                             "scatter chunk-parallel across dedicated "
                             "batches and the JSON gains longdoc_chunks/"
                             "longdoc_scatter_batches + longdoc p50/p95. "
                             "0 = leg off")
    parser.add_argument("--serve_long_requests", type=int, default=4,
                        help="serve mode: repeats of the long-request leg "
                             "document (the longdoc p50/p95 sample size)")
    # --mode fleet knobs (router tier over N in-process engines; reuses the
    # serve_* knobs for the engine plane and the closed-loop client count)
    parser.add_argument("--fleet_engines", type=int, default=2,
                        help="fleet mode: engines behind the router")
    parser.add_argument("--fleet_docs", type=int, default=8,
                        help="fleet mode: distinct documents in the zipf "
                             "workload (small set + repeats = the affinity "
                             "signal consistent hashing exploits)")
    parser.add_argument("--max_batch_delay_ms", type=float, default=10.0)
    # geometry autotuner + HBM pre-flight (mirrors config/parser.py)
    parser.add_argument("--autotune", type=_str2bool, default=True,
                        help="Compile-probe kernel geometry autotuner; off "
                             "reverts to analytic VMEM arithmetic.")
    parser.add_argument("--autotune_cache", type=str, default=None,
                        help="Tuning-cache directory (default "
                             "artifacts/tuning/ or $MLRT_AUTOTUNE_CACHE).")
    parser.add_argument("--aot_cache", type=str, default=None,
                        help="AOT compiled-program store (ops/aot.py): "
                             "inactive unless a directory is named here "
                             "or in $MLRT_AOT_CACHE ('off' overrides the "
                             "env). The train/serve JSON lines carry "
                             "aot_cache/aot_hits/aot_misses either way.")
    parser.add_argument("--aot_cold_warm_probe", action="store_true",
                        help="train/serve modes: measure the store's win "
                             "directly — build the same program twice "
                             "against a fresh store directory (first build "
                             "cold-compiles and persists, second "
                             "deserializes) and emit both timings as "
                             "cold_vs_warm_compile_s.")
    parser.add_argument("--hbm_preflight", type=_str2bool, default=True,
                        help="Raise batch_split from compiled "
                             "memory_analysis instead of OOMing in XLA.")
    # ZeRO-1 sharded optimizer state (train mode + the HBM-planning probe)
    parser.add_argument("--optimizer_sharding", type=str, default="off",
                        choices=["off", "zero1"],
                        help="train mode: optimizer-state layout — 'zero1' "
                             "shards every state leaf over the mesh data "
                             "axis (memory ~1/N per chip; grads reduce-"
                             "scatter, updated params all-gather). The "
                             "JSON line gains opt_sharding / "
                             "opt_state_bytes_per_chip either way.")
    parser.add_argument("--zero1_overlap", type=str, default="off",
                        choices=["off", "bucketed"],
                        help="train mode: ZeRO-1 collective overlap — "
                             "'bucketed' splits the flat gradient carry "
                             "into --zero1_bucket_mb buckets so each "
                             "bucket's reduce-scatter / all-gather is "
                             "independently schedulable (same arithmetic, "
                             "GSPMD reduction-order tolerance); the JSON "
                             "line gains zero1_overlap / "
                             "zero1_bucket_count either way.")
    parser.add_argument("--zero1_bucket_mb", type=float, default=4.0,
                        help="train mode: target f32 payload per gradient "
                             "bucket in MB under --zero1_overlap bucketed.")
    parser.add_argument("--async_checkpoint", type=_str2bool, default=False,
                        help="train mode: measure the checkpoint-latency "
                             "leg through the async overlapped save "
                             "(snapshot blocks, persist on a background "
                             "thread) instead of the sync save; the JSON "
                             "line gains checkpoint_blocking_ms / "
                             "checkpoint_total_ms either way.")
    parser.add_argument("--optimizer", type=str, default="adam",
                        choices=["adam", "adamod"],
                        help="train mode + --param_count_probe: optimizer "
                             "whose state is sized (adam: 2 f32 moments, "
                             "adamod: 3).")
    parser.add_argument("--param_count_probe", action="store_true",
                        help="train mode: print modeled replicated-vs-"
                             "zero1 optimizer bytes per chip from "
                             "eval_shape alone — no step is compiled or "
                             "run, so pod-scale HBM planning works before "
                             "a TPU window opens.")
    parser.add_argument("--probe_devices", type=int, default=None,
                        help="--param_count_probe: model this data-axis "
                             "width instead of the visible device count "
                             "(e.g. 64 for a planned v5e-64 run).")
    parser.add_argument("--zero_min_size", type=int, default=16384,
                        help="zero1: state leaves below this many elements "
                             "stay replicated (sharding them buys nothing "
                             "and costs collective latency).")
    parser.add_argument("--mesh", type=str, default=None,
                        help="train mode: device mesh axes for the timed "
                             "step, e.g. 'data:8' or 'data:2,pipe:2' "
                             "(same grammar as the trainer's --mesh). "
                             "None = all visible devices on the data "
                             "axis.")
    parser.add_argument("--pipe_sweep_microbatches", type=str, default=None,
                        help="train mode under a pipe-bearing --mesh: "
                             "comma list of micro-batch counts (e.g. "
                             "'1,2,4') to re-time at the same global "
                             "batch; each point runs BOTH --pipe_schedule "
                             "variants (gpipe and 1f1b), and the JSON "
                             "gains pipe_bubble_sweep with measured vs "
                             "modeled bubble fractions plus the compiled-"
                             "program peak bytes per point — the "
                             "pipeline-efficiency instrument.")
    parser.add_argument("--pipe_schedule", type=str, default="gpipe",
                        choices=["gpipe", "1f1b"],
                        help="train mode under a pipe-bearing --mesh: tick "
                             "schedule for the MAIN timed step (the "
                             "micro-batch sweep always times both); 1f1b "
                             "caps resident activations at the in-flight "
                             "window instead of all batch_split "
                             "microbatches.")
    parser.add_argument("--quantize", type=str, default="off",
                        choices=["off", "int8"],
                        help="infer/serve modes: post-training int8 "
                             "quantization of the scoring path (quant/) — "
                             "the JSON line gains quantize / "
                             "quant_mem_bytes / parity_* fields either "
                             "way.")
    args = parser.parse_args()

    if args.mode == "input":
        # host-only: no backend dial, no autotune — the point is measuring
        # the input pipeline in isolation
        return bench_input(args)

    from ml_recipe_tpu.ops import aot, autotune
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    autotune.configure(enabled=args.autotune, cache_dir=args.autotune_cache)
    aot.configure(
        enabled=args.aot_cache != "off",
        cache_dir=(
            args.aot_cache if args.aot_cache not in (None, "off") else None),
    )

    if args.mode == "infer":
        return bench_infer(args)
    if args.mode == "converge":
        return bench_converge(args)
    if args.mode == "serve":
        return bench_serve(args)
    if args.mode == "fleet":
        return bench_fleet(args)

    if args.param_count_probe:
        # modeled bytes only — no params materialized, no step compiled
        return param_count_probe(args)

    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.losses import build_loss
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.parallel import ParallelPlan
    from ml_recipe_tpu.parallel.pipeline import (
        modeled_bubble_fraction as _modeled_bubble,
    )
    from ml_recipe_tpu.train import Trainer

    n_chips = len(jax.devices())
    # the declarative parallelism plan: the timed step runs under exactly
    # the topology the trainer would (--mesh grammar shared)
    plan = ParallelPlan.from_spec(getattr(args, "mesh", None))
    mesh = plan.mesh

    cfg = MODEL_PRESETS[args.model]
    cfg = _widen_positions(cfg, args.seq_len)
    # a seq axis in --mesh selects ring attention — whose inner step runs the
    # composed streaming-KV kernels whenever the local length has a legal
    # streaming geometry (mirrors compose.init_model's 'auto' resolution);
    # this is the seq-4096/8192 long-document regime
    seq_parallel = plan.seq_size > 1
    model = QAModel(cfg, dtype=jnp.bfloat16,
                    attention_impl="ring" if seq_parallel else "auto",
                    ln_impl=args.ln_impl, remat=args.remat,
                    # ring needs the mesh; so do the Pallas kernels on any
                    # multi-device mesh (ops/attention.py shard_maps them)
                    mesh=mesh)

    class TP:
        loss = "smooth"; smooth_alpha = 0.01; focal_alpha = 1; focal_gamma = 2
        w_start = 1; w_end = 1; w_start_reg = 1; w_end_reg = 1; w_cls = 1
        lr = 1e-5; weight_decay = 1e-4; warmup_coef = 0.0
        optimizer = args.optimizer; finetune = False

    rng = np.random.default_rng(0)
    B, L = args.global_batch, args.seq_len

    def _init_params():
        # init through an XLA-attention twin under ring: param structure is
        # identical across attention impls, and ring's shard_map rejects the
        # tiny init example shape (same trick as compose.init_model)
        import dataclasses as _dc

        init_module = (
            _dc.replace(model, attention_impl="xla", mesh=None)
            if model.attention_impl == "ring" else model
        )
        return init_module.init(
            jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
        )["params"]

    params = _init_params()

    # test-only Trainer skips optimizer construction; build it for the bench
    from ml_recipe_tpu.train.optim import build_optimizer

    def _bench_trainer(batch_split, params_tree, *, hbm_preflight,
                       pipe_schedule="gpipe"):
        """ONE bench-trainer bootstrap for the main timed step AND the
        pipe-bubble sweep — the sweep must characterize exactly the
        optimizer-sharding configuration the user benched, only the
        micro-batch count (and, in the sweep, the tick schedule)
        varies."""
        tr = Trainer(
            model=model, params=params_tree, loss=build_loss(TP()),
            collate_fun=None, trainer_params=None,
            mesh=mesh, batch_split=batch_split, seed=0,
            train_batch_size=args.global_batch, hbm_preflight=hbm_preflight,
            optimizer_sharding=args.optimizer_sharding,
            zero_min_size=args.zero_min_size,
            zero1_overlap=args.zero1_overlap,
            zero1_bucket_mb=args.zero1_bucket_mb,
            async_checkpoint=args.async_checkpoint,
            pipe_schedule=pipe_schedule,
        )
        tr.optimizer, tr.scheduler, tr._schedule_count = build_optimizer(
            TP(), tr.params, num_training_steps=10_000, max_grad_norm=None,
            warmup_coef=0.0,
        )
        tr.init_opt_state()
        return tr

    # --pipe_sweep_microbatches: parse + validate UP FRONT (a count that
    # cannot split the global batch must fail before the main timed run,
    # not minutes later inside _split_micro)
    sweep_ms = None
    if args.pipe_sweep_microbatches:
        if plan.pipe_size <= 1:
            print(
                "WARNING: --pipe_sweep_microbatches set but the --mesh has "
                "no pipe axis (> 1); the sweep is skipped — add e.g. "
                "'pipe:2' to --mesh.",
                file=sys.stderr,
            )
        else:
            sweep_ms = sorted({
                int(s) for s in args.pipe_sweep_microbatches.split(",")
                if s.strip()
            })
            for m in sweep_ms:
                if m < 1 or B % m or (B // m) % max(plan.data_size, 1):
                    raise SystemExit(
                        f"--pipe_sweep_microbatches {m}: counts must be "
                        f">= 1 and split global batch {B} into micro-"
                        f"batches divisible over the {plan.data_size}-way "
                        f"data axis"
                    )

    trainer = _bench_trainer(
        args.batch_split, params, hbm_preflight=args.hbm_preflight,
        pipe_schedule=args.pipe_schedule,
    )

    # UNSPLIT host batch: the HBM pre-flight may raise batch_split, and the
    # micro split must follow whatever it decides
    host_inputs = {
        "input_ids": rng.integers(1, cfg.vocab_size, (B, L)).astype(np.int32),
        "attention_mask": np.ones((B, L), dtype=np.int32),
        "token_type_ids": np.zeros((B, L), dtype=np.int32),
    }
    host_labels = {
        "start_class": rng.integers(0, L, (B,)).astype(np.int32),
        "end_class": rng.integers(0, L, (B,)).astype(np.int32),
        "start_reg": rng.random((B,)).astype(np.float32),
        "end_reg": rng.random((B,)).astype(np.float32),
        "cls": rng.integers(0, 5, (B,)).astype(np.int32),
    }

    with mesh:
        # pre-flight: compile once, read memory_analysis, raise batch_split
        # if the requested configuration exceeds device HBM (the compile is
        # jit-cached, so this is also the first step's compile)
        trainer.preflight_train_step(host_inputs, host_labels)
        if trainer._jit_train_step is None:
            trainer._jit_train_step = trainer._build_train_step()
        step_fn = trainer._jit_train_step

        inputs = trainer._global_batch(
            trainer._split_micro(host_inputs), leading_accum=True
        )
        labels = trainer._global_batch(
            trainer._split_micro(host_labels), leading_accum=True
        )

        # in-memory goodput accountant (metrics/goodput.py, path=None):
        # the warmup leg (compile + first dispatches) is compile/warmup
        # badput, the measured windows are productive — the same partition
        # --goodput_ledger keeps for real runs, on the bench JSON line
        from ml_recipe_tpu.metrics.goodput import GoodputLedger

        goodput = GoodputLedger(None)
        goodput.note_run_start(0)

        t_warm = time.perf_counter()
        params_d, opt_d = trainer.params, trainer.opt_state
        for i in range(args.warmup):
            params_d, opt_d, values = step_fn(params_d, opt_d, inputs, labels, i)
        jax.block_until_ready(values)
        goodput.note_step(
            0, wall_s=time.perf_counter() - t_warm, compile=True
        )

        win = max(1, args.window)
        sizes = [win] * (args.steps // win)
        if args.steps % win:
            sizes.append(args.steps % win)
        window_step_s = []
        step_i = args.warmup
        for size in sizes:
            t0 = time.perf_counter()
            for _ in range(size):
                params_d, opt_d, values = step_fn(
                    params_d, opt_d, inputs, labels, step_i
                )
                step_i += 1
            jax.block_until_ready(values)  # window sync
            per_step = (time.perf_counter() - t0) / size
            window_step_s.append(per_step)
            for k in range(size):
                goodput.note_step(step_i - size + k, wall_s=per_step)

        # Checkpoint-latency leg: one save of the LIVE step state through
        # the configured save path. blocking = what the step loop pays on
        # its critical path (sync: full serialize+write; async: the
        # device->host snapshot only); total adds the background persist
        # wait — their gap is the persist tail a real training run hides
        # under subsequent steps (here nothing follows the save, so the
        # harness measures the split rather than realized overlap), fed
        # to the ledger as the blocking-vs-overlapped checkpoint split.
        import shutil
        import tempfile

        trainer.params, trainer.opt_state = params_d, opt_d
        trainer.global_step = step_i
        ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
        t_ck = time.perf_counter()
        trainer.save_state_dict(os.path.join(ckpt_dir, "bench.ch"))
        ckpt_blocking_s = time.perf_counter() - t_ck
        trainer.finish_pending_checkpoint()
        ckpt_total_s = time.perf_counter() - t_ck
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        goodput.note_checkpoint("save", ckpt_blocking_s)
        if ckpt_total_s > ckpt_blocking_s:
            # the harness BLOCKS in finish_pending for the persist tail
            # (nothing trains concurrently here), so the ledger books it
            # as blocking checkpoint time, not overlap — the async SPLIT
            # this leg measures lives in checkpoint_blocking_ms /
            # checkpoint_total_ms; a live run's ledger is where genuinely
            # overlapped persist time appears as checkpoint_overlapped_s
            goodput.note_checkpoint("save", ckpt_total_s - ckpt_blocking_s)
        goodput.note_run_end(step_i)

        # --aot_cold_warm_probe: the program store's win measured directly.
        # Build the SAME train-step program twice against a fresh store
        # directory: the first build cold-compiles and persists, the second
        # — dispatch memo cleared, exactly a restarted process's state —
        # deserializes. Runs AFTER note_run_end so neither build pollutes
        # the goodput partition of the benched configuration; the session
        # summary for the JSON line is snapshotted first for the same
        # reason.
        aot_summary = aot.get().session_summary()
        aot_probe = None
        if getattr(args, "aot_cold_warm_probe", False):
            probe_dir = tempfile.mkdtemp(prefix="bench_aot_probe_")
            aot.reset()
            aot.configure(enabled=True, cache_dir=probe_dir)
            trainer._compiled_steps.clear()
            t0 = time.perf_counter()
            trainer._aot_train_step_program(inputs, labels)
            cold_s = time.perf_counter() - t0
            trainer._compiled_steps.clear()
            t0 = time.perf_counter()
            trainer._aot_train_step_program(inputs, labels)
            warm_s = time.perf_counter() - t0
            probe_store = aot.get()
            aot_probe = {
                "cold_compile_s": round(cold_s, 4),
                "warm_load_s": round(warm_s, 4),
                "speedup_x": (
                    round(cold_s / warm_s, 1) if warm_s > 0 else None),
                "hits": probe_store.hits,
                "misses": probe_store.misses,
            }
            shutil.rmtree(probe_dir, ignore_errors=True)

        # pipe-bubble sweep (--pipe_sweep_microbatches, validated above):
        # re-time the step at the same global batch with varying micro-
        # batch counts; under the GPipe model T(m) = ideal * (m+K-1)/m,
        # so the measured bubble should track (K-1)/(K-1+m) — decreasing
        # as m grows. Runs AFTER note_run_end so its trainer builds and
        # compiles never pollute the goodput partition of the benched
        # configuration.
        pipe_sweep = None
        if sweep_ms:
            from ml_recipe_tpu.data.bucketing import synthetic_qa_batch
            from ml_recipe_tpu.parallel.pipeline import (
                PIPE_SCHEDULES,
                measured_bubble_fractions,
                modeled_bubble_fraction,
            )
            from ml_recipe_tpu.utils.hbm import preflight_bytes

            sweep_in, sweep_lab = synthetic_qa_batch(B, L)
            # schedule dimension (ISSUE-19): every sweep point is timed
            # under BOTH tick schedules, with the compiled-program peak
            # bytes alongside — one JSON compares gpipe's m-resident
            # activations against 1F1B's in-flight window on chip
            times = {sched: {} for sched in PIPE_SCHEDULES}
            peak_bytes = {sched: {} for sched in PIPE_SCHEDULES}
            for m in sweep_ms:
                for sched in PIPE_SCHEDULES:
                    # fresh runtime-owned params per point (deterministic
                    # init): re-handing one host tree to several trainers
                    # aliases memory into donated buffers on the CPU
                    # runtime — the PR-8 heap-corruption class
                    tr_m = _bench_trainer(
                        m,
                        model.init(
                            jax.random.key(0),
                            np.zeros((1, 8), dtype=np.int32),
                        )["params"],
                        hbm_preflight=False,
                        pipe_schedule=sched,
                    )
                    step_m = tr_m._build_train_step()
                    di = tr_m._global_batch(
                        tr_m._split_micro(sweep_in), leading_accum=True
                    )
                    dl = tr_m._global_batch(
                        tr_m._split_micro(sweep_lab), leading_accum=True
                    )
                    p_m, o_m = tr_m.params, tr_m.opt_state
                    try:
                        compiled = step_m.lower(
                            p_m, o_m, di, dl, 0
                        ).compile()
                        peak_bytes[sched][m] = preflight_bytes(
                            compiled.memory_analysis()
                        )
                    except Exception:  # noqa: BLE001 - analysis optional
                        peak_bytes[sched][m] = None
                    p_m, o_m, v_m = step_m(p_m, o_m, di, dl, 0)
                    jax.block_until_ready(v_m)  # compile + sync
                    best = float("inf")
                    for rep in range(3):
                        t0 = time.perf_counter()
                        p_m, o_m, v_m = step_m(p_m, o_m, di, dl, rep + 1)
                        jax.block_until_ready(v_m)
                        best = min(best, time.perf_counter() - t0)
                    times[sched][m] = best
            measured = {
                sched: measured_bubble_fractions(
                    times[sched], plan.pipe_size, schedule=sched
                )
                for sched in PIPE_SCHEDULES
            }
            pipe_sweep = [
                {
                    "microbatches": m,
                    "schedule": sched,
                    "step_time_ms": round(times[sched][m] * 1e3, 1),
                    "bubble_measured": round(measured[sched][m], 4),
                    "bubble_modeled": round(
                        modeled_bubble_fraction(
                            plan.pipe_size, m, schedule=sched
                        ), 4
                    ),
                    "compiled_peak_bytes": peak_bytes[sched][m],
                }
                for m in sweep_ms
                for sched in PIPE_SCHEDULES
            ]

    # observability twins of the --metrics_port surface: step-time
    # percentiles over the measured windows + the slow-step detector run
    # over the same series (a thermal-throttled / noisy-neighbor window
    # shows up as a nonzero anomaly count in the JSON line)
    from ml_recipe_tpu.metrics.anomaly import SlowStepDetector

    detector = SlowStepDetector(
        factor=3.0, window=max(2, len(window_step_s)), warmup=0, min_steps=2)
    for i, s in enumerate(window_step_s):
        detector.update(i, s, {"device": s})

    med = float(np.median(window_step_s))
    step_time_ms = med * 1000.0
    examples_per_sec = args.global_batch / med
    per_chip = examples_per_sec / n_chips
    train_gflops = _matmul_gflops_per_example(cfg, L, train=True)
    device = _device_record()
    peak = _chip_peak_tflops(device)

    # padding accounting of the ACTUAL batch fed to the step: the share of
    # step tokens that are pad (pure FLOP waste) and the per-chip throughput
    # in REAL tokens — the number bucketed batching moves
    real_tokens = int(np.asarray(host_inputs["attention_mask"]).sum())
    total_tokens = int(np.asarray(host_inputs["attention_mask"]).size)

    tuning = autotune.get().session_summary()
    print(
        json.dumps(
            {
                "metric": f"{args.model}_qa_finetune_seq{L}_examples_per_sec_per_chip",
                "value": round(per_chip, 2),
                "unit": "examples/sec/chip",
                "vs_baseline": round(per_chip / V100_EXAMPLES_PER_SEC_EST, 3),
                "model_gflops_per_example": round(train_gflops, 2),
                "device": device,
                "mfu": _mfu(train_gflops, per_chip, peak),
                "peak_tflops_bf16": peak or NOT_MEASURED,
                "padding_waste_pct": round(
                    100.0 * (1.0 - real_tokens / total_tokens), 2
                ),
                # packing accounting twins (ISSUE-5): the fraction of step
                # tokens that are real, and the row (= step-batch-row)
                # throughput a packed input path would scale by
                "packing_efficiency": round(real_tokens / total_tokens, 4),
                "rows_per_sec": round(examples_per_sec, 1),
                "nonpad_tokens_per_sec_per_chip": round(
                    real_tokens / med / n_chips, 1
                ),
                "step_time_ms": round(step_time_ms, 1),
                "step_time_ms_windows": [
                    round(s * 1000.0, 1) for s in window_step_s
                ],
                # step-time breakdown percentiles + anomaly count (this
                # loop is device-bound by construction: the batch is
                # pre-placed, so data-wait/host are zero here — the full
                # three-way breakdown lives on the --metrics_port surface)
                "step_time_ms_p50": round(
                    float(np.percentile(window_step_s, 50)) * 1e3, 1),
                "step_time_ms_p95": round(
                    float(np.percentile(window_step_s, 95)) * 1e3, 1),
                "slow_step_anomalies": detector.anomalies,
                # run-level goodput partition of this bench invocation:
                # warmup/compile is badput, measured windows productive
                "goodput": _goodput_json(goodput.summary()),
                "global_batch": args.global_batch,
                # pre-flight may have raised this above --batch_split
                "batch_split": trainer.batch_split,
                # the declarative plan the step ran under: axis sizes,
                # stranded-device count, and (when pipe > 1) the GPipe
                # stage count + modeled bubble at the measured
                # batch_split — the pipeline-efficiency instrument for
                # the first pipe:2 TPU capture
                "mesh_axes": plan.describe(),
                "mesh_unused_devices": plan.unused_devices,
                "pipe_stages": plan.pipe_size,
                "pipe_schedule": (
                    trainer.pipe_schedule if plan.pipe_size > 1 else None
                ),
                "pipe_bubble_fraction": round(_modeled_bubble(
                    plan.pipe_size, trainer.batch_split,
                    schedule=trainer.pipe_schedule), 4),
                "pipe_bubble_sweep": pipe_sweep,
                "hbm_preflight": trainer.preflight_report,
                # optimizer-state layout + measured per-chip residency
                # (zero1: ~1/N of the replicated footprint)
                "opt_sharding": trainer.effective_opt_sharding,
                "opt_state_bytes_per_chip": _opt_bytes(trainer),
                # collective-overlap + async-checkpoint instrumentation:
                # bucket count is 0 when the overlap is off/inert, and
                # blocking==total for a sync save — the async win is the
                # gap between the two
                "zero1_overlap": args.zero1_overlap,
                "zero1_bucket_count": trainer.zero1_bucket_count,
                "async_checkpoint": bool(args.async_checkpoint),
                "checkpoint_blocking_ms": round(ckpt_blocking_s * 1e3, 1),
                "checkpoint_total_ms": round(ckpt_total_s * 1e3, 1),
                # tuning provenance: 'hit' = every geometry served from the
                # on-disk cache (zero compile probes this run)
                "autotune_cache": tuning["cache"],
                "autotune_probes": tuning["probes"],
                "autotune_geometry": tuning["decisions"],
                # AOT program-store provenance: 'hit' = every program this
                # run needed was deserialized (zero XLA compiles)
                "aot_cache": aot_summary["cache"],
                "aot_hits": aot_summary["hits"],
                "aot_misses": aot_summary["misses"],
                "cold_vs_warm_compile_s": aot_probe,
                # 'ring' under a seq-bearing --mesh: the composed
                # streaming-ring long-document path (the seq-4096/8192
                # baseline rows key off this)
                "attention_impl": model.attention_impl,
                "ln_impl": args.ln_impl,
                "n_chips": n_chips,
                "backend": jax.default_backend(),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
