"""The serve runner: ``QAServer`` + ``QAEngine`` as ``cli.serve`` builds them
from ``config/serve.cfg``, in this process (it holds the chip), answering
``POST /v1/qa`` over loopback from ``perfbench/loadgen.py`` in a process of
its own that never imports jax.

Latency is the generator's: response received minus the time the request was
due. The engine's counters are read here before and after the window; its own
latency percentiles start at admission and are not used.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from ..harness import checks, device, profiler, reference, textgen
from ..harness import trace_reduce
from ..harness.compile_watch import CompileWatch
from ..harness.manifest import BENCH_DIR, CACHE_DIR, ROOT, Cell, write_cfg
from ..harness.result import end_to_end, last_line, note, read_per_layer

SHIPPED_CFG = ROOT / "config" / "serve.cfg"
FAILED_MS = 60_000.0        # what a failed request counts as: the time limit


def build_server(cell: Cell, job: dict, work: Path, seed: int):
    """``cli.serve.main`` up to ``server.start()``, weights from the seed."""
    from ml_recipe_tpu.compose import init_model
    from ml_recipe_tpu.config.parser import (
        get_model_parser,
        get_params,
        get_serve_parser,
    )
    from ml_recipe_tpu.ops import aot, autotune
    from ml_recipe_tpu.parallel import ParallelPlan
    from ml_recipe_tpu.serve.bucketing import BucketGrid
    from ml_recipe_tpu.serve.engine import QAEngine
    from ml_recipe_tpu.serve.server import QAServer

    cfg_path = write_cfg(SHIPPED_CFG, {
        **job["flags"], "model": job.get("model", cell.config["model"]),
        "vocab_file": work / "vocab.txt"}, work / "job.cfg")
    _, (params, model_params) = get_params(
        (get_serve_parser, get_model_parser), ["-c", str(cfg_path)])
    autotune.configure(enabled=params.autotune,
                       cache_dir=params.autotune_cache)
    aot.configure(
        enabled=params.aot_cache != "off",
        cache_dir=params.aot_cache if params.aot_cache not in (None, "off")
        else None,
        cache_bytes=params.aot_cache_bytes or None)
    model, model_state, tokenizer = init_model(
        model_params, checkpoint=params.checkpoint, rng_seed=seed,
        quantize=params.quantize)
    mesh = ParallelPlan.from_spec(params.mesh).mesh
    engine = QAEngine(
        model, model_state, tokenizer,
        grid=BucketGrid.from_spec(params.buckets), mesh=mesh,
        max_batch_delay_ms=params.max_batch_delay_ms,
        queue_size=params.queue_size,
        max_question_len=params.max_question_len,
        doc_stride=params.doc_stride, quantize=params.quantize,
        serve_cache_bytes=params.serve_cache_bytes,
        doc_cache_bytes=params.doc_cache_bytes,
        long_scatter_chunks=params.long_scatter_chunks)
    engine.warmup(hbm_preflight=params.hbm_preflight)
    server = QAServer(engine, host=params.host, port=params.port,
                      request_timeout_s=params.request_timeout_s,
                      drain_timeout_s=params.drain_timeout_s)
    server.start()
    return server, engine, params


def counters(engine) -> dict:
    return {"batches": engine.m_occupancy.count,
            "occupancy_sum": engine.m_occupancy.sum,
            "padding_sum": engine.m_padding_waste.sum,
            "requests": engine.m_requests.value,
            "completed": engine.m_completed.value,
            "failed": engine.m_failed.value,
            "rejected_full": engine.m_rejected_full.value}


class Generator:
    """The child process and its one-word-a-line protocol."""

    def __init__(self, spec: dict, work: Path):
        spec_path = work / "loadgen.json"
        spec_path.write_text(json.dumps(spec))
        self.out = Path(spec["out"])
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH_DIR / "loadgen.py"), str(spec_path)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def expect(self, word: str) -> None:
        line = self.proc.stdout.readline().strip()
        if line != word:
            raise RuntimeError(
                f"load generator said {line!r}, expected {word!r} "
                f"(exit code {self.proc.poll()})")

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            pipe.close()


def check_against_reference(engine, cell: Cell, job: dict, words, seed: int) -> dict:
    """A seeded sample of requests through ``engine.submit``: what the
    serving forward returned for each chunk (answerability score, span
    argmaxes) against the reference's forward over the same chunks."""
    import jax

    cfg = cell.config if "model" not in job else job["reference_config"]
    sample = textgen.serve_requests(
        seed + 7919, words, job["mix"], int(job["check_requests"]))
    chunks, rows = [], []
    for r in sample:
        body = json.loads(r["body"])
        ticket = engine.submit(body["question"], body["document"])
        ticket.result(timeout=float(job["timeout_s"]))
        for i, ids in enumerate(ticket.chunks):
            chunks.append(list(ids))
            rows.append(ticket._outputs[i])
    n, seq = int(job["check_rows"]), int(job["mix"]["max_seq"])
    chunks, rows = chunks[:n], rows[:n]
    ids = np.zeros((n, seq), np.int32)
    lengths = np.ones((n,), np.int32)
    for i, c in enumerate(chunks):
        ids[i, :len(c)] = c
        lengths[i] = len(c)
    inputs = engine._host_arrays(ids, lengths)
    host_params = jax.device_get(engine.params)
    want = jax.device_get(jax.jit(
        lambda p, i: reference.forward(p, cfg, **i))(host_params, inputs))
    ref = {k: np.asarray(v) for k, v in reference.answerability(want).items()}
    k = len(chunks)
    span_tol = checks.logit_tolerances(
        host_params, int(cfg["num_hidden_layers"]))["start_class"]
    tol = 4 * span_tol                      # a score is a sum of four logits
    score_err = max(abs(rows[i]["scores"] - float(ref["scores"][i]))
                    for i in range(k))
    # the system's argmax must be a maximum of the reference within tolerance
    arg_err = max(
        max(float(ref["start_max"][i]) - float(
                want["start_class"][i, int(rows[i]["start_ids"])]),
            float(ref["end_max"][i]) - float(
                want["end_class"][i, int(rows[i]["end_ids"])]))
        for i in range(k))
    ok = score_err <= tol and arg_err <= 2 * span_tol
    return {"chunks_compared": k, "score_err": score_err, "score_tol": tol,
            "argmax_shortfall": arg_err, "argmax_tol": 2 * span_tol,
            "ok": bool(ok)}


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell: Cell, **how) -> int:
    with CompileWatch() as watch:
        return measure(cell, watch, **how)


def measure(cell: Cell, watch: CompileWatch, *, seed: int, seconds: float,
            trace: bool, rehearse: bool, t_start: float) -> int:
    from ml_recipe_tpu.utils.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    record = device.require_chips(cell.chips, rehearse=rehearse)
    job = cell.job(rehearse)
    work = CACHE_DIR / "work" / cell.name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    words = textgen.write_vocab(
        work / "vocab.txt", seed, int(cell.config["vocab_size"]))

    gen = Generator({
        "vocab_file": str(work / "vocab.txt"), "seed": seed,
        "rate": float(job["rate"]), "seconds": float(seconds),
        "mix": job["mix"], "warmup_requests": int(job["warmup_requests"]),
        "timeout_s": float(job["timeout_s"]), "host": "127.0.0.1",
        "out": str(work / "loadgen.out.json"),
    }, work)
    server = None
    try:
        server, engine, params = build_server(cell, job, work, seed)
        gen.expect("ready")
        gen.tell(f"go {server.port}")
        gen.expect("warm")
        setup_compile = watch.mark()
        before = counters(engine)
        setup_s = time.perf_counter() - t_start
        t_open = time.perf_counter()
        gen.tell("start")
        trace_file = None
        if trace:
            trace_dir = CACHE_DIR / "trace" / cell.name
            time.sleep(min(1.0, seconds / 4))
            profiler.start(trace_dir)
            time.sleep(min(float(job["trace_seconds"]), seconds / 2))
            trace_file = profiler.stop(trace_dir)
        gen.expect("done")
        window_s = time.perf_counter() - t_open
        after = counters(engine)
        window_compiles = watch.since(setup_compile)["programs"]
        peak_bytes = device.memory_peak_bytes(1)
        memory_stats = device.memory_stats()
        out = json.loads(gen.out.read_text())
        verdict = check_against_reference(engine, cell, job, words, seed)
    finally:
        gen.close()
        if server is not None:
            server.shutdown()

    rows = out["rows"]
    ok_rows = [r for r in rows if r["status"] == 200]
    failed = len(rows) - len(ok_rows)
    latency = [1e3 * (r["done"] - r["due"]) for r in ok_rows] \
        + [FAILED_MS] * failed
    late = [1e3 * (r["sent"] - r["due"]) for r in rows]
    delta = {k: after[k] - before[k] for k in after}
    note(run={"workload": cell.name, "seed": seed, "seconds": seconds,
              "trace": int(trace), "rehearse": rehearse},
         compile_cache=cache_dir, rate=job["rate"], knee=job.get("knee"),
         buckets=engine.warmup_report["buckets"],
         preflight=engine.warmup_report["preflight"],
         bytes_limit=device.bytes_limit(), memory_stats=memory_stats)
    note(setup={"setup_s": setup_s, "compile": setup_compile,
                "missed": watch.missed[:12],
                "warmup_s": engine.warmup_report["warmup_seconds"]})
    statuses: dict = {}
    for r in rows:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    chunk_hist = np.bincount([r["chunks"] for r in ok_rows], minlength=2)
    if latency:
        note(requests={
            "attempted": len(rows), "statuses": statuses,
            "p50_ms": percentile(latency, 50), "p95_ms": percentile(latency, 95),
            "p99_ms": percentile(latency, 99), "max_ms": max(latency),
            "late_p50_ms": percentile(late, 50),
            "late_p95_ms": percentile(late, 95),
            "window_s": window_s, "last_done_s": max(r["done"] for r in rows),
            "chunks_mean": float(np.mean([r["chunks"] for r in ok_rows] or [0])),
            "chunks_share_1_2": float(chunk_hist[1:3].sum() / max(len(ok_rows), 1)),
            "chunks_share_9up": float(chunk_hist[9:].sum() / max(len(ok_rows), 1)),
            "engine": delta})
    note(reference_check=verdict)
    correct = (verdict["ok"] and failed == 0 and window_compiles == 0
               and len(rows) > 0)
    note(correct={"reference": verdict["ok"], "all_200": failed == 0,
                  "window_compiles": window_compiles})
    dev = dict(record, count=1, memory_peak_bytes=peak_bytes)

    if rehearse:
        note(rehearsal={"requests": len(rows), "answered": len(ok_rows),
                        "batches": delta["batches"],
                        "window_compiles": window_compiles})
        last_line(correct=correct, attempted=len(rows), failed=failed,
                  metrics={}, device=dev)
        return 0

    if not trace:
        metrics = end_to_end(cell, {
            "serve_p50_ms": percentile(latency, 50),
            "serve_p95_ms": percentile(latency, 95),
            "setup_s": setup_s})
        last_line(correct=correct, attempted=len(rows), failed=failed,
                  metrics=metrics, device=dev)
        return 0

    tr = trace_reduce.load(trace_file)
    busy = trace_reduce.busy_idle(tr)
    if busy is None:
        raise RuntimeError("the traced stretch shows no device operation")
    ctx = {
        "cell": cell, "device": dev, "peaks": device.peaks(record["kind"]),
        "trace": tr, "busy": busy, "chips": 1, "train": False,
        "counters": dict(delta, window_s=float(seconds),
                         chunks=sum(r["chunks"] for r in ok_rows)),
        "generator": {"late_p95_ms": percentile(late, 95)},
        "compile": {"setup": setup_compile,
                    "window_compiles": window_compiles},
        "memory_peak_bytes": peak_bytes,
    }
    dev.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
    note(trace={"file": trace_file, "busy": busy})
    last_line(correct=correct, attempted=len(rows), failed=failed,
              metrics=read_per_layer(cell, ctx), device=dev,
              breakdown=trace_reduce.breakdown(tr))
    return 0
