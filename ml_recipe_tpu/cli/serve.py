"""Online QA serving entry point.

Boots the serving subsystem (``ml_recipe_tpu/serve/``): load model +
checkpoint, build the bucket grid, warm every bucket program through the
autotune cache (a warm restart performs zero probes), pre-flight each
bucket against device HBM (shrinking the grid instead of OOMing
mid-traffic), then serve ``POST /v1/qa`` until SIGTERM drains it.

Usage::

    python -m ml_recipe_tpu.cli.serve -c config/serve.cfg

No reference counterpart: the reference stack (and this repo's
``cli/validate.py``) is an offline batch predictor; this is the long-running
request/response engine the ROADMAP's "serves heavy traffic" north star
needs.
"""

from __future__ import annotations

import json
import os
from pathlib import Path

from ..compose import init_model
from ..config.parser import get_model_parser, get_params, get_serve_parser
from ..ops import aot, autotune
from ..parallel import ParallelPlan
from ..utils.logging import get_logger, show_params


def main(params, model_params) -> int:
    from ..serve.bucketing import BucketGrid
    from ..serve.engine import QAEngine
    from ..serve.server import QAServer

    show_params(model_params, "model")
    show_params(params, "serve")

    autotune.configure(
        enabled=params.autotune, cache_dir=params.autotune_cache
    )
    # AOT program-store wiring (ops/aot.py): a rolling-restart replacement
    # engine deserializes every bucket program from the shared store
    # instead of recompiling the grid
    aot.configure(
        enabled=params.aot_cache != "off",
        cache_dir=(
            params.aot_cache if params.aot_cache not in (None, "off")
            else None),
        cache_bytes=params.aot_cache_bytes or None,
    )

    # --trace_spans: structured request-lifecycle spans (admission ->
    # queue -> flush -> device -> span_reduce -> respond, keyed by request
    # id) as Chrome trace-event JSON, written out when the drain completes
    tracer = None
    if getattr(params, "trace_spans", None):
        from ..metrics import trace as trace_mod

        tracer = trace_mod.install(trace_mod.TraceWriter(
            str(Path(params.trace_spans) / f"serve_trace_{os.getpid()}.json"),
            process_name="serve",
        ))

    model, model_state, tokenizer = init_model(
        model_params, checkpoint=params.checkpoint,
        quantize=getattr(params, "quantize", "off"),
    )
    # one declarative plan from --mesh; the engine derives its bucket
    # placements from it
    mesh = ParallelPlan.from_spec(getattr(params, "mesh", None)).mesh

    engine = QAEngine(
        model,
        model_state,
        tokenizer,
        grid=BucketGrid.from_spec(params.buckets),
        mesh=mesh,
        max_batch_delay_ms=params.max_batch_delay_ms,
        queue_size=params.queue_size,
        max_question_len=params.max_question_len,
        doc_stride=params.doc_stride,
        quantize=getattr(params, "quantize", "off"),
        serve_cache_bytes=getattr(params, "serve_cache_bytes", 0),
        doc_cache_bytes=getattr(params, "doc_cache_bytes", 0),
        long_scatter_chunks=getattr(params, "long_scatter_chunks", 0),
    )
    engine.warmup(hbm_preflight=params.hbm_preflight)

    server = QAServer(
        engine,
        host=params.host,
        port=params.port,
        request_timeout_s=params.request_timeout_s,
        drain_timeout_s=params.drain_timeout_s,
    )
    server.install_signal_handlers()
    server.start()

    if params.ready_file:
        # orchestration hook (supervisor, chaos drills): the listener is up
        # and every bucket is compiled — traffic is safe to send
        ready = Path(params.ready_file)
        tmp = ready.with_name(ready.name + ".tmp")
        tmp.write_text(json.dumps({
            "host": server.host, "port": server.port, "pid": os.getpid(),
            "buckets": [str(b) for b in engine.grid],
        }))
        os.replace(tmp, ready)

    try:
        server.wait()
    finally:
        server.shutdown()
        if tracer is not None:
            from ..metrics import trace as trace_mod

            trace_mod.install(None)
            tracer.close()
    return 0


def cli() -> None:
    from ..utils.platform import configure_compile_cache

    configure_compile_cache()
    _, (params, model_params) = get_params((get_serve_parser, get_model_parser))
    get_logger(logger_name="serve")

    raise SystemExit(main(params, model_params))


if __name__ == "__main__":
    cli()
