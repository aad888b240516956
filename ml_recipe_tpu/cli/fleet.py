"""Serving-fleet entry point: router tier + N supervised engines.

Boots the fleet subsystem (``ml_recipe_tpu/fleet/``): launch N
``cli.serve`` engine children against the shared AOT program store (each
warms its bucket grid before admitting traffic), put the consistent-hash
router in front of them, and serve ``POST /v1/qa`` until SIGTERM. The
router sheds load health-first; crashed engines are classified with the
``resilience/`` exit-code contract and relaunched behind the router's
ejection. ``--rolling_restart true`` performs one zero-compile rolling
restart pass once the tier is up.

Usage::

    python -m ml_recipe_tpu.cli.fleet -c config/fleet.cfg

``--host``/``--port`` bind the ROUTER; engines always bind ephemeral
ports on the same host. ``--ready_file`` documents the router address +
every engine endpoint once the whole tier admits traffic.
"""

from __future__ import annotations

import json
import os
import signal
import tempfile
import threading
from pathlib import Path

from ..config.parser import (
    get_fleet_parser,
    get_model_parser,
    get_params,
    get_serve_parser,
)
from ..fleet import FleetManager, FleetRouter
from ..utils.logging import get_logger, show_params

# (flag, attr, kind) map from the parsed serve+model namespaces onto the
# engine-child argv. 'value' flags are skipped when None; 'bool' flags
# are forwarded as true/false (_str2bool surface); 'switch' flags are
# store_true and forwarded only when set.
_MODEL_FLAGS = (
    ("--model", "model", "value"),
    ("--vocab_file", "vocab_file", "value"),
    ("--merges_file", "merges_file", "value"),
    ("--lowercase", "lowercase", "switch"),
    ("--handle_chinese_chars", "handle_chinese_chars", "switch"),
    ("--hf_checkpoint", "hf_checkpoint", "value"),
    ("--param_dtype", "param_dtype", "value"),
    ("--compute_dtype", "compute_dtype", "value"),
    ("--flash_attention", "flash_attention", "value"),
    ("--ln_impl", "ln_impl", "value"),
    ("--max_position_embeddings", "max_position_embeddings", "value"),
)
_SERVE_FLAGS = (
    ("--host", "host", "value"),
    ("--buckets", "buckets", "value"),
    ("--max_batch_delay_ms", "max_batch_delay_ms", "value"),
    ("--queue_size", "queue_size", "value"),
    ("--request_timeout_s", "request_timeout_s", "value"),
    ("--drain_timeout_s", "drain_timeout_s", "value"),
    ("--max_question_len", "max_question_len", "value"),
    ("--doc_stride", "doc_stride", "value"),
    ("--mesh", "mesh", "value"),
    ("--autotune", "autotune", "bool"),
    ("--autotune_cache", "autotune_cache", "value"),
    ("--aot_cache", "aot_cache", "value"),
    ("--aot_cache_bytes", "aot_cache_bytes", "value"),
    ("--hbm_preflight", "hbm_preflight", "bool"),
    ("--serve_cache_bytes", "serve_cache_bytes", "value"),
    ("--doc_cache_bytes", "doc_cache_bytes", "value"),
    ("--quantize", "quantize", "value"),
    ("--trace_spans", "trace_spans", "value"),
)


def engine_argv(serve_params, model_params) -> list:
    """The common ``cli.serve`` child argv from the parsed namespaces
    (everything but --port/--ready_file/--checkpoint, which the manager
    owns per-engine)."""
    argv = []
    for flags, params in ((_MODEL_FLAGS, model_params),
                          (_SERVE_FLAGS, serve_params)):
        for flag, attr, kind in flags:
            value = getattr(params, attr, None)
            if kind == "switch":
                if value:
                    argv.append(flag)
            elif kind == "bool":
                argv.extend([flag, "true" if value else "false"])
            elif value is not None:
                argv.extend([flag, str(value)])
    return argv


def main(fleet_params, params, model_params) -> int:
    show_params(model_params, "model")
    show_params(params, "serve")
    show_params(fleet_params, "fleet")

    run_dir = Path(
        fleet_params.fleet_run_dir
        or tempfile.mkdtemp(prefix="mlrt_fleet_")
    )
    checkpoints = None
    if fleet_params.engine_checkpoints:
        checkpoints = [
            c.strip() or None
            for c in fleet_params.engine_checkpoints.split(",")
        ]
    elif params.checkpoint:
        checkpoints = [params.checkpoint]

    router = FleetRouter(
        host=params.host,
        port=params.port,
        ring_replicas=fleet_params.ring_replicas,
        health_poll_s=fleet_params.health_poll_s,
        eject_after=fleet_params.eject_after,
        degrade_weight=fleet_params.degrade_weight,
        queue_pressure=fleet_params.queue_pressure,
        spill_retries=fleet_params.spill_retries,
        request_timeout_s=params.request_timeout_s,
        routing=fleet_params.routing,
    )
    manager = FleetManager(
        engine_argv(params, model_params),
        n_engines=fleet_params.engines,
        run_dir=run_dir,
        checkpoints=checkpoints,
        drain_timeout_s=params.drain_timeout_s,
        router=router,
    )

    stop = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001 - signal API
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    try:
        manager.start()
        router.start()

        if params.ready_file:
            # orchestration hook: the router is listening and every
            # engine's bucket grid is compiled — traffic is safe to send
            ready = Path(params.ready_file)
            tmp = ready.with_name(ready.name + ".tmp")
            tmp.write_text(json.dumps({
                "host": router.host, "port": router.port, "pid": os.getpid(),
                "engines": [
                    {"node": ep.node_id, "host": ep.host, "port": ep.port,
                     "checkpoint": ep.checkpoint}
                    for ep in router.endpoints()
                ],
            }))
            os.replace(tmp, ready)

        if fleet_params.rolling_restart:
            manager.rolling_restart()

        while not stop.wait(2.0):
            manager.reap()
    finally:
        manager.stop()
        router.close()
    return 0


def cli() -> None:
    from ..utils.platform import configure_compile_cache

    configure_compile_cache()
    _, (fleet_params, params, model_params) = get_params(
        (get_fleet_parser, get_serve_parser, get_model_parser)
    )
    get_logger(logger_name="fleet")

    raise SystemExit(main(fleet_params, params, model_params))


if __name__ == "__main__":
    cli()
