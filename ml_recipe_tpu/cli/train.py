"""Distributed training entry point.

Parity target: reference ``modules/train.py`` — config parsing + round-trip
serialization (train.py:151-165), topology setup, worker bootstrap with NCCL
rendezvous (train.py:18-59), Trainer construction with after-epoch hooks
``save_last``/``save_each``/``test_fun`` (train.py:104-116), KeyboardInterrupt
-> ``interrupt.ch`` (train.py:117-119).

TPU redesign: ONE process per host (no ``mp.spawn`` fan-out — SPMD covers all
local devices through the mesh), ``jax.distributed.initialize`` replaces the
TCP process group, and the mesh spec replaces world-size arithmetic
(train.py:133-136). Run under the same env contract the platform launcher
exports (MASTER_IP/MASTER_PORT/LOCAL_RANK/WORLD_SIZE → flags, worker.sh:6).

Usage::

    python -m ml_recipe_tpu.cli.train -c config/test_bert.cfg [--flag value ...]
"""

from __future__ import annotations

import functools
import logging
import os
import signal
import sys
import threading
from datetime import datetime

from ..compose import init_collate_fun, init_datasets, init_loss, init_model
from ..config.parser import (
    get_model_parser,
    get_params,
    get_trainer_parser,
    write_config_file,
)
from ..data import RawPreprocessor
from ..data.bucketing import parse_length_buckets
from ..parallel import ParallelPlan, barrier, initialize_from_params, is_primary
from ..train import AccuracyCallback, MAPCallback, SaveBestCallback, Trainer
from ..utils.logging import get_logger, show_params
from ..utils.seed import set_seed

logger = logging.getLogger(__name__)


def _arm_watchdog(params):
    """Install the process-global step watchdog from ``--watchdog_timeout``
    (or CLEAR it when unset — a stale instance from a previous in-process
    run must not keep governing barrier call sites). Must run BEFORE the
    distributed rendezvous: a rendezvous that never completes is the
    canonical startup hang the watchdog exists to catch."""
    from ..resilience import watchdog as watchdog_mod

    timeout = getattr(params, "watchdog_timeout", None)
    return watchdog_mod.install(
        watchdog_mod.Watchdog(timeout) if timeout else None
    )


def run_worker(params, model_params) -> None:
    """One SPMD host process (reference run_worker, train.py:18-122)."""
    from ..resilience import watchdog as watchdog_mod

    # Step watchdog: armed around every train/eval step and checkpoint
    # barrier; a missed deadline dumps stacks and aborts with a distinct
    # exit code so a supervisor restarts instead of the pod wedging.
    # main() normally armed it before the rendezvous; arm here only for
    # direct run_worker callers (embedding launchers) — and tear it down
    # symmetrically, so a second config in the same process neither
    # inherits a stale instance nor leaks monitor threads.
    watchdog = watchdog_mod.current()
    locally_armed = False
    if watchdog is None and getattr(params, "watchdog_timeout", None):
        watchdog = _arm_watchdog(params)
        locally_armed = True
    try:
        _run_worker(params, model_params, watchdog)
    finally:
        if locally_armed:
            watchdog.stop()
            watchdog_mod.install(None)


def _run_worker(params, model_params, watchdog) -> None:
    import jax

    log_file = params.log_file if is_primary() else None
    log_level = logging.INFO if is_primary() else logging.WARN
    local_logger = get_logger(
        level=log_level, filename=str(log_file) if log_file else None,
        filemode="a", logger_name="train", debug=params.debug,
    )

    # Geometry autotuner wiring: --autotune / --autotune_cache drive the
    # process-wide selector the attention kernels consult (ops/autotune.py).
    from ..ops import aot, autotune

    autotune.configure(
        enabled=getattr(params, "autotune", True),
        cache_dir=getattr(params, "autotune_cache", None),
    )
    # AOT program-store wiring: --aot_cache is 'off' | a directory | None
    # (default directory). A warm restart deserializes its train-step
    # programs from the store instead of recompiling them (ops/aot.py).
    _aot_cache = getattr(params, "aot_cache", None)
    aot.configure(
        enabled=_aot_cache != "off",
        cache_dir=_aot_cache if _aot_cache not in (None, "off") else None,
        cache_bytes=getattr(params, "aot_cache_bytes", 0) or None,
    )

    # the declarative parallelism plan: built ONCE from --mesh; the
    # trainer (and through it the ZeRO-1 planner, HBM pre-flight and
    # checkpoint manifests) derives every sharding from it. With
    # --elastic on the requested mesh may no longer fit the live device
    # set (a restart after host loss): the data axis shrinks, structural
    # axes refuse (parallel/mesh.elastic_axes).
    if getattr(params, "elastic", "off") != "off":
        plan = ParallelPlan.elastic_from_spec(params.mesh)
        if plan.shrunk:
            local_logger.warning(
                f"ELASTIC RESUME: mesh re-derived for the live device set: "
                f"requested {plan.requested_axes} -> running {plan.describe()}."
            )
    else:
        plan = ParallelPlan.from_spec(params.mesh)
    mesh = plan.mesh
    local_logger.warning(
        f"Process {jax.process_index()}/{jax.process_count()}. "
        f"Mesh: {plan.describe()} "
        f"({plan.unused_devices} visible device(s) unused). "
        f"Global batch {params.train_batch_size} spans the whole data axis — "
        f"scale the learning rate for the GLOBAL batch, not per-device."
    )

    rng_pool = set_seed(params.seed)
    data_rng = rng_pool.host_rng("chunk_sampling") if rng_pool else None

    # Observability plane (all off by default): --trace_spans installs the
    # process-global span tracer (trainer + checkpoint call sites emit
    # through it), --metrics_port builds the training telemetry registry
    # whose exporter starts once the Trainer exists (its health document
    # reads live trainer state). Tracer install and the teardown of both
    # bracket EVERYTHING below — a startup failure (model init, dataset
    # build, a corrupt --last restore) must uninstall the process-global
    # tracer and close the exporter port, not leak the instrumented path
    # into later in-process runs.
    tracer = None
    if getattr(params, "trace_spans", None):
        from ..metrics import trace as trace_mod

        tracer = trace_mod.install(trace_mod.TraceWriter(
            os.path.join(
                str(params.trace_spans),
                f"train_trace_p{jax.process_index()}.json",
            ),
            process_name="train",
        ))

    state = {"exporter": None}
    try:
        _run_instrumented(
            params, model_params, watchdog, local_logger, plan, data_rng,
            state,
        )
    finally:
        if state["exporter"] is not None:
            state["exporter"].close()
        if tracer is not None:
            from ..metrics import trace as trace_mod

            trace_mod.install(None)
            tracer.close()  # flush the span file even on a non-clean exit


def _run_instrumented(params, model_params, watchdog, local_logger, plan,
                      data_rng, state) -> None:
    import jax

    from ..ops import aot

    mesh = plan.mesh
    exp_dir = params.dump_dir / params.experiment_name

    if (
        getattr(params, "elastic", "off") != "off"
        and os.environ.get("MLRT_SUPERVISED")
        and watchdog is not None
    ):
        # elastic child heartbeat: piggyback on the step watchdog's beat so
        # the cross-host coordination file carries this child's last
        # completed step at training cadence (peer supervisors read it as
        # the straggler/liveness signal) — no second timer thread
        from ..resilience.coordination import COORD_DIRNAME, write_child_heartbeat
        from ..resilience.faults import current_host

        _coord_dir = os.path.join(str(exp_dir), COORD_DIRNAME)
        _host = current_host()
        watchdog.add_on_beat(
            lambda step: write_child_heartbeat(_coord_dir, _host, step=step)
        )
    telemetry = None
    goodput = None
    flightrec = None
    if getattr(params, "goodput_ledger", False):
        from ..metrics.goodput import GOODPUT_FILENAME, GoodputLedger

        # lives next to supervisor_state.json; construction reads prior
        # attempts' events, so a resumed run reports whole-run goodput.
        # Only process 0 writes the shared file: every host feeds the same
        # global steps, so N file-backed writers would multiply productive
        # time by N in the run summary — peers keep an in-memory ledger
        # (their local /metrics gauges stay honest) and process 0's file
        # is the run-level record
        goodput = GoodputLedger(
            os.path.join(str(exp_dir), GOODPUT_FILENAME)
            if jax.process_index() == 0 else None,
            process_index=jax.process_index(),
        )
    if getattr(params, "flight_recorder", False):
        from ..metrics.flightrec import FlightRecorder

        flightrec = FlightRecorder.open_in(
            str(exp_dir), process_index=jax.process_index(),
            capacity=getattr(params, "flightrec_events", 256),
        )
        if plan.shrunk:
            # the crash-loop diagnosis timeline must explain a topology
            # change: this attempt runs NARROWER than the operator asked
            flightrec.record(
                "mesh_shrunk", old=plan.requested_axes, new=plan.describe(),
            )
        if watchdog is not None:
            # a hang abort dumps the last-K-step timeline before the
            # watchdog's os._exit(87)
            watchdog.add_on_timeout(
                lambda label: flightrec.dump("watchdog", label=label)
            )
    if (
        getattr(params, "metrics_port", None) is not None
        or goodput is not None
        or flightrec is not None
    ):
        from ..resilience.supervisor import STATE_FILENAME
        from ..train.telemetry import TrainTelemetry

        # the telemetry plane is also how the ledger/recorder get their
        # per-step feeds, so either flag builds it; the HTTP exporter
        # itself still starts only with --metrics_port
        telemetry = TrainTelemetry(
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            anomaly_factor=getattr(params, "anomaly_factor", 3.0),
            anomaly_window=getattr(params, "anomaly_window", 64),
            watchdog=watchdog,
            # the supervisor (parent process) keeps this sidecar current;
            # reading it cross-process is what puts restart counts on the
            # child's /metrics without any coordination channel
            supervisor_state_path=os.path.join(str(exp_dir), STATE_FILENAME),
            goodput=goodput,
            flightrec=flightrec,
        )

    model, model_state, tokenizer = init_model(
        model_params, bpe_dropout=params.bpe_dropout,
        rng_seed=params.seed if params.seed is not None else 0,
        mesh=mesh,
    )

    # Rank 0 prepares the (shared-dir) dataset; everyone else waits, then
    # loads the cached artifacts (train.py:49-59).
    if is_primary():
        train_dataset, test_dataset, train_weights = init_datasets(
            params, tokenizer=tokenizer, clear=params.clear_processed, rng=data_rng
        )
    barrier("dataset_prep")
    if not is_primary():
        train_dataset, test_dataset, train_weights = init_datasets(
            params, tokenizer=tokenizer, clear=False, rng=data_rng
        )

    loss = init_loss(params, train_weights)

    trainer = Trainer(
        model=model,
        params=model_state,
        loss=loss,
        collate_fun=init_collate_fun(tokenizer, max_seq_len=params.max_seq_len),
        trainer_params=params,
        train_dataset=train_dataset,
        test_dataset=test_dataset,
        writer_dir=params.dump_dir / f"board/{params.experiment_name}",
        mesh=mesh,
        n_epochs=params.n_epochs,
        train_batch_size=params.train_batch_size,
        test_batch_size=params.test_batch_size,
        batch_split=params.batch_split,
        n_jobs=params.n_jobs,
        warmup_coef=params.warmup_coef,
        max_grad_norm=params.max_grad_norm,
        train_weights=train_weights,
        drop_optimizer=params.drop_optimizer,
        debug=params.debug,
        seed=params.seed if params.seed is not None else 0,
        optimizer_sharding=getattr(params, "optimizer_sharding", None),
        shard_optimizer=getattr(params, "shard_optimizer", False),
        pipe_schedule=getattr(params, "pipe_schedule", "gpipe"),
        pipe_param_sharding=getattr(params, "pipe_param_sharding", "auto"),
        async_checkpoint=getattr(params, "async_checkpoint", False),
        sharded_checkpoint=getattr(params, "sharded_checkpoint", False),
        trace_dir=(
            params.dump_dir / f"board/{params.experiment_name}/trace"
            if getattr(params, "trace", False) else None
        ),
        watchdog=watchdog,
        hbm_preflight=getattr(params, "hbm_preflight", True),
        length_buckets=parse_length_buckets(
            getattr(params, "length_buckets", None), params.max_seq_len
        ),
        sequence_packing=getattr(params, "sequence_packing", False),
        pack_max_segments=getattr(params, "pack_max_segments", 8),
        pack_splitting=getattr(params, "pack_splitting", "off"),
        pack_min_fragment=getattr(params, "pack_min_fragment", 32),
        device_prefetch=getattr(params, "device_prefetch", 0),
        log_every=getattr(params, "log_every", 10),
        telemetry=telemetry,
    )

    if params.last is not None:
        trainer.load_state_dict(params.last)

    if goodput is not None:
        # the FIRST step id this attempt will execute: the summarizer
        # reclassifies previously ledgered work on steps >= it as the
        # recompute badput a resume pays
        goodput.note_run_start(trainer.global_step)
    if flightrec is not None:
        flightrec.record("run_start", step=trainer.global_step)

    if telemetry is not None and getattr(params, "metrics_port", None) is not None:
        from ..metrics.exporter import MetricsExporter

        # multi-host: each process exports its own plane one port up from
        # the base (port 0 = ephemeral stays ephemeral everywhere)
        base_port = int(params.metrics_port)
        port = base_port + jax.process_index() if base_port else 0

        def health():
            # one liveness+productivity probe: goodput ratio and flight-
            # recorder last-event age ride the same document the serving
            # fleet's router and the supervisor poll
            return telemetry.health_document(
                global_step=trainer.global_step,
                process_index=jax.process_index(),
            )

        # the caller's finally closes it, whatever unwinds from here on
        state["exporter"] = MetricsExporter(
            telemetry.registry, port=port, health_fn=health,
        ).start()
        state["exporter"].add_pre_render(telemetry.refresh)

        hosts = getattr(params, "metrics_hosts", None)
        if hosts and jax.process_index() == 0:
            from ..metrics.aggregator import PodAggregator

            # process 0 fans in every host's exporter into one merged
            # pod page (sum/min/max, per-host views, straggler gauges)
            aggregator = PodAggregator(str(hosts).split(","))
            state["exporter"].add_route("/metrics/pod", aggregator.render)
            local_logger.info(
                f"Pod-scope aggregation over {len(aggregator.targets)} "
                f"host exporter(s) at /metrics/pod."
            )

    def save_last(*args, **kwargs):
        trainer.save_state_dict(params.dump_dir / params.experiment_name / "last.ch")

    def save_each(epoch_i):
        trainer.save_state_dict(
            params.dump_dir / params.experiment_name / f"epoch_{epoch_i}.ch"
        )

    test_fun = functools.partial(
        trainer.test,
        callbacks=[
            MAPCallback(list(RawPreprocessor.labels2id.keys())),
            AccuracyCallback(),
            SaveBestCallback(params),
        ],
    )

    # TPU preemptions/evictions deliver SIGTERM (not SIGINT): route it into
    # the same interrupt-checkpoint path as Ctrl-C (reference train.py:117-119
    # only covered KeyboardInterrupt). Installed here — after Trainer
    # construction — so a SIGTERM during compile/init still aborts cleanly.
    def _sigterm_to_interrupt(signum, frame):
        raise KeyboardInterrupt(f"signal {signum}")

    # signal.signal raises ValueError off the main thread — an embedding
    # launcher running run_worker from a worker thread should train without
    # the SIGTERM hook, not crash before the first step.
    on_main_thread = threading.current_thread() is threading.main_thread()
    if on_main_thread:
        prev_handler = signal.signal(signal.SIGTERM, _sigterm_to_interrupt)
    else:
        local_logger.info(
            "Not on the main thread; SIGTERM-to-checkpoint handler not installed."
        )
    try:
        trainer.train(after_epoch_funcs=[save_last, save_each, test_fun])
    except KeyboardInterrupt:
        # disarm first: a second SIGTERM during the (multi-second) save must
        # not re-raise inside save_state_dict and abort the very checkpoint
        # this path exists to produce
        if on_main_thread:
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
        local_logger.error("Training process was interrupted.")
        if flightrec is not None:
            # before the (fallible) interrupt save: the timeline into the
            # preemption must survive even a failed emergency checkpoint
            flightrec.dump("sigterm", step=trainer.global_step)
        if goodput is not None:
            # same ordering: the open step window's accounting must land
            # durably even if the emergency save below fails
            goodput.flush()
        # drain any STALE background-persist failure non-strictly first: a
        # failed earlier save (already logged) must not abort the very
        # emergency checkpoint this path exists to produce
        trainer.finish_pending_checkpoint(raise_errors=False)
        trainer.save_state_dict(params.dump_dir / params.experiment_name / "interrupt.ch")
        # async checkpointing: the interrupt save must be DURABLE before
        # this process exits and the supervisor resumes from it — a resume
        # that races the background persist would restart from stale state
        trainer.finish_pending_checkpoint()
        if goodput is not None:
            _store = aot.get()
            goodput.note_aot(
                _store.hits, _store.misses, sum(_store.load_times_s))
            goodput.note_run_end(trainer.global_step)
            local_logger.warning(goodput.summary_message())
        # under a supervisor, a caught preemption is a reason to RESUME:
        # exit with the tempfail code the supervisor classifies as
        # 'preempted' (a bare return here would read as a clean finish)
        if os.environ.get("MLRT_SUPERVISED"):
            from ..resilience.supervisor import PREEMPT_EXIT_CODE

            raise SystemExit(PREEMPT_EXIT_CODE)
    except Exception as e:
        local_logger.error(e)
        if flightrec is not None:
            flightrec.dump("exception", error=f"{type(e).__name__}: {e}")
        if goodput is not None:
            goodput.flush()  # keep the open step window's accounting
        # best-effort completion barrier: let an in-flight persist land (a
        # valid checkpoint to resume from beats a torn one) but never mask
        # the propagating error with a persist failure
        trainer.finish_pending_checkpoint(raise_errors=False)
        raise e
    else:
        # at-exit completion barrier: a clean run must not report success
        # while its final checkpoint is still (or failed) persisting
        trainer.finish_pending_checkpoint()
        if goodput is not None:
            # this attempt's program-store tally: a zero-compile warm
            # restart is visible in the ledger as an aot event with
            # misses == 0 next to a load-time-only compile_warmup share
            _store = aot.get()
            goodput.note_aot(
                _store.hits, _store.misses, sum(_store.load_times_s))
            goodput.note_run_end(trainer.global_step)
            local_logger.warning(goodput.summary_message())
        if flightrec is not None:
            flightrec.record("run_end", step=trainer.global_step)
            flightrec.dump("clean")
    finally:
        if on_main_thread:
            signal.signal(signal.SIGTERM, prev_handler)


def main(params, model_params) -> None:
    show_params(model_params, "model")
    show_params(params, "trainer")

    # Arm the watchdog BEFORE joining the world: the rendezvous itself is
    # the first thing that can hang (one host missing) and its watch frame
    # only exists if the watchdog is already installed.
    watchdog = _arm_watchdog(params)

    try:
        # Join the multi-host world BEFORE any jax device use (train.py:27-28's
        # init_process_group, re-expressed as jax.distributed.initialize).
        initialize_from_params(params)

        run_worker(params, model_params)
    finally:
        # stop the monitor and clear the global slot so an embedding caller
        # running several configs in one process never inherits a stale one
        if watchdog is not None:
            watchdog.stop()
        from ..resilience import watchdog as watchdog_mod

        watchdog_mod.install(None)


def cli() -> None:
    from ..utils.platform import configure_compile_cache

    configure_compile_cache()
    (parser, model_parser), (params, model_params) = get_params(
        (get_trainer_parser, get_model_parser)
    )

    os.makedirs(params.dump_dir / params.experiment_name, exist_ok=True)

    # Fault drills: arm the configured plan in THIS process (children of the
    # supervisor re-arm from their own argv/config/env).
    if getattr(params, "fault_plan", None):
        from ..resilience import faults

        faults.install_plan(params.fault_plan)

    # --supervise: this process becomes the supervisor; each attempt is a
    # child running the same CLI minus the flag (MLRT_SUPERVISED breaks the
    # recursion even when `supervise` comes from a config file) with --last
    # re-pointed at the newest valid checkpoint.
    if getattr(params, "supervise", False) and not os.environ.get("MLRT_SUPERVISED"):
        from ..resilience.supervisor import supervise_cli

        raise SystemExit(supervise_cli(params, sys.argv[1:]))

    params.log_file = (
        params.dump_dir / params.experiment_name
        / f'{datetime.now().strftime("%d-%m-%Y_%H-%M-%S")}.log'
        if params.local_rank in [-1, 0]
        else None
    )

    params.n_jobs = max(1, min(params.n_jobs, (os.cpu_count() or 2) // 2))

    get_logger(
        filename=str(params.log_file) if params.log_file else None,
        filemode="w", logger_name="train", debug=params.debug,
    )

    if params.local_rank in [0, -1]:
        write_config_file(parser, params, params.dump_dir / params.experiment_name / "trainer.cfg")
        write_config_file(
            model_parser, model_params, params.dump_dir / params.experiment_name / "model.cfg"
        )

    main(params, model_params)


if __name__ == "__main__":
    cli()
