"""SPMD training runtime.

Parity target: reference ``modules/model/trainer/trainer.py:48-403`` — the
``Trainer`` dataclass: dataloader construction with distributed/weighted
sampling, linear-warmup schedule, mixed precision, gradient accumulation via
``batch_split``, grad clipping, TensorBoard writes, rank-0 test loop with
callbacks, checkpoint save/load with ``drop_optimizer``, debug mode.

TPU-first redesign (SURVEY.md §7):
- One process per host, ONE jitted train step containing
  forward + loss + grad + clip + optimizer update. Data parallelism is not a
  wrapper (DDP, trainer.py:136-142) but a sharding: the batch is laid out
  over the mesh ``data`` axis, params are replicated (or sharded by TP
  rules), and the loss is written over the *global* batch, so the summed
  gradient matches DDP's average semantics exactly (SURVEY.md §7 hard part
  (e)).
- Gradient accumulation is a ``lax.scan`` over ``batch_split`` micro-batches
  *inside* the compiled step (reference steps the optimizer every Nth
  dataloader batch, trainer.py:284-287) — no host round-trips between
  micro-batches.
- The step program itself is built by ``train/step.py`` from a frozen
  ``StepSpec`` this class fills in (``_build_train_step``): the layout of
  the accumulated gradient (``GradCarry``), and where the gradients cross
  the mesh. Under plain GSPMD the scan's carry is replicated, so XLA must
  finish every micro-batch's weight gradients with an all-reduce before the
  carry may add them: DDP without ``no_sync()``, ``batch_split`` exchanges a
  step (measured on four v5e chips: 84 of 777 ms, all of it exposed;
  PERF.md). On a mesh whose only axis wider than 1 is ``data``, with
  ``batch_split > 1``, the scan therefore runs as a DATA ISLAND
  (``step.island_loop``) and ONE f32 reduction follows the loop. The
  trainer logs which body it built (``gradient exchange: once a step`` /
  ``every micro-batch``) and reports it as ``train_grad_exchanges_per_step``.
- Mixed precision is the model's bf16 compute dtype (native, no loss scaling
  needed on TPU) — replaces the apex AMP plumbing (trainer.py:128-133).
- Eval runs SPMD on all hosts (devices stay busy; reference parks every rank
  but 0 on a barrier, trainer.py:302-319); predictions are gathered to host
  once per step for the metric callbacks, which then agree bit-for-bit on
  every host.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import os
import threading
import time
import weakref
from collections import defaultdict, deque
from contextlib import nullcontext
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..data.bucketing import BucketedBatch, BucketedDataLoader, synthetic_qa_batch
from ..data.device_prefetch import DevicePrefetcher
from ..data.loader import DataLoader, ShardedBatchSampler
from ..data.packing import (
    DEFAULT_MAX_SEGMENTS,
    DEFAULT_MIN_FRAGMENT,
    PackedBatch,
    PackedDataLoader,
    parse_pack_splitting,
    parse_sequence_packing,
)
from ..losses import PackedWeightedLoss
from ..metrics import AverageMeter
from ..metrics import trace as trace_mod
from ..metrics.anomaly import SlowStepDetector
from ..ops import aot, traced as kernels_traced
from ..metrics.trace import XplaneWindow
from ..resilience.faults import fire as _fault
from ..parallel import build_mesh, gather_to_host, make_global_array, shard_params
from ..parallel.plan import ParallelPlan
from ..parallel.sharding import (
    is_single_device,
    opt_state_bytes_per_chip,
    split_micro,
    zero_pad_tree,
)
from ..utils.hbm import device_hbm_bytes, preflight_bytes
from ..utils.pipeline import LaggedConsumer
from ..utils.profiler import time_profiler
from . import loss_scale as ls_lib
from . import step as step_lib
from .callback import TestCallback
from .checkpoint import load_state_dict as _load_ckpt
from .checkpoint import save_state_dict as _save_ckpt
from .optim import build_optimizer, trainable_mask
from .telemetry import EPOCH_HEAD_STEPS, covering_phase
from .writer import init_writer

logger = logging.getLogger(__name__)

try:  # pragma: no cover - cosmetic only
    from tqdm.auto import tqdm
except Exception:  # noqa: BLE001
    tqdm = None


# --device_prefetch auto: steps timed synchronously at the start of epoch 1
# before the depth decision (the first is discarded when more than one was
# captured — it may carry compile time).
_PREFETCH_AUTO_PROBE_STEPS = 3


def resolve_prefetch_auto(place_s, step_s, *, threshold: float = 0.05) -> int:
    """Depth heuristic for ``--device_prefetch auto``: depth 2 (double
    buffering) when the host-side placement (micro-split + H2D copy) costs
    at least ``threshold`` of the measured step wall — that is the overlap
    double buffering actually buys — else depth 1 (still off the step path,
    no second in-flight batch pinning HBM). Lists may be ragged/empty
    (short epochs): defaults to 1."""
    if not place_s or not step_s:
        return 1
    if len(place_s) > 1:
        place_s, step_s = place_s[1:], step_s[1:]
    place = sum(place_s) / len(place_s)
    step = sum(step_s) / len(step_s)
    return 2 if place >= threshold * max(step, 1e-9) else 1


# The HBM byte arithmetic (device_hbm_bytes / preflight_bytes) lives in
# utils/hbm.py, shared with serve/engine.py's predict-step pre-flight — one
# definition of "projected per-device bytes" for train and predict steps.
# Private aliases keep this module's historical names importable.
_device_hbm_bytes = device_hbm_bytes
_preflight_bytes = preflight_bytes


def reconcile_state_shapes(restored, live):
    """Reshard a restored (host) optimizer-state tree onto the LIVE leaf
    shapes: ``zero1`` stores each sharded leaf zero-padded to its mesh
    data-axis multiple, so a checkpoint taken at mesh N restores at mesh M
    (M != N) — or under ``--optimizer_sharding off``, or vice versa — by
    corner-cropping every leaf to the shape overlap and zero-filling the
    live padding. The pad region is zeros by construction (padded gradients
    are zero there, so Adam moments never leave zero) and never feeds a
    real element's update, which is what makes this crop/fill exact rather
    than approximate."""

    def fix(saved, live_leaf):
        target = tuple(np.shape(live_leaf))
        arr = np.asarray(saved)
        if tuple(arr.shape) == target:
            return saved
        if arr.ndim != len(target):
            raise ValueError(
                f"optimizer-state leaf rank changed across restore: saved "
                f"{arr.shape} vs live {target} — this is a layout mismatch "
                f"(different optimizer chain?), not ZeRO padding"
            )
        arr = arr[tuple(slice(0, min(s, t)) for s, t in zip(arr.shape, target))]
        widths = [(0, t - s) for s, t in zip(arr.shape, target)]
        if any(w for _, w in widths):
            arr = np.pad(arr, widths)
        return arr

    return jax.tree_util.tree_map(fix, restored, live)


def _console_str(meters: dict) -> str:
    return ", ".join(
        f"{k}: {v() if isinstance(v, AverageMeter) else v:.3e}" for k, v in meters.items()
    )


@dataclasses.dataclass
class Trainer:
    model: Any                      # flax Module (QAModel)
    params: Any                     # initial parameter pytree
    loss: Any                       # WeightedLoss
    collate_fun: Any

    trainer_params: Any = None      # namespace driving optimizer/finetune knobs

    train_dataset: Any = None
    test_dataset: Any = None

    writer_dir: Any = None

    mesh: Optional[Mesh] = None

    n_epochs: int = 0

    train_batch_size: int = 32      # GLOBAL optimizer-step batch (documented delta:
                                    # the reference's is per-process, train.py:42-44)
    test_batch_size: int = 32

    batch_split: int = 1
    n_jobs: int = 4

    warmup_coef: float = 0.01
    max_grad_norm: float = 1.0

    train_weights: Any = None       # {'label_weights','sampler_weights'} (init.py:169-201)

    drop_optimizer: bool = False
    debug: bool = False
    seed: int = 0

    # xplane trace of a few steady-state steps (SURVEY.md §5 tracing):
    # directory to dump to, or None to disable. Steps 2-4 of epoch 1 are
    # captured (past compilation, one full accumulation cycle each).
    trace_dir: Any = None

    # PRNG implementation for in-step dropout keys. 'rbg' (XLA
    # RngBitGenerator, hardware-accelerated on TPU) measured 15% faster
    # train steps than 'threefry2x32' on v5e — bert-base seq 512 generates
    # ~300M dropout bits per micro-step and threefry burns VPU cycles on
    # them. Same PRNG-key API; streams differ across impls/backends, which
    # dropout does not care about.
    prng_impl: str = "rbg"

    # ZeRO-1: shard optimizer moments over the mesh data axis (memory 1/N;
    # the reference keeps a full replica per process, SURVEY.md §2.3). XLA
    # all-gathers the sharded param updates — the ZeRO-1 pattern.
    # `optimizer_sharding` is the public mode ('off'|'zero1', the
    # --optimizer_sharding flag); None defers to the legacy
    # `shard_optimizer` boolean so existing callers keep working.
    optimizer_sharding: Any = None
    shard_optimizer: bool = False
    zero_min_size: int = 16384      # leaves smaller than this stay replicated

    # Pipeline schedule + stage-local state (--pipe_schedule, pipe axis >1
    # only): 'gpipe' runs the PR-15 all-m-resident schedule, '1f1b' the
    # one-forward-one-backward tick program that caps resident activations
    # at the in-flight window (parallel/pipeline.py). With
    # `pipe_param_sharding` (default on when the pipe axis is >1 on a
    # multi-device mesh) each rank STORES only its stage's slice of the
    # trunk params and optimizer state (~1/K per-chip bytes); the islands
    # all-gather the slices explicitly per tick.
    pipe_schedule: str = "gpipe"
    pipe_param_sharding: Any = None

    # Async overlapped checkpointing (--async_checkpoint): saves block
    # only for the device->host snapshot; the serialize+write persist
    # runs on a background thread (resilience/checkpoint_async.py) with
    # the same crc32 + atomic-rename discipline as a sync save, a
    # completion barrier before the next save/restore/exit, and the
    # previous valid checkpoint staying newest if a crash lands
    # mid-persist. Off (default) is the historical blocking save.
    async_checkpoint: bool = False

    # Sharded checkpoint writes: each process saves only the array shards it
    # owns (directory layout) instead of gathering the full state to every
    # host for one single-file write — the save path that scales to
    # genuinely sharded pod states (SURVEY §7 hard part (c)). Restores
    # auto-detect either layout.
    sharded_checkpoint: bool = False

    # Optional metrics tap: called as ``on_train_metrics(meters, step=N)``
    # after every consumed train step with the epoch's running AverageMeters
    # (the supported way to capture a loss curve — bench --mode converge and
    # the convergence test use it; the TB writer is unaffected).
    on_train_metrics: Any = None

    # Optional resilience.Watchdog: armed around every train/eval step and
    # checkpoint save so a hung collective / stuck host aborts the process
    # (with stacks dumped) for the supervisor to restart, instead of
    # wedging. None = zero overhead.
    watchdog: Any = None

    # Optional train.telemetry.TrainTelemetry (--metrics_port): per-step
    # wall-time breakdown (data wait / host / device), tokens/sec, padding
    # waste, checkpoint durations, and the slow-step anomaly detector, all
    # exported at /metrics. None (the default) = zero instrumentation, the
    # step loop is untouched. When attached, the step loop blocks on each
    # step's results before dispatching the next (the StepTimer
    # block-until-ready discipline — async dispatch cannot fake device
    # time), trading the one-step metric lag for honest attribution.
    telemetry: Any = None

    # Length-bucketed token-budget batching (data/bucketing.py): a sorted
    # seq grid (e.g. [128, 256, 384, 512]) or None for pad-to-max batching
    # (exactly the historical behavior). Batches are padded to their BUCKET
    # instead of the global max and the per-bucket batch size scales
    # inversely with seq to hold train_batch_size * max(grid) tokens per
    # step; jit compiles one program per occupied bucket (zero probes on a
    # warm autotune cache). Single-process only — multi-host runs fall back
    # with a warning (bucket composition is length-dependent and step
    # shapes would diverge across hosts).
    length_buckets: Any = None

    # Sequence packing (data/packing.py): concatenate short chunks into one
    # fixed (train_batch_size, max_seq_len) row layout with block-diagonal
    # attention — ~every token real, ONE compiled train program. Off (the
    # default) reproduces the bucketed/padded path bit-exactly (pinned in
    # tests/test_dp_equivalence.py). Supersedes length_buckets when both
    # are on (packing subsumes the bucketed win); single-process only, like
    # bucketing — multi-host runs fall back with a warning.
    sequence_packing: Any = False
    # Per-row segment cap: the static S of the [rows, S] label planes and
    # per-segment head outputs.
    pack_max_segments: int = DEFAULT_MAX_SEGMENTS
    # Hole-filling chunk splitting (--pack_splitting off|fill): a chunk
    # that fits no open pack row is split at a label-safe token boundary
    # and its head fragment fills the largest residual hole — the only
    # path below the ~1.6% waste floor quantized chunk mixes impose on any
    # non-splitting packer. 'off' (default) is the pre-splitting packer
    # bit-exactly (pinned in tests/test_dp_equivalence.py). Fragments are
    # ordinary segments; only the gold-span-bearing one carries labels
    # (siblings get ignore-index via segment_mask 0), so examples are
    # never double-counted by the packed loss or row-weighted metrics.
    pack_splitting: Any = "off"
    # No fragment goes below this many tokens (head or tail).
    pack_min_fragment: int = DEFAULT_MIN_FRAGMENT

    # Double-buffered device prefetch (data/device_prefetch.py): keep this
    # many placed global batches in flight on a background thread so the
    # host->device copy of step k+1 overlaps the compute of step k.
    # 0 = synchronous placement (exactly the historical behavior). The
    # trajectory is bit-identical either way (pinned in
    # tests/test_device_prefetch.py). 'auto' times the first few steps of
    # epoch 1 (synchronously) and picks depth 1 vs 2 from the share of the
    # step the host-side placement costs, logging the choice.
    device_prefetch: Any = 0

    # Throttle per-step host overhead: tqdm postfix + TensorBoard writes
    # happen every `log_every` consumed steps (and once more at epoch end)
    # instead of every step. Meters and the on_train_metrics tap still
    # update every step — only the DISPLAY/IO cadence changes.
    log_every: int = 10

    # HBM pre-flight planner: before the first train step executes, lower
    # and compile the jitted step once, read ``compiled.memory_analysis()``,
    # and if the projected HBM requirement exceeds the device limit, raise
    # ``batch_split`` (doubling, honoring the mesh data-axis divisibility)
    # and re-plan — instead of dying in XLA allocation. This is what
    # restores bert-large at its BASELINE-recorded batch-256 settings: the
    # plan runs at split 8 instead of OOMing at split 4, and the decision is
    # logged with before/after byte counts. No-op where the device reports
    # no memory limit (CPU) or the analysis is unavailable.
    hbm_preflight: bool = True

    def __post_init__(self):
        # the step clock's intervals "since the trainer was built" start here
        self._built_at = time.perf_counter()
        with trace_mod.span("trainer_init", cat="setup"):
            self._post_init()

    def _post_init(self):
        if self.mesh is None:
            self.mesh = build_mesh()

        # The declarative parallelism plan (parallel/plan.py): every
        # layout below — batch placement, param/opt-state shardings, the
        # ZeRO-1 leaf plan, the pipeline stage layout, the manifest/
        # pre-flight topology records — derives from this ONE object.
        self.plan = ParallelPlan.from_mesh(self.mesh)
        self.pipe_stages = self.plan.pipe_size
        self.pipe_schedule = str(self.pipe_schedule or "gpipe").lower()
        pps = self.pipe_param_sharding
        if isinstance(pps, str):
            pps = {"stage": True, "on": True, "replicated": False,
                   "off": False, "auto": None}.get(pps.lower(), pps)
            if isinstance(pps, str):
                raise ValueError(
                    f"--pipe_param_sharding must be one of "
                    f"auto|stage|replicated, got {self.pipe_param_sharding!r}"
                )
        if pps is None:
            # stage-local storage is the default whenever it can shard:
            # a pipe axis on a one-device mesh has nothing to split
            pps = self.pipe_stages > 1 and not self.plan.single_device
        self.pipe_param_sharding = bool(pps)
        if self.pipe_stages > 1:
            from ..parallel.pipeline import (
                modeled_bubble_fraction, validate_pipeline_plan,
            )

            validate_pipeline_plan(
                self.plan, self.model, batch_split=self.batch_split,
                schedule=self.pipe_schedule,
            )
            logger.info(
                "Pipeline parallelism: %d stages x %d layers over the "
                "pipe axis, %s schedule over %d micro-batch(es) "
                "(modeled bubble %.1f%%, stage-local params %s).",
                self.pipe_stages,
                int(self.model.cfg.num_layers) // self.pipe_stages,
                self.pipe_schedule,
                self.batch_split,
                100.0 * modeled_bubble_fraction(
                    self.pipe_stages, self.batch_split, self.pipe_schedule
                ),
                "on" if self.pipe_param_sharding else "off",
            )
        elif self.pipe_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"--pipe_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipe_schedule!r}"
            )

        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.is_primary = self.process_index == 0

        # resolve the optimizer-state layout once; 'zero1' is what the
        # --optimizer_sharding flag threads down, the shard_optimizer bool
        # is the legacy spelling
        from .optim import parse_optimizer_sharding

        self.opt_sharding_mode = parse_optimizer_sharding(
            self.optimizer_sharding, shard_optimizer=self.shard_optimizer
        )

        # set when the step is built: how often a step's gradients cross
        # the mesh's data axis (0 = it has none wider than 1, 1 = after
        # the micro-batch loop, else batch_split: after every micro-batch)
        self.grad_exchanges_per_step = 0

        # async checkpointing: one single-flight background persist
        # executor for the Trainer's lifetime (its wait() is the
        # completion barrier before the next save / restore / exit)
        self._async_ckpt = None
        if self.async_checkpoint:
            from ..resilience.checkpoint_async import AsyncCheckpointer

            self._async_ckpt = AsyncCheckpointer()

        if self.debug:
            self.n_epochs = 2

        # -- data loaders (trainer.py:100-114,150-181) ------------------------
        self._packing = self._resolve_packing()
        self._seq_grid = None if self._packing else self._resolve_seq_grid()
        if self._packing:
            # packed batches carry per-segment labels + a segment_mask;
            # every head's mean must run over REAL segments only
            self.loss = PackedWeightedLoss(self.loss)
        data_size = int(
            self.mesh.shape.get("data", 1) if hasattr(self.mesh, "shape") else 1
        )
        self.train_dataloader = None
        if self.train_dataset is not None:
            sampler_weights = None
            if self.train_weights is not None:
                sampler_weights = self.train_weights.get("sampler_weights")
            if sampler_weights is not None:
                assert len(sampler_weights) == len(self.train_dataset)
                logger.info("Used train sampler: weighted-with-replacement.")
            else:
                logger.info("Used train sampler: shuffled.")
            self._train_sampler = ShardedBatchSampler(
                len(self.train_dataset),
                self.train_batch_size,
                process_index=self.process_index,
                process_count=self.process_count,
                shuffle=True,
                weights=sampler_weights,
                drop_last=True,
                seed=self.seed,
            )
            if self._packing:
                self.train_dataloader = PackedDataLoader(
                    self.train_dataset, self._train_sampler,
                    self._collate_tokenizer(),
                    max_seq_len=self._collate_max_seq_len(),
                    rows_per_batch=self.train_batch_size,
                    max_segments=self.pack_max_segments,
                    splitting=self.pack_splitting,
                    min_fragment=self.pack_min_fragment,
                    n_jobs=self.n_jobs,
                )
                logger.info(
                    "Sequence packing: %d rows x %d tokens per step, "
                    "max %d segments per row (one compiled program), "
                    "splitting %s.",
                    self.train_batch_size, self.train_dataloader.max_seq_len,
                    self.pack_max_segments, self.train_dataloader.splitting,
                )
            elif self._seq_grid is not None:
                self.train_dataloader = BucketedDataLoader(
                    self.train_dataset, self._train_sampler, self.collate_fun,
                    seq_grid=self._seq_grid,
                    token_budget=self.train_batch_size * self._seq_grid[-1],
                    batch_multiple=self.batch_split * max(data_size, 1),
                    n_jobs=self.n_jobs,
                )
                logger.info(
                    "Length-bucketed batching: grid %s, token budget %d, "
                    "per-bucket batches %s.",
                    self._seq_grid, self.train_dataloader.token_budget,
                    self.train_dataloader.batch_sizes,
                )
            else:
                self.train_dataloader = DataLoader(
                    self.train_dataset, self._train_sampler, self.collate_fun,
                    n_jobs=self.n_jobs,
                )
            logger.info(f"Train dataset len: {len(self.train_dataset)}. #JOBS: {self.n_jobs}.")

        self.test_dataloader = None
        if self.test_dataset is not None:
            self._test_sampler = ShardedBatchSampler(
                len(self.test_dataset),
                self.test_batch_size,
                process_index=self.process_index,
                process_count=self.process_count,
                shuffle=False,
                drop_last=False,
                pad_last=True,
                seed=self.seed,
            )
            if self._packing:
                self.test_dataloader = PackedDataLoader(
                    self.test_dataset, self._test_sampler,
                    self._collate_tokenizer(),
                    max_seq_len=self._collate_max_seq_len(),
                    rows_per_batch=self.test_batch_size,
                    max_segments=self.pack_max_segments,
                    splitting=self.pack_splitting,
                    min_fragment=self.pack_min_fragment,
                    n_jobs=self.n_jobs,
                    pad_last=True,
                )
            elif self._seq_grid is not None:
                self.test_dataloader = BucketedDataLoader(
                    self.test_dataset, self._test_sampler, self.collate_fun,
                    seq_grid=self._seq_grid,
                    token_budget=self.test_batch_size * self._seq_grid[-1],
                    batch_multiple=max(data_size, 1),
                    n_jobs=self.n_jobs,
                    pad_last=True,
                )
            else:
                self.test_dataloader = DataLoader(
                    self.test_dataset, self._test_sampler, self.collate_fun,
                    n_jobs=self.n_jobs,
                )
            logger.info(f"Test dataset len: {len(self.test_dataset)}. #JOBS: {self.n_jobs}.")

        # -- params onto the mesh --------------------------------------------
        # shard_params skips NamedSharding commitment on single-device meshes
        # (see parallel/sharding.is_single_device).
        # Under stage-local pipeline storage the trunk leaves land
        # pipe-sharded (parallel/pipeline.stage_param_specs) instead of
        # replicated — ~1/K per-chip param bytes.
        self._stage_param_specs = None
        if self.pipe_param_sharding and self.pipe_stages > 1 \
                and not is_single_device(self.mesh):
            # the plan's derivation (MLA009: stage-spec construction
            # stays inside parallel/)
            self._stage_param_specs = self.plan.stage_specs(self.params)
        self.params = shard_params(
            self.params, self.mesh, pspecs=self._stage_param_specs
        )
        self._param_shardings = (
            None
            if is_single_device(self.mesh)
            else jax.tree_util.tree_map(lambda x: x.sharding, self.params)
        )

        # -- optimizer + schedule (init.py:134-145, trainer.py:116-126) -------
        self.optimizer = None
        self.opt_state = None
        self.scheduler = None
        self._schedule_count = None
        self._planned_steps_per_epoch = None
        self._zero_shardings = None
        self._zero_plan = None
        self._zero_param_shardings = None
        self._opt_state_shardings = None
        self._use_loss_scale = False
        if self.train_dataloader is not None and self.trainer_params is not None:
            micro_batch = self.train_batch_size // self.batch_split
            data_size = int(
                self.mesh.shape.get("data", 1) if hasattr(self.mesh, "shape") else 1
            )
            if micro_batch % max(data_size, 1) != 0:
                raise ValueError(
                    f"Micro-batch {micro_batch} (train_batch_size "
                    f"{self.train_batch_size} / batch_split {self.batch_split}) "
                    f"must divide over the {data_size}-way mesh data axis; "
                    f"lower batch_split or raise train_batch_size."
                )

            # LR-schedule sizing: packed/bucketed epochs take a content-
            # dependent number of steps far below len(dataset)/batch (the
            # packer merges several items per row; bucket batches carry
            # more rows than the global batch). Sizing the schedule from
            # the pad-to-max upper bound silently stretches warmup and
            # never finishes the decay — so derive the estimate from the
            # loader's PLANNED step count (a cheap length-only simulation
            # of epoch 1's packing/bucketing, data/packing.py) instead.
            self._planned_steps_per_epoch = self._plan_schedule_steps()
            steps_per_epoch = (
                self._planned_steps_per_epoch
                if self._planned_steps_per_epoch is not None
                else len(self.train_dataloader)
            )
            num_training_steps = max(self.n_epochs * steps_per_epoch, 1)
            if self.warmup_coef > 0:
                logger.info(
                    f"Warmup schedule is used. #Training steps: {num_training_steps}. "
                    f"#Warmup steps: {int(num_training_steps * self.warmup_coef)}."
                )
            # clipping happens in the train step on the FLAT gradient vector
            # (one fused kernel; optax.clip_by_global_norm costs ~2 launches
            # per parameter tensor) — so the chain is built without it
            self.optimizer, self.scheduler, self._schedule_count = build_optimizer(
                self.trainer_params,
                self.params,
                num_training_steps=num_training_steps,
                max_grad_norm=None,
                warmup_coef=self.warmup_coef,
                optimizer_sharding=self.opt_sharding_mode,
            )
            if getattr(self.trainer_params, "sync_bn", False):
                # Reference converts BatchNorm -> SyncBN (trainer.py:89-95).
                # Under GSPMD there is nothing to convert: normalization
                # statistics computed over the global (data-sharded) batch
                # are cross-replica by construction — XLA inserts the
                # collective; LayerNorm (BERT) is per-token and needs none.
                logger.info(
                    "sync_bn: cross-replica statistics are inherent under "
                    "GSPMD (global-batch reductions); nothing to convert."
                )

            # apex-parity loss scaling (trainer.py:128-133,200-202): 'dynamic'
            # or a static scale; None (the TPU-native default) disables it —
            # bf16 shares fp32's exponent range and needs no scaling.
            raw_scale = getattr(self.trainer_params, "apex_loss_scale", None)
            if raw_scale not in (None, "None"):
                self._use_loss_scale = True
                self._ls_dynamic = raw_scale == "dynamic"
                if not self._ls_dynamic and float(raw_scale) <= 0:
                    raise ValueError(
                        f"apex_loss_scale must be positive or 'dynamic', got "
                        f"{raw_scale!r} (0 would zero every loss and NaN the "
                        f"unscaled grads)."
                    )
                self._ls_init_scale = (
                    2.0 ** 15 if self._ls_dynamic else float(raw_scale)
                )
                logger.info(
                    f"Loss scaling enabled: "
                    f"{'dynamic' if self._ls_dynamic else self._ls_init_scale}."
                )

            self.init_opt_state()

        self.global_step = 0
        self._prefetch_choice = None  # --device_prefetch auto's decision
        self.writer = init_writer(self.is_primary, self.writer_dir)

        self._jit_train_step = None
        self._jit_eval_step = None
        self._preflight_done = not self.hbm_preflight
        self.preflight_report = None
        # AOT program-store dispatch plane (ops/aot.py): placed-shape
        # signature -> compiled executable. Filled by the pre-flight /
        # first-step routing; run_step dispatches through it when the
        # store is enabled, so a warm restart performs ZERO XLA compiles.
        # Cleared whenever the jitted step is rebuilt (batch_split raise).
        self._compiled_steps: dict = {}
        # (inputs, labels) of the last placed batch a step ran on: the
        # shapes and shardings the scope map's lowering needs, should a
        # trace reader ever ask for it (_train_step_hlo_text)
        self._last_placed = None
        # first train-step store outcome ('hit'/'miss') — the goodput
        # ledger's compile_warmup window carries it as the aot_hit flag
        self._aot_first_outcome = None
        # the unblocked step clock's intervals (``_train``): one slower than
        # 3x the running median is logged with the phase that held it
        self._interval_detector = SlowStepDetector(factor=3.0, warmup=0)
        # ``setup:first_step``: where it began while it is in flight, and
        # whether this trainer has dispatched a step at all
        self._first_step_t0 = None
        self._first_step_seen = False

    def zero_enabled(self) -> bool:
        """True when the resolved layout is ``zero1`` AND the mesh has a
        multi-way data axis to shard over (a 1-chip 'zero1' run takes the
        replicated path bit-exactly — there is nothing to shard)."""
        return (
            self.opt_sharding_mode == "zero1"
            and not is_single_device(self.mesh)
            and int(self.mesh.shape.get("data", 1)) > 1
        )

    @property
    def effective_opt_sharding(self) -> str:
        """The layout the state ACTUALLY lives in — 'zero1' only when the
        mesh lets it shard; a requested-but-inert zero1 (1-chip mesh)
        reports 'off'. The one spelling every report/manifest/bench field
        uses."""
        return self.opt_sharding_mode if self.zero_enabled() else "off"

    def init_opt_state(self):
        """(Re)initialize ``opt_state`` from ``self.optimizer``, honoring
        ``optimizer_sharding`` (ZeRO-1). Also used by callers that build
        the optimizer themselves (bench, dry-run).

        Under ``zero1`` every state leaf is laid out by the padding-aware
        per-leaf plan (parallel/sharding.zero1_plan): the ``data`` axis
        lands on the largest divisible dim, or the leaf is zero-padded up
        to the next multiple when none divides — so the stored state is
        genuinely 1/N per chip, not "1/N where divisibility allowed".

        Placement is always EXPLICIT on multi-device meshes:
        ``optimizer.init`` reads only param shapes, so XLA prunes the param
        arguments and without ``out_shardings`` every leaf (scalars like
        ``count`` included) would land committed to the default device.
        """
        use_zero = self.zero_enabled()
        stage_pipe = bool(self._stage_param_specs is not None)
        if is_single_device(self.mesh):
            self._zero_shardings = None
            self._zero_plan = None
            self._zero_param_shardings = None
            self._opt_state_shardings = None
            self.opt_state = jax.jit(self.optimizer.init)(self.params)
            self._bundle_ls()
            return

        if use_zero:
            zplan = self.plan.zero1(
                self.params, min_size=self.zero_min_size,
                stage_pipe=stage_pipe,
            )
            self._zero_plan = zplan
            self._zero_param_shardings = self.plan.zero1_param_shardings(
                zplan
            )
            init_fn = lambda p: self.optimizer.init(zero_pad_tree(p, zplan))
        else:
            self._zero_plan = None
            self._zero_param_shardings = None
            init_fn = self.optimizer.init

        state_shapes = jax.eval_shape(init_fn, self.params)
        # the one derivation of the optimizer-state layout (ZeRO-1 over
        # the plan's data axis, stage-local over pipe, or replicated-
        # with-TP-rules) — shared with the layout-consistency tests and
        # checkpoint reconciliation
        shardings = self.plan.opt_state_shardings(
            state_shapes, zero1=use_zero, min_size=self.zero_min_size,
            stage_pipe=stage_pipe,
        )
        self._zero_shardings = shardings if use_zero else None
        self._opt_state_shardings = shardings
        self.opt_state = jax.jit(
            init_fn, out_shardings=shardings
        )(self.params)
        if use_zero:
            logger.info(
                "ZeRO-1: optimizer state sharded over the %d-way data axis "
                "(%.1f MB per chip).",
                int(self.mesh.shape.get("data", 1)),
                opt_state_bytes_per_chip(self.opt_state) / 1e6,
            )
        if stage_pipe:
            logger.info(
                "Stage-local state: trunk params + optimizer moments "
                "sharded over the %d-way pipe axis.", self.pipe_stages,
            )
        self._bundle_ls()

    def _prefetch_auto(self) -> bool:
        return str(self.device_prefetch).strip().lower() == "auto"

    def _prefetch_depth_static(self) -> int:
        """Resolved prefetch depth for loops that do not self-measure (the
        eval loop; train epochs after the auto decision): 0 = synchronous
        placement. 'auto' before any measurement conservatively runs at
        depth 1 (off the step path, no second in-flight batch)."""
        if self._prefetch_auto():
            return self._prefetch_choice if self._prefetch_choice else 1
        return int(self.device_prefetch) if self.device_prefetch else 0

    def _watched(self, label: str, *, scale: float = 1.0):
        """Watchdog frame around a unit of host-side work, yielding a
        per-step ``tick`` (re-entrant: checkpoint barriers arm their own
        frame on top). ``scale`` multiplies the configured timeout for
        units that are legitimately slower than a step. No-op context
        without a watchdog."""
        if self.watchdog is None:
            return nullcontext(lambda *_: None)
        timeout = self.watchdog.timeout * scale if scale != 1.0 else None
        return self.watchdog.watch(label, timeout)

    # -- batch placement ------------------------------------------------------

    def _global_batch(self, tree, *, leading_accum: bool = False):
        """Host numpy -> global jax.Array over the mesh data axis.

        ``leading_accum``: leaves are [G, B, ...] (micro-batch major) and the
        batch dim is axis 1; otherwise leaves are [B, ...] with batch axis 0.

        Ring attention additionally places the token dim over the ``seq``
        axis at ingest, so the embedding lookup and every activation up to
        the ring shard_map are born sequence-sharded — at 8k+ the
        replicated-activation alternative is the memory ceiling.
        """
        return make_global_array(
            tree, self.mesh, batch_axis=1 if leading_accum else 0,
            shard_seq=getattr(self.model, "attention_impl", None) == "ring",
        )

    def _split_micro(self, tree):
        """[B_local, ...] -> [G, B_local/G, ...] for the in-step scan
        (shared implementation: parallel.sharding.split_micro)."""
        return split_micro(tree, self.batch_split)

    def _resolve_packing(self) -> bool:
        """Normalize ``sequence_packing``; with ``length_buckets`` also
        set, packing wins (it subsumes the bucketed padding win) with a log
        line. Multi-host runs are first-class: the loaders derive every
        host's identical pack plan from the shared length oracle
        (data/packing.oracle_read), so step shapes stay in lockstep."""
        # validate the splitting spec up front (fail at construction, not
        # mid-epoch on the loader thread), even when packing is off
        parse_pack_splitting(self.pack_splitting)
        if not parse_sequence_packing(self.sequence_packing):
            return False
        if self.process_count > 1:
            logger.info(
                "sequence_packing: multi-host run — the per-epoch pack "
                "plan derives from the shared length oracle, each host "
                "collates its row slice."
            )
        if self.collate_fun is None or self._collate_tokenizer() is None:
            logger.warning(
                "sequence_packing needs a tokenizer-bound collate_fun "
                "(make_collate_fun); falling back to pad-to-max batching."
            )
            return False
        if self.length_buckets:
            logger.info(
                "sequence_packing supersedes length_buckets: packed rows "
                "are already ~pad-free and compile ONE program (buckets "
                "would only re-introduce per-shape programs)."
            )
        return True

    def _collate_tokenizer(self):
        return getattr(self.collate_fun, "keywords", {}).get("tokenizer")

    def _collate_max_seq_len(self) -> int:
        max_len = getattr(self.collate_fun, "keywords", {}).get("max_seq_len")
        if max_len is None:
            raise ValueError(
                "sequence_packing needs the collate's static max_seq_len "
                "(make_collate_fun(..., max_seq_len=...))"
            )
        return int(max_len)

    def _plan_schedule_steps(self):
        """Planned steps per epoch for LR-schedule sizing, from the
        loader's length-only packing/bucketing simulation (epoch 1's plan
        stands in for all epochs — orderings reshuffle but the length
        population is the same). None = no planner (plain loader: the
        historical ``len(dataloader)`` arithmetic is already exact)."""
        loader = self.train_dataloader
        if not hasattr(loader, "planned_epoch_steps"):
            return None
        try:
            planned = int(loader.planned_epoch_steps(1))
        except Exception as e:  # noqa: BLE001 - planning is best-effort
            logger.warning(
                "LR-schedule step planning failed (%s); falling back to "
                "the len(dataloader) upper bound.", e,
            )
            return None
        planned = max(planned, 1)
        upper = len(loader)
        if planned != upper:
            logger.info(
                "LR schedule sized from the planned epoch step count: %d "
                "steps/epoch (the pad-to-max upper bound would have been "
                "%d — a %.0f%% overshoot that would stretch warmup/decay).",
                planned, upper, 100.0 * (upper - planned) / max(upper, 1),
            )
        return planned

    def _resolve_seq_grid(self):
        """Normalized sorted bucket grid from ``length_buckets`` (or None).
        Extended to cover the collate's static max_seq_len (an item longer
        than every bucket would have nowhere to go). Multi-host runs are
        first-class: every host derives the identical bucket plan from the
        shared length oracle (see BucketedDataLoader)."""
        buckets = self.length_buckets
        if not buckets:
            return None
        if self.process_count > 1:
            logger.info(
                "length_buckets: multi-host run — the per-epoch bucket "
                "plan derives from the shared length oracle, each host "
                "collates its row slice."
            )
        from ..data.bucketing import parse_length_buckets

        # one normalizer for every entry point: sort/dedupe/validate and
        # extend the grid to cover the collate's static max_seq_len
        max_len = getattr(self.collate_fun, "keywords", {}).get("max_seq_len")
        return parse_length_buckets(buckets, max_len)

    @staticmethod
    def _normalize_batch(batch):
        """Loader item -> ``(inputs, labels, meta)``; ``meta`` is the
        BucketedBatch (bucket seq + real_rows) on the bucketed path, the
        PackedBatch (rows + real segment count) on the packed path, None on
        the plain pad-to-max path."""
        if isinstance(batch, (BucketedBatch, PackedBatch)):
            return batch.inputs, batch.labels, batch
        inputs, labels = batch
        return inputs, labels, None


    # -- HBM pre-flight planner ------------------------------------------------

    def _next_batch_split(self) -> Optional[int]:
        """Smallest batch_split above the current one that still divides the
        global batch AND the per-host local batch (``_split_micro`` splits
        the local arrays, so a split legal globally but not locally would
        assert on an 8-host run), and keeps the micro-batch divisible over
        the mesh data axis (the same legality the constructor enforces).
        ``None`` when no such split exists."""
        data_size = self.plan.data_size
        local_batch = self.train_batch_size // max(self.process_count, 1)
        split = self.batch_split * 2
        while split <= local_batch:
            if (self.train_batch_size % split == 0
                    and local_batch % split == 0
                    and (self.train_batch_size // split) % max(data_size, 1)
                    == 0):
                return split
            split *= 2
        return None

    def _preflight_pipe_fields(self) -> dict:
        """The pipeline-aware slice of both pre-flight reports:
        per-chip PARAM residency (which drops ~1/K under stage-local
        storage — the planner must see the real number, not the
        replicated fiction), the schedule, and the stage -> layer / bytes
        map (so the report can tell you which layers rank 2 owns)."""
        fields = {
            "param_bytes": (
                opt_state_bytes_per_chip(self.params)
                if self.params is not None else None
            ),
            "pipe_schedule": (
                self.pipe_schedule if self.pipe_stages > 1 else None
            ),
            "pipe_param_layout": (
                ("stage" if self._stage_param_specs is not None
                 else "replicated")
                if self.pipe_stages > 1 else None
            ),
            "pipe_stage_layers": None,
            "pipe_stage_param_bytes": None,
        }
        if self.pipe_stages > 1:
            from ..parallel.pipeline import stage_param_bytes

            fields["pipe_stage_layers"] = self.plan.stage_map(
                int(self.model.cfg.num_layers)
            )
            fields["pipe_stage_param_bytes"] = stage_param_bytes(
                self.params, pipe_size=self.pipe_stages,
                model_size=self.plan.model_size,
            )["per_stage_bytes"]
        return fields

    def preflight_train_step(self, host_inputs, host_labels, *,
                             compile_fn=None, limit_bytes=None):
        """HBM pre-flight: lower + compile the jitted train step once at the
        current ``batch_split``, read ``compiled.memory_analysis()``, and if
        the projected per-device requirement exceeds the device HBM, raise
        ``batch_split`` and re-plan — so an over-committed configuration
        (bert-large at batch 256 / split 4) degrades to a running plan with
        a logged decision instead of an XLA allocation failure.

        ``host_inputs``/``host_labels`` are UNSPLIT host batches
        ([B_local, ...] leaves, exactly what the dataloader yields). The
        compiled executable is cached by jit, so the planning compile is
        also the first step's compile — no double work. ``compile_fn`` /
        ``limit_bytes`` exist for tests (mock the XLA memory analysis and
        the device limit); both default to the real thing. Returns the
        decision report dict (also kept as ``self.preflight_report``).
        """
        self._preflight_done = True
        if not self.hbm_preflight:
            return None

        loop = self._preflight(
            lambda: [(None, None)], limit_bytes,
            {"bytes_before": None, "bytes": None})
        got = None
        try:
            while True:
                loop.send(got)
                try:
                    got = compile_fn(self) if compile_fn is not None \
                        else self._aot_train_step_program(*(
                            self._global_batch(
                                self._split_micro(t), leading_accum=True)
                            for t in (host_inputs, host_labels)))
                except Exception as e:  # noqa: BLE001 - the loop's to judge
                    got = e
        except StopIteration as done:
            return done.value

    def preflight_bucket_steps(self, *, compile_fn=None, limit_bytes=None):
        """Per-bucket HBM pre-flight — the train-side analogue of
        ``QAEngine.preflight_predict_step``: before the first bucketed step
        executes, lower + compile ONE train step per bucket shape (largest
        seq first — it is the heaviest: same token count, O(L^2) attention),
        read each ``memory_analysis()``, and if any bucket exceeds device
        HBM, raise ``batch_split`` and re-derive every bucket's batch size
        (``BucketedDataLoader.rescale``) before re-checking. jit caches by
        shape, so these planning compiles are exactly the compiles the epoch
        would pay anyway — a warm autotune cache makes them zero-probe.

        ``compile_fn(trainer, seq, batch)`` / ``limit_bytes`` exist for
        tests; both default to the real thing. Returns the report dict (also
        ``self.preflight_report``); None when disabled or the device reports
        no memory limit (CPU).
        """
        self._preflight_done = True
        loader = self.train_dataloader
        if not self.hbm_preflight or not isinstance(loader, BucketedDataLoader):
            return None

        loop = self._preflight(
            lambda: [(f"{b}x{seq}", (seq, b)) for seq, b in sorted(
                loader.batch_sizes.items(), reverse=True)],
            limit_bytes, {"buckets": []},
            rescale=lambda split: loader.rescale(
                split * max(self.plan.data_size, 1)),
        )
        got = None
        try:
            while True:
                seq, b = loop.send(got)
                try:
                    got = compile_fn(self, seq, b) if compile_fn is not None \
                        else self._aot_train_step_program(*(
                            self._global_batch(
                                self._split_micro(t), leading_accum=True)
                            for t in synthetic_qa_batch(b, seq)))
                except Exception as e:  # noqa: BLE001 - the loop's to judge
                    got = e
        except StopIteration as done:
            return done.value

    def _preflight(self, shapes, limit_bytes, measured, *, rescale=None):
        """The one pre-flight loop, as a generator: it yields the key of
        each step shape it wants compiled at the CURRENT ``batch_split``
        (``shapes()`` lists ``(label, key)``, heaviest first; label None: the
        batch's own shape), is sent the executable (or the exception the
        compile raised), and returns the report. The caller compiles in its
        own frame, through the AOT program store (a warm restart's planning
        "compile" is a deserialization; loaded executables expose
        memory_analysis() too): the step is traced no deeper in the Python
        stack than the caller stands, and trace time on this interpreter
        depends on that depth (PERF.md section 6, PR 29). ``measured`` holds
        the report's keys for what the analysis reads (``bytes`` /
        ``bytes_before`` of the one shape, or ``buckets``);
        ``rescale(new_split)`` runs after every raise. A shape over the
        limit, or refused by the compiler itself, stops the pass and is
        answered: ``batch_split`` rises and every shape is checked again."""
        limit = limit_bytes if limit_bytes is not None else _device_hbm_bytes()
        if limit is None:
            logger.info(
                "HBM pre-flight: device reports no memory limit; skipping."
            )
            return None
        report = {
            "limit_bytes": int(limit),
            "batch_split_before": self.batch_split,
            "batch_split": self.batch_split,
            **measured,
            "applied": False,
            # plan topology: which axes the step runs under, and how many
            # visible devices the mesh strands (idle but allocated)
            "mesh_axes": self.plan.describe(),
            "mesh_unused_devices": self.plan.unused_devices,
            # optimizer-state residency: under zero1 this is ~1/N of the
            # replicated footprint, which is exactly why the planner must
            # re-measure rather than keep raising batch_split for memory
            # that no longer exists
            "opt_sharding": self.effective_opt_sharding,
            "opt_state_bytes_per_chip": (
                opt_state_bytes_per_chip(self.opt_state)
                if self.opt_state is not None
                else None
            ),
            **self._preflight_pipe_fields(),
        }
        while True:
            if self._jit_train_step is None:
                self._jit_train_step = self._build_train_step()
            checked, what, need, refusal = [], "step", None, None
            # one attempt: every shape at this batch_split, the caller's
            # compiles (or cache reads) between the yields included
            kept_before = getattr(self.model, "remat_kept_bytes", 0)
            kernels_before = kernels_traced()
            with trace_mod.span("preflight_attempt", cat="setup",
                                args={"split": self.batch_split}) as attempt:
                for label, key in shapes():
                    what = "step" if label is None else f"bucket {label}"
                    need = None
                    compiled = yield key
                    if isinstance(compiled, Exception):
                        # the compiler itself refuses a program that cannot
                        # fit ("RESOURCE_EXHAUSTED: ... Used 16.64G of
                        # 15.75G"): the same verdict as an analysis over the
                        # limit, reached earlier, and answered the same way;
                        # only OOM is handled
                        if "RESOURCE_EXHAUSTED" not in str(compiled) \
                                or self._next_batch_split() is None:
                            raise compiled
                        refusal = str(compiled).strip().splitlines()[0][:200]
                        break
                    try:
                        need = _preflight_bytes(compiled.memory_analysis())
                    except Exception as e:  # noqa: BLE001 - best-effort
                        logger.info("HBM pre-flight: memory_analysis "
                                    "unavailable (%s); skipping.", e)
                        break
                    if need is None:
                        logger.info("HBM pre-flight: memory analysis "
                                    "unavailable; skipping.")
                        break
                    checked.append({"bucket": label, "bytes": int(need)})
                    if need > limit:
                        break
                attempt.args["verdict"] = (
                    "refused" if refusal is not None
                    else "unknown" if need is None
                    else "fits" if need <= limit else "over")
                # what ``remat``'s policy kept of a micro-batch's layers in
                # the step this attempt traced (0: ``remat`` off, a trunk
                # whose ``remat`` has no policy, or a step not traced anew)
                report["kept_bytes"] = attempt.args["kept_bytes"] = getattr(
                    self.model, "remat_kept_bytes", 0) - kept_before
                # the kernel families' calls that step holds, by the form
                # they were traced in (``ops/kernel_forms.py``; none: no such
                # family, or a step not traced anew); the compiled program's
                # own names are ``metrics.trace.kernel_calls``'s to read, once
                # a capture asks for its text (1 s of set-up on the chip's
                # host)
                calls = kernels_traced(since=kernels_before)
                if calls:
                    report["kernel_calls"] = calls
            if "buckets" in report:
                report["buckets"] = checked
            elif checked:
                report["bytes"] = checked[-1]["bytes"]
                if report["bytes_before"] is None:
                    report["bytes_before"] = report["bytes"]
            new_split = self._next_batch_split()
            if refusal is not None:
                logger.warning(
                    "HBM pre-flight: the compiler refused the %s at "
                    "batch_split %d (%s); raising batch_split to %d.",
                    what, self.batch_split, refusal, new_split,
                )
                report.setdefault("compile_refused_at", []).append(
                    self.batch_split)
            elif need is None:
                break
            elif need <= limit:
                if report["applied"]:
                    logger.warning(
                        "HBM pre-flight: raised batch_split %d -> %d "
                        "(projected %.2f GB vs %.2f GB device HBM); "
                        "proceeding with the raised split.",
                        report["batch_split_before"], self.batch_split,
                        need / 1e9, limit / 1e9,
                    )
                break
            else:
                if new_split is None:
                    logger.warning(
                        "HBM pre-flight: %s needs %.2f GB vs %.2f GB device "
                        "HBM and batch_split %d cannot be raised further "
                        "(train_batch_size %d); proceeding — XLA will "
                        "decide.", what, need / 1e9, limit / 1e9,
                        self.batch_split, self.train_batch_size,
                    )
                    break
                logger.warning(
                    "HBM pre-flight: %s at batch_split %d needs %.2f GB vs "
                    "%.2f GB device HBM; raising batch_split to %d.",
                    what, self.batch_split, need / 1e9, limit / 1e9,
                    new_split,
                )
            self.batch_split = new_split
            report["batch_split"] = new_split
            report["applied"] = True
            if rescale is not None:
                rescale(new_split)
            # the step was built for the old batch_split
            self._jit_train_step = None

        self.preflight_report = report
        return report

    # -- compiled steps --------------------------------------------------------

    def _step_signature(self, dev_inputs, dev_labels) -> str:
        """Stable placed-shape key of one train-step program: every leaf's
        shape+dtype (micro-split accumulation dim included, so a raised
        batch_split keys differently)."""
        parts = []
        for tree in (dev_inputs, dev_labels):
            for leaf in jax.tree_util.tree_leaves(tree):
                parts.append(
                    "x".join(str(d) for d in leaf.shape) + str(leaf.dtype)
                )
        return "_".join(parts)

    def _sharding_signature(self, dev_inputs, dev_labels) -> str:
        """Hash of every argument leaf's placement. AOT executables BAKE
        IN input shardings: on a TP mesh the compiled step's outputs come
        back resharded by its in-step constraints, so the program compiled
        against the initial placement rejects step two's params — where a
        jit wrapper would silently recompile, the dispatch plane must key
        each sharding regime to its own executable (and a warm restart,
        whose restored state already carries the steady-state placement,
        hits the steady-state artifact directly)."""
        specs = {}
        parts = []
        for tree in (self.params, self.opt_state, dev_inputs, dev_labels):
            for leaf in jax.tree_util.tree_leaves(tree):
                sharding = getattr(leaf, "sharding", None)
                text = specs.get(id(sharding))
                if text is None:
                    text = str(getattr(sharding, "spec", sharding))
                    specs[id(sharding)] = text
                parts.append(text)
        digest = hashlib.sha256("|".join(parts).encode()).hexdigest()
        return digest[:12]

    def _model_signature(self) -> str:
        """Model-geometry key component (the serving engine's
        ``_program_cost_key`` discipline: the store is shared per device
        kind — bert-tiny's step must never load as bert-large's)."""
        cfg = getattr(self.model, "cfg", None)
        if cfg is None:
            return "anon"
        sig = (
            f"h{cfg.hidden_size}l{cfg.num_layers}n{cfg.num_heads}"
            f"v{cfg.vocab_size}"
        )
        if self.pipe_stages > 1:
            # gpipe and 1f1b compile DIFFERENT programs over identical
            # shapes + shardings — a schedule flip must never deserialize
            # the other schedule's executable
            layout = "s" if self._stage_param_specs is not None else "r"
            sig += f"-{self.pipe_schedule}{layout}"
        return sig

    def _aot_train_step_program(self, dev_inputs, dev_labels):
        """The train-step executable for these PLACED batches, through the
        AOT program store (ops/aot.py): loaded on a warm restart, compiled
        (and persisted) cold — memoized per placed shape, so the HBM
        pre-flight's program IS the first step's program. With the store
        disabled this is exactly the ``lower().compile()`` HEAD performed
        (and ``run_step`` keeps dispatching through the jit wrapper)."""
        if not hasattr(self._jit_train_step, "lower"):
            # the step fn was swapped for a plain wrapper (debug
            # instrumentation, test recording seams): nothing to lower,
            # dispatch it directly — the pre-store behavior
            return self._jit_train_step
        sig = (
            f"{self._step_signature(dev_inputs, dev_labels)}"
            f"-s{self._sharding_signature(dev_inputs, dev_labels)}"
        )
        program = self._compiled_steps.get(sig)
        if program is not None:
            return program
        store = aot.get()
        program, outcome, seconds = store.load_or_compile_ex(
            "train-step", self._jit_train_step,
            self.params, self.opt_state, dev_inputs, dev_labels,
            self.global_step,
            geometry=sig, plan=aot.plan_signature(self.plan),
            extra=self._model_signature(),
        )
        if outcome != "bypass":
            self._compiled_steps[sig] = program
            if self._aot_first_outcome is None:
                self._aot_first_outcome = outcome
            if self.telemetry is not None:
                self.telemetry.observe_aot(outcome, seconds)
        return program

    def _build_train_step(self):
        """The jitted train step (``train/step.py`` builds the program from
        the ``StepSpec`` filled in here)."""
        # any rebuild (batch_split raise, elastic re-mesh) orphans the
        # dispatch plane's executables: they belong to the old program
        self._compiled_steps.clear()
        spec = step_lib.StepSpec(
            model=self.model, loss=self.loss, optimizer=self.optimizer,
            plan=self.plan, batch_split=self.batch_split, seed=self.seed,
            prng_impl=self.prng_impl,
            scheduler=self.scheduler, schedule_count=self._schedule_count,
            use_loss_scale=self._use_loss_scale,
            max_grad_norm=self.max_grad_norm,
            # fine-tune freezing: gradients of non-trainable modules are
            # zeroed before the finite-check / clip / optimizer, so the clip
            # norm measures trainable gradients only (reference
            # trainer.py:221-225) and the optax.masked passthrough leaves
            # get a zero update
            trainable=(
                trainable_mask(self.params, self.trainer_params)
                if self.trainer_params is not None else None
            ),
            zero_plan=self._zero_plan,
            zero_param_shardings=self._zero_param_shardings,
            zero_state_shardings=self._zero_shardings,
            param_shardings=self._param_shardings,
            stage_param_specs=self._stage_param_specs,
            opt_state_shardings=self._opt_state_shardings,
            pipe_schedule=self.pipe_schedule,
        )

        pipe = self.pipe_stages > 1
        exchange_once = step_lib.exchanges_once(spec)
        plan, batch_split = self.plan, self.batch_split
        self.grad_exchanges_per_step = (
            0 if plan.data_size <= 1
            else 1 if exchange_once or pipe else batch_split
        )
        mesh_text = ",".join(f"{a}:{n}" for a, n in plan.describe().items())
        if plan.data_size > 1 and not pipe:
            logger.info(
                "gradient exchange: %s (%s, %d micro-batch(es))",
                "once a step" if exchange_once else "every micro-batch",
                mesh_text, batch_split,
            )
        if self.telemetry is not None:
            self.telemetry.observe_grad_exchange(
                self.grad_exchanges_per_step, mesh=mesh_text,
                micro_batches=batch_split,
            )

        step_fn = step_lib.build_step(spec)
        # the trace shows this program as "jit_<name>(<id>)": tell the trace
        # readers where its optimized HLO text can be had, should they ask
        # (a weak reference: the table must not keep a dropped trainer alive)
        weak_text = weakref.WeakMethod(self._train_step_hlo_text)

        def text_source():
            text = weak_text()
            return text() if text is not None else None

        program = f"jit_{step_fn.__name__}"

        def log_kernel_forms():
            for family, forms in trace_mod.kernel_calls(program).items():
                if any(forms.values()):
                    logger.info("%s: %s call(s) in %s", family, ", ".join(
                        f"{n} {form}" for form, n in forms.items()), program)

        trace_mod.register_program(program, text_source, log_kernel_forms)
        return jax.jit(step_fn, donate_argnums=(0, 1))

    def _train_step_hlo_text(self) -> Optional[str]:
        """Optimized HLO text of the train-step program the last placed
        batch ran, through the same routing as the step itself: the
        executable the AOT store's dispatch plane already holds, else the
        jitted step lowered and compiled on that batch (both cache reads).
        Called only through ``metrics.trace.scope_map``, after a device
        capture; ``None`` before the first step."""
        if self._last_placed is None \
                or not hasattr(self._jit_train_step, "lower"):
            return None
        with self.mesh:
            return self._aot_train_step_program(*self._last_placed).as_text()

    def _build_eval_step(self):
        model, loss = self.model, self.loss

        def eval_step(params, inputs, labels):
            preds = model.apply({"params": params}, **inputs, deterministic=True)
            _, values = loss(preds, labels)
            return preds, values

        return jax.jit(eval_step)

    # -- console / writer (trainer.py:206-219) --------------------------------

    def _update_writer(self, meters: dict, *, prefix: str, step: Optional[int] = None):
        if self.writer is None:
            return
        for k, v in meters.items():
            self.writer.add_scalar(
                f"{prefix}/{k}",
                v() if isinstance(v, AverageMeter) else v,
                global_step=self.global_step if step is None else step,
            )

    # -- train loop (trainer.py:253-300) --------------------------------------

    def train(self, after_epoch_funcs=None):
        if self.train_dataloader is None:
            logger.warning("No train dataset was provided; train() is a no-op.")
            return

        after_epoch_funcs = after_epoch_funcs or []

        with self.mesh:
            for epoch_i in range(1, self.n_epochs + 1):
                self._train(epoch_i)
                with trace_mod.span(
                        "after_epoch", cat="train",
                        args={"step": self.global_step, "epoch": epoch_i}):
                    for func in after_epoch_funcs:
                        func(epoch_i)

    @time_profiler
    def _train(self, epoch_i):
        if self._jit_train_step is None:
            self._jit_train_step = self._build_train_step()

        self.train_dataloader.set_epoch(epoch_i)
        avg_meters: dict = defaultdict(AverageMeter)
        bucketed = isinstance(self.train_dataloader, BucketedDataLoader)
        packed = isinstance(self.train_dataloader, PackedDataLoader)
        # variable per-step example counts: weight each step's mean by them
        weighted = bucketed or packed

        if bucketed and not self._preflight_done:
            # per-bucket plan BEFORE any batch is drawn: may raise
            # batch_split and re-derive the loader's bucket batch sizes
            with trace_mod.span("preflight", cat="setup"):
                self.preflight_bucket_steps()

        iterator = self.train_dataloader
        tqdm_data = None
        if tqdm is not None:
            tqdm_data = tqdm(iterator, desc=f"Train (epoch #{epoch_i} / {self.n_epochs})")
            iterator = tqdm_data

        # steady-state steps 2-4 when the epoch has them; short/debug epochs
        # (the smoke config breaks after one step) trace from step 0 instead
        # of silently capturing nothing
        trace_from = (
            0 if self.debug or len(self.train_dataloader) < 5 else 2
        )
        # xplane capture window (epoch 1 only), refactored onto
        # metrics.trace.XplaneWindow: host spans and the jax.profiler
        # capture mark the same step boundaries
        xplane = (
            XplaneWindow(str(self.trace_dir), start=trace_from, steps=3)
            if self.trace_dir is not None and epoch_i == 1
            else None
        )
        tele = self.telemetry
        # the telemetry's partition wants the honest-timing discipline:
        # block on each step's results so 'device' is execution, not
        # dispatch (costs the one-step metric lag). The span plane's step
        # clock below needs no block, and an installed TraceWriter asks
        # for none: with no telemetry the loop runs as it always does
        instrument = tele is not None
        if tele is not None:
            tele.observe_span_record(since=self._built_at)
        log_every = max(1, int(self.log_every))
        last_consumed = [None]  # last consumed step no (for the final write)
        epoch_step0 = self.global_step
        boundary = [None]       # when the step consumed last had finished

        def consume(values, step_no: int, rows: int, t_dispatch: float) -> None:
            # this device_get blocks until the producing step finishes — by
            # then the NEXT step is already enqueued (see the lag below),
            # so the device never idles on host-side metric/IO work. Its
            # return is the step's boundary as the host sees it
            with trace_mod.span("consume", cat="train", args={
                    "step": step_no, "epoch": epoch_i,
                    "blocked": instrument}) as fetched:
                host_values = jax.device_get(values)
            trace_mod.complete("step", t_dispatch, fetched.t1, cat="train",
                               args={"step": step_no, "rows": rows})
            if self._first_step_t0 is not None:
                # the run's first step: its batch in hand (the pre-flight
                # over) to its outputs ready
                trace_mod.complete("first_step", self._first_step_t0,
                                   fetched.t1, cat="setup",
                                   args={"step": step_no})
                self._first_step_t0 = None
            if tele is None and step_no - epoch_step0 >= EPOCH_HEAD_STEPS:
                # (with telemetry on, its own detector sees the blocked wall)
                slow = self._interval_detector.update(
                    step_no, fetched.t1 - boundary[0])
                if slow is not None:
                    phase, held = covering_phase(
                        trace_mod.recent("train"), boundary[0], fetched.t1,
                        threading.get_ident())
                    logger.warning(
                        "SLOW STEP INTERVAL %d: %.1f ms between step "
                        "boundaries vs rolling median %.1f ms; %s held "
                        "%.1f ms of it.", step_no, 1e3 * slow.total_s,
                        1e3 * slow.median_s, phase, 1e3 * held)
            boundary[0] = fetched.t1
            for k, v in host_values.items():
                if k == "lr":
                    avg_meters["lr"] = float(v)
                else:
                    # bucketed steps carry bucket-dependent batch sizes and
                    # packed steps row-dependent SEGMENT counts, so the
                    # epoch mean must weight each step's mean by its example
                    # count to stay per-example-correct; plain batches are
                    # equal-sized (weight 1 = historical arithmetic)
                    avg_meters[k].update(float(v), rows if weighted else 1)
            if tele is not None:
                tele.observe_scalars(host_values)
            if self.on_train_metrics is not None:
                self.on_train_metrics(avg_meters, step=step_no)
            last_consumed[0] = step_no
            # writer + progress-bar IO throttled to every `log_every` steps
            # (meters above still integrate every step); the epoch's final
            # state is always written once more in the finally below
            if (step_no + 1) % log_every == 0:
                self._update_writer(avg_meters, prefix="train", step=step_no)
                if tqdm_data is not None:
                    tqdm_data.set_postfix_str(_console_str(avg_meters))

        # Metrics are consumed with a ONE-STEP lag: dispatch step N, then
        # fetch step N-1's scalars while N runs. Without this the per-step
        # device_get serializes device compute with host batch prep.
        # (Bucketed/packed epochs take a data-dependent number of steps <=
        # the sampler length, so the known-total early-drain stays off.)
        lag = LaggedConsumer(
            consume, total=None if weighted else len(self.train_dataloader)
        )

        # instrumented accounting, FIFO-matched to batch order (one worker
        # thread, bounded queue — the prefetcher's ordering guarantee):
        # place() appends, run_step() pops the stats for the batch it runs
        host_stats = deque()
        fetch_wait = [0.0]      # time blocked obtaining the current batch
        host_inline = [True]    # place() ran on the consumer thread?
        placed_n = [0]          # batches placed this epoch, in step order

        def place(batch):
            """Host batch -> placed global arrays + example count (runs on
            the prefetch thread when device_prefetch > 0, inline otherwise —
            same code either way, which is what makes the trajectories
            bit-identical). The count is what the meters weight by: rows
            for plain/bucketed batches, REAL segments for packed ones."""
            # the span lies on whichever thread ran the placement, so a
            # trace shows the prefetch overlap on a line of its own
            with trace_mod.span("place", cat="train", args={
                    "step": epoch_step0 + placed_n[0]}) as placing:
                placed_n[0] += 1
                inputs, labels, meta = self._normalize_batch(batch)
                if isinstance(meta, PackedBatch):
                    rows = meta.segments
                elif meta is not None:
                    rows = meta.rows
                else:
                    rows = int(np.shape(next(iter(inputs.values())))[0])
                if instrument:
                    mask = inputs.get("attention_mask")
                    real_tokens = int(np.asarray(mask).sum()) if mask is not None else 0
                    total_tokens = int(np.asarray(mask).size) if mask is not None else 0
                placed = (
                    self._global_batch(self._split_micro(inputs), leading_accum=True),
                    self._global_batch(self._split_micro(labels), leading_accum=True),
                    rows,
                )
            if instrument:
                host_stats.append(
                    (placing.t1 - placing.t0, real_tokens, total_tokens))
            return placed

        def waited(iterator):
            """Yield from ``iterator`` with each ``next`` in a ``data_wait``
            span (the loader or the prefetch queue; placement too, as a span
            of its own inside, when it runs inline), and its length in
            ``fetch_wait``. The epoch's last wait finds the data at its end
            and bears the number of the step that did not come."""
            iterator = iter(iterator)
            while True:
                with trace_mod.span("data_wait", cat="train", args={
                        "step": self.global_step}) as waiting:
                    item = next(iterator, None)
                if item is None:
                    return
                fetch_wait[0] = waiting.t1 - waiting.t0
                yield item

        step_i = [0]

        def run_step(placed) -> None:
            dev_inputs, dev_labels, rows = placed
            if xplane is not None:
                xplane.on_step_start(step_i[0])

            # store-enabled runs dispatch the AOT executable (a warm
            # restart's first step LOADS it: zero XLA compiles); with the
            # store off the jit wrapper runs exactly as before. The call
            # returns when the step is enqueued
            with trace_mod.span("dispatch", cat="train", args={
                    "step": self.global_step}) as dispatched:
                step_fn = (
                    self._aot_train_step_program(dev_inputs, dev_labels)
                    if aot.get().enabled else self._jit_train_step
                )
                self.params, self.opt_state, values = step_fn(
                    self.params, self.opt_state, dev_inputs, dev_labels,
                    self.global_step,
                )
            self._last_placed = (dev_inputs, dev_labels)
            if not self._first_step_seen:
                self._first_step_seen = True
                if self._first_step_t0 is None:     # no pre-flight before it
                    self._first_step_t0 = dispatched.t0
            if instrument:
                # StepTimer discipline: block before reading the clock, so
                # 'device' is actual execution time under async dispatch
                jax.block_until_ready(values)
                t1 = time.perf_counter()
                host_s, real_tokens, total_tokens = (
                    host_stats.popleft() if host_stats else (0.0, 0, 0)
                )
                # inline placement runs inside the fetch wait — subtract it
                # so the three components partition the step wall exactly
                wait_s = fetch_wait[0]
                data_wait_s = (
                    max(0.0, wait_s - host_s) if host_inline[0] else wait_s
                )
                fetch_wait[0] = 0.0
                tele.observe_step(
                    self.global_step,
                    data_wait_s=data_wait_s,
                    host_s=host_s,
                    device_s=t1 - dispatched.t0,
                    examples=rows,
                    real_tokens=real_tokens,
                    total_tokens=total_tokens,
                    # prefetch-thread placement overlaps the previous
                    # step's device time — it is not on the step wall
                    host_overlapped=not host_inline[0],
                    epoch_head=step_i[0] < EPOCH_HEAD_STEPS,
                )

            if xplane is not None:
                xplane.on_step_end(step_i[0], values)

            lag.feed(values, self.global_step, rows, dispatched.t0)
            self.global_step += 1
            step_i[0] += 1
            if self.watchdog is not None:
                self.watchdog.note_progress(self.global_step)

        prefetcher = None
        # one watchdog frame per epoch, re-ticked per step: the deadline
        # covers dataloader/prefetch waits, step dispatch AND the lagged
        # device_get — any of them can be the thing that hangs
        with self._watched(f"train epoch {epoch_i}") as tick:
            try:
                host_iter = iter(iterator)
                interrupted = False
                if not self._preflight_done:
                    # first batch of the run: plan HBM before executing — may
                    # raise batch_split and rebuild the jitted step, so it
                    # must see UNSPLIT host arrays and must happen before the
                    # prefetch thread bakes the old split into placed batches
                    with trace_mod.span("data_wait", cat="train", args={
                            "step": self.global_step}):
                        first = next(host_iter, None)
                    if first is not None:
                        _fault("trainer.step")
                        tick(f"train step {self.global_step} (epoch {epoch_i})")
                        inputs, labels, _ = self._normalize_batch(first)
                        with trace_mod.span("preflight", cat="setup"):
                            self.preflight_train_step(inputs, labels)
                        self._first_step_t0 = time.perf_counter()
                        run_step(place(first))
                        if self.debug:
                            interrupted = True
                if not interrupted and self._prefetch_auto() and (
                    self._prefetch_choice is None
                ):
                    # --device_prefetch auto: time a few steps synchronously
                    # (placement wall vs step wall, first sample discarded as
                    # possibly-compiling) and pick depth 1 vs 2 for the rest
                    # of the run
                    place_s, step_s = [], []
                    for _ in range(_PREFETCH_AUTO_PROBE_STEPS):
                        with trace_mod.span("data_wait", cat="train", args={
                                "step": self.global_step}):
                            b = next(host_iter, None)
                        if b is None:
                            break
                        _fault("trainer.step")
                        tick(f"train step {self.global_step} (epoch {epoch_i})")
                        t0 = time.perf_counter()
                        placed = place(b)
                        t1 = time.perf_counter()
                        run_step(placed)
                        jax.block_until_ready(self.params)
                        place_s.append(t1 - t0)
                        step_s.append(time.perf_counter() - t1)
                        if self.debug:
                            interrupted = True
                            break
                    self._prefetch_choice = resolve_prefetch_auto(
                        place_s, step_s
                    )
                    logger.info(
                        "device_prefetch auto: placement %.1f ms vs step "
                        "%.1f ms over %d probe steps -> depth %d.",
                        1e3 * (sum(place_s) / len(place_s)) if place_s else 0,
                        1e3 * (sum(step_s) / len(step_s)) if step_s else 0,
                        len(place_s), self._prefetch_choice,
                    )
                if not interrupted:
                    depth = self._prefetch_depth_static()
                    if depth > 0:
                        prefetcher = DevicePrefetcher(
                            host_iter, place, depth=depth
                        )
                        placed_iter = iter(prefetcher)
                        host_inline[0] = False
                    else:
                        placed_iter = (place(b) for b in host_iter)
                    for placed in waited(placed_iter):
                        _fault("trainer.step")
                        tick(f"train step {self.global_step} (epoch {epoch_i})")
                        run_step(placed)
                        if self.debug:
                            interrupted = True
                            break
                if interrupted:
                    logger.info("Training was interrupted because of debug mode.")
            finally:
                # drain the prefetch thread and flush the metric lag even on
                # a mid-epoch exception/SIGTERM — without this the last
                # steps' metrics (and the trace/writer below) are silently
                # dropped on any non-clean epoch exit
                close_err = None
                if prefetcher is not None:
                    try:
                        prefetcher.close()
                    except BaseException as e:  # noqa: BLE001
                        # close() raises only on a CLEAN exit with a wedged
                        # thread (it just warns when an exception is already
                        # propagating) — hold it until the flushes ran
                        close_err = e
                lag.flush()

                if xplane is not None:  # close a window still open mid-epoch
                    xplane.abort(self.params)

                if last_consumed[0] is not None and (
                    (last_consumed[0] + 1) % log_every != 0
                ):
                    # final throttled write so the epoch always ends with
                    # current meters on the writer/progress bar
                    self._update_writer(
                        avg_meters, prefix="train", step=last_consumed[0]
                    )
                    if tqdm_data is not None:
                        tqdm_data.set_postfix_str(_console_str(avg_meters))

                if weighted and self.train_dataloader.epoch_stats:
                    stats = self.train_dataloader.epoch_stats
                    if packed:
                        logger.info(
                            "Packed epoch %d: %d batches, packing "
                            "efficiency %.2f%% (padding waste %.2f%%; "
                            "pad-to-max would waste %.2f%%; %d splits in "
                            "%d fragment rows).",
                            epoch_i, stats["batches"],
                            100.0 * stats.get("packing_efficiency", 0.0),
                            stats.get("padding_waste_pct", 0.0),
                            stats.get("padmax_waste_pct", 0.0),
                            stats.get("split_count", 0),
                            stats.get("fragment_rows", 0),
                        )
                    else:
                        logger.info(
                            "Bucketed epoch %d: %d batches, padding waste "
                            "%.2f%% (pad-to-max would be %.2f%%).",
                            epoch_i, stats["batches"],
                            stats.get("padding_waste_pct", 0.0),
                            stats.get("padmax_waste_pct", 0.0),
                        )
                    # the LR schedule is sized from the loader's PLANNED
                    # step count (a length-only simulation of the packer/
                    # bucketer — _plan_schedule_steps) rather than the old
                    # len(dataset)/batch upper bound; the warning now only
                    # fires when the ACTUAL epoch undershoots even that
                    # plan (stochastic chunk lengths drifting, mid-epoch
                    # abort), so it flags real schedule stretch instead of
                    # the planner's known overshoot
                    estimate = (
                        self._planned_steps_per_epoch
                        if self._planned_steps_per_epoch is not None
                        else len(self.train_dataloader)
                    )
                    if epoch_i == 1 and stats["batches"] < 0.8 * estimate:
                        logger.warning(
                            "Epoch took %d steps vs the %d-step schedule "
                            "estimate: the LR decay will end ~%.0f%% early "
                            "(warmup stretched accordingly). Consider "
                            "raising n_epochs or lowering warmup_coef.",
                            stats["batches"], estimate,
                            100.0 * (1.0 - stats["batches"] / estimate),
                        )

                if self.writer is not None:
                    self.writer.flush()  # survive preemption with events intact
                if close_err is not None:
                    raise close_err

    # -- test loop (trainer.py:302-353) ----------------------------------------

    def test(self, epoch_i, *, callbacks=None):
        if self.test_dataloader is None:
            logger.warning("No test dataset was provided; test() is a no-op.")
            return None

        if callbacks is not None and not isinstance(callbacks, (list, tuple)):
            callbacks = (callbacks,)
        if callbacks is not None:
            assert all(isinstance(c, TestCallback) for c in callbacks)

        # eval wall time is badput under the goodput discipline (chips
        # busy, no training progress): hand it to the ledger via telemetry
        t0 = time.perf_counter()
        try:
            with self.mesh:
                return self._test(epoch_i, callbacks=callbacks)
        finally:
            if self.telemetry is not None:
                self.telemetry.observe_eval(time.perf_counter() - t0)

    @time_profiler
    def _test(self, epoch_i, *, callbacks=None):
        if self._jit_eval_step is None:
            self._jit_eval_step = self._build_eval_step()

        avg_meters: dict = defaultdict(AverageMeter)
        bucketed = isinstance(self.test_dataloader, BucketedDataLoader)
        packed = isinstance(self.test_dataloader, PackedDataLoader)

        iterator = self.test_dataloader
        tqdm_data = None
        if tqdm is not None:
            tqdm_data = tqdm(
                self.test_dataloader, desc=f"Test (epoch #{epoch_i} / {self.n_epochs})"
            )
            iterator = tqdm_data

        def consume(i, labels, dev_labels, preds, values, meta) -> None:
            # blocks on batch i's results — batch i+1 is already enqueued
            # (same one-step-lag pipelining as the train loop)
            if isinstance(meta, PackedBatch):
                # packed eval: the device loss is already a mean over REAL
                # segments only (PackedWeightedLoss keys every head on
                # segment_mask, and pad rows carry zero mask), so no
                # partial-batch recompute is needed; callbacks receive the
                # per-chunk arrays scattered out of the [rows, S] segment
                # planes through the packing map (row-major segment order)
                n_valid = meta.segments
                host_values = jax.device_get(values)
                for k, v in host_values.items():
                    avg_meters[k].update(float(v), n_valid)
                if callbacks is not None:
                    host_preds = gather_to_host(preds)
                    host_labels = (
                        labels if self.process_count == 1
                        else gather_to_host(dev_labels)
                    )
                    m = np.asarray(host_labels["segment_mask"]).reshape(-1) > 0
                    host_preds = {
                        k: np.asarray(v).reshape(
                            (-1,) + np.asarray(v).shape[2:]
                        )[m]
                        for k, v in host_preds.items()
                    }
                    host_labels = {
                        k: np.asarray(v).reshape(-1)[m]
                        for k, v in host_labels.items()
                        if k != "segment_mask"
                    }
                    for callback in callbacks:
                        callback.at_iteration_end(
                            host_preds, host_labels, avg_meters
                        )
                if tqdm_data is not None:
                    tqdm_data.set_postfix_str(_console_str(avg_meters))
                return
            if meta is not None:  # bucketed batch carries its own row count
                n_valid = meta.real_rows
                batch_rows = meta.rows
            else:
                n_valid = self.test_dataloader.real_rows(i)
                batch_rows = self._test_sampler.global_batch_size
            is_partial = n_valid < batch_rows

            host_preds = host_labels = None
            if callbacks is not None or is_partial:
                host_preds = gather_to_host(preds)
                host_labels = (
                    labels if self.process_count == 1 else gather_to_host(dev_labels)
                )
                # trim padding rows of the final partial batch
                host_preds = {k: v[:n_valid] for k, v in host_preds.items()}
                host_labels = {k: np.asarray(v)[:n_valid] for k, v in host_labels.items()}

            if is_partial:
                # the device loss averaged over pad-duplicated rows; recompute
                # on the trimmed batch so meters see only real examples
                _, values_ = self.loss(
                    {k: jnp.asarray(v) for k, v in host_preds.items()},
                    {k: jnp.asarray(v) for k, v in host_labels.items()},
                )
            else:
                values_ = values

            host_values = jax.device_get(values_)
            for k, v in host_values.items():
                # weight by REAL rows: pad_last repetition rows carry zero
                # weight, and bucketed batches of different sizes contribute
                # per-example-correctly to the epoch mean
                avg_meters[k].update(float(v), n_valid)

            if callbacks is not None:
                for callback in callbacks:
                    callback.at_iteration_end(host_preds, host_labels, avg_meters)

            if tqdm_data is not None:
                tqdm_data.set_postfix_str(_console_str(avg_meters))

        # bucketed/packed epochs take a data-dependent number of batches, so
        # the known-total early drain stays off there (flush() covers the
        # tail)
        lag = LaggedConsumer(
            consume,
            total=None if (bucketed or packed) else len(self.test_dataloader),
        )

        def place_eval(batch):
            """Host batch -> (host labels, placed inputs/labels, meta); runs
            on the prefetch thread when device_prefetch > 0."""
            inputs, labels, meta = self._normalize_batch(batch)
            return (
                labels,
                self._global_batch(inputs),
                self._global_batch(labels),
                meta,
            )

        prefetcher = None
        eval_depth = self._prefetch_depth_static()
        if eval_depth > 0:
            prefetcher = DevicePrefetcher(
                iter(iterator), place_eval, depth=eval_depth,
                name="device-prefetch-eval",
            )
            placed_iter = iter(prefetcher)
        else:
            placed_iter = (place_eval(b) for b in iterator)

        with self._watched(f"test epoch {epoch_i}") as tick:
            try:
                for i, (labels, dev_inputs, dev_labels, meta) in enumerate(placed_iter):
                    _fault("trainer.eval_step")
                    tick(f"eval step {i} (epoch {epoch_i})")

                    preds, values = self._jit_eval_step(self.params, dev_inputs, dev_labels)

                    lag.feed(i, labels, dev_labels, preds, values, meta)

                    if self.debug and i >= 10:
                        logger.info("Test was interrupted because of debug mode.")
                        break
            finally:
                # same mid-epoch guarantees as _train: drain the prefetch
                # thread and flush the metric lag even on exception/SIGTERM
                # (close() raises only on a clean exit with a wedged thread;
                # hold that until the in-flight batches have been consumed)
                close_err = None
                if prefetcher is not None:
                    try:
                        prefetcher.close()
                    except BaseException as e:  # noqa: BLE001
                        close_err = e
                lag.flush()
                if close_err is not None:
                    raise close_err

        if callbacks is not None:
            for callback in callbacks:
                callback.at_epoch_end(avg_meters, self)

        self._update_writer(avg_meters, prefix="test")
        if self.writer is not None:
            self.writer.flush()

        metrics = {
            k: v() if isinstance(v, AverageMeter) else v for k, v in avg_meters.items()
        }
        logger.info(f"Test metrics after epoch {epoch_i} - {_console_str(metrics)}")
        return metrics

    # -- checkpointing (trainer.py:355-403) ------------------------------------

    def _bundle_ls(self):
        """Wrap a freshly initialized ``opt_state`` with a fresh scaling
        state when loss scaling is on (no-op otherwise)."""
        if not self._use_loss_scale:
            return
        ls_state = ls_lib.init_state(self._ls_init_scale, dynamic=self._ls_dynamic)
        if not is_single_device(self.mesh):
            ls_state = self.plan.put_replicated(ls_state)
        self.opt_state = ls_lib.OptStateWithLS(self.opt_state, ls_state)

    def _split_ls(self):
        """Live ``(opt_state, ls_state)``; ls_state is None when scaling is off."""
        if isinstance(self.opt_state, ls_lib.OptStateWithLS):
            return self.opt_state.inner, self.opt_state.ls
        return self.opt_state, None

    def _checkpoint_extra(self) -> dict:
        """Topology record every checkpoint carries: the actual optimizer
        layout and the plan's mesh axes — so ``peek_checkpoint_layout``
        can report what topology wrote a checkpoint (restores stay
        shape-driven and reshard onto any live plan). Pipeline runs
        additionally stamp the tick schedule and whether the trunk was
        stored stage-local (``stage``) or replicated per rank — purely
        informational for the peek: both restore paths are shape-driven,
        so a stage-sharded save at ``pipe:K`` restores at ``pipe:K'``,
        under no pipe axis at all, or under the other schedule."""
        pipe = self.pipe_stages > 1
        return {
            "opt_sharding": self.effective_opt_sharding,
            "mesh_axes": self.plan.describe(),
            "pipe_schedule": self.pipe_schedule if pipe else None,
            "pipe_param_layout": (
                ("stage" if self._stage_param_specs is not None
                 else "replicated") if pipe else None
            ),
        }

    def save_state_dict(self, path_):
        if self.debug:
            logger.info(f"Model was not saved to {path_} because of debug mode.")
            return
        if self._async_ckpt is not None and self._async_supported():
            return self._save_state_dict_async(path_)
        if self._async_ckpt is not None:
            # sync fallback still honors the single-flight contract: a
            # previous async persist must land before this save writes
            self.finish_pending_checkpoint()
        opt_state, ls_state = self._split_ls()
        # its own watchdog frame: the sharded save crosses process barriers,
        # and a peer that died mid-save must abort this host (for restart)
        # rather than park it on the barrier forever. 8x the step timeout:
        # a save legitimately gathers/writes the FULL state (the non-sharded
        # path in particular), which dwarfs a step — a slow save must not be
        # misclassified as a hang and crash-looped. Barriers inside inherit
        # this budget (watchdog.arm nested-frame default).
        extra = self._checkpoint_extra()
        t0 = time.perf_counter()
        with self._watched(f"checkpoint save {path_}", scale=8.0), \
                trace_mod.span("checkpoint_save", cat="train",
                               args={"path": str(path_),
                                     "step": self.global_step}):
            if self.sharded_checkpoint:
                from .checkpoint import save_state_dict_sharded

                save_state_dict_sharded(
                    path_,
                    params=self.params,
                    opt_state=opt_state,
                    loss_scale=ls_state,
                    global_step=self.global_step,
                    extra=extra,
                )
            else:
                _save_ckpt(
                    path_,
                    params=self.params,
                    opt_state=opt_state,
                    loss_scale=ls_state,
                    global_step=self.global_step,
                    is_primary=self.is_primary,
                    extra=extra,
                )
        if self.telemetry is not None:
            self.telemetry.observe_checkpoint_save(time.perf_counter() - t0)

    def _async_supported(self) -> bool:
        """Async persist is restricted to configurations whose persist leg
        is free of cross-process DEVICE collectives: a multi-host SHARDED
        persist runs ``sync_global_devices`` barriers, and issuing those
        from a background thread concurrently with the main thread's
        train-step collectives can reorder collective launches across
        hosts (pod deadlock) — and would arm watchdog frames on the
        process-global LIFO stack from the wrong thread. Single-process
        sharded persists skip the barriers entirely, and single-file
        persists never had any; multi-host sharded saves fall back to the
        sync path with a (once) log line."""
        if not (self.sharded_checkpoint and self.process_count > 1):
            return True
        if not getattr(self, "_async_fallback_logged", False):
            self._async_fallback_logged = True
            logger.warning(
                "--async_checkpoint with --sharded_checkpoint on a "
                "multi-host world: the sharded persist crosses process "
                "barriers, which must not run on a background thread "
                "concurrently with training collectives — saving "
                "synchronously instead."
            )
        return False

    def _save_state_dict_async(self, path_):
        """Async overlapped save (--async_checkpoint): block only for the
        device->host snapshot (plus the completion barrier on any previous
        persist), then serialize+write on the background thread with the
        same crc32/atomic-rename discipline a sync save uses. The snapshot
        deep-copies every leaf (``copy=True``) because the very next train
        step DONATES the live buffers the gather would otherwise view."""
        from .checkpoint import (
            persist_state,
            persist_state_sharded,
            snapshot_state,
            snapshot_state_sharded,
        )

        opt_state, ls_state = self._split_ls()
        extra = self._checkpoint_extra()
        t0 = time.perf_counter()
        with self._watched(f"checkpoint save {path_}", scale=8.0), \
                trace_mod.span("checkpoint_save", cat="train",
                               args={"path": str(path_),
                                     "step": self.global_step,
                                     "async": True}):
            # completion barrier BEFORE snapshotting anew: two persists
            # must never interleave on one path, and a failed background
            # persist surfaces here, not silently
            self._async_ckpt.wait()
            with trace_mod.span("ckpt_snapshot", cat="train",
                                args={"step": self.global_step}):
                if self.sharded_checkpoint:
                    snap = snapshot_state_sharded(
                        params=self.params, opt_state=opt_state,
                        loss_scale=ls_state, global_step=self.global_step,
                        extra=extra, copy=True,
                    )
                    persist = functools.partial(
                        persist_state_sharded, os.fspath(path_), snap
                    )
                else:
                    state = snapshot_state(
                        params=self.params, opt_state=opt_state,
                        loss_scale=ls_state, global_step=self.global_step,
                        extra=extra, is_primary=self.is_primary, copy=True,
                    )
                    persist = (
                        None if state is None
                        else functools.partial(
                            persist_state, os.fspath(path_), state
                        )
                    )
        blocking = time.perf_counter() - t0
        if self.telemetry is not None:
            self.telemetry.observe_checkpoint_snapshot(blocking)
        if persist is not None:
            on_done = (
                self.telemetry.observe_checkpoint_persist
                if self.telemetry is not None else None
            )
            self._async_ckpt.submit(path_, persist, on_done=on_done)
            logger.info(
                "Async checkpoint: step %d snapshot blocked %.3fs; persist "
                "to %s running in the background.",
                self.global_step, blocking, path_,
            )

    def finish_pending_checkpoint(self, *, raise_errors: bool = True) -> None:
        """Completion barrier for --async_checkpoint: block until the
        in-flight background persist lands (no-op when async checkpointing
        is off or idle). Must run before process exit and before a
        checkpoint is handed to the supervisor for resume (the SIGTERM
        path). ``raise_errors=False`` is for best-effort paths (an
        exception already propagating, or an emergency save that a STALE
        failure must not abort): the failure is logged at ERROR and
        CONSUMED — a later barrier will not re-raise it."""
        if self._async_ckpt is None:
            return
        with self._watched("checkpoint persist wait", scale=8.0):
            self._async_ckpt.wait(raise_errors=raise_errors)

    def _warn_topology_change(self, path_) -> None:
        """Name an elastic (or manual) topology change at restore time.

        Sharded directories record the saver's ``mesh_axes`` in the
        manifest; when they differ from the live plan the restore is a
        cross-topology reshard — legitimate and supported (crop/zero-fill
        reconciliation plus shape-driven resharding), but it must be LOUD:
        the operator reading this log is deciding whether a shrunk pod is
        still the run they want. Single-file checkpoints are skipped —
        peeking one costs a full deserialize and they are replicated
        saves, so there is no sharded layout to mismatch."""
        if not os.path.isdir(os.fspath(path_)):
            return
        from .checkpoint import peek_checkpoint_layout

        layout = peek_checkpoint_layout(path_)
        saved = (layout or {}).get("mesh_axes")
        live = self.plan.describe()
        if not saved or dict(saved) == live:
            return
        logger.warning(
            f"ELASTIC RESUME / topology change: checkpoint {path_} was "
            f"saved under mesh {dict(saved)}, restoring onto {live}. "
            f"Optimizer state is corner-cropped/zero-filled onto the live "
            f"ZeRO-1 layout; the LR schedule is keyed to the GLOBAL batch "
            f"and global_step, so it continues unchanged — at a smaller "
            f"data axis each step consumes the same global batch over "
            f"fewer devices (slower wall-clock, identical math)."
        )
        if self.telemetry is not None:
            flightrec = getattr(self.telemetry, "flightrec", None)
            if flightrec is not None:
                flightrec.record("mesh_shrunk", old=dict(saved), new=live)

    def load_state_dict(self, path_):
        if self._async_ckpt is not None:
            # a restore must observe the last save durably on disk (and a
            # background persist failure must surface before training
            # resumes from possibly-stale state)
            self.finish_pending_checkpoint()
        t0 = time.perf_counter()
        live_opt, live_ls = self._split_ls()
        with trace_mod.span("checkpoint_restore", cat="train",
                            args={"path": str(path_)}):
            params, opt_state, ls_state, global_step = _load_ckpt(
                path_,
                params=self.params,
                opt_state=live_opt,
                loss_scale=live_ls,
                drop_optimizer=self.drop_optimizer,
            )
        if self.telemetry is not None:
            self.telemetry.observe_checkpoint_restore(
                time.perf_counter() - t0)
        if global_step is None:
            return
        self._warn_topology_change(path_)
        if not self.drop_optimizer and live_opt is not None and opt_state is not None:
            # mesh-shape / sharding-mode portability: crop/zero-fill each
            # restored leaf onto the LIVE (possibly differently padded)
            # zero1 layout before re-placement — a save at mesh N resumes
            # at mesh M and across --optimizer_sharding modes
            opt_state = reconcile_state_shapes(opt_state, live_opt)
        if live_ls is not None:
            mode_differs = bool(ls_state.dynamic) != bool(live_ls.dynamic)
            static_value_differs = (
                not bool(live_ls.dynamic)
                and float(ls_state.scale) != float(live_ls.scale)
            )
            if ls_state is not live_ls and (mode_differs or static_value_differs):
                # the flag is CONFIG: neither the mode nor a static value may
                # be silently overridden by what a checkpoint happened to
                # contain — keep the freshly configured state
                logger.warning(
                    "Checkpoint loss-scale state differs from --apex_loss_scale; "
                    "keeping the configured scaling state."
                )
                ls_state = live_ls
            opt_state = ls_lib.OptStateWithLS(opt_state, ls_state)
        # re-place restored host values with the original shardings
        if self._param_shardings is None:
            self.params = shard_params(params, self.mesh)
            if not self.drop_optimizer and self.opt_state is not None:
                from ..parallel.sharding import put_single

                self.opt_state = jax.tree_util.tree_map(
                    lambda x: put_single(x, self.mesh), opt_state
                )
        else:
            # Restored host state goes through a jitted identity with
            # explicit out_shardings, NOT a plain device_put: on the CPU
            # runtime device_put zero-copies a host numpy buffer without
            # keeping it alive, the train step then DONATES that buffer,
            # and the next step reads freed memory (observed as heap
            # corruption on every resume-then-train on the virtual
            # multi-device mesh; msgpack-restored leaves are additionally
            # read-only views into the checkpoint blob, which donation
            # must never write into). The jit identity copies every leaf
            # into runtime-owned buffers in one compiled program.
            self.params = jax.jit(
                lambda x: x, out_shardings=self._param_shardings
            )(params)
            if not self.drop_optimizer and self.opt_state is not None:
                shardings = jax.tree_util.tree_map(lambda x: x.sharding, self.opt_state)
                self.opt_state = jax.jit(
                    lambda x: x, out_shardings=shardings
                )(opt_state)
        self.global_step = global_step
