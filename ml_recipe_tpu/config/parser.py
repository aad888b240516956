"""Layered file+CLI config system.

Parity target: reference ``modules/model/utils/parser.py`` (configargparse-based,
~50 flags across three composable parsers, ``key = value`` config files, the
``'None'``-string cast, multi-parser routing via unused-arg intersection in
``get_params`` parser.py:9-31, and reproducibility round-trip via
``write_config_file`` parser.py:38-50 / ``load_config_file`` parser.py:53-57).

Re-implemented first-party (no configargparse dependency) on top of argparse:
config files are pre-parsed into defaults, and config-file keys unknown to a
given parser are surfaced through ``parse_known_args`` exactly like
configargparse does, so the reference's routing trick — feeding one cfg file to
both the model parser and the trainer parser and erroring only on keys *neither*
recognises — works identically.

TPU deltas (flag names kept wherever the concept survives):
- ``apex_level`` is accepted for config compatibility and mapped onto the
  native ``precision`` policy (O1/O2/O3 -> bf16, O0/None -> f32); Apex itself
  (reference trainer.py:23-32) has no TPU equivalent or need.
- NCCL flags (``dist_backend``/``dist_init_method``/``dist_world_size``/
  ``local_rank``, reference parser.py:162-170) survive with the same names but
  drive ``jax.distributed.initialize`` + mesh construction instead of a TCP
  process-group rendezvous.
- ``mesh`` adds explicit device-mesh axis sizing (``data:8,model:1,seq:1``),
  which has no reference counterpart (the reference is data-parallel only).
"""

from __future__ import annotations

import argparse
import logging
import shlex
import sys
from pathlib import Path
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

from ..models.config import MODEL_PRESETS

logger = logging.getLogger(__name__)


def cast2(type_):
    """'None'-string-aware cast (reference parser.py:34-35)."""
    return lambda x: type_(x) if x != "None" else None


def _str2bool(value: str) -> bool:
    return str(value).strip().lower() in ("1", "true", "yes", "on")


def cast_prefetch(value):
    """Device-prefetch depth domain: an int depth, or 'auto' (measure the
    first few step times and pick depth 1 vs 2, data/device_prefetch.py +
    trainer.resolve_prefetch_auto)."""
    if str(value).strip().lower() == "auto":
        return "auto"
    return int(value)


def cast_bytes(value) -> int:
    """Byte-budget domain for the serving caches: a plain int, or a
    human-friendly K/M/G(iB) suffix ('64M', '1g'). 0 disables."""
    text = str(value).strip().lower()
    for suffix, mult in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30)):
        if text.endswith(suffix):
            return int(float(text[:-1]) * mult)
    return int(text)


def cast_loss_scale(value: str):
    """'None' -> None, 'dynamic' -> 'dynamic', anything else -> float
    (mirrors apex's loss_scale flag domain)."""
    if value == "None":
        return None
    if value == "dynamic":
        return "dynamic"
    return float(value)


# The ONE --mesh help string (both the trainer and the predictor/serve
# parsers register the flag; two hand-maintained copies drifted — ISSUE 15).
# Documents every first-class axis the ParallelPlan understands.
MESH_HELP = (
    "Device mesh axes as 'name:size' pairs, e.g. 'data:8', "
    "'data:4,model:2', 'data:2,seq:4', or 'data:2,pipe:2'. Axes: "
    "data = data parallelism (batch rows; gradients reduce over it, "
    "ZeRO-1 shards optimizer state over it), seq = sequence/context "
    "parallelism (ring attention), model = tensor parallelism "
    "(attention heads / MLP width), pipe = pipeline parallelism "
    "(contiguous encoder-layer stages on a GPipe micro-batch schedule "
    "over the batch_split micro-batches). None = all visible devices "
    "on the data axis."
)


def parse_mesh_spec(spec: Optional[str]) -> dict:
    """Parse ``"data:8,model:1"`` / ``"data=8,model=1"`` into an ordered
    dict. Duplicate axis names and sizes < 1 are rejected HERE, with the
    offending spec in the message — the alternative is a downstream
    device-array reshape failure that names neither."""
    if not spec:
        return {}
    axes: dict = {}
    for part in spec.replace("=", ":").split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size_s = part.partition(":")
        name = name.strip()
        if not name or not sep or not size_s.strip():
            raise ValueError(
                f"mesh spec {spec!r}: malformed entry {part!r} "
                f"(expected 'axis:size')"
            )
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(
                f"mesh spec {spec!r}: axis {name!r} has non-integer size "
                f"{size_s.strip()!r}"
            ) from None
        if name in axes:
            raise ValueError(
                f"mesh spec {spec!r}: duplicate axis {name!r}"
            )
        if size < 1:
            raise ValueError(
                f"mesh spec {spec!r}: axis {name!r} size must be >= 1, "
                f"got {size}"
            )
        axes[name] = size
    return axes


class ConfigArgumentParser(argparse.ArgumentParser):
    """argparse with configargparse-style ``key = value`` config-file layering.

    Arguments registered with ``is_config_file=True`` name the config-file
    options; files listed there are read before parsing and their values
    injected as defaults (CLI always wins). Keys a parser does not know are
    returned as pseudo-args (``--key=value``) from ``parse_known_args`` so
    multi-parser routing can intersect them (reference parser.py:9-31).
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._config_file_dests: List[str] = []

    def add_argument(self, *args, **kwargs):  # type: ignore[override]
        is_config_file = kwargs.pop("is_config_file", False)
        action = super().add_argument(*args, **kwargs)
        if is_config_file:
            self._config_file_dests.append(action.dest)
        return action

    # -- config file handling -------------------------------------------------

    @staticmethod
    def read_config_file(path) -> dict:
        """Read ``key = value`` lines; '#'/';' comments; later keys win."""
        items: dict = {}
        with open(path, "r") as fh:
            for raw in fh:
                line = raw.strip()
                if not line or line.startswith("#") or line.startswith(";"):
                    continue
                key, sep, value = line.partition("=")
                if not sep:
                    continue
                items[key.strip()] = value.split("#")[0].strip()
        return items

    def _find_config_files(self, args: Sequence[str]) -> List[str]:
        option_names = {}
        for action in self._actions:
            if action.dest in self._config_file_dests:
                for opt in action.option_strings:
                    option_names[opt] = action.dest
        files = []
        it = iter(range(len(args)))
        for i in it:
            arg = args[i]
            if "=" in arg and arg.split("=", 1)[0] in option_names:
                files.append(arg.split("=", 1)[1])
            elif arg in option_names and i + 1 < len(args):
                files.append(args[i + 1])
        return files

    def _apply_config_items(self, items: dict) -> List[str]:
        """Inject known keys as defaults; return unknown keys as pseudo-args."""
        known = {a.dest: a for a in self._actions}
        unknown: List[str] = []
        for key, value in items.items():
            action = known.get(key)
            if action is None or action.dest in self._config_file_dests:
                if action is None:
                    unknown.append(f"--{key}={value}")
                continue
            if isinstance(action, (argparse._StoreTrueAction, argparse._StoreFalseAction)):
                self.set_defaults(**{key: _str2bool(value)})
                continue
            converted = action.type(value) if action.type is not None else value
            # set_defaults skips argparse's choice validation — enforce it
            # here so a config-file typo fails as loudly as a CLI one
            if action.choices is not None and converted not in action.choices:
                self.error(
                    f"argument --{key}: invalid choice: {converted!r} "
                    f"(choose from {', '.join(map(str, action.choices))})"
                )
            self.set_defaults(**{key: converted})
        return unknown

    def parse_known_args(self, args=None, namespace=None):  # type: ignore[override]
        if args is None:
            args = sys.argv[1:]
        args = list(args)
        config_unknown: List[str] = []
        for path in self._find_config_files(args):
            items = self.read_config_file(path)
            config_unknown.extend(self._apply_config_items(items))
        namespace, cli_unknown = super().parse_known_args(args, namespace)
        return namespace, config_unknown + cli_unknown

    # -- round-trip serialization --------------------------------------------

    def serialize(self, config_items: dict) -> str:
        lines = []
        for key, value in config_items.items():
            lines.append(f"{key} = {value}")
        return "\n".join(lines) + "\n"


def get_params(
    parser_getters: Iterable[Callable[[], ConfigArgumentParser]],
    args: Optional[Sequence[str]] = None,
) -> Tuple[list, list]:
    """Parse with several parsers; die only on args *no* parser recognises.

    Reference parity: parser.py:9-31 (unused-arg intersection routing).
    """
    unused = None
    parsers = []
    params = []

    for parser_getter in parser_getters:
        parser = parser_getter()
        parsed_params, unused_params = parser.parse_known_args(args)

        parsers.append(parser)
        params.append(parsed_params)

        unused_set = {u.split("=", 1)[0] for u in unused_params}
        unused = unused_set if unused is None else unused.intersection(unused_set)

    if unused:
        for parser in parsers:
            parser.print_help()
        raise SystemExit(f"Incorrect command line parameters: {sorted(unused)}.")

    return parsers, params


def write_config_file(parser: ConfigArgumentParser, parsed_namespace, output_path) -> None:
    """Serialize the effective config into the experiment dir (parser.py:38-50)."""
    config_items = {
        k: getattr(parsed_namespace, k)
        for k in sorted(parsed_namespace.__dict__.keys())
        if "config" not in k
    }
    file_contents = parser.serialize(config_items)

    try:
        with open(output_path, "w") as output_file:
            output_file.write(file_contents)
    except IOError as e:
        logger.error(f"Could not open file {output_path}.")
        raise e

    logger.info(f"Config was saved to {output_path}.")


def load_config_file(parser_getter, config_path):
    """Reload a serialized config (notebook path, parser.py:53-57)."""
    parser = parser_getter()
    parsed_params, _ = parser.parse_known_args(shlex.split(f"-c {config_path}"))
    return parser, parsed_params


# ---------------------------------------------------------------------------
# Parser factories — flag surface parity with reference parser.py:60-207.
# ---------------------------------------------------------------------------

# derived from the preset registry so the flag and the registry cannot drift
MODEL_CHOICES = list(MODEL_PRESETS)


def get_model_parser() -> ConfigArgumentParser:
    parser = ConfigArgumentParser(description="Model config parser.", add_help=False)

    parser.add_argument("-c", "--config_file", required=False, is_config_file=True,
                        help="Config file path.")
    parser.add_argument("--model_config_file", required=False, is_config_file=True,
                        help="Model config file path.")

    parser.add_argument("--model", type=str, default="bert-base-uncased",
                        choices=MODEL_CHOICES, help="Transformer model name.")

    parser.add_argument("--hidden_dropout_prob", type=float, default=0.1,
                        help="Model dropout probability.")
    parser.add_argument("--attention_probs_dropout_prob", type=float, default=0.1,
                        help="Attention dropout probability.")
    parser.add_argument("--layer_norm_eps", type=float, default=1e-12, help="Layer norm eps.")
    parser.add_argument("--max_position_embeddings", type=cast2(int), default=None,
                        help="Widen the position-embedding table past the "
                             "preset's (required for max_seq_len beyond it — "
                             "positions past the table are a hard error, "
                             "never a silent clamp).")

    parser.add_argument("--vocab_file", type=cast2(str), default=None,
                        help="Path to WordPiece/BPE vocab.")
    parser.add_argument("--merges_file", type=cast2(str), default=None,
                        help="BPE merge table path.")

    parser.add_argument("--lowercase", action="store_true", help="Tokenize lowercase strings.")
    parser.add_argument("--handle_chinese_chars", action="store_true",
                        help="Do not replace chinese symbols with UNK tokens.")

    # TPU-native additions (no reference counterpart):
    parser.add_argument("--hf_checkpoint", type=cast2(str), default=None,
                        help="HF pretrained dir/name to convert initial weights from "
                             "(None = random init).")
    parser.add_argument("--param_dtype", type=str, default="float32",
                        choices=["float32", "bfloat16"], help="Parameter dtype.")
    parser.add_argument("--compute_dtype", type=str, default="bfloat16",
                        choices=["float32", "bfloat16"],
                        help="Activation/matmul dtype (native mixed precision; "
                             "replaces Apex AMP levels).")
    parser.add_argument("--flash_attention", type=cast2(str), default="auto",
                        choices=[None, "auto", "pallas", "xla", "ring"],
                        help="Attention implementation: pallas kernel, plain XLA, "
                             "auto (pallas on TPU when shapes/dropout allow), or "
                             "ring (sequence-parallel over the mesh 'seq' axis).")
    parser.add_argument("--remat", action="store_true",
                        help="Rematerialize the trunk's layers (jax.checkpoint) to "
                             "trade FLOPs for HBM: an encoder layer keeps its "
                             "inputs and is run again whole in its backward "
                             "pass; a decoder-trunk layer keeps every matmul's "
                             "output and every kernel call's outputs and runs "
                             "only its elementwise work again.")
    parser.add_argument("--ln_impl", type=cast2(str), default="xla",
                        choices=[None, "xla", "fused", "auto", "interpret"],
                        help="LayerNorm implementation: xla (default — the "
                             "round-5 on-chip A/B measured the fused kernel "
                             "a wash, XLA already fuses LN into matmul "
                             "epilogues), fused (one-pass Pallas backward "
                             "on TPU; falls back to xla off-TPU), auto "
                             "(fused on TPU when the geometry qualifies), "
                             "interpret (kernel under pallas interpret mode "
                             "— tests only).")

    return parser


def init_base_arguments(parser: ConfigArgumentParser) -> None:
    parser.add_argument("-c", "--config_file", required=False, is_config_file=True,
                        help="Config file path.")

    parser.add_argument("--data_path", type=str, default=None,
                        help="Path to JSON with documents.")
    parser.add_argument("--processed_data_path", type=str, default=None,
                        help="Path where processed dataset will be saved.")

    parser.add_argument("--gpu", action="store_true",
                        help="Accepted for reference-config compatibility; device "
                             "selection on TPU is automatic.")

    parser.add_argument("--max_seq_len", type=int, default=384, help="Max input seq length.")
    parser.add_argument("--max_question_len", type=int, default=64, help="Max question length.")
    parser.add_argument("--doc_stride", type=int, default=128,
                        help="Step size during doc splitting.")

    parser.add_argument("--split_by_sentence", action="store_true",
                        help="Split document by sentence instead.")
    parser.add_argument("--truncate", action="store_true",
                        help="Cut off long sentences during splitting by sentence.")

    parser.add_argument("--n_jobs", type=int, default=16,
                        help="Number of host-side data pipeline workers.")


def get_trainer_parser() -> ConfigArgumentParser:
    parser = ConfigArgumentParser(description="Trainer config parser.", add_help=False)
    init_base_arguments(parser)

    parser.add_argument("--trainer_config_file", required=False, is_config_file=True,
                        help="Trainer config file path.")

    parser.add_argument("--dump_dir", type=Path, default=Path("./results"), help="Dump path.")
    parser.add_argument("--experiment_name", type=str, default="test", help="Experiment name.")

    parser.add_argument("--last", type=cast2(str), default=None, help="Restored checkpoint.")

    parser.add_argument("--seed", type=cast2(int), default=None, help="Seed for random state.")

    parser.add_argument("--n_epochs", type=int, default=10, help="Number of epochs.")

    parser.add_argument("--train_batch_size", type=int, default=128,
                        help="Global number of items in an optimizer-step batch.")
    parser.add_argument("--test_batch_size", type=int, default=16,
                        help="Number of items in batch.")
    parser.add_argument("--batch_split", type=int, default=1,
                        help="Micro-batch count for gradient accumulation "
                             "(lax.scan inside the jitted step).")

    parser.add_argument("--lr", type=float, default=1e-5, help="Learning rate for optimizer.")
    parser.add_argument("--weight_decay", type=float, default=0.01,
                        help="Weight decay for optimizer.")

    parser.add_argument("--clear_processed", action="store_true",
                        help="Clear previous processed dataset.")

    parser.add_argument("--w_start", type=float, default=1,
                        help="Weight of start position classification.")
    parser.add_argument("--w_end", type=float, default=1,
                        help="Weight of end position classification.")
    parser.add_argument("--w_start_reg", type=float, default=0,
                        help="Weight of start position regression loss.")
    parser.add_argument("--w_end_reg", type=float, default=0,
                        help="Weight of end position regression loss.")
    parser.add_argument("--w_cls", type=float, default=1,
                        help="Weight of doc label classification.")

    parser.add_argument("--loss", type=str, default="ce", choices=["ce", "focal", "smooth"],
                        help="Type of doc label classification loss")

    parser.add_argument("--smooth_alpha", type=float, default=0.01,
                        help="Smooth CE loss parameter.")
    parser.add_argument("--focal_alpha", type=float, default=1, help="Focal loss parameter.")
    parser.add_argument("--focal_gamma", type=float, default=2, help="Focal loss parameter.")

    parser.add_argument("--max_grad_norm", type=float, default=1,
                        help="Max global norm of the gradients")
    parser.add_argument("--optimizer_sharding", type=cast2(str), default=None,
                        choices=[None, "off", "zero1"],
                        help="Optimizer-state layout: 'zero1' shards every "
                             "AdamW/AdaMod state leaf over the mesh data "
                             "axis (padding-aware per-leaf specs; memory "
                             "~1/N per chip) and runs the weight update on "
                             "each replica's shard only — grads reduce-"
                             "scatter, updated params all-gather back "
                             "replicated. 'off' replicates the full state "
                             "per chip (historical layout; 1-chip zero1 is "
                             "bit-identical to off). Default defers to the "
                             "legacy --shard_optimizer boolean.")
    parser.add_argument("--shard_optimizer", action="store_true",
                        help="Legacy alias of --optimizer_sharding zero1 "
                             "(kept for existing configs): shard optimizer "
                             "moments over the mesh data axis (memory 1/N; "
                             "XLA all-gathers the sharded updates). The "
                             "reference replicates optimizer state per "
                             "process.")
    parser.add_argument("--pipe_schedule", type=cast2(str), default="gpipe",
                        choices=["gpipe", "1f1b"],
                        help="Pipeline tick schedule when --mesh has a pipe "
                             "axis > 1: 'gpipe' (default) keeps all "
                             "batch_split micro-batch activations resident "
                             "through the forward sweep; '1f1b' interleaves "
                             "one-forward-one-backward so at most "
                             "min(batch_split, 2K-1) stage inputs stay "
                             "resident. Gradients accumulate exactly as the "
                             "sequential scan (same trajectory within "
                             "pipeline tolerance); inert without a pipe "
                             "axis.")
    parser.add_argument("--pipe_param_sharding", type=cast2(str),
                        default="auto",
                        choices=["auto", "stage", "replicated"],
                        help="Pipeline parameter/optimizer storage: 'stage' "
                             "keeps each pipe rank holding ONLY its own "
                             "stage's trunk weights and moments (~1/K "
                             "per-chip bytes; islands all-gather slices "
                             "per tick), 'replicated' keeps the PR-15 "
                             "every-rank-holds-everything layout, 'auto' "
                             "(default) picks 'stage' whenever the pipe "
                             "axis is > 1 on a multi-device mesh.")
    parser.add_argument("--async_checkpoint", action="store_true",
                        help="Async overlapped checkpointing: saves block "
                             "only for the device-to-host snapshot; the "
                             "serialize+write persist runs on a background "
                             "thread with the same per-leaf crc32 and "
                             "atomic-rename discipline, a completion "
                             "barrier before the next save / restore / "
                             "exit / SIGTERM resume, and the previous "
                             "valid checkpoint staying newest if a crash "
                             "lands mid-persist. Saved bytes are identical "
                             "to a sync save of the same step.")
    parser.add_argument("--sharded_checkpoint", action="store_true",
                        help="Checkpoint saves write a per-process sharded "
                             "directory (each host saves only the array "
                             "shards it owns) instead of gathering the full "
                             "state for one single-file write. Restore "
                             "auto-detects either layout and works across "
                             "topology changes (save at world N, restore at "
                             "world M), but reassembles the full state on "
                             "each host — the no-gather memory bound applies "
                             "to saves only.")
    parser.add_argument("--sync_bn", action="store_true",
                        help="Cross-replica normalization statistics sync (reference "
                             "SyncBN flag; BERT has LayerNorm so this is a no-op "
                             "unless BatchNorm layers are present).")

    parser.add_argument("--warmup_coef", type=float, default=0.05, help="Warmup coefficient.")

    # Padding-free input pipeline (data/bucketing.py + data/device_prefetch.py).
    parser.add_argument("--length_buckets", type=str, default="off",
                        help="Length-bucketed token-budget batching: 'off' "
                             "(pad every batch to max_seq_len — historical "
                             "behavior), 'auto' (evenly spaced seq grid "
                             "ending at max_seq_len, e.g. 128,256,384,512), "
                             "or explicit comma-separated seq edges. Batches "
                             "pad to their BUCKET and the per-bucket batch "
                             "size scales inversely with seq (constant "
                             "token budget per step); one compiled program "
                             "per occupied bucket. Single-process only.")
    parser.add_argument("--sequence_packing", type=str, default="off",
                        help="Sequence packing (data/packing.py): "
                             "concatenate short chunks into full "
                             "max_seq_len rows with block-diagonal "
                             "attention and per-segment heads — ~every "
                             "token real, ONE compiled train program "
                             "(vs one per bucket). 'off' (default) keeps "
                             "the bucketed/padded path bit-exactly; 'on' "
                             "enables it and supersedes --length_buckets. "
                             "Single-process only.")
    parser.add_argument("--pack_max_segments", type=int, default=8,
                        help="Sequence packing: max chunks packed into one "
                             "row (the static S of the per-segment label "
                             "planes and head outputs).")
    parser.add_argument("--pack_splitting", type=str, default="off",
                        help="Hole-filling chunk splitting for the packer: "
                             "'off' (default — the non-splitting packer, "
                             "bit-identical to before) or 'fill' (a chunk "
                             "that fits no open row is split at a "
                             "label-safe token boundary — never through "
                             "the gold answer span — and its head "
                             "fragment fills the largest residual hole; "
                             "the span-bearing fragment keeps the labels, "
                             "siblings are ignore-indexed). Breaks the "
                             "~1.6%% waste floor of quantized chunk mixes.")
    parser.add_argument("--pack_min_fragment", type=int, default=32,
                        help="Splitting packer: minimum fragment size in "
                             "tokens (no head or tail fragment goes below "
                             "this — avoids degenerate few-token "
                             "segments).")
    parser.add_argument("--device_prefetch", type=cast_prefetch, default=0,
                        help="Double-buffered device prefetch depth: keep "
                             "this many placed global batches in flight on "
                             "a background thread so the host->device copy "
                             "of step k+1 overlaps compute of step k. 0 = "
                             "synchronous placement (historical behavior); "
                             "2 is the intended on-chip setting; 'auto' "
                             "times the first few steps of epoch 1 and "
                             "picks depth 1 vs 2, logging the choice. The "
                             "trajectory is bit-identical at any depth.")
    parser.add_argument("--log_every", type=int, default=10,
                        help="Steps between tqdm-postfix/TensorBoard writes "
                             "in the train loop (meters still update every "
                             "step; the epoch's final state is always "
                             "written).")

    # Kernel geometry autotuner + HBM pre-flight planner (measured
    # configuration over analytic byte-counting).
    parser.add_argument("--autotune", type=_str2bool, default=True,
                        help="Compile-probe kernel geometry autotuner "
                             "(ops/autotune.py): on TPU, attention block "
                             "geometries are validated with real lowering "
                             "probes, ranked by modeled step cost, and "
                             "persisted in the on-disk tuning cache; off "
                             "reverts to pure analytic VMEM arithmetic. "
                             "CPU/interpret always uses the arithmetic.")
    parser.add_argument("--autotune_cache", type=cast2(str), default=None,
                        help="Directory of the tuning cache (default "
                             "artifacts/tuning/, or $MLRT_AUTOTUNE_CACHE).")
    parser.add_argument("--aot_cache", type=cast2(str), default=None,
                        help="AOT compiled-program store (ops/aot.py): "
                             "inactive unless a directory is named here or "
                             "in $MLRT_AOT_CACHE ('off' overrides the env). "
                             "With a store, a warm restart deserializes its "
                             "train-step programs instead of recompiling "
                             "them; without one JAX's persistent compilation "
                             "cache serves every compile.")
    parser.add_argument("--aot_cache_bytes", type=cast_bytes, default=0,
                        help="Byte budget for the AOT program store "
                             "(K/M/G suffixes); oldest artifacts are "
                             "evicted past it. 0 = unbounded.")
    parser.add_argument("--hbm_preflight", type=_str2bool, default=True,
                        help="Before the first train step, compile once and "
                             "read XLA's memory_analysis; if the step "
                             "exceeds device HBM, raise batch_split "
                             "(logged with before/after byte counts) "
                             "instead of dying in XLA allocation.")

    # Mixed precision: native policy + accepted Apex aliases.
    parser.add_argument("--precision", type=cast2(str), default=None,
                        choices=[None, "f32", "bf16"],
                        help="Mixed-precision policy. None defers to apex_level mapping.")
    parser.add_argument("--apex_level", type=cast2(str),
                        choices=[None, "O0", "O1", "O2", "O3"], default=None,
                        help="Reference-compat alias: O1/O2/O3 -> bf16, O0/None -> f32.")
    parser.add_argument("--apex_verbosity", type=int, default=1,
                        help="Accepted for config compatibility.")
    parser.add_argument("--apex_loss_scale", type=cast_loss_scale, default=None,
                        help="Loss scale: a number for static, 'dynamic' for "
                             "apex-style dynamic scaling (halve on overflow, "
                             "double after 2000 finite steps, update skipped "
                             "on overflow). bf16 on TPU normally needs none.")

    parser.add_argument("--drop_optimizer", action="store_true",
                        help="Not restore optimizer and scheduler from checkpoint.")

    parser.add_argument("--debug", action="store_true", help="Debug mode.")
    parser.add_argument("--trace", action="store_true",
                        help="Dump an xplane device trace of train steps 2-4 "
                             "into <dump_dir>/board/<experiment>/trace "
                             "(view with TensorBoard/XProf).")
    parser.add_argument("--dummy_dataset", action="store_true",
                        help="Use generated dataset instead real data.")

    # Distributed: reference names preserved, XLA semantics underneath.
    parser.add_argument("--local_rank", type=int, default=-1,
                        help="Process index of this host (reference name kept; feeds "
                             "jax.distributed.initialize process_id).")
    parser.add_argument("--dist_backend", type=str, default="xla", choices=["xla", "nccl"],
                        help="Accepted for compatibility; collectives always run "
                             "through XLA over ICI/DCN.")
    parser.add_argument("--dist_init_method", type=str, default="tcp://127.0.0.1:9080",
                        help="Coordinator address (host:port); tcp:// prefix accepted "
                             "for reference compatibility.")
    parser.add_argument("--dist_world_size", type=int, default=1,
                        help="Number of host processes.")
    parser.add_argument("--mesh", type=cast2(str), default=None,
                        help=MESH_HELP)

    # Fault tolerance (resilience/): supervised restart + watchdog + drills.
    parser.add_argument("--supervise", action="store_true",
                        help="Wrap the run in the auto-resume supervisor: "
                             "restart on preemption/hang/crash with "
                             "exponential backoff, resume from the newest "
                             "valid checkpoint, abort on a crash-loop.")
    parser.add_argument("--max_restarts", type=int, default=5,
                        help="Supervisor: restarts after the first attempt.")
    parser.add_argument("--backoff_base", type=float, default=1.0,
                        help="Supervisor: seconds before the first restart "
                             "(doubles per restart, seeded +-10%% jitter).")
    parser.add_argument("--backoff_max", type=float, default=30.0,
                        help="Supervisor: backoff ceiling in seconds.")
    parser.add_argument("--crash_loop_window", type=int, default=3,
                        help="Supervisor: abort with a diagnosis after this "
                             "many consecutive failed attempts with no "
                             "global_step progress.")
    parser.add_argument("--watchdog_timeout", type=cast2(float), default=None,
                        help="Seconds a train/eval step or checkpoint "
                             "barrier may take before the watchdog dumps "
                             "all-thread stacks and aborts for restart. "
                             "None disables. Must comfortably exceed the "
                             "first (compiling) step.")
    parser.add_argument("--fault_plan", type=cast2(str), default=None,
                        help="Fault-injection drill spec, e.g. "
                             "'ckpt.pre_manifest:kill@2!once;"
                             "loader.read:raise@1x3' "
                             "(see resilience/faults.py for the grammar, "
                             "including %%hostN host scoping; "
                             "also via $MLRT_FAULTS).")
    parser.add_argument("--elastic", type=cast2(str), default="off",
                        choices=["off", "on"],
                        help="Elastic pod supervision (with --supervise): "
                             "per-host supervisors coordinate through "
                             "<exp_dir>/pod/ heartbeat files — a dead "
                             "host's peers kill+restart their children "
                             "immediately and resume on a re-derived "
                             "smaller mesh (data axis shrinks; pipe/seq/"
                             "model refuse). Default off: fixed-world "
                             "supervision, byte-identical to before.")
    parser.add_argument("--min_world", type=int, default=1,
                        help="Elastic: abort (instead of shrinking further) "
                             "when fewer live hosts remain — training "
                             "degenerately narrow burns budget silently.")
    parser.add_argument("--host_timeout", type=float, default=60.0,
                        help="Elastic: seconds a peer host's heartbeat may "
                             "age before it is declared lost and the pod "
                             "restarts without it.")
    parser.add_argument("--coord_poll", type=float, default=2.0,
                        help="Elastic: seconds between coordination sweeps "
                             "(heartbeat publish + peer reads) while the "
                             "child runs.")

    # Observability plane (metrics/ + train/telemetry.py): everything off
    # by default — the off path is pinned bit-identical.
    parser.add_argument("--metrics_port", type=cast2(int), default=None,
                        help="Serve the training-plane Prometheus registry "
                             "at http://0.0.0.0:<port>/metrics (+ /healthz) "
                             "from a daemon thread: per-step wall-time "
                             "breakdown (data wait / host / device), "
                             "tokens/sec, padding waste, checkpoint "
                             "durations, watchdog heartbeat age, supervisor "
                             "restart counts. 0 binds an ephemeral port "
                             "(logged); None (default) disables. Multi-host "
                             "runs add the process index to the port so "
                             "each host exports its own plane.")
    parser.add_argument("--trace_spans", type=cast2(str), default=None,
                        help="Write structured host trace spans (loader -> "
                             "place/H2D -> step -> checkpoint) as Chrome "
                             "trace-event JSON into this directory — load "
                             "in Perfetto. Composes with --trace: the "
                             "xplane window boundaries are marked in the "
                             "span stream. None (default) disables.")
    parser.add_argument("--anomaly_factor", type=float, default=3.0,
                        help="Slow-step detector (active with "
                             "--metrics_port): a step slower than this "
                             "factor times the rolling median step time "
                             "logs one structured WARNING with the "
                             "breakdown attribution and increments "
                             "train_slow_steps_total.")
    parser.add_argument("--anomaly_window", type=int, default=64,
                        help="Slow-step detector: rolling window size "
                             "(steps) for the median+MAD baseline.")
    parser.add_argument("--goodput_ledger", action="store_true",
                        help="Keep the run-level goodput ledger "
                             "(goodput.jsonl next to supervisor_state.json "
                             "in the experiment dir): an append-only event "
                             "log partitioning total run wall-clock into "
                             "productive step time vs named badput "
                             "(compile/warmup, data wait, checkpoint "
                             "save/restore, eval, restart downtime, "
                             "recomputed steps), summarized at run end and "
                             "exported as train_goodput_ratio + "
                             "train_badput_seconds_total{category=...}. "
                             "Survives supervised restarts. Off by "
                             "default.")
    parser.add_argument("--flight_recorder", action="store_true",
                        help="Arm the crash flight recorder: a bounded "
                             "ring of the last N structured events (step "
                             "breakdown, anomaly verdicts, checkpoint "
                             "events, loss-scale adjustments) dumped "
                             "atomically to a timestamped JSON in the "
                             "experiment dir on crash, watchdog abort, "
                             "SIGTERM and periodically — the supervisor's "
                             "crash-loop diagnosis reads the newest dump "
                             "back. Off by default.")
    parser.add_argument("--flightrec_events", type=int, default=256,
                        help="Flight recorder: ring capacity (events kept "
                             "in the crash dump).")
    parser.add_argument("--metrics_hosts", type=cast2(str), default=None,
                        help="Comma-separated host:port list of every "
                             "host's /metrics exporter. Process 0 then "
                             "serves the pod-scope merged page (sum/min/"
                             "max + per-host views, slowest-host and "
                             "step-time-skew gauges) at /metrics/pod on "
                             "its own exporter. Requires --metrics_port. "
                             "None (default) disables.")

    parser.add_argument("--best_metric", choices=["map"], type=str, default="map",
                        help="Best metric name.")
    parser.add_argument("--best_order", choices=[">", "<"], type=str, default=">",
                        help="Best metric order.")

    parser.add_argument("--finetune", action="store_true", help="Turn on finetune mode.")
    parser.add_argument("--finetune_transformer", action="store_true",
                        help="Finetune transformer module.")
    parser.add_argument("--finetune_position", action="store_true",
                        help="Finetune classification head.")
    parser.add_argument("--finetune_position_reg", action="store_true",
                        help="Finetune regression head.")
    parser.add_argument("--finetune_class", action="store_true",
                        help="Finetune doc label classification head.")

    parser.add_argument("--bpe_dropout", type=cast2(float), default=None, help="Use BPE dropout.")

    parser.add_argument("--optimizer", type=str, default="adam", choices=["adam", "adamod"],
                        help="Optimizer name.")

    parser.add_argument("--train_label_weights", action="store_true",
                        help="Use label weights in CE loss.")
    parser.add_argument("--train_sampler_weights", action="store_true",
                        help="Use oversampling.")

    parser.add_argument("--log_file", type=str, default=None,
                        help="This parameter is ignored. After dump will consist "
                             "path to log file.")

    return parser


def get_predictor_parser() -> ConfigArgumentParser:
    parser = ConfigArgumentParser(description="Validation config parser.", add_help=False)
    init_base_arguments(parser)

    parser.add_argument("--predictor_config_file", required=False, is_config_file=True,
                        help="Predictor config file path.")

    parser.add_argument("--checkpoint", type=cast2(str), default=None,
                        help="Restored checkpoint path.")

    parser.add_argument("--batch_size", type=int, default=16, help="Batch size.")
    parser.add_argument("--buffer_size", type=int, default=4096, help="Buffer queue size.")

    parser.add_argument("--limit", type=cast2(int), default=None,
                        help="Process only specified number of documents.")

    parser.add_argument("--fetch_every", type=int, default=1,
                        help="Group device->host output fetches over this many "
                             "completed batches (amortizes per-fetch latency; "
                             "1 = fetch per batch, the default — grouping "
                             "only pays when the loop is fetch-bound, which "
                             "is unmeasured on this chip; sweep it with "
                             "bench.py --mode infer --fetch_every N).")

    parser.add_argument("--gpu_compat", action="store_true",
                        help="Accepted for reference-config compatibility.")

    parser.add_argument("--length_buckets", type=str, default="off",
                        help="Length-bucketed chunk batching for offline "
                             "eval: 'off', 'auto', or comma-separated seq "
                             "edges (see the trainer flag). Chunks pad to "
                             "their bucket instead of max_seq_len; the "
                             "per-bucket batch size holds the token budget "
                             "batch_size * max_seq_len constant.")
    parser.add_argument("--sequence_packing", type=str, default="off",
                        help="Sequence packing for offline eval: chunks "
                             "concatenate into full max_seq_len rows "
                             "(block-diagonal attention, per-segment "
                             "scoring with per-chunk score parity); "
                             "supersedes --length_buckets (see the "
                             "trainer flag).")
    parser.add_argument("--pack_max_segments", type=int, default=8,
                        help="Sequence packing: max chunks per packed row.")
    parser.add_argument("--pack_splitting", type=str, default="off",
                        help="Hole-filling chunk splitting for packed "
                             "offline eval ('off'|'fill'); fragment span "
                             "logits re-merge to per-chunk outputs before "
                             "the span reduction.")
    parser.add_argument("--pack_min_fragment", type=int, default=32,
                        help="Splitting packer: minimum fragment size in "
                             "tokens.")

    parser.add_argument("--quantize", type=str, default="off",
                        choices=["off", "int8"],
                        help="Post-training quantization for offline eval "
                             "(quant/): 'int8' converts the restored float "
                             "checkpoint to per-channel int8 kernels and "
                             "scores through the fused int8 matmul path — "
                             "the same conversion the serving engine "
                             "performs, so quantized span accuracy can be "
                             "measured before deployment. 'off' (default) "
                             "is bit-identical to the historical path.")

    return parser


def get_serve_parser() -> ConfigArgumentParser:
    """Online-serving config ([serve] surface): bucket grid, micro-batch
    deadline, bounded-queue backpressure, HTTP bind, drain budget. No
    reference counterpart — the reference stack is offline-only."""
    parser = ConfigArgumentParser(description="Serve config parser.", add_help=False)

    parser.add_argument("-c", "--config_file", required=False, is_config_file=True,
                        help="Config file path.")
    parser.add_argument("--serve_config_file", required=False, is_config_file=True,
                        help="Serve config file path.")

    parser.add_argument("--checkpoint", type=cast2(str), default=None,
                        help="Restored checkpoint path (None = random init — "
                             "smoke/bench only).")

    parser.add_argument("--host", type=str, default="127.0.0.1",
                        help="HTTP bind address.")
    parser.add_argument("--port", type=int, default=8080,
                        help="HTTP bind port (0 = ephemeral).")

    parser.add_argument("--buckets", type=str, default="8x128,8x384,32x384",
                        help="Serving bucket grid 'BATCHxSEQ,...': the fixed "
                             "set of pre-compiled (batch, seq) programs. A "
                             "chunk runs in the smallest seq bucket that "
                             "fits it; concurrent chunks coalesce up to the "
                             "bucket batch.")
    parser.add_argument("--max_batch_delay_ms", type=float, default=10.0,
                        help="Micro-batch deadline: a queued chunk waits at "
                             "most this long for co-riders before its "
                             "bucket launches (a full bucket launches "
                             "immediately).")
    parser.add_argument("--queue_size", type=int, default=256,
                        help="Bounded work-queue size in CHUNKS; admission "
                             "past it is rejected with 429 (backpressure) "
                             "instead of growing unboundedly.")
    parser.add_argument("--request_timeout_s", type=float, default=60.0,
                        help="Per-request completion deadline (504 past it).")
    parser.add_argument("--drain_timeout_s", type=float, default=30.0,
                        help="SIGTERM drain budget: flush admitted work and "
                             "close within this long.")

    parser.add_argument("--max_question_len", type=int, default=64,
                        help="Max question length in tokens.")
    parser.add_argument("--doc_stride", type=int, default=128,
                        help="Sliding-window stride for request chunking.")
    parser.add_argument("--long_scatter_chunks", type=int, default=0,
                        help="Long-request scatter threshold: a request "
                             "whose document windows into at least this "
                             "many chunks bypasses deadline coalescing and "
                             "launches its chunks chunk-parallel as "
                             "dedicated batches (BucketGrid.scatter_plan) "
                             "— a whole book answers in one POST /v1/qa "
                             "call. 0 disables the path.")

    parser.add_argument("--mesh", type=cast2(str), default=None,
                        help=MESH_HELP)

    parser.add_argument("--autotune", type=_str2bool, default=True,
                        help="Kernel-geometry autotuner during bucket "
                             "warmup compiles (ops/autotune.py); the "
                             "on-disk tuning cache makes a warm restart "
                             "zero-probe.")
    parser.add_argument("--autotune_cache", type=cast2(str), default=None,
                        help="Tuning-cache directory (default "
                             "artifacts/tuning/, or $MLRT_AUTOTUNE_CACHE).")
    parser.add_argument("--aot_cache", type=cast2(str), default=None,
                        help="AOT compiled-program store (ops/aot.py): "
                             "inactive unless a directory is named here or "
                             "in $MLRT_AOT_CACHE ('off' overrides the env). "
                             "With a store, a rolling-restart replacement "
                             "engine deserializes every bucket program "
                             "instead of recompiling the grid.")
    parser.add_argument("--aot_cache_bytes", type=cast_bytes, default=0,
                        help="Byte budget for the AOT program store "
                             "(K/M/G suffixes); oldest artifacts are "
                             "evicted past it. 0 = unbounded.")
    parser.add_argument("--hbm_preflight", type=_str2bool, default=True,
                        help="Per-bucket predict-step HBM pre-flight at "
                             "warmup: memory_analysis each bucket program "
                             "and DROP buckets that exceed device HBM "
                             "instead of OOMing mid-traffic.")
    parser.add_argument("--serve_cache_bytes", type=cast_bytes, default=0,
                        help="Tier-2 chunk-result cache byte budget "
                             "(serve/cache.py; plain bytes or K/M/G "
                             "suffix). Caches the packed span-logit row of "
                             "each exact device input row, keyed by a hash "
                             "of the assembled row + the checkpoint "
                             "fingerprint + the active precision, with "
                             "single-flight dedup of identical in-flight "
                             "chunks — repeated (question, document) "
                             "traffic bypasses the device entirely. 0 "
                             "(default) disables the tier; cached and "
                             "uncached responses are bit-identical.")
    parser.add_argument("--doc_cache_bytes", type=cast_bytes, default=0,
                        help="Tier-1 document-preprocessing cache byte "
                             "budget (serve/cache.py; plain bytes or K/M/G "
                             "suffix). Caches encode_document tokens and "
                             "the window_chunks layout keyed by document "
                             "content hash, so hot documents skip host "
                             "tokenization entirely. 0 (default) disables "
                             "the tier.")
    parser.add_argument("--quantize", type=str, default="off",
                        choices=["off", "int8"],
                        help="Serving precision: 'int8' converts the float "
                             "checkpoint to per-channel int8 kernels at "
                             "startup (quant/; no retraining, checkpoints "
                             "unchanged) and compiles every bucket program "
                             "through the fused int8 matmul path — ~2x MXU "
                             "peak and ~4x smaller weight residency (the "
                             "HBM pre-flight sees it; bigger buckets fit). "
                             "'off' (default) serves bf16 bit-identically "
                             "to the historical engine.")

    parser.add_argument("--ready_file", type=cast2(str), default=None,
                        help="Write {host, port, pid} JSON here once the "
                             "listener is up (supervisor / test "
                             "orchestration hook).")

    parser.add_argument("--trace_spans", type=cast2(str), default=None,
                        help="Write structured serving trace spans "
                             "(admission -> queue -> flush -> device -> "
                             "span_reduce -> respond, keyed by request id) "
                             "as Chrome trace-event JSON into this "
                             "directory — load in Perfetto. The file is "
                             "flushed on drain. None (default) disables.")

    return parser


def get_fleet_parser() -> ConfigArgumentParser:
    """Serving-fleet config ([fleet] surface): router tier size, ring
    geometry, health-driven shedding thresholds, rolling restarts. The
    fleet CLI composes this with the serve + model parsers — serve flags
    (buckets, caches, drain budget, --host/--port for the ROUTER bind)
    are forwarded to every engine child."""
    parser = ConfigArgumentParser(description="Fleet config parser.", add_help=False)

    parser.add_argument("-c", "--config_file", required=False, is_config_file=True,
                        help="Config file path.")
    parser.add_argument("--fleet_config_file", required=False, is_config_file=True,
                        help="Fleet config file path.")

    parser.add_argument("--engines", type=int, default=2,
                        help="Engine processes behind the router. Each is "
                             "one ml_recipe_tpu.cli.serve child on an "
                             "ephemeral port, launched against the shared "
                             "AOT program store.")
    parser.add_argument("--engine_checkpoints", type=cast2(str), default=None,
                        help="Comma list of checkpoint paths assigned "
                             "per-engine (1 entry = every engine, N "
                             "entries = one each — multi-checkpoint A/B "
                             "routing in one tier; the checkpoint-"
                             "fingerprint cache keys isolate results). "
                             "None = every engine uses --checkpoint.")
    parser.add_argument("--ring_replicas", type=int, default=64,
                        help="Virtual nodes per engine on the consistent-"
                             "hash ring (bounded; health weighting scales "
                             "a node's share of them).")
    parser.add_argument("--health_poll_s", type=float, default=1.0,
                        help="Router health-poll interval: every engine's "
                             "/healthz (status + queue depth) is polled "
                             "this often; ejection latency for a dead "
                             "engine is bounded by eject_after polls.")
    parser.add_argument("--eject_after", type=int, default=2,
                        help="Consecutive health failures before an engine "
                             "is ejected from the ring (the first failure "
                             "weight-reduces it to --degrade_weight).")
    parser.add_argument("--degrade_weight", type=float, default=0.25,
                        help="Ring weight of a degraded engine (failing "
                             "polls, 429/503 answers, or queue pressure "
                             "past --queue_pressure).")
    parser.add_argument("--queue_pressure", type=float, default=0.75,
                        help="Queue-depth fraction of an engine's bounded "
                             "queue past which the router weight-reduces "
                             "it (healthy-but-saturated: load is moved, "
                             "no ejection counter advances).")
    parser.add_argument("--spill_retries", type=int, default=1,
                        help="Ring successors to try after the owning "
                             "engine refuses a request (connection error, "
                             "429, 503). Only when every candidate "
                             "refuses does the router shed with 503 + "
                             "Retry-After.")
    parser.add_argument("--routing", type=str, default="hash",
                        choices=["hash", "random"],
                        help="Request routing policy: 'hash' pins each "
                             "document's traffic to one engine via the "
                             "consistent-hash ring (cache affinity), "
                             "'random' scatters uniformly (the bench "
                             "baseline).")
    parser.add_argument("--rolling_restart", type=_str2bool, default=False,
                        help="After the tier is ready, perform one rolling "
                             "restart pass (drain -> relaunch off the "
                             "shared AOT store with zero compiles "
                             "asserted -> re-admit, one engine at a "
                             "time), then keep serving.")
    parser.add_argument("--fleet_run_dir", type=cast2(str), default=None,
                        help="Directory for engine ready files + logs "
                             "(None = a fresh temp dir).")

    return parser


def resolve_precision(params) -> str:
    """Map (precision, apex_level) onto the native policy: 'bf16' or 'f32'."""
    if getattr(params, "precision", None):
        return params.precision
    apex_level = getattr(params, "apex_level", None)
    if apex_level in ("O1", "O2", "O3"):
        return "bf16"
    return "f32"
