"""Inference predictor.

Parity target: reference ``modules/model/inference/predictor.py:23-144`` —
streams chunk batches from the async loader, scores each chunk with the
answerability score from arXiv 1901.08634
(``s = max(start)+max(end) − (start[0]+end[0])``, predictor.py:119-120),
keeps the argmax-scored candidate per document under validity rules (span
order, answer not inside the question, beats prior score, predictor.py:63-75),
and renders predictions (predictor.py:133-144).

TPU deltas:
- argmax/softmax/score computation happens INSIDE the jitted forward (the
  reference pulled full logit tensors to host each batch; here ONE packed
  [6, B] f32 array per batch crosses the host boundary — a single fetch,
  measured 2.4x end-to-end loop throughput vs six separate vector fetches);
- batches are padded to the static ``batch_size`` so one compiled program
  serves the whole stream (the trailing partial batch is trimmed host-side);
- the model forward is SPMD over the mesh data axis.
"""

from __future__ import annotations

import logging
import queue
import sys
import threading
import traceback
from collections import defaultdict
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from ..data import RawPreprocessor
from ..data.bucketing import (
    TokenBudgetBucketer,
    bucket_batch_sizes,
    parse_length_buckets,
)
from ..data.collate import rebind_collate_seq
from ..data.loader import ListDataloader
from ..data.packing import (
    DEFAULT_MAX_SEGMENTS,
    DEFAULT_MIN_FRAGMENT,
    SequencePacker,
    collate_packed,
    parse_pack_splitting,
    parse_sequence_packing,
)
from ..parallel import ParallelPlan, build_mesh, gather_to_host, make_global_array
from ..serve.bucketing import pad_trailing_batch
from ..utils.pipeline import LaggedConsumer
from .score import (
    OUT_KEYS,
    PACKED_OUT_KEYS,
    FragmentMerger,
    build_packed_score_fn,
    build_score_fn,
)

logger = logging.getLogger(__name__)

try:  # pragma: no cover - cosmetic only
    from tqdm.auto import tqdm
except Exception:  # noqa: BLE001
    tqdm = None


class WorkerShutdownError(RuntimeError):
    """The transfer worker was still alive after the join timeout: something
    it blocks on (a device transfer, the upstream loader) is wedged. Raised
    so the hang is VISIBLE at the call site instead of leaking a zombie
    daemon thread that silently pins the device."""


def _ensure_worker_stopped(
    worker: threading.Thread, *, timeout: float = 10.0
) -> None:
    """Join ``worker``; on timeout, log its current stack (the only clue to
    WHAT it is stuck on) and raise — unless an exception is already
    propagating, in which case only warn: the original error is the story,
    and replacing it with a shutdown complaint would hide it."""
    worker.join(timeout=timeout)
    if not worker.is_alive():
        return
    frame = sys._current_frames().get(worker.ident)
    stack = (
        "".join(traceback.format_stack(frame)) if frame is not None
        else "<no frame available>"
    )
    logger.warning(
        f"Worker thread {worker.name!r} still alive {timeout:g}s after "
        f"shutdown was requested; its stack:\n{stack}"
    )
    if sys.exc_info()[0] is None:
        raise WorkerShutdownError(
            f"worker thread {worker.name!r} failed to stop within "
            f"{timeout:g}s (stack logged above)"
        )


@dataclass
class PredictorCandidate:
    start_id: int
    end_id: int
    start_reg: float
    end_reg: float
    label: int


class Predictor:
    def __init__(
        self,
        model,
        params,
        *,
        mesh=None,
        collate_fun=None,
        batch_size: int = 256,
        n_jobs: int = 16,
        buffer_size: int = 4096,
        limit: Optional[int] = None,
        fetch_every: int = 1,
        length_buckets: Optional[list] = None,
        sequence_packing=False,
        pack_max_segments: int = DEFAULT_MAX_SEGMENTS,
        pack_splitting="off",
        pack_min_fragment: int = DEFAULT_MIN_FRAGMENT,
    ):
        self.model = model
        self.params = params
        self.mesh = mesh if mesh is not None else build_mesh()
        # the declarative parallelism plan: batch placement (and the
        # data-axis arithmetic below) derives from it, not from
        # per-feature mesh spelunking
        self.plan = ParallelPlan.from_mesh(self.mesh)

        self.scores: dict = defaultdict(int)
        self.candidates: dict = {}
        self.items: dict = {}

        self.batch_size = batch_size
        self.n_jobs = n_jobs
        self.collate_fun = collate_fun
        self.buffer_size = buffer_size
        self.limit = limit
        # outputs are fetched in groups of ``fetch_every`` completed batches
        # (one device->host transfer instead of one per batch) while 2 more
        # stay in flight — a high-RTT channel pays its round-trip latency
        # once per group instead of once per [6, B] output. Default 1 =
        # per-batch fetching: the round-5 on-chip sweep measured grouping
        # NEGATIVE (423/408/394 chunks/s at 1/4/8, artifacts/r4/
        # bench_infer_fetch*.json) because that loop was loader-bound —
        # grouping only pays when per-fetch RTT dominates; sweep before
        # raising it.
        self.fetch_every = max(1, int(fetch_every))

        self.dump = None
        self._jit_fwd = None

        # ids-only wire format: attention_mask is (ids != pad) and BERT
        # token_type_ids are "1 strictly after the first [SEP]" — both
        # derivable INSIDE the jit from the ids alone (bit-exact for every
        # output the predictor consumes: pad positions are -inf'd by the QA
        # heads via the derived mask, and pad-row token types only touch
        # masked rows). Shipping one uint16 [B, L] array instead of three
        # int32 planes is 6x fewer wire bytes. Whether host->device
        # bandwidth bounds this loop is unmeasured on this chip (ROADMAP
        # D5).
        tok = getattr(self.collate_fun, "keywords", {}).get("tokenizer")
        vocab = None
        if tok is not None:
            try:
                vocab = len(tok)
            except TypeError:
                vocab = getattr(tok, "vocab_size", None)
        self._wire_ids_only = (
            tok is not None and vocab is not None and vocab < 2 ** 16
        )
        if self._wire_ids_only:
            self._pad_id = int(tok.pad_token_id)
            self._sep_id = int(tok.sep_token_id)
            self._is_bert = getattr(tok, "model_name", "bert") == "bert"

        # Sequence packing (data/packing.py): chunks CONCATENATE into full
        # max_seq_len rows with block-diagonal attention — one compiled
        # forward at one shape, ~every token real. Each chunk is scored
        # once per segment with chunk-relative spans and its own [CLS]
        # anchor (infer/score.build_packed_score_fn), so per-chunk scores
        # pin to the pad-to-max path's. Supersedes length_buckets.
        # pack_splitting='fill' additionally splits chunks that fit no open
        # row into hole-filling fragments; their per-fragment span logits
        # re-merge host-side into per-chunk outputs (score.FragmentMerger:
        # offset-shifted argmax over the concatenated fragments) BEFORE
        # candidate tracking, so everything downstream of process() sees
        # per-chunk outputs unchanged. Fragments attend only within
        # themselves (block-diagonal), so split-chunk logits are an
        # approximation of the unsplit chunk's — exact for attention-free
        # heads, within model tolerance otherwise.
        self._packing = parse_sequence_packing(sequence_packing)
        self._pack_max_segments = max(1, int(pack_max_segments))
        self._pack_splitting = parse_pack_splitting(pack_splitting)
        self._pack_min_fragment = max(1, int(pack_min_fragment))
        # observability: fragments/cuts performed by the last run's packer
        self.pack_split_count = 0
        if self._packing:
            kw = getattr(self.collate_fun, "keywords", {}) or {}
            if kw.get("tokenizer") is None:
                raise ValueError(
                    "sequence_packing needs a tokenizer-bound collate_fun "
                    "(init_collate_fun)"
                )
            if kw.get("max_seq_len") is None:
                # fail HERE, not with a bare TypeError on the transfer
                # thread mid-stream: packing needs the static row length
                raise ValueError(
                    "sequence_packing needs the collate's static "
                    "max_seq_len (init_collate_fun(..., max_seq_len=...))"
                )
            if length_buckets:
                logger.info(
                    "sequence_packing supersedes length_buckets for "
                    "offline eval (packed rows are already ~pad-free)."
                )
                length_buckets = None

        # Length-bucketed chunk batching (data/bucketing.py): chunks pad to
        # the smallest bucket seq that fits them instead of the collate's
        # global max, and per-bucket batch sizes hold the token budget
        # batch_size * max_seq constant — one compiled forward per occupied
        # bucket. None = pad-to-max batching (historical behavior).
        self._seq_grid = None
        self._bucket_batches = None
        if length_buckets:
            max_len = getattr(self.collate_fun, "keywords", {}).get("max_seq_len")
            grid = parse_length_buckets(length_buckets, max_len)
            data_size = self.plan.data_size
            self._seq_grid = grid
            self._bucket_batches = bucket_batch_sizes(
                grid, self.batch_size * grid[-1], multiple=max(data_size, 1)
            )
            logger.info(
                f"Predictor length buckets: grid {grid}, per-bucket batches "
                f"{self._bucket_batches}."
            )

        logger.info(
            f"Predictor uses mesh {self.plan.describe()} "
            f"({self.plan.unused_devices} visible device(s) unused). "
            f"Batch size: {self.batch_size}. #workers: {self.n_jobs}. "
            f"Buffer size: {self.buffer_size}. Set limit: {self.limit}."
        )

    @staticmethod
    def _check_ids_wire(packed, attention_mask, pad_id) -> None:
        """The in-jit mask is ``(ids != pad_id)``; if a VALID position ever
        carried the pad token id (e.g. literal "[PAD]" text surviving
        tokenization), that derivation would silently diverge from collate's
        row-length mask — fail loudly instead (advisor r3)."""
        derived = packed != pad_id
        if not np.array_equal(derived, np.asarray(attention_mask, bool)):
            raise ValueError(
                "ids-only wire precondition violated: pad_token_id occurs "
                "at an attended position (or a padded position carries a "
                "non-pad id); construct the Predictor without a tokenizer-"
                "bound collate_fun to use the 3-plane wire"
            )

    # -- compiled forward ------------------------------------------------------

    # row order of the packed [6, B] output (kept as a class attribute for
    # back-compat; the canonical tuple lives in infer/score.py, shared with
    # the serving engine)
    _OUT_KEYS = OUT_KEYS

    def _build_fwd(self):
        # the scoring forward is shared with serve/engine.py (one packed
        # [6, B] fetch per batch; see infer/score.py for the wire formats)
        if self._packing:
            return jax.jit(build_packed_score_fn(self.model))
        if self._wire_ids_only:
            fwd = build_score_fn(
                self.model, wire_ids_only=True, pad_id=self._pad_id,
                sep_id=self._sep_id, is_bert=self._is_bert,
            )
        else:
            fwd = build_score_fn(self.model, wire_ids_only=False)
        return jax.jit(fwd)

    # -- candidate tracking (predictor.py:63-87) -------------------------------

    def _is_valid(self, item, score, start_id, end_id) -> bool:
        assert score >= 0

        if start_id > end_id:
            return False

        # answer must not start inside "[CLS] question [SEP]"
        if start_id < item.question_len + 2:
            return False

        if self.scores[item.item_id] > score:
            return False

        return True

    def _update_candidates(self, out: dict, items) -> None:
        for i, item in enumerate(items):
            score = float(out["scores"][i])
            start_id = int(out["start_ids"][i])
            end_id = int(out["end_ids"][i])
            if self._is_valid(item, score, start_id, end_id):
                self.scores[item.item_id] = score
                self.candidates[item.item_id] = PredictorCandidate(
                    start_id=start_id,
                    end_id=end_id,
                    start_reg=float(out["start_regs"][i]),
                    end_reg=float(out["end_regs"][i]),
                    label=int(out["labels"][i]),
                )
                self.items[item.item_id] = item

    # -- main loop (predictor.py:89-131) ---------------------------------------

    def __call__(self, dataset, *, save_dump: bool = False):
        if self._jit_fwd is None:
            self._jit_fwd = self._build_fwd()
        # per-run splitter observability (a previous run's packer must not
        # leak its split count into a run that never built one)
        self._live_packer = None
        self.pack_split_count = 0

        bucketed = self._seq_grid is not None
        packing = self._packing
        async_dataset = ListDataloader(
            dataset,
            batch_size=self.batch_size,
            n_jobs=self.n_jobs,
            # bucketed/packed: stream RAW chunk lists and collate below
            collate_fun=None if (bucketed or packing) else self.collate_fun,
            buffer_size=self.buffer_size,
            shuffle=True,
        )

        if save_dump:
            self.dump = []

        iterator = async_dataset
        if tqdm is not None:
            iterator = tqdm(
                async_dataset,
                desc="Scoring document chunks",
                total=self.limit,
            )

        merger = FragmentMerger() if (
            packing and self._pack_splitting != "off"
        ) else None

        def process(packed, n_valid, items) -> None:
            if packing:
                # [8, R, S] per-segment outputs -> per-chunk vectors through
                # the packing map (row-major segment order over the mask);
                # ``n_valid`` is the host-side [R, S] segment_mask
                m = np.asarray(n_valid).reshape(-1) > 0
                out = {
                    k: packed[i].reshape(-1)[m]
                    for i, k in enumerate(PACKED_OUT_KEYS)
                }
                assert len(items) == int(m.sum()), (len(items), int(m.sum()))
                if merger is not None:
                    # entries may be ChunkFragments: buffer them until their
                    # chunk is complete (fragments routinely span batches),
                    # then re-merge into per-chunk outputs — everything
                    # below this point sees whole chunks only
                    done_items: list = []
                    done_fields: dict = {k: [] for k in self._OUT_KEYS}
                    for j, entry in enumerate(items):
                        fields = {k: out[k][j] for k in PACKED_OUT_KEYS}
                        for item, merged in merger.add(entry, fields):
                            done_items.append(item)
                            for k in self._OUT_KEYS:
                                done_fields[k].append(merged[k])
                    items = done_items
                    out = {
                        k: np.asarray(v, dtype=np.float32)
                        for k, v in done_fields.items()
                    }
            else:
                out = {
                    k: packed[i, :n_valid]
                    for i, k in enumerate(self._OUT_KEYS)
                }

            self._update_candidates(out, items)

            if save_dump:
                self.dump.append(
                    (out["scores"], out["start_ids"], out["end_ids"],
                     out["labels"], items)
                )

        # Grouped output fetching: completed [6, B] outputs ([8, R, S]
        # on the packed path) accumulate on
        # device and are gathered ``fetch_every`` at a time in ONE
        # device->host transfer (a jnp.stack + one gather), while 2 newer
        # batches stay in flight (the depth-2 lag that hides per-batch
        # round-trip latency). Grouping amortizes a per-fetch latency over
        # ``fetch_every`` batches; that such a latency matters is
        # unmeasured on this chip (ROADMAP D3/D5). Multi-process runs fetch
        # per batch: their outputs are not fully addressable, and an eager
        # jnp.stack on such arrays is an error — gather_to_host handles
        # them per array. (Defensive only: inference is a single-process
        # workload here as in the reference — its validate.py has no
        # distributed path — so the per-batch branch just prevents a crash
        # class if a multi-process world ever constructs a Predictor.)
        import jax

        import jax.numpy as jnp

        # Bucketed batches have per-bucket shapes, so the grouped fetch's
        # jnp.stack cannot apply — fetch per batch there. Packed batches
        # fetch per batch too (the [8, R, S] output must pair with its own
        # host-side segment mask).
        group_n = (
            self.fetch_every
            if jax.process_count() == 1 and not bucketed and not packing
            else 1
        )

        def drain_group(batch) -> None:
            if len(batch) == 1:
                stacked = np.asarray(gather_to_host(batch[0][0]))[None]
            else:
                stacked = np.asarray(
                    gather_to_host(jnp.stack([g[0] for g in batch]))
                )
            for row, (_, n_valid_i, items_i) in zip(stacked, batch):
                process(row, n_valid_i, items_i)

        if group_n > 1:
            lag = LaggedConsumer(drain_group, depth=2, group=group_n)
        else:  # group=1 keeps LaggedConsumer's unpacked-args convention
            lag = LaggedConsumer(
                lambda *args: drain_group([args]), depth=2
            )

        # Double-buffered host->device staging: a transfer thread pads the
        # trailing partial batch and runs make_global_array for batch N+1
        # while the main thread dispatches batch N and gathers batch N-1.
        # What running them serially on one thread costs is unmeasured on
        # this chip (ROADMAP D5; the one capture that decomposed this loop
        # is artifacts/r4/infer_decomp.json).
        stop = threading.Event()
        stage: queue.Queue = queue.Queue(maxsize=2)
        _DONE = object()

        def host_batches():
            """Collated+padded host batches as ``(inputs, n_valid, items)``.

            Pad-to-max path: the loader already collated at the global max;
            pad the trailing partial batch to the static batch. Bucketed
            path: the loader streams raw chunk lists; chunks route to the
            smallest bucket seq that fits, each bucket collates at ITS seq
            when its (token-budget-scaled) batch fills, and the per-bucket
            tails flush padded with ``real`` counts — same trim discipline.
            Packed path: chunks first-fit into full max_seq_len rows
            (data/packing.SequencePacker); ``inputs`` becomes the
            ``((planes, segment_starts))`` pair of the packed wire,
            ``n_valid`` the host [rows, S] segment_mask, ``items`` the
            flattened chunks in row-major segment order (the packing map).
            """
            if packing:
                tok = self.collate_fun.keywords["tokenizer"]
                max_len = int(self.collate_fun.keywords["max_seq_len"])
                packer = SequencePacker(
                    max_len, max_segments=self._pack_max_segments,
                    splitting=self._pack_splitting,
                    min_fragment=self._pack_min_fragment,
                )
                self._live_packer = packer
                pending: list = []

                def packed_batch(rows):
                    real = len(rows)
                    rows = rows + [rows[-1]] * (self.batch_size - real)
                    inputs, seg_mask = collate_packed(
                        rows, tok, max_seq_len=max_len,
                        max_segments=self._pack_max_segments,
                        with_labels=False,
                    )
                    if real < len(rows):
                        seg_mask[real:] = 0  # pad rows: no phantom chunks
                    planes = np.stack([
                        inputs["input_ids"],
                        inputs["token_type_ids"],
                        inputs["segment_ids"],
                        inputs["position_ids"],
                    ])
                    items_flat = [it for row in rows[:real] for it in row]
                    return (
                        (planes, inputs["segment_starts"]),
                        seg_mask, items_flat,
                    )

                for group in iterator:  # raw chunk lists
                    for chunk in group:
                        pending.extend(
                            packer.add(
                                chunk, len(chunk.input_ids),
                                (chunk.start_id, chunk.end_id),
                            )
                        )
                        while len(pending) >= self.batch_size:
                            yield packed_batch(pending[: self.batch_size])
                            del pending[: self.batch_size]
                pending.extend(packer.flush())
                while pending:
                    yield packed_batch(pending[: self.batch_size])
                    del pending[: self.batch_size]
                return
            if not bucketed:
                for inputs, labels, items in iterator:
                    n_valid = len(items)
                    if n_valid < self.batch_size:
                        # pad the trailing partial batch to the static shape
                        # (shared helper — serving pads rows the same way)
                        inputs = pad_trailing_batch(inputs, self.batch_size)
                    yield inputs, n_valid, items
                return
            bucketer = TokenBudgetBucketer(self._seq_grid, self._bucket_batches)
            collates = {
                seq: rebind_collate_seq(self.collate_fun, seq)
                for seq in self._seq_grid
            }

            def collated(seq, chunk_items):
                inputs, _labels, chunk_items = collates[seq](chunk_items)
                n_valid = len(chunk_items)
                if n_valid < self._bucket_batches[seq]:
                    inputs = pad_trailing_batch(
                        inputs, self._bucket_batches[seq]
                    )
                return inputs, n_valid, chunk_items

            for group in iterator:  # raw chunk lists
                for chunk in group:
                    emitted = bucketer.add(len(chunk.input_ids), chunk)
                    if emitted is not None:
                        yield collated(*emitted)
            for seq, tail in bucketer.flush():
                yield collated(seq, tail)

        def transfer_worker() -> None:
            try:
                for batch_i, (inputs, n_valid, items) in enumerate(host_batches()):
                    if packing:
                        planes, starts = inputs
                        dev_inputs = (
                            make_global_array(planes, self.mesh, batch_axis=1),
                            make_global_array(starts, self.mesh),
                        )
                    elif self._wire_ids_only:
                        packed = np.asarray(
                            inputs["input_ids"], np.uint16
                        )
                        self._check_ids_wire(
                            packed, inputs["attention_mask"], self._pad_id
                        )
                        dev_inputs = make_global_array(packed, self.mesh)
                    else:
                        packed = np.stack(
                            [
                                np.asarray(inputs["input_ids"], np.int32),
                                np.asarray(inputs["attention_mask"], np.int32),
                                np.asarray(inputs["token_type_ids"], np.int32),
                            ]
                        )
                        dev_inputs = make_global_array(
                            packed, self.mesh, batch_axis=1
                        )
                    payload = (dev_inputs, n_valid, items)
                    while not stop.is_set():
                        try:
                            stage.put(payload, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                    if self.limit is not None and batch_i >= self.limit:
                        break
            except BaseException as exc:  # propagate into the main loop
                stage.put(exc)
            else:
                stage.put(_DONE)

        worker = threading.Thread(
            target=transfer_worker, name="predictor-transfer", daemon=True
        )

        with self.mesh:
            worker.start()
            try:
                while True:
                    got = stage.get()
                    if got is _DONE:
                        break
                    if isinstance(got, BaseException):
                        raise got
                    dev_inputs, n_valid, items = got
                    if isinstance(dev_inputs, tuple):  # packed wire
                        dev_out = self._jit_fwd(self.params, *dev_inputs)
                    else:
                        dev_out = self._jit_fwd(self.params, dev_inputs)
                    lag.feed(dev_out, n_valid, items)
                lag.flush()
            finally:
                stop.set()
                while True:  # unblock a worker waiting on a full queue
                    try:
                        stage.get_nowait()
                    except queue.Empty:
                        break
                _ensure_worker_stopped(worker, timeout=10)

        if packing:
            live = getattr(self, "_live_packer", None)
            self.pack_split_count = live.split_count if live else 0
            if self.pack_split_count:
                logger.info(
                    "Sequence packing split %d chunk(s) into hole-filling "
                    "fragments (re-merged to per-chunk outputs).",
                    self.pack_split_count,
                )
        if merger is not None and merger.pending:
            # every fragment is collated and scored (eval pads, never
            # drops), so a leftover here is a re-merge bookkeeping bug —
            # surface it instead of silently losing chunks
            logger.warning(
                "Fragment re-merge finished with %d incomplete chunk(s); "
                "their candidates were dropped.", merger.pending,
            )

        return self

    def show_predictions(self, *, n_docs: Optional[int] = None) -> None:
        for doc_i, doc_id in enumerate(self.scores.keys()):
            if n_docs is not None and doc_i >= n_docs:
                break

            doc = self.items[doc_id]
            candidate = self.candidates[doc_id]

            logger.info(f"Text: {doc.true_text}")
            logger.info(f"Question: {doc.true_question}")
            logger.info(
                f"True label: {RawPreprocessor.id2labels[doc.true_label]}. "
                f"Pred label: {RawPreprocessor.id2labels[candidate.label]}."
            )
