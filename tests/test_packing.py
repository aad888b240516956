"""Sequence packing (ISSUE 5): packer, packed collate, packed loader,
packed loss, and the packed train/eval loops on the virtual CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ml_recipe_tpu.data.chunking import label_safe_cut
from ml_recipe_tpu.data.collate import make_collate_fun
from ml_recipe_tpu.data.datasets import DatasetItem
from ml_recipe_tpu.data.loader import ShardedBatchSampler
from ml_recipe_tpu.data.packing import (
    ChunkFragment,
    PackedBatch,
    PackedDataLoader,
    SequencePacker,
    collate_packed,
    parse_pack_splitting,
    parse_sequence_packing,
)
from ml_recipe_tpu.losses import PackedWeightedLoss, build_loss
from ml_recipe_tpu.models import EncoderConfig, QAModel
from ml_recipe_tpu.parallel import build_mesh
from ml_recipe_tpu.train import Trainer

from helpers import make_tokenizer
from test_trainer import MAX_SEQ_LEN, TP

pytestmark = pytest.mark.unit


class VarLenDataset:
    """DummyDataset-style QA items with a packable length mix (a pure
    function of the index, like DummyDataset — thread-safe + replayable)."""

    def __init__(self, tokenizer, n, max_seq_len, *, lo=10, hi=None):
        self.tok, self.n, self.L = tokenizer, n, max_seq_len
        self.lo = lo
        self.hi = hi if hi is not None else max_seq_len // 2

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng([11, int(i)])
        n = int(rng.integers(self.lo, self.hi + 1))
        body = rng.integers(5, len(self.tok), max(n - 3, 1)).tolist()
        ids = [self.tok.cls_token_id, *body,
               self.tok.sep_token_id, self.tok.sep_token_id]
        start = int(rng.integers(0, len(ids)))
        return DatasetItem(
            example_id=str(i), input_ids=ids, start_id=start,
            end_id=min(start + 2, len(ids) - 1),
            label_id=int(rng.integers(0, 5)),
            start_position=start / self.L,
            end_position=(start + 2) / self.L,
        )


def _items(tok, lengths):
    out = []
    for j, n in enumerate(lengths):
        body = list(range(5, 5 + n - 3))
        ids = [tok.cls_token_id, *body, tok.sep_token_id, tok.sep_token_id]
        out.append(DatasetItem(
            example_id=str(j), input_ids=ids[:n], start_id=1,
            end_id=2, label_id=j % 5, start_position=0.1, end_position=0.2,
        ))
    return out


# ---------------------------------------------------------------------------
# SequencePacker
# ---------------------------------------------------------------------------


def test_parse_sequence_packing_domain():
    for off in (None, False, "off", "none", "0", "false", ""):
        assert parse_sequence_packing(off) is False
    for on in (True, "on", "1", "true", "yes"):
        assert parse_sequence_packing(on) is True


def test_packer_first_fit_deterministic():
    def run():
        p = SequencePacker(100, max_segments=4, open_rows=2)
        rows = []
        for n in (60, 30, 50, 40, 10, 90, 10):
            rows.extend(p.add(n, n))
        rows.extend(p.flush())
        return rows

    a, b = run(), run()
    assert a == b
    assert all(sum(r) <= 100 for r in a)
    assert sorted(x for r in a for x in r) == sorted(
        (60, 30, 50, 40, 10, 90, 10)
    )


def test_packer_exact_fill_closes_eagerly():
    p = SequencePacker(100, open_rows=4)
    assert p.add(60, 60) == []
    done = p.add(40, 40)  # 60 + 40 == 100: closes without a forced emit
    assert done == [[60, 40]]
    assert p.flush() == []


def test_packer_segment_cap_closes_row():
    p = SequencePacker(1000, max_segments=2, open_rows=4)
    assert p.add("a", 10) == []
    assert p.add("b", 10) == [["a", "b"]]  # cap 2 reached, space left


def test_packer_forced_emit_picks_fullest():
    p = SequencePacker(100, open_rows=2)
    p.add("a", 30)   # row0: 30
    p.add("b", 90)   # doesn't fit row0 -> row1: 90 (window now full)
    done = p.add("c", 80)  # fits nowhere: the FULLEST row (90) is emitted
    assert done == [["b"]]
    assert p.flush() == [["a"], ["c"]]


def test_packer_rejects_oversized_item():
    p = SequencePacker(64)
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        p.add("x", 65)


def test_packer_under_two_pct_on_continuous_nq_mix():
    """ISSUE-5 acceptance (capability pin): on a continuous NQ-like chunk
    mix — full windows + mid-length chunks + striding tails, the eval-side
    chunk population — the greedy packer lands UNDER 2% waste. (The bench's
    synthetic train mix is quantized — its 463-token chunks leave a hole no
    chunk can fill, flooring ANY non-splitting packer around 2.4%; that
    number is pinned in test_bench_harness.py.)"""
    rng = np.random.default_rng(0)
    L = 512
    lengths = np.concatenate([
        np.full(2000, L),
        rng.integers(150, 505, 1200),
        rng.integers(20, 120, 800),
    ])
    rng.shuffle(lengths)
    p = SequencePacker(L)
    rows = []
    for n in lengths:
        rows.extend(p.add(int(n), int(n)))
    rows.extend(p.flush())
    waste = 100.0 * (1.0 - sum(sum(r) for r in rows) / (len(rows) * L))
    assert waste < 2.0, waste
    # every item survived, no row overflows
    assert sorted(x for r in rows for x in r) == sorted(int(n) for n in lengths)
    assert all(sum(r) <= L for r in rows)


# ---------------------------------------------------------------------------
# splitting packer (ISSUE 11): hole-filling chunk fragments
# ---------------------------------------------------------------------------


def test_parse_pack_splitting_domain():
    for off in (None, False, "off", "none", "0", "false", ""):
        assert parse_pack_splitting(off) == "off"
    for fill in (True, "fill", "on", "1", "true", "yes"):
        assert parse_pack_splitting(fill) == "fill"
    with pytest.raises(ValueError, match="off|fill"):
        parse_pack_splitting("sideways")


def test_label_safe_cut_arithmetic():
    # nominal: fill the hole, keep min_fragment on both sides
    assert label_safe_cut(100, None, 40, 10) == 40
    # hole bigger than length - min_fragment: the tail floor binds
    assert label_safe_cut(100, None, 95, 10) == 90
    # no legal cut: hole below min_fragment, or chunk too short to split
    assert label_safe_cut(100, None, 5, 10) is None
    assert label_safe_cut(15, None, 40, 10) is None
    # span straddling the nominal cut retreats to the span start (the
    # whole span moves into the tail fragment)
    assert label_safe_cut(100, (35, 45), 40, 10) == 35
    assert label_safe_cut(100, (35, 89), 40, 10) == 35
    # ...and when that violates min_fragment there is no legal cut
    assert label_safe_cut(100, (5, 89), 40, 10) is None
    # span wholly on one side never moves the cut
    assert label_safe_cut(100, (5, 9), 40, 10) == 40
    assert label_safe_cut(100, (60, 70), 40, 10) == 40


def test_splitting_packer_breaks_quantized_floor_with_integrity():
    """The tentpole number, packer-level: a fully quantized 463-token mix
    at L=512 floors the NON-splitting packer near 10% (no pair of chunks
    shares a row), while the splitting packer lands under 1% — and every
    split chunk reassembles exactly: contiguous offsets, all fragments
    stamped with the final count, tokens conserved, and the gold span
    wholly inside the single keep_labels fragment (the
    never-splits-through-gold-span property, here over randomized spans)."""
    rng = np.random.default_rng(3)
    L, n = 512, 1000

    def run(mode):
        p = SequencePacker(L, splitting=mode, min_fragment=32)
        rows = []
        spans = {}
        for i in range(n):
            s = int(rng.integers(0, 463 - 2))
            spans[f"c{i}"] = (s, min(s + int(rng.integers(0, 40)), 462))
            rows.extend(p.add(f"c{i}", 463, spans[f"c{i}"]))
        rows.extend(p.flush())
        return p, rows, spans

    def waste(rows):
        def tok(e):
            return e.length if isinstance(e, ChunkFragment) else 463

        used = sum(tok(e) for r in rows for e in r)
        return 100.0 * (1.0 - used / (len(rows) * L))

    _, rows_off, _ = run("off")
    packer, rows_fill, spans = run("fill")
    assert waste(rows_off) > 8.0  # the quantized floor, unsplittable
    assert waste(rows_fill) < 1.0, waste(rows_fill)
    assert packer.split_count > 0

    frags = {}
    whole = []
    for r in rows_fill:
        assert sum(
            e.length if isinstance(e, ChunkFragment) else 463 for e in r
        ) <= L
        for e in r:
            if isinstance(e, ChunkFragment):
                frags.setdefault(e.chunk_id, []).append(e)
            else:
                whole.append(e)
    assert frags, "no chunk was split on the quantized mix"
    split_names = set()
    for cid, fs in frags.items():
        fs.sort(key=lambda f: f.index)
        split_names.add(fs[0].item)
        assert [f.index for f in fs] == list(range(len(fs)))
        assert all(f.count == len(fs) for f in fs)
        assert fs[0].offset == 0 and fs[0].chunk_len == 463
        for a, b in zip(fs, fs[1:]):
            assert b.offset == a.offset + a.length
        assert sum(f.length for f in fs) == 463
        assert all(f.length >= 32 for f in fs)
        # the property: exactly one fragment carries labels, and the gold
        # span lies WHOLLY inside it — no cut ever bisected it
        carriers = [f for f in fs if f.keep_labels]
        assert len(carriers) == 1, (cid, carriers)
        s, e = spans[fs[0].item]
        c = carriers[0]
        assert c.offset <= s and e < c.offset + c.length, (cid, (s, e), c)
    # every chunk placed exactly once (whole or split, never both)
    assert split_names.isdisjoint(set(whole))
    assert len(split_names) + len(whole) == n


def test_splitting_off_is_bit_identical_packer():
    """splitting='off' must walk the EXACT historical code path: same row
    compositions, same emission order, span argument ignored."""
    rng = np.random.default_rng(1)
    lengths = [int(x) for x in rng.integers(10, 100, 200)]

    def run(**kw):
        p = SequencePacker(100, open_rows=4, **kw)
        rows = []
        for i, n in enumerate(lengths):
            rows.extend(p.add(i, n, (2, 4) if kw else None))
        rows.extend(p.flush())
        return rows

    assert run() == run(splitting="off", min_fragment=5)


def test_collate_packed_fragment_planes(tmp_path):
    """Fragment collate: input_ids slice the parent, position_ids CONTINUE
    at the token offset, token types inherit the parent's plane, the
    keep_labels fragment carries the rebased span, siblings carry mask 0
    and ignore-index spans, and the provenance planes round-trip."""
    tok = make_tokenizer(tmp_path)
    (parent,) = _items(tok, [30])
    parent.start_id, parent.end_id = 20, 24  # span in the tail fragment
    head = ChunkFragment(item=parent, chunk_id=7, offset=0, length=12,
                         index=0, count=2, keep_labels=False, chunk_len=30)
    tail = ChunkFragment(item=parent, chunk_id=7, offset=12, length=18,
                         index=1, count=2, keep_labels=True, chunk_len=30)
    (filler,) = _items(tok, [10])

    inputs, labels, prov = collate_packed(
        [[filler, head], [tail]], tok, max_seq_len=40, max_segments=3,
        with_provenance=True,
    )
    # fragment token planes slice the parent exactly
    assert inputs["input_ids"][0, 10:22].tolist() == parent.input_ids[:12]
    assert inputs["input_ids"][1, :18].tolist() == parent.input_ids[12:30]
    # positions continue at the fragment's offset (unsplit-chunk embedding)
    assert inputs["position_ids"][0, 10:22].tolist() == list(range(12))
    assert inputs["position_ids"][1, :18].tolist() == list(range(12, 30))
    # token types: the parent's plane, sliced — _items puts the [SEP]s at
    # the chunk END (position 28), so the head fragment is all zeros and
    # the tail flips to 1 exactly at parent position 29 (= local 17)
    sep_pos = parent.input_ids.index(tok.sep_token_id)
    assert sep_pos == 28
    assert (inputs["token_type_ids"][0, 10:22] == 0).all()
    assert (inputs["token_type_ids"][1, :17] == 0).all()
    assert inputs["token_type_ids"][1, 17] == 1
    # labels: sibling masked + ignored, carrier rebased row-absolute
    np.testing.assert_array_equal(
        labels["segment_mask"], [[1, 0, 0], [1, 0, 0]]
    )
    assert labels["start_class"][0, 1] == -1  # sibling: ignore-index
    assert labels["start_class"][1, 0] == 20 - 12  # rebased by offset
    assert labels["end_class"][1, 0] == 24 - 12
    assert labels["cls"][1, 0] == parent.label_id
    # provenance planes
    np.testing.assert_array_equal(prov["chunk_id"], [[-1, 7, -1], [7, -1, -1]])
    np.testing.assert_array_equal(
        prov["fragment_index"], [[0, 0, 0], [1, 0, 0]]
    )
    np.testing.assert_array_equal(
        prov["token_offset"], [[0, 0, 0], [12, 0, 0]]
    )
    # inference collate (with_labels=False): EVERY present segment is in
    # the packing map, fragments included (the re-merge needs them all)
    _inputs2, seg_mask = collate_packed(
        [[filler, head], [tail]], tok, max_seq_len=40, max_segments=3,
        with_labels=False,
    )
    np.testing.assert_array_equal(seg_mask, [[1, 1, 0], [1, 0, 0]])


def _split_loader(tmp_path, *, n=64, rows=4, pad_last=False, **kw):
    tok = make_tokenizer(tmp_path)
    # longer items than _loader's so rows leave holes worth filling
    ds = VarLenDataset(tok, n, MAX_SEQ_LEN, lo=14, hi=44)
    sampler = ShardedBatchSampler(n, rows, shuffle=True, drop_last=True, seed=0)
    return tok, ds, PackedDataLoader(
        ds, sampler, tok, max_seq_len=MAX_SEQ_LEN, rows_per_batch=rows,
        n_jobs=2, pad_last=pad_last, splitting="fill", min_fragment=4, **kw,
    )


def test_split_loader_stats_and_accounting(tmp_path):
    tok, ds, loader = _split_loader(tmp_path)
    loader.set_epoch(1)
    batches = list(loader)
    assert batches
    stats = loader.epoch_stats
    assert stats["split_count"] > 0, "splitting never triggered on this mix"
    assert stats["fragment_rows"] > 0
    # the histogram counts every emitted fragment (heads included), so it
    # covers at least the counted cuts
    assert sum(stats["fragment_size_hist"].values()) >= stats["split_count"]
    # items + dropped still partitions the epoch (label-carrier accounting)
    assert stats["items"] + stats["dropped_items"] == 64
    # waste strictly below the non-splitting loader on the same epoch
    off = PackedDataLoader(
        ds, ShardedBatchSampler(64, 4, shuffle=True, drop_last=True, seed=0),
        tok, max_seq_len=MAX_SEQ_LEN, rows_per_batch=4, n_jobs=2,
    )
    off.set_epoch(1)
    for _ in off:
        pass
    assert (
        stats["padding_waste_pct"] < off.epoch_stats["padding_waste_pct"]
    )
    # every batch's labels stay within their fragment rows: spans are
    # row-absolute indices into a real token (never pad, never -2)
    for b in batches:
        sc = b.labels["start_class"]
        mask = b.labels["segment_mask"]
        seg = b.inputs["segment_ids"]
        for r, s in zip(*np.nonzero(mask)):
            if sc[r, s] >= 0:
                assert seg[r, sc[r, s]] == s + 1  # span inside its segment
        assert b.provenance is not None  # provenance rides PackedBatch


def test_split_loader_planned_steps_match_actual(tmp_path):
    """ISSUE-11 satellite: the LR-schedule plan simulates SPLITTING too —
    on a fully-read fixed corpus, planned == consumed exactly."""
    tok, ds, loader = _split_loader(tmp_path)
    planned = loader.planned_epoch_steps(1)
    loader.set_epoch(1)
    actual = sum(1 for _ in loader)
    assert planned == actual
    # and the splitting plan differs from the non-splitting one on this
    # mix (the simulation is really split-aware, not length-only)
    off = PackedDataLoader(
        ds, loader.sampler, tok, max_seq_len=MAX_SEQ_LEN, rows_per_batch=4,
        n_jobs=2,
    )
    assert off.planned_epoch_steps(1) >= planned


def test_split_loader_multi_host_lockstep(tmp_path):
    """ISSUE-11 satellite: two process-ranked SPLITTING loaders derive the
    identical epoch plan (cuts included) from the shared length oracle —
    same per-step shapes and segment counts, concatenated slices equal to
    the single-process batches bit for bit, host-invariant step plan."""
    tok = make_tokenizer(tmp_path)
    ds = VarLenDataset(tok, 64, MAX_SEQ_LEN, lo=14, hi=44)

    def loader(pi, pc):
        sampler = ShardedBatchSampler(
            len(ds), 8, process_index=pi, process_count=pc,
            shuffle=True, drop_last=True, seed=0,
        )
        ldr = PackedDataLoader(
            ds, sampler, tok, max_seq_len=MAX_SEQ_LEN, rows_per_batch=8,
            n_jobs=2, splitting="fill", min_fragment=4,
        )
        ldr.set_epoch(1)
        return ldr

    single, p0, p1 = loader(0, 1), loader(0, 2), loader(1, 2)
    bs, b0, b1 = list(single), list(p0), list(p1)
    assert len(bs) == len(b0) == len(b1) >= 1
    assert single.epoch_stats["split_count"] > 0
    assert p0.epoch_stats["split_count"] == single.epoch_stats["split_count"]
    for s, a, b in zip(bs, b0, b1):
        assert (s.rows, s.segments, s.seq) == (a.rows, a.segments, a.seq)
        assert (a.rows, a.segments, a.seq) == (b.rows, b.segments, b.seq)
        for key in ("input_ids", "segment_ids", "position_ids"):
            merged = np.concatenate([a.inputs[key], b.inputs[key]])
            np.testing.assert_array_equal(merged, s.inputs[key])
        merged_mask = np.concatenate(
            [a.labels["segment_mask"], b.labels["segment_mask"]]
        )
        np.testing.assert_array_equal(merged_mask, s.labels["segment_mask"])
        merged_start = np.concatenate(
            [a.labels["start_class"], b.labels["start_class"]]
        )
        np.testing.assert_array_equal(merged_start, s.labels["start_class"])
    assert (
        p0.planned_epoch_steps(1)
        == p1.planned_epoch_steps(1)
        == single.planned_epoch_steps(1)
    )


def test_packed_trainer_splitting_trains_and_evals(tmp_path, caplog):
    """End to end: a packed trainer under --pack_splitting fill trains and
    evals with finite metrics, the loader really splits, the LR schedule
    was sized from the split-aware plan (epoch-1 stretch warning stays
    quiet), and the weighted meters count each example once."""
    import logging

    from ml_recipe_tpu.train import AccuracyCallback

    with caplog.at_level(logging.WARNING):
        trainer = _packed_trainer(
            tmp_path, pack_splitting="fill", pack_min_fragment=4
        )
        trainer.train()
    stats = trainer.train_dataloader.epoch_stats
    assert stats["split_count"] > 0
    assert stats["batches"] == trainer._planned_steps_per_epoch
    assert "LR decay will end" not in caplog.text  # plan == consumption
    metrics = trainer.test(1, callbacks=[AccuracyCallback()])
    for key in ("loss", "s_acc", "c_acc"):
        assert key in metrics and np.isfinite(metrics[key])
    # eval counted each original example exactly once: segments across
    # batches == dataset size (pad rows and sibling fragments excluded)
    assert trainer.test_dataloader.epoch_stats["items"] == 20


# ---------------------------------------------------------------------------
# collate_packed
# ---------------------------------------------------------------------------


def test_collate_packed_schema(tmp_path):
    tok = make_tokenizer(tmp_path)
    a, b, c = _items(tok, [10, 14, 20])
    inputs, labels = collate_packed(
        [[a, b], [c]], tok, max_seq_len=40, max_segments=3
    )

    seg = inputs["segment_ids"]
    pos = inputs["position_ids"]
    # row 0: segments 1 (10 tokens) and 2 (14), pad after
    assert seg[0, :10].tolist() == [1] * 10
    assert seg[0, 10:24].tolist() == [2] * 14
    assert seg[0, 24:].tolist() == [0] * 16
    # positions reset to 0 at the segment boundary
    assert pos[0, :10].tolist() == list(range(10))
    assert pos[0, 10:24].tolist() == list(range(14))
    # mask == (seg > 0)
    np.testing.assert_array_equal(
        inputs["attention_mask"], (seg > 0).astype(np.int32)
    )
    # each segment's [CLS] really is at its recorded start
    np.testing.assert_array_equal(inputs["segment_starts"][0, :2], [0, 10])
    assert inputs["input_ids"][0, 10] == tok.cls_token_id
    # pad tokens carry pad_token_id
    assert (inputs["input_ids"][0, 24:] == tok.pad_token_id).all()

    # labels: row-absolute span targets; absent segments -1 + mask 0
    np.testing.assert_array_equal(labels["segment_mask"], [[1, 1, 0], [1, 0, 0]])
    assert labels["start_class"][0, 1] == b.start_id + 10
    assert labels["end_class"][0, 1] == b.end_id + 10
    assert labels["start_class"][0, 2] == -1
    assert labels["cls"][0, 1] == b.label_id

    # BERT token types: 1 strictly after each segment's own first [SEP]
    tt = inputs["token_type_ids"]
    row = a.input_ids
    sep_pos = row.index(tok.sep_token_id)
    assert (tt[0, :sep_pos + 1] == 0).all()
    assert (tt[0, sep_pos + 1:10] == 1).all()


def test_collate_packed_spanless_chunk_stays_ignored(tmp_path):
    tok = make_tokenizer(tmp_path)
    (item,) = _items(tok, [12])
    item.start_id = item.end_id = -1  # unanswerable chunk
    _, labels = collate_packed([[item]], tok, max_seq_len=20, max_segments=2)
    assert labels["start_class"][0, 0] == -1
    assert labels["end_class"][0, 0] == -1


# ---------------------------------------------------------------------------
# PackedDataLoader
# ---------------------------------------------------------------------------


def _loader(tmp_path, *, n=48, rows=8, pad_last=False, L=MAX_SEQ_LEN):
    tok = make_tokenizer(tmp_path)
    ds = VarLenDataset(tok, n, L)
    sampler = ShardedBatchSampler(n, rows, shuffle=True, drop_last=True, seed=0)
    return tok, ds, PackedDataLoader(
        ds, sampler, tok, max_seq_len=L, rows_per_batch=rows, n_jobs=2,
        pad_last=pad_last,
    )


def test_packed_loader_batches_and_stats(tmp_path):
    tok, ds, loader = _loader(tmp_path)
    loader.set_epoch(1)
    batches = list(loader)
    assert batches and all(isinstance(b, PackedBatch) for b in batches)
    for b in batches:
        assert b.inputs["input_ids"].shape == (8, MAX_SEQ_LEN)
        assert b.segments == int(b.labels["segment_mask"].sum())
        # every row is multi-or-single segment, never empty
        assert (b.inputs["segment_ids"].max(axis=1) >= 1).all()
    stats = loader.epoch_stats
    assert 0 < stats["packing_efficiency"] <= 1
    assert stats["items"] + stats["dropped_items"] == 48
    # short items => real packing happened: more items than rows
    assert stats["items"] > stats["rows"]
    assert stats["padding_waste_pct"] < stats["padmax_waste_pct"]


def test_packed_loader_preserves_epoch_item_order(tmp_path):
    """Items are assigned to rows in EXACTLY the sampler's epoch order
    (packing changes row composition, never which items an epoch visits)."""
    tok, ds, loader = _loader(tmp_path)
    # replay the packer directly on the epoch's items: the loader must
    # produce the identical token stream (row composition AND batching)
    indices = [int(i) for i in loader.sampler.epoch_indices(3)]
    items = [ds[i] for i in indices]
    packer = SequencePacker(
        loader.max_seq_len, max_segments=loader.max_segments,
        open_rows=loader.open_rows,
    )
    rows = []
    for it in items:
        rows.extend(packer.add(it, len(it.input_ids)))
    rows.extend(packer.flush())
    n_batches = len(rows) // loader.rows_per_batch
    loader.set_epoch(3)
    got = list(loader)
    assert len(got) == n_batches
    got_ids = [
        int(x)
        for b in got
        for x in b.inputs["input_ids"][b.inputs["segment_ids"] > 0]
    ]
    want_ids = [
        int(x)
        for row in rows[: n_batches * loader.rows_per_batch]
        for it in row
        for x in it.input_ids
    ]
    assert got_ids == want_ids


def test_packed_loader_pad_last_zeroes_mask(tmp_path):
    tok, ds, loader = _loader(tmp_path, n=20, rows=8, pad_last=True)
    loader.set_epoch(1)
    batches = list(loader)
    # all items survive in eval mode
    assert loader.epoch_stats["dropped_items"] == 0
    assert loader.epoch_stats["items"] == 20
    last = batches[-1]
    assert last.inputs["input_ids"].shape[0] == 8  # padded to full shape
    # pad rows repeat the last real row but carry ZERO segment mask
    pad_rows = last.rows - int(
        (last.labels["segment_mask"].sum(axis=1) > 0).sum()
    )
    if pad_rows:
        assert (last.labels["segment_mask"][-pad_rows:] == 0).all()


def test_packed_loader_planned_steps_match_actual(tmp_path):
    tok, ds, loader = _loader(tmp_path)
    planned = loader.planned_epoch_steps(1)
    loader.set_epoch(1)
    actual = sum(1 for _ in loader)
    assert planned == actual
    # the plan is far below the pad-to-max upper bound on a short-item mix
    assert planned < len(loader)


def test_packed_loader_multi_host_lockstep(tmp_path):
    """ISSUE-8 satellite: multi-host packing — two process-ranked loaders
    derive the IDENTICAL epoch pack plan from the shared length oracle
    (same (rows, segments) per step, in the same order), their
    concatenated row slices reproduce the single-process loader's batches
    bit for bit (segment_mask included), and the LR-schedule plan is
    host-invariant."""
    tok = make_tokenizer(tmp_path)
    ds = VarLenDataset(tok, 48, MAX_SEQ_LEN)

    def loader(pi, pc):
        sampler = ShardedBatchSampler(
            len(ds), 8, process_index=pi, process_count=pc,
            shuffle=True, drop_last=True, seed=0,
        )
        ldr = PackedDataLoader(
            ds, sampler, tok, max_seq_len=MAX_SEQ_LEN, rows_per_batch=8,
            n_jobs=2,
        )
        ldr.set_epoch(1)
        return ldr

    single, p0, p1 = loader(0, 1), loader(0, 2), loader(1, 2)
    bs, b0, b1 = list(single), list(p0), list(p1)
    assert len(bs) == len(b0) == len(b1) >= 1
    for s, a, b in zip(bs, b0, b1):
        assert (s.rows, s.segments, s.seq) == (a.rows, a.segments, a.seq)
        assert (a.rows, a.segments, a.seq) == (b.rows, b.segments, b.seq)
        assert a.inputs["input_ids"].shape[0] == s.rows // 2
        for key in ("input_ids", "segment_ids", "position_ids"):
            merged = np.concatenate([a.inputs[key], b.inputs[key]])
            np.testing.assert_array_equal(merged, s.inputs[key])
        merged_mask = np.concatenate(
            [a.labels["segment_mask"], b.labels["segment_mask"]]
        )
        np.testing.assert_array_equal(merged_mask, s.labels["segment_mask"])
    assert (
        p0.planned_epoch_steps(1)
        == p1.planned_epoch_steps(1)
        == single.planned_epoch_steps(1)
    )


def test_packed_loader_multi_host_requires_divisible_rows(tmp_path):
    tok = make_tokenizer(tmp_path)
    sampler = ShardedBatchSampler(
        16, 8, process_index=0, process_count=2, seed=0
    )
    with pytest.raises(ValueError, match="divide over"):
        PackedDataLoader(
            VarLenDataset(tok, 16, MAX_SEQ_LEN), sampler, tok,
            max_seq_len=MAX_SEQ_LEN, rows_per_batch=5,
        )


# ---------------------------------------------------------------------------
# PackedWeightedLoss
# ---------------------------------------------------------------------------


def _packed_preds(rng, R, S, L, C=5):
    return {
        "start_class": jnp.asarray(rng.standard_normal((R, S, L)), jnp.float32),
        "end_class": jnp.asarray(rng.standard_normal((R, S, L)), jnp.float32),
        "start_reg": jnp.asarray(rng.random((R, S)), jnp.float32),
        "end_reg": jnp.asarray(rng.random((R, S)), jnp.float32),
        "cls": jnp.asarray(rng.standard_normal((R, S, C)), jnp.float32),
    }


def _packed_targets(rng, R, S, L, mask):
    return {
        "start_class": jnp.asarray(rng.integers(0, L, (R, S)), jnp.int32),
        "end_class": jnp.asarray(rng.integers(0, L, (R, S)), jnp.int32),
        "start_reg": jnp.asarray(rng.random((R, S)), jnp.float32),
        "end_reg": jnp.asarray(rng.random((R, S)), jnp.float32),
        "cls": jnp.asarray(rng.integers(0, 5, (R, S)), jnp.int32),
        "segment_mask": jnp.asarray(mask, jnp.int32),
    }


@pytest.mark.parametrize("loss_kind", ["ce", "focal", "smooth"])
def test_packed_loss_matches_base_on_single_segment_batches(loss_kind):
    """A packed batch of single-segment rows (S=1, all real) must reproduce
    the base WeightedLoss on the same flat batch — the packed adapter only
    adds masking, never different head math."""
    class P(TP):
        loss = loss_kind

    base = build_loss(P())
    packed = PackedWeightedLoss(base)
    rng = np.random.default_rng(0)
    R, L = 8, 24
    preds = _packed_preds(rng, R, 1, L)
    targets = _packed_targets(rng, R, 1, L, np.ones((R, 1)))
    total_p, values_p = packed(preds, targets)

    flat_preds = {k: v.reshape((R,) + v.shape[2:]) for k, v in preds.items()}
    flat_targets = {
        k: v.reshape(R) for k, v in targets.items() if k != "segment_mask"
    }
    total_b, values_b = base(flat_preds, flat_targets)
    np.testing.assert_allclose(
        float(total_p), float(total_b), rtol=1e-6, atol=1e-7
    )
    for k in values_b:
        np.testing.assert_allclose(
            float(values_p[k]), float(values_b[k]), rtol=1e-6, atol=1e-7,
            err_msg=f"head {k} diverged",
        )


@pytest.mark.parametrize("loss_kind", ["ce", "focal", "smooth"])
def test_packed_loss_ignores_absent_segments(loss_kind):
    """Garbage predictions/targets in masked-out segments must not move any
    head's value (the scatter-back-through-the-mask contract)."""
    class P(TP):
        loss = loss_kind

    packed = PackedWeightedLoss(build_loss(P()))
    rng = np.random.default_rng(1)
    R, S, L = 4, 3, 24
    mask = np.zeros((R, S)); mask[:, 0] = 1; mask[:2, 1] = 1
    preds = _packed_preds(rng, R, S, L)
    targets = _packed_targets(rng, R, S, L, mask)
    total_a, values_a = packed(preds, targets)

    # corrupt everything outside the mask
    m = jnp.asarray(mask)[..., None] > 0
    preds_b = dict(preds)
    preds_b["start_class"] = jnp.where(m, preds["start_class"], 1e3)
    preds_b["cls"] = jnp.where(m, preds["cls"], -1e3)
    preds_b["start_reg"] = jnp.where(
        jnp.asarray(mask) > 0, preds["start_reg"], 7.0
    )
    targets_b = dict(targets)
    targets_b["cls"] = jnp.where(jnp.asarray(mask) > 0, targets["cls"], 4)
    targets_b["start_class"] = jnp.where(
        jnp.asarray(mask) > 0, targets["start_class"], 3
    )
    total_b, values_b = packed(preds_b, targets_b)
    np.testing.assert_allclose(float(total_a), float(total_b), rtol=1e-6)
    for k in values_a:
        np.testing.assert_allclose(
            float(values_a[k]), float(values_b[k]), rtol=1e-6,
            err_msg=f"head {k} leaked masked segments",
        )


def test_packed_loss_value_structure_matches_base():
    base = build_loss(TP())
    packed = PackedWeightedLoss(base)
    assert packed.value_structure() == base.value_structure()
    assert list(packed.keys) == list(base.keys)


# ---------------------------------------------------------------------------
# packed Trainer end to end (train + eval with callbacks)
# ---------------------------------------------------------------------------


def _packed_trainer(tmp_path, *, mesh_spec="data:8", dropout=0.1,
                    train_batch_size=8, batch_split=1, n_epochs=1,
                    sequence_packing=True, **extra):
    tok = make_tokenizer(tmp_path)
    train_ds = VarLenDataset(tok, 48, MAX_SEQ_LEN)
    test_ds = VarLenDataset(tok, 20, MAX_SEQ_LEN)
    cfg = EncoderConfig(
        vocab_size=len(tok), hidden_size=16, num_layers=2, num_heads=2,
        intermediate_size=32, max_position_embeddings=MAX_SEQ_LEN + 2,
        num_labels=5, hidden_dropout_prob=dropout,
        attention_probs_dropout_prob=dropout,
    )
    mesh = build_mesh(mesh_spec)
    model = QAModel(cfg, attention_impl="xla", mesh=mesh)
    params = QAModel(cfg).init(
        jax.random.key(0),
        np.asarray(train_ds[0].input_ids, dtype=np.int32)[None, :],
    )["params"]
    return Trainer(
        model=model, params=params, loss=build_loss(TP()),
        collate_fun=make_collate_fun(tok, max_seq_len=MAX_SEQ_LEN),
        trainer_params=TP(), train_dataset=train_ds, test_dataset=test_ds,
        mesh=mesh, n_epochs=n_epochs, train_batch_size=train_batch_size,
        test_batch_size=8, batch_split=batch_split, n_jobs=2,
        warmup_coef=0.1, max_grad_norm=1.0, seed=0,
        sequence_packing=sequence_packing, **extra,
    )


def test_packed_trainer_trains_and_evals(tmp_path):
    from test_trainer import _param_snapshot
    from ml_recipe_tpu.train import AccuracyCallback, MAPCallback

    trainer = _packed_trainer(tmp_path)
    # the schedule is sized from the packer's plan, far below the
    # pad-to-max upper bound on this short-item mix (ISSUE-5 satellite)
    assert trainer._planned_steps_per_epoch is not None
    assert trainer._planned_steps_per_epoch < len(trainer.train_dataloader)

    before = _param_snapshot(trainer.params)
    trainer.train()
    after = _param_snapshot(trainer.params)
    assert any(
        not np.array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)
        )
    )
    stats = trainer.train_dataloader.epoch_stats
    assert stats["batches"] == trainer._planned_steps_per_epoch
    assert stats["items"] > stats["rows"]  # genuinely multi-segment rows

    metrics = trainer.test(
        1, callbacks=[AccuracyCallback(),
                      MAPCallback(["a", "b", "c", "d", "e"])]
    )
    for key in ("loss", "s_acc", "c_acc", "map"):
        assert key in metrics and np.isfinite(metrics[key])


def test_packing_flag_off_is_default_path(tmp_path):
    """sequence_packing=False must construct the exact plain-loader setup."""
    from ml_recipe_tpu.data.loader import DataLoader

    on_dir = tmp_path / "on"
    on_dir.mkdir()
    trainer = _packed_trainer(on_dir)
    assert isinstance(trainer.train_dataloader, PackedDataLoader)
    assert isinstance(trainer.loss, PackedWeightedLoss)

    off_dir = tmp_path / "off"
    off_dir.mkdir()
    off = _packed_trainer(off_dir)
    off2 = Trainer(
        model=off.model, params=off.params, loss=build_loss(TP()),
        collate_fun=off.collate_fun, trainer_params=TP(),
        train_dataset=off.train_dataset, mesh=off.mesh, n_epochs=1,
        train_batch_size=8, batch_split=1, n_jobs=2, seed=0,
        sequence_packing=False,
    )
    assert isinstance(off2.train_dataloader, DataLoader)
    assert not isinstance(off2.loss, PackedWeightedLoss)


def test_packing_supersedes_length_buckets(tmp_path, caplog):
    import logging

    with caplog.at_level(logging.INFO):
        trainer = _packed_trainer(tmp_path, length_buckets=[24, MAX_SEQ_LEN])
    assert isinstance(trainer.train_dataloader, PackedDataLoader)
    assert "supersedes length_buckets" in caplog.text


def test_prefetch_auto_heuristic_unit():
    from ml_recipe_tpu.train.trainer import resolve_prefetch_auto

    # placement negligible -> depth 1; placement heavy -> depth 2
    assert resolve_prefetch_auto([0.5, 0.001, 0.001], [0.1, 0.1, 0.1]) == 1
    assert resolve_prefetch_auto([0.5, 0.02, 0.02], [0.1, 0.1, 0.1]) == 2
    # first (possibly compiling) sample is discarded
    assert resolve_prefetch_auto([0.9, 0.001], [0.01, 0.1]) == 1
    # no data -> conservative depth 1
    assert resolve_prefetch_auto([], []) == 1


def test_prefetch_auto_picks_and_logs(tmp_path, caplog):
    import logging

    with caplog.at_level(logging.INFO):
        trainer = _packed_trainer(tmp_path, device_prefetch="auto")
        trainer.train()
    assert trainer._prefetch_choice in (1, 2)
    assert "device_prefetch auto" in caplog.text


def test_oracle_read_is_per_epoch_deterministic_but_epoch_fresh(tmp_path):
    """The shared length oracle pins a stochastic-chunk dataset's draw to
    (epoch, index): repeats within an epoch are bit-identical (the length
    pass and the collate pass must see the SAME item on every host), while
    a new epoch draws fresh chunks — multi-host runs keep the cross-epoch
    chunk-resampling augmentation the single-host live-rng path has."""
    import numpy as np

    from ml_recipe_tpu.data.packing import oracle_epoch_lengths, oracle_read

    class StochasticDS:
        def __init__(self):
            self.rng = np.random.default_rng(123)

        def __len__(self):
            return 8

        def __getitem__(self, i):
            n = int(self.rng.integers(5, 40))
            return DatasetItem(
                example_id=str(i), input_ids=list(range(n)), start_id=0,
                end_id=1, label_id=0, start_position=0.0, end_position=0.1,
            )

    ds = StochasticDS()
    train_state = ds.rng.bit_generator.state  # snapshot the live stream
    a = oracle_read(ds, 3, epoch=1)
    b = oracle_read(ds, 3, epoch=1)
    c = oracle_read(ds, 3, epoch=2)
    assert a.input_ids == b.input_ids            # repeatable within epoch
    # fresh draws next epoch: over 8 indices the all-collide probability
    # is negligible (per-index lengths are drawn from 35 values)
    e1 = [len(oracle_read(ds, i, epoch=1).input_ids) for i in range(8)]
    e2 = [len(oracle_read(ds, i, epoch=2).input_ids) for i in range(8)]
    assert e1 != e2
    assert len(c.input_ids) == e2[3]
    # the training draw stream was never perturbed by oracle reads
    assert ds.rng.bit_generator.state == train_state

    cache = {}
    l1 = oracle_epoch_lengths(ds, [3, 3, 5], cache=cache, n_jobs=2,
                              read_retries=0, epoch=1)
    l2 = oracle_epoch_lengths(ds, [3, 5], cache=cache, n_jobs=2,
                              read_retries=0, epoch=2)
    assert l1[0] == l1[1] == len(a.input_ids)
    assert l2[0] == len(c.input_ids)
    # per-epoch cache keys: both epochs' lengths live side by side
    assert (1, 3) in cache and (2, 3) in cache
