"""Seeded text for the benchmark: the WordPiece vocabulary, the NQ-schema
corpus and the serving requests. numpy only: the load generator imports this
module and must never import jax.

The vocabulary writer is a copy of ``chip_smoke.py:write_vocab`` grown to the
published 30,522 entries; the corpus writer is a copy of
``bench.py:_write_synthetic_nq_corpus`` with the 12-value length cycle
replaced by a seeded heavy-tailed draw (both originals are listed in PERF.md
for a later PR to delete).
"""

from __future__ import annotations

import json
from pathlib import Path
from statistics import NormalDist

import numpy as np

SPECIALS = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
            "<p>", "</p>", ".", "?", ","]
_LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def vocab_words(seed: int, size: int) -> list:
    """``size - len(SPECIALS)`` distinct lower-case words of 3-9 letters,
    sorted, a pure function of ``seed``."""
    need = size - len(SPECIALS)
    rng = np.random.default_rng([seed, 0x70CAB])
    words: set = set()
    while len(words) < need:
        n = 2 * (need - len(words)) + 64
        lengths = rng.integers(3, 10, size=n)
        flat = rng.choice(_LETTERS, size=int(lengths.sum()))
        pos = 0
        for k in lengths:
            words.add("".join(flat[pos:pos + k]))
            pos += k
    return sorted(sorted(words)[:need])


def write_vocab(path: Path, seed: int, size: int) -> list:
    """One token per line; returns the plain words (no specials)."""
    words = vocab_words(seed, size)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("\n".join(SPECIALS + words) + "\n")
    return words


def _sentences(rng, words: np.ndarray, n_words: int, lo: int, hi: int) -> list:
    """Whitespace tokens of one document body: sentences of ``lo..hi`` words,
    first word capitalised (``data/sentence.py`` splits before a capital),
    each closed by a lone full stop. Exactly ``n_words`` tokens."""
    out: list = []
    picks = words[rng.integers(0, len(words), size=n_words)]
    pos = 0
    while pos < n_words:
        k = int(min(rng.integers(lo, hi + 1), n_words - pos))
        sent = list(picks[pos:pos + k])
        sent[0] = sent[0].capitalize()
        if k > 1:
            sent[-1] = "."
        out.extend(sent)
        pos += k
    return out


FIXED = 0xF17ED      # what is the same for every --seed: the work, not the words


def doc_word_counts(n_docs: int, p: dict) -> np.ndarray:
    """Whitespace-word counts of the documents: the ``n_docs`` quantiles of
    a log-normal (median ``median_words``, shape ``sigma``: the heavy tail of
    whole Wikipedia pages), clipped, in an order that does not depend on
    ``--seed``. Every seed's corpus therefore holds the same amount of work,
    document by document, and differs only in its words: a run-to-run
    difference is the system's, not the draw's (with iid draws the mean
    length of the ~250 documents a window consumes swings by 7%)."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n_docs)
                    for i in range(n_docs)])
    counts = np.exp(np.log(p["median_words"]) + p["sigma"] * z)
    counts = np.clip(counts, p["min_words"], p["max_words"]).astype(np.int64)
    return counts[np.random.default_rng(FIXED).permutation(n_docs)]


def write_nq_corpus(path: Path, seed: int, words: list, p: dict) -> dict:
    """``simplified-nq-train.jsonl`` schema, ``p['documents']`` lines: one
    annotated long answer (a paragraph) per document, a short answer inside
    it in ``p['short_answer_share']`` of them (the same documents for every
    seed, as their lengths are). Returns the corpus counts."""
    rng = np.random.default_rng([seed, 0xC0595])
    vocab = np.asarray(words)
    n_docs = int(p["documents"])
    counts = doc_word_counts(n_docs, p)
    with_short = np.zeros(n_docs, bool)
    with_short[np.random.default_rng(FIXED + 1).permutation(n_docs)[
        :int(round(p["short_answer_share"] * n_docs))]] = True
    lo, hi = p["sentence_words"]
    n_short = 0
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as fh:
        for i, n_words in enumerate(counts):
            body = _sentences(rng, vocab, int(n_words), lo, hi)
            # paragraphs of ~120 words: <P> ... </P>
            tokens: list = []
            paragraphs = []
            pos = 0
            while pos < len(body):
                k = int(rng.integers(60, 181))
                start = len(tokens)
                tokens.append("<P>")
                tokens.extend(body[pos:pos + k])
                tokens.append("</P>")
                paragraphs.append((start, len(tokens)))
                pos += k
            la_idx = int(rng.integers(0, len(paragraphs)))
            la_start, la_end = paragraphs[la_idx]
            short = []
            if with_short[i] and la_end - la_start > 8:
                s = int(rng.integers(la_start + 1, la_end - 6))
                short = [{"start_token": s,
                          "end_token": s + int(rng.integers(1, 6))}]
                n_short += 1
            q_words = vocab[rng.integers(0, len(vocab),
                                         size=int(rng.integers(6, 13)))]
            fh.write(json.dumps({
                "example_id": str(i),
                "document_text": " ".join(tokens),
                "question_text": " ".join(q_words) + " ?",
                "annotations": [{
                    "yes_no_answer": "NONE",
                    "long_answer": {"start_token": la_start,
                                    "end_token": la_end,
                                    "candidate_index": la_idx},
                    "short_answers": short,
                }],
                "long_answer_candidates": [
                    {"start_token": a, "end_token": b, "top_level": True}
                    for a, b in paragraphs],
            }) + "\n")
    return {"documents": int(len(counts)),
            "words_mean": float(counts.mean()),
            "words_median": float(np.median(counts)),
            "words_max": int(counts.max()),
            "short_answers": n_short}


def serve_requests(seed: int, words: list, p: dict, n: int) -> list:
    """``n`` distinct requests for the serve cells: a question of
    ``question_words`` words and a document whose token count lands it in a
    drawn number of sliding-window chunks (``chunk_mix``: [share, lo, hi]
    rows). Every word is one vocabulary entry, so tokens = words, and
    ``data/chunking.py:window_chunks`` starts a chunk at every multiple of
    ``doc_stride`` below the token count: ``c`` chunks need ``(c-1)*stride <
    tokens <= c*stride``. Returns dicts with ``body`` (bytes) and the chunk
    count aimed at."""
    rng = np.random.default_rng([seed, 0x5E27E])
    vocab = np.asarray(words)
    shares = np.asarray([row[0] for row in p["chunk_mix"]], dtype=np.float64)
    shares = shares / shares.sum()
    q_lo, q_hi = p["question_words"]
    out = []
    for _ in range(n):
        q_n = int(rng.integers(q_lo, q_hi + 1))
        _, c_lo, c_hi = p["chunk_mix"][int(rng.choice(len(shares), p=shares))]
        chunks = int(rng.integers(c_lo, c_hi + 1))
        stride = p["doc_stride"]
        n_tok = int(rng.integers(
            max((chunks - 1) * stride + 1, p["min_doc_tokens"]),
            chunks * stride + 1))
        body = _sentences(rng, vocab, n_tok, *p["sentence_words"])
        question = " ".join(vocab[rng.integers(0, len(vocab), size=q_n)]) + " ?"
        out.append({
            "chunks": chunks,
            "body": json.dumps({"question": question,
                                "document": " ".join(body)}).encode(),
        })
    return out
