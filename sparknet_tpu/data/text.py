"""Text data layer for the BERT family and the decoder: corpus -> MLM
batches (:func:`mlm_feed`), next-token batches over one unbroken stream
(:func:`clm_feed`) or over packed documents (:func:`packed_feed`).

No reference counterpart (SparkNet has no text path — SURVEY.md §2);
follows the framework's RDD-style contract: partitions are pure
functions, masking is a deterministic per-batch transform keyed by the
feed rng, so every batch is recomputable after preemption.

Two corpus sources:
- plain-text files: whitespace tokenization over a vocab built from the
  corpus (deterministic: sorted by frequency then token);
- synthetic: a fixed-transition Markov chain over the vocab — learnable
  structure (MLM loss drops fast) with zero bytes on disk.

Special token ids follow BERT convention: 0=[PAD] 1=[UNK] 2=[CLS]
3=[SEP] 4=[MASK]; real tokens start at 5.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .rdd import ShardedDataset

PAD, UNK, CLS, SEP, MASK = 0, 1, 2, 3, 4
NUM_SPECIAL = 5


class Vocab:
    def __init__(self, tokens: Sequence[str]):
        self.itos = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + list(tokens)
        self.stoi = {t: i for i, t in enumerate(self.itos)}

    def __len__(self) -> int:
        return len(self.itos)

    def encode(self, words: Sequence[str]) -> List[int]:
        return [self.stoi.get(w, UNK) for w in words]

    @classmethod
    def from_corpus(cls, texts: Sequence[str], max_size: int = 30000) -> "Vocab":
        counts: Dict[str, int] = {}
        for t in texts:
            for w in t.split():
                counts[w] = counts.get(w, 0) + 1
        ordered = sorted(counts, key=lambda w: (-counts[w], w))
        return cls(ordered[: max_size - NUM_SPECIAL])


def synthetic_token_stream(
    n_tokens: int, vocab_size: int, seed: int = 0
) -> np.ndarray:
    """Markov chain over real-token ids [NUM_SPECIAL, vocab_size): each
    token strongly predicts a successor — structure MLM can learn."""
    real = vocab_size - NUM_SPECIAL
    assert real >= 2, "vocab too small"
    rng = np.random.default_rng(seed)
    # deterministic successor table + noise
    succ = (np.arange(real) * 17 + 3) % real
    toks = np.empty(n_tokens, np.int64)
    t = 0
    for i in range(n_tokens):
        toks[i] = t + NUM_SPECIAL
        t = succ[t] if rng.random() < 0.8 else rng.integers(0, real)
    return toks


def mlm_mask(
    tokens: np.ndarray,
    rng: np.random.Generator,
    vocab_size: int,
    max_preds: int,
    mask_prob: float = 0.15,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """BERT masking on one sequence (no [CLS]/[SEP]/[PAD] positions):
    of chosen positions 80% -> [MASK], 10% -> random token, 10% kept.
    Returns (masked_tokens, positions, labels, weights), fixed length
    ``max_preds`` (zero-padded)."""
    maskable = np.flatnonzero(tokens >= NUM_SPECIAL)
    n = min(max_preds, max(1, int(round(len(maskable) * mask_prob))))
    if len(maskable) == 0:
        n = 0
    chosen = (
        rng.choice(maskable, size=n, replace=False) if n else np.empty(0, np.int64)
    )
    out = tokens.copy()
    labels = np.zeros(max_preds, np.int64)
    positions = np.zeros(max_preds, np.int64)
    weights = np.zeros(max_preds, np.float32)
    for j, p in enumerate(sorted(chosen)):
        positions[j] = p
        labels[j] = tokens[p]
        weights[j] = 1.0
        r = rng.random()
        if r < 0.8:
            out[p] = MASK
        elif r < 0.9:
            out[p] = rng.integers(NUM_SPECIAL, vocab_size)
        # else keep original
    return out, positions, labels, weights


def mlm_dataset(
    *,
    text_files: Optional[Sequence[str]] = None,
    vocab: Optional[Vocab] = None,
    vocab_size: int = 1024,
    n_tokens: int = 1 << 16,
    seq_len: int = 128,
    num_partitions: int = 8,
    seed: int = 0,
) -> Tuple[ShardedDataset, int]:
    """Dataset of {"tokens": (seq_len,) int sequences with [CLS]/[SEP]}.
    Returns (dataset, vocab_size)."""
    if text_files:
        texts = [open(f).read() for f in text_files]
        vocab = vocab or Vocab.from_corpus(texts, max_size=vocab_size)
        ids: List[int] = []
        for t in texts:
            ids.extend(vocab.encode(t.split()))
        stream = np.asarray(ids, np.int64)
        vsize = len(vocab)
    else:
        stream = synthetic_token_stream(n_tokens, vocab_size, seed)
        vsize = vocab_size
    body = seq_len - 2  # room for [CLS] ... [SEP]
    n_seq = len(stream) // body
    seqs = np.full((n_seq, seq_len), PAD, np.int64)
    seqs[:, 0] = CLS
    seqs[:, 1 : body + 1] = stream[: n_seq * body].reshape(n_seq, body)
    seqs[:, body + 1] = SEP
    ds = ShardedDataset.from_arrays({"tokens": seqs}, num_partitions)
    return ds, vsize


def mlm_feed(
    ds: ShardedDataset,
    batch_size: int,
    vocab_size: int,
    max_preds: int,
    seed: int = 0,
) -> Iterator[Dict[str, np.ndarray]]:
    """Batches in the BertMLM blob layout (host numpy)."""

    def transform(batch, rng):
        toks = batch["tokens"]
        b, s = toks.shape
        ids = np.empty((b, s), np.int32)
        positions = np.empty((b, max_preds), np.int32)
        labels = np.empty((b, max_preds), np.int32)
        weights = np.empty((b, max_preds), np.float32)
        for i in range(b):
            o, p, l, w = mlm_mask(toks[i], rng, vocab_size, max_preds)
            ids[i], positions[i], labels[i], weights[i] = o, p, l, w
        return {
            "input_ids": ids,
            "token_type_ids": np.zeros((b, s), np.int32),
            "attention_mask": (toks != PAD).astype(np.int32),
            "mlm_positions": positions,
            "mlm_labels": labels,
            "mlm_weights": weights,
        }

    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


def mlm_feed_tokens(
    ds: ShardedDataset,
    batch_size: int,
    vocab_size: int,
    seed: int = 0,
    mask_prob: float = 0.15,
) -> Iterator[Dict[str, np.ndarray]]:
    """Token-level MLM batches for sequence-parallel training: labels and
    weights are (B, S) arrays (shardable along S), plus global
    ``position_ids`` — the layout
    :func:`sparknet_tpu.parallel.sequence.make_sp_train_step` consumes."""

    def transform(batch, rng):
        toks = batch["tokens"]
        b, s = toks.shape
        ids = np.empty((b, s), np.int32)
        labels = np.zeros((b, s), np.int32)
        weights = np.zeros((b, s), np.float32)
        max_preds = max(1, int(round(s * mask_prob)) + 1)
        for i in range(b):
            o, p, l, w = mlm_mask(toks[i], rng, vocab_size, max_preds, mask_prob)
            ids[i] = o
            n = int(w.sum())
            labels[i, p[:n]] = l[:n]
            weights[i, p[:n]] = 1.0
        return {
            "input_ids": ids,
            "token_type_ids": np.zeros((b, s), np.int32),
            "attention_mask": (toks != PAD).astype(np.int32),
            "position_ids": np.broadcast_to(
                np.arange(s, dtype=np.int32), (b, s)
            ).copy(),
            "mlm_labels": labels,
            "mlm_weights": weights,
        }

    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


def clm_dataset(
    *,
    vocab_size: int,
    n_tokens: int = 1 << 16,
    seq_len: int = 128,
    num_partitions: int = 8,
    seed: int = 0,
) -> ShardedDataset:
    """Dataset of {"tokens": (seq_len + 1,)} windows of the synthetic
    stream, back to back: no padding, no packing, no special tokens
    added, every id under ``vocab_size`` (a slice of a larger vocabulary
    is a smaller vocabulary)."""
    stream = synthetic_token_stream(n_tokens, vocab_size, seed)
    n_seq = len(stream) // (seq_len + 1)
    if n_seq == 0:
        raise ValueError(
            f"{n_tokens} tokens hold no window of {seq_len} + 1"
        )
    windows = stream[: n_seq * (seq_len + 1)].reshape(n_seq, seq_len + 1)
    return ShardedDataset.from_arrays(
        {"tokens": windows}, min(num_partitions, n_seq)
    )


def clm_feed(
    ds: ShardedDataset, batch_size: int, seed: int = 0
) -> Iterator[Dict[str, np.ndarray]]:
    """Batches in the DecoderLM blob layout (host numpy): ``input_ids``
    and ``labels``, the same window shifted by one, so that every
    position predicts the next token."""

    def transform(batch, rng):
        toks = batch["tokens"].astype(np.int32)
        return {
            "input_ids": np.ascontiguousarray(toks[:, :-1]),
            "labels": np.ascontiguousarray(toks[:, 1:]),
        }

    return ds.batches(batch_size, shuffle=True, seed=seed, transform=transform)


# a position of a packed batch that bears no loss: the last token of a
# document (and of a sequence), whose next token is another document's
NO_LABEL = -100


def packed_dataset(
    *,
    vocab_size: int,
    n_tokens: int = 1 << 16,
    seq_len: int = 128,
    median_len: float = 1024.0,
    sigma: float = 1.0,
    min_len: int = 32,
    max_len: int = 8192,
    num_partitions: int = 8,
    seed: int = 0,
) -> ShardedDataset:
    """Documents packed into sequences of ``seq_len`` tokens, as a code or
    chat corpus is: lengths ``clip(lognormal(median_len, sigma), min_len,
    max_len)`` from ``seed``, concatenated and cut every ``seq_len`` tokens;
    a document cut by a sequence's end goes on in the next sequence as a
    document of its own.  No padding: every position is a token.  Rows:
    ``input_ids``; ``labels`` (the next token, ``NO_LABEL`` where it is
    another document's or the sequence ends); ``segment_ids`` (0, 1, ... along
    a sequence); ``positions`` (inside the document), all (seq_len,) int32.

    Token ids are a chain over ``[NUM_SPECIAL, vocab_size)`` that restarts
    at a random id with every document and, inside one, with probability
    0.2 a token: each token otherwise predicts ``previous + 17``, so there
    is structure to learn.  Built with array operations only (the pool is
    made once, in set-up)."""
    real = vocab_size - NUM_SPECIAL
    assert real >= 2, "vocab too small"
    if seq_len < min_len:
        raise ValueError(
            f"sequences of {seq_len} tokens are shorter than the shortest "
            f"document ({min_len}): nothing to pack"
        )
    n_seq = n_tokens // seq_len
    if n_seq == 0:
        raise ValueError(f"{n_tokens} tokens hold no sequence of {seq_len}")
    total = n_seq * seq_len
    rng = np.random.default_rng(seed)
    lengths = np.clip(
        np.rint(rng.lognormal(np.log(median_len), sigma, total // min_len + 1)),
        min_len, max_len,
    ).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    at = np.arange(total, dtype=np.int32)
    opens = np.zeros(total, bool)  # a document begins here
    opens[starts[starts < total]] = True
    opens[::seq_len] = True  # a sequence begins with (the rest of) one
    since_last = lambda flags: at - np.maximum.accumulate(
        np.where(flags, at, np.int32(0))
    )
    # the chain: restarts where a document begins, and at random inside
    since = since_last(opens | (rng.random(total, dtype=np.float32) < 0.2))
    base = rng.integers(0, real, total, dtype=np.int32)[at - since]
    tokens = (base + 17 * since) % np.int32(real) + np.int32(NUM_SPECIAL)
    labels = np.empty_like(tokens)  # the next token, unless it opens another
    labels[:-1] = np.where(opens[1:], np.int32(NO_LABEL), tokens[1:])
    labels[-1] = NO_LABEL
    rows = lambda x: x.reshape(n_seq, seq_len)
    segment = np.cumsum(rows(opens), axis=1, dtype=np.int32) - np.int32(1)
    position = since_last(opens)
    return ShardedDataset.from_arrays(
        {
            "input_ids": rows(tokens), "labels": rows(labels),
            "segment_ids": segment, "positions": rows(position),
        },
        min(num_partitions, n_seq),
    )


def packed_feed(
    ds: ShardedDataset, batch_size: int, seed: int = 0
) -> Iterator[Dict[str, np.ndarray]]:
    """Batches of :func:`packed_dataset`'s rows in the DecoderLM blob
    layout (host numpy): ``input_ids``, ``labels``, ``segment_ids`` and
    ``positions``, (B, S) int32, through the same iterator as
    :func:`clm_feed`."""
    return ds.batches(batch_size, shuffle=True, seed=seed)


def pool_pairs(ds: ShardedDataset, window: Optional[int] = None) -> float:
    """The (query, key) pairs a causal attention layer computes on one
    sequence of :func:`packed_dataset`'s pool, the mean over the pool: each
    token with the keys of its own document at or before it, the nearest
    ``window`` of them where given."""
    seen = np.concatenate([
        ds.collect_partition(i)["positions"] for i in range(ds.num_partitions)
    ]).astype(np.int64) + 1
    if window is not None:
        seen = np.minimum(seen, window)
    return float(seen.sum() / len(seen))
