"""Text pipeline — the rebuild of the reference's Wikipedia text RDD plane.

The reference tokenizes Wikipedia into MLM examples inside text RDD
partitions (SURVEY.md §2 'Data: text pipeline'). Same shape here: RDD-style
transforms over :class:`~distributeddeeplearningspark_tpu.rdd.
PartitionedDataset` running on the host, yielding fixed-shape example dicts
(static shapes keep the jitted step compile count at one):

``{"input_ids": [S] i32, "attention_mask": [S] i32,
   "mlm_labels": [S] i32, "mlm_weights": [S] f32}``

Tokenizer: greedy-longest-match WordPiece over a corpus-built vocab — the
BERT scheme, self-contained (no HF download; the env has no egress). For real
runs a pre-built vocab file can be loaded.
"""

from __future__ import annotations

import collections
import itertools
import os
import re
from typing import Iterable, Iterator, Sequence

import numpy as np

from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

PAD, UNK, CLS, SEP, MASK = "[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"
SPECIAL_TOKENS = (PAD, UNK, CLS, SEP, MASK)

_WORD_RE = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")

#: most words a tokenizer keeps the pieces of (a word that finds no room is
#: tokenized again on every occurrence): a dump with endless rare words
#: cannot grow the memo without limit; some 60 MB of str keys and id lists
#: when full
_WORD_MEMO_WORDS = 1 << 18


class WordPieceTokenizer:
    """Greedy longest-match-first subword tokenizer (BERT's scheme)."""

    def __init__(self, vocab: dict[str, int]):
        self.vocab = dict(vocab)
        self.inv = {i: t for t, i in self.vocab.items()}
        for tok in SPECIAL_TOKENS:
            if tok not in self.vocab:
                raise ValueError(f"vocab missing special token {tok}")
        self.pad_id = self.vocab[PAD]
        self.unk_id = self.vocab[UNK]
        self.cls_id = self.vocab[CLS]
        self.sep_id = self.vocab[SEP]
        self.mask_id = self.vocab[MASK]
        #: ids never selected for masking
        self.special_ids = frozenset(self.vocab[t] for t in SPECIAL_TOKENS)
        #: the same as a lookup table over the vocabulary's ids, and the ids
        #: a random replacement may be drawn from (loaded vocabs, e.g. the
        #: stock BERT vocab.txt, don't keep specials in a contiguous prefix):
        #: what :func:`mask_tokens` needs of the tokenizer, built once here
        self.special_mask = np.zeros(max(self.inv) + 1, bool)
        self.special_mask[list(self.special_ids)] = True
        self.replacement_ids = np.setdiff1d(
            np.arange(len(self.vocab), dtype=np.int32),
            np.fromiter(self.special_ids, np.int32))
        # whitespace-delimited word -> ids of its pieces, filled by encode()
        self._word_pieces: dict[str, list[int]] = {}
        self._words = self._memo_misses = 0

    @property
    def vocab_size(self) -> int:
        return len(self.vocab)

    def tokenize_word(self, word: str) -> list[int]:
        ids, start = [], 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = self.vocab[piece]
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]
            ids.append(cur)
            start = end
        return ids

    def encode(self, text: str) -> np.ndarray:
        """Ids of a document as one int32 array.

        The text is split at whitespace (no match of ``_WORD_RE`` crosses
        it) and each distinct word, punctuation attached, goes through
        ``_WORD_RE`` and :meth:`tokenize_word` once, while the memo has
        room; after that the document costs a split and a dict lookup a word.
        """
        words = text.lower().split()
        pieces = list(map(self._word_pieces.get, words))
        self._words += len(words)
        if None in pieces:
            for i, word in enumerate(words):
                if pieces[i] is None:
                    pieces[i] = self._pieces_of_new_word(word)
        return np.fromiter(itertools.chain.from_iterable(pieces), np.int32)

    def _pieces_of_new_word(self, word: str) -> list[int]:
        """What :meth:`encode` does with a word its memo did not hold."""
        memo = self._word_pieces
        ids = memo.get(word)  # met earlier in the same text
        if ids is None:
            ids = [piece for part in _WORD_RE.findall(word)
                   for piece in self.tokenize_word(part)]
            self._memo_misses += 1
            if len(memo) < _WORD_MEMO_WORDS:
                memo[word] = ids
        return ids

    def stats(self) -> dict[str, int]:
        """How often the word memo of :meth:`encode` engaged so far."""
        return {"words": self._words,
                "memo_hits": self._words - self._memo_misses,
                "memo_words": len(self._word_pieces)}

    def decode(self, ids: Sequence[int]) -> str:
        pieces = [self.inv.get(int(i), UNK) for i in ids]
        out: list[str] = []
        for p in pieces:
            if p.startswith("##") and out:
                out[-1] += p[2:]
            else:
                out.append(p)
        return " ".join(out)

    @staticmethod
    def train(corpus: Iterable[str], vocab_size: int = 8192, *, min_freq: int = 2
              ) -> "WordPieceTokenizer":
        """Frequency-based vocab: whole words first, then char fallbacks.

        A full WordPiece-training (likelihood-driven merges) is overkill for
        the contract; frequency top-k with char-level backstop gives the same
        interface and sub-linear UNK rates on natural text.
        """
        counts: collections.Counter = collections.Counter()
        chars: set[str] = set()
        for line in corpus:
            for w in _WORD_RE.findall(line.lower()):
                counts[w] += 1
                chars.update(w)
        vocab: dict[str, int] = {t: i for i, t in enumerate(SPECIAL_TOKENS)}
        for ch in sorted(chars):  # char backstop: no word is ever fully UNK
            for piece in (ch, "##" + ch):
                if piece not in vocab:
                    vocab[piece] = len(vocab)
        for w, c in counts.most_common():
            if len(vocab) >= vocab_size:
                break
            if c >= min_freq and w not in vocab:
                vocab[w] = len(vocab)
        return WordPieceTokenizer(vocab)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            for tok, _ in sorted(self.vocab.items(), key=lambda kv: kv[1]):
                f.write(tok + "\n")

    @staticmethod
    def load(path: str) -> "WordPieceTokenizer":
        with open(path) as f:
            return WordPieceTokenizer({line.rstrip("\n"): i for i, line in enumerate(f)})


def segments_from_docs(
    docs: Iterable[str], tokenizer: WordPieceTokenizer, seq_len: int
) -> Iterator[np.ndarray]:
    """Pack tokenized documents into fixed [CLS] ... [SEP] windows."""
    for ids, _ in _packed_windows((tokenizer.encode(doc) for doc in docs),
                                  tokenizer, seq_len, segments=False):
        yield ids


def _pack_token_windows(
    doc_tokens: Iterable, window: int, *, segments: bool
) -> Iterator[tuple[np.ndarray, np.ndarray | None, bool]]:
    """Lockstep token/segment-id packer shared by the MLM and causal-LM
    pipelines: concatenate per-document token arrays, tag every position
    with a running document counter (only if ``segments``; else None),
    and cut ``window``-sized chunks → ``(chunk, seg_ids, is_partial)``, all
    int32. The final partial chunk (corpus tail) is yielded unpadded with
    ``is_partial=True`` — framing (CLS/SEP vs EOS, pad conventions) belongs
    to the caller. ONE copy of the buffer-slicing invariant lives here.
    Chunks are views of the packer's buffer: a caller copies them into its
    frame.
    """
    pending: list[np.ndarray] = []
    pending_seg: list[np.ndarray] = []
    held = 0
    for doc_id, toks in enumerate(doc_tokens):
        toks = np.asarray(toks, np.int32)
        pending.append(toks)
        if segments:
            pending_seg.append(np.full(len(toks), doc_id, np.int32))
        held += len(toks)
        if held < window:
            continue
        buf = np.concatenate(pending)
        seg = np.concatenate(pending_seg) if segments else None
        full = held - held % window
        for off in range(0, full, window):
            yield (buf[off:off + window],
                   seg[off:off + window] if segments else None, False)
        pending, held = [buf[full:]], held - full
        if segments:
            pending_seg = [seg[full:]]
    if held:
        yield (np.concatenate(pending),
               np.concatenate(pending_seg) if segments else None, True)


def _padded(values: np.ndarray, length: int, fill) -> np.ndarray:
    """``values`` followed by ``fill`` up to ``length``, in a new array."""
    out = np.full(length, fill, values.dtype)
    out[:len(values)] = values
    return out


def _framed(chunk: np.ndarray, tokenizer: WordPieceTokenizer, seq_len: int
            ) -> np.ndarray:
    """``[CLS] chunk [SEP] [PAD]...`` as one [seq_len] int32 array."""
    ids = np.full(seq_len, tokenizer.pad_id, np.int32)
    ids[0] = tokenizer.cls_id
    ids[1:len(chunk) + 1] = chunk
    ids[len(chunk) + 1] = tokenizer.sep_id
    return ids


def packed_segments_from_docs(
    docs: Iterable[str], tokenizer: WordPieceTokenizer, seq_len: int
) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """Pack documents back-to-back into full windows, tracking which document
    owns each position → (ids [S] i32, segment_ids [S] i32).

    Every window is completely full (zero padding) except the corpus tail —
    this is why the measured tokens/sec here IS effective tokens/sec
    (VERDICT r2 #4; contrast ``padded_segments_from_docs``). Segment ids are
    a running document counter; [CLS] joins the window's first document and
    the final [SEP] its last; padding (tail window only) gets id -1 so real
    tokens never attend to pad positions even without a padding mask.
    """
    return packed_segments_from_tokens(
        (tokenizer.encode(doc) for doc in docs), tokenizer, seq_len)


def packed_segments_from_tokens(
    doc_tokens: Iterable, tokenizer: WordPieceTokenizer, seq_len: int
) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """:func:`packed_segments_from_docs` over PRE-tokenized documents — the
    split that lets the tokenize stage (the per-doc map) run in the
    :mod:`.workers` process pool while the stateful cross-document packing
    stays on the consumer. Since the tokenizer remembers its words and every
    stage handles a document as one array, tokenizing costs well under a
    microsecond a token in process: the pool pays where the per-doc map
    also reads and cleans a real dump (``wikipedia_dump``:
    ``clean_wikitext``), not for synthetic text. Accepts lists or int arrays
    per doc."""
    return _packed_windows(doc_tokens, tokenizer, seq_len, segments=True)


def _packed_windows(
    doc_tokens: Iterable, tokenizer: WordPieceTokenizer, seq_len: int, *,
    segments: bool
) -> Iterator[tuple[np.ndarray, np.ndarray | None]]:
    """Framed packed windows → (ids, segment ids or None if not asked for)."""
    for chunk, cseg, _ in _pack_token_windows(doc_tokens, seq_len - 2,
                                              segments=segments):
        # [CLS] joins the window's first document, [SEP] its last
        sids = _padded(np.concatenate((cseg[:1], cseg, cseg[-1:])), seq_len,
                       -1) if segments else None
        yield _framed(chunk, tokenizer, seq_len), sids


def padded_segments_from_docs(
    docs: Iterable[str], tokenizer: WordPieceTokenizer, seq_len: int
) -> Iterator[np.ndarray]:
    """One document per window, padded to ``seq_len`` (long docs split).

    The reference-era per-document pipeline shape — kept as the measured
    baseline for the packing A/B (VERDICT r2 #4): real Wikipedia documents
    average far under 512 tokens, so most of each window is [PAD] and the
    naive tokens/sec number is mostly padding throughput.
    """
    return _padded_from_tokens(
        (tokenizer.encode(doc) for doc in docs), tokenizer, seq_len)


def _padded_from_tokens(
    doc_tokens: Iterable, tokenizer: WordPieceTokenizer, seq_len: int
) -> Iterator[np.ndarray]:
    """Padded-window framing over pre-tokenized docs (lists or int arrays)
    — the tokenize/frame split that lets the worker pool own the encode."""
    budget = seq_len - 2
    for toks in doc_tokens:
        toks = np.asarray(toks, np.int32)
        for off in range(0, len(toks), budget):
            yield _framed(toks[off:off + budget], tokenizer, seq_len)


def _tokens_dataset(docs: PartitionedDataset, tok_fn, num_workers: int | None,
                    *, label: str) -> PartitionedDataset:
    """Per-doc tokenize as a dataset stage: pooled over worker processes
    when ``num_workers`` (or ``DLS_DATA_WORKERS``) asks for it, the plain
    in-process ``map`` otherwise — same token stream either way."""
    from distributeddeeplearningspark_tpu.data import workers as workers_lib

    if workers_lib.resolve_num_workers(num_workers) > 0:
        return workers_lib.WorkerMappedDataset(docs, tok_fn, num_workers,
                                               label=label)
    return docs.map(tok_fn)


def mask_tokens(
    ids: np.ndarray,
    tokenizer: WordPieceTokenizer,
    rng: np.random.Generator,
    *,
    mask_prob: float = 0.15,
) -> dict[str, np.ndarray]:
    """BERT's 80/10/10 MLM corruption → fixed-shape example dict."""
    ids = np.asarray(ids, np.int32)
    maskable = ~tokenizer.special_mask[ids]
    sel = (rng.random(ids.shape) < mask_prob) & maskable
    if not sel.any() and maskable.any():  # guarantee ≥1 target per segment
        sel[rng.choice(np.flatnonzero(maskable))] = True

    corrupted = ids.copy()
    r = rng.random(ids.shape)
    corrupted[sel & (r < 0.8)] = tokenizer.mask_id
    rand_sel = sel & (r >= 0.8) & (r < 0.9)
    if rand_sel.any():
        corrupted[rand_sel] = rng.choice(tokenizer.replacement_ids,
                                         rand_sel.sum())
    # remaining 10%: keep original token

    return {
        "input_ids": corrupted,
        "attention_mask": (ids != tokenizer.pad_id).astype(np.int32),
        "mlm_labels": ids,
        "mlm_weights": sel.astype(np.float32),
    }


def pack_mlm_predictions(
    example: dict[str, np.ndarray], max_predictions: int
) -> dict[str, np.ndarray]:
    """Full-length MLM example → gathered form (original TPU BERT layout).

    Adds ``mlm_positions`` [P] and rewrites ``mlm_labels``/``mlm_weights``
    to [P] (P = ``max_predictions``, zero-padded/weighted-0), so
    :class:`~..models.bert.BertForMLM` runs its vocab projection on masked
    positions only. Targets beyond P are dropped (weight-0), matching the
    reference BERT data pipeline's ``max_predictions_per_seq`` truncation.
    """
    sel = np.flatnonzero(example["mlm_weights"] > 0)[:max_predictions]
    pos = np.zeros((max_predictions,), np.int32)
    labels = np.zeros((max_predictions,), np.int32)
    weights = np.zeros((max_predictions,), np.float32)
    pos[: len(sel)] = sel
    labels[: len(sel)] = example["mlm_labels"][sel]
    weights[: len(sel)] = example["mlm_weights"][sel]  # preserve weighting
    out = {
        "input_ids": example["input_ids"],
        "attention_mask": example["attention_mask"],
        "mlm_positions": pos,
        "mlm_labels": labels,
        "mlm_weights": weights,
    }
    if "segment_ids" in example:  # packed batches keep their doc boundaries
        out["segment_ids"] = example["segment_ids"]
    return out


def mlm_dataset(
    docs: PartitionedDataset,
    tokenizer: WordPieceTokenizer,
    *,
    seq_len: int = 128,
    mask_prob: float = 0.15,
    seed: int = 0,
    max_predictions: int | None = None,
    segment_ids: bool = False,
    pack: bool = True,
    num_workers: int | None = None,
) -> PartitionedDataset:
    """Text RDD → MLM example RDD (tokenize → pack → mask, per partition).

    ``max_predictions``: emit the gathered (``mlm_positions``) form so the
    model's vocab projection runs on masked positions only (recommended:
    ``ceil(seq_len * mask_prob) + a few``, e.g. 80 for 512×0.15).
    ``segment_ids``: also emit per-position document ids so attention is
    blocked across packed-document boundaries (the model/flash kernel
    consume them — VERDICT r2 #4); without them packing follows the
    RoBERTa FULL-SENTENCES convention (documents share the window).
    ``pack=False``: one padded document per window — the reference-era
    shape, kept for the padding-waste A/B (see ``token_stats``).
    ``num_workers`` (default ``DLS_DATA_WORKERS``): run the per-doc map
    (tokenize) across worker processes (:mod:`.workers`); the stateful
    window packing and the per-partition-seeded masking stay on the
    consumer, so the example stream is byte-identical for any count. One
    thread tokenizes, packs and masks in about a microsecond a token (the
    tokenizer remembers its words; PERF.md §5), so the pool pays only
    where ``docs`` itself is dear per document: a real dump read and
    cleaned by ``wikipedia_dump`` (``clean_wikitext``), words the memo has
    not met. Each worker process fills a memo of its own.
    """

    if not pack and segment_ids:
        raise ValueError(
            "segment_ids=True requires pack=True (padded mode has one "
            "document per window — there are no boundaries to mark)")

    token_ds = _tokens_dataset(
        docs, lambda doc: np.asarray(tokenizer.encode(doc), np.int32),
        num_workers, label="mlm_tokenize")

    def per_partition(pidx: int, toks: Iterable[np.ndarray]) -> Iterator[dict]:
        rng = np.random.default_rng(seed * 100003 + pidx)
        if not pack:
            gen: Iterator = (
                (ids, None)
                for ids in _padded_from_tokens(toks, tokenizer, seq_len))
        else:
            gen = _packed_windows(toks, tokenizer, seq_len,
                                  segments=segment_ids)
        for seg, sids in gen:
            ex = mask_tokens(seg, tokenizer, rng, mask_prob=mask_prob)
            if sids is not None:
                ex["segment_ids"] = sids
            yield (pack_mlm_predictions(ex, max_predictions)
                   if max_predictions else ex)

    return token_ds.map_partitions_with_index(per_partition)


def token_stats(dataset: PartitionedDataset, *, max_examples: int = 10_000) -> dict:
    """Measured padding waste of an MLM/LM example stream (VERDICT r2 #4).

    Returns ``{examples, tokens, pad_tokens, pad_frac, effective_frac}``
    over up to ``max_examples`` examples — ``effective_frac`` is the factor
    that turns raw tokens/sec into honest non-pad tokens/sec.
    """
    examples = tokens = pad = 0
    stream = (ex for p in range(dataset.num_partitions)
              for ex in dataset.iter_partition(p))
    for i, ex in enumerate(stream):
        if i >= max_examples:
            break
        am = ex.get("attention_mask")
        if am is None:  # LM form: loss_mask plays the same role
            am = ex["loss_mask"]
        examples += 1
        tokens += int(np.size(am))
        pad += int(np.size(am) - np.count_nonzero(am))
    eff = (tokens - pad) / tokens if tokens else 0.0
    return {"examples": examples, "tokens": tokens, "pad_tokens": pad,
            "pad_frac": round(1.0 - eff, 4), "effective_frac": round(eff, 4)}


class HFTokenizerAdapter:
    """Wrap a local Hugging Face tokenizer behind this module's interface.

    Used when fine-tuning imported checkpoints (config 5): token ids must
    index the *pretrained* embedding rows, so the checkpoint's own vocab is
    mandatory — a corpus-trained WordPiece vocab would map text to unrelated
    rows. Loads strictly from local files (the env has no egress).
    """

    def __init__(self, hf_tokenizer):
        self._tok = hf_tokenizer
        self.pad_id = hf_tokenizer.pad_token_id
        self.sep_id = hf_tokenizer.eos_token_id
        if self.sep_id is None:
            raise ValueError("tokenizer must define an EOS token")
        if self.pad_id is None:  # Llama tokenizers ship without a pad token
            self.pad_id = self.sep_id

    @staticmethod
    def load(path: str) -> "HFTokenizerAdapter":
        from transformers import AutoTokenizer

        return HFTokenizerAdapter(AutoTokenizer.from_pretrained(path, local_files_only=True))

    @property
    def vocab_size(self) -> int:
        return len(self._tok)

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(list(ids))


def lm_dataset(
    docs: PartitionedDataset,
    tokenizer: WordPieceTokenizer,
    *,
    seq_len: int = 512,
    eos_between_docs: bool = True,
    segment_ids: bool = False,
    num_workers: int | None = None,
) -> PartitionedDataset:
    """Text RDD → packed causal-LM blocks (config 5's fine-tune feed).

    Documents are tokenized and concatenated (SEP as document separator, the
    standard packing trick that keeps every position a real target), then cut
    into fixed [seq_len] windows: ``{"input_ids": [S] i32, "loss_mask": [S]
    f32}``. ``loss_mask`` zeroes padding in the final short block so
    :func:`~distributeddeeplearningspark_tpu.train.losses.causal_lm` ignores it.

    ``segment_ids=True`` adds per-position document ids (running counter;
    the SEP separator belongs to the document it ends; pads get -1) so
    attention is blocked across packed-document boundaries — the model
    consumes ``batch["segment_ids"]`` through the flash kernel / ring
    (GPT-style packing without it is also standard; measure both).
    ``num_workers``: tokenize across worker processes, packing stays on
    the consumer — byte-identical stream for any count. It pays where the
    documents are dear to read and clean, not for the tokenizing (see
    :func:`mlm_dataset`).
    """
    eos = np.full(1 if eos_between_docs else 0, tokenizer.sep_id, np.int32)
    token_ds = _tokens_dataset(
        docs,
        lambda doc: np.concatenate(
            (np.asarray(tokenizer.encode(doc), np.int32), eos)),
        num_workers, label="lm_tokenize")

    def per_partition(pidx: int, stream: Iterable[np.ndarray]) -> Iterator[dict]:
        del pidx
        for chunk, cseg, partial in _pack_token_windows(
                stream, seq_len, segments=segment_ids):
            if partial and len(chunk) <= 1:
                continue  # a lone token has no next-token target
            ex = {"input_ids": _padded(chunk, seq_len, tokenizer.pad_id),
                  "loss_mask": _padded(np.ones(len(chunk), np.float32),
                                       seq_len, 0.0)}
            if segment_ids:
                ex["segment_ids"] = _padded(cseg, seq_len, -1)
            yield ex

    return token_ds.map_partitions_with_index(per_partition)


def packed_token_windows(
    token_docs: PartitionedDataset,
    *,
    seq_len: int,
    eos_id: int | None = None,
    num_partitions: int = 1,
    key: str = "tokens",
    segment_ids: bool = False,
) -> PartitionedDataset:
    """PRE-tokenized documents -> full causal-LM windows, no tokenizer: what
    LM pre-training reads (token arrays in record shards, ``array_records``).

    ``token_docs`` yields int arrays, or dicts holding one under ``key``; its
    partitions chained in order are ONE document stream. Every document is
    followed by ``eos_id`` (if given), the stream is packed back to back by
    the one packer (:func:`_pack_token_windows`) and cut into windows of
    exactly ``seq_len``: ``{"input_ids": [seq_len] int32}``, no padding, no
    loss mask; the stream's tail that does not fill a window is left out.
    With ``segment_ids`` a window also holds ``"segment_ids": [seq_len]
    int32`` from the same packer: the document of every position, counted
    from 0 at the window's first (a document's EOS belongs to it), for models
    whose operators must not cross a document boundary. The windows are a
    function of the document stream alone:
    output partition ``p`` of ``num_partitions`` holds the contiguous run
    ``[W*p/P, W*(p+1)/P)`` of the ``W`` windows, so the partitions chained in
    order are byte for byte the same for any ``num_partitions``. The price
    is one pass over the documents' lengths the first time a partition is
    read, and that a partition walks past the documents before its own.
    """
    if seq_len < 2 or num_partitions < 1:
        raise ValueError(f"seq_len {seq_len}, num_partitions {num_partitions}")
    extra = 0 if eos_id is None else 1
    eos = np.full(extra, eos_id or 0, np.int32)

    def docs() -> Iterator[np.ndarray]:
        for i in range(token_docs.num_partitions):
            for ex in token_docs.iter_partition(i):
                yield ex[key] if isinstance(ex, dict) else ex

    starts: list[np.ndarray] = []  # filled once: where each document begins

    def doc_starts() -> np.ndarray:
        if not starts:
            lens = np.fromiter((len(d) + extra for d in docs()), np.int64)
            starts.append(np.concatenate(([0], np.cumsum(lens))))
        return starts[0]

    def partition(p: int):
        def gen() -> Iterator[dict]:
            begin = doc_starts()
            windows = int(begin[-1]) // seq_len
            w0, w1 = windows * p // num_partitions, \
                windows * (p + 1) // num_partitions
            if w1 == w0:
                return
            first = int(np.searchsorted(begin, w0 * seq_len, "right")) - 1
            skip = w0 * seq_len - int(begin[first])

            def tail_docs() -> Iterator[np.ndarray]:
                for n, d in enumerate(itertools.islice(docs(), first, None)):
                    d = np.concatenate((np.asarray(d, np.int32), eos))
                    yield d[skip:] if n == 0 else d

            packed = _pack_token_windows(tail_docs(), seq_len,
                                         segments=segment_ids)
            for chunk, seg, partial in itertools.islice(packed, w1 - w0):
                assert not partial
                ex = {"input_ids": chunk.copy()}
                if segment_ids:
                    ex["segment_ids"] = seg - seg[0]
                yield ex

        return gen

    return PartitionedDataset([partition(p) for p in range(num_partitions)])


def synthetic_wikipedia(
    num_docs: int = 512, *, num_partitions: int = 4, seed: int = 0
) -> PartitionedDataset:
    """Markov-chain pseudo-prose: learnable bigram structure, Zipfian vocab.

    Gives MLM training real signal (predictable successors) so tests can
    assert loss decreases and masked accuracy beats chance.
    """
    base = [
        "the", "of", "and", "in", "to", "was", "is", "for", "as", "on", "by",
        "with", "city", "river", "history", "population", "century", "state",
        "university", "world", "war", "government", "species", "music", "film",
        "science", "theory", "system", "language", "island", "mountain",
    ]

    # fixed bigram table (shared across partitions: same "language"):
    # word index -> the indices of its four successors
    trng = np.random.default_rng(20260729)
    index = {w: i for i, w in enumerate(base)}
    nxt = [[index[s] for s in trng.choice(base, 4, replace=True)]
           for _ in base]

    def make_partition(pidx: int):
        def gen() -> Iterator[str]:
            rng = np.random.default_rng(seed * 1000 + pidx)
            for _ in range(num_docs // num_partitions):
                w = int(rng.integers(len(base)))
                walk = [w]
                # a document's transitions in one draw: the stream of the
                # scalar draws (tests/test_text_stream.py pins the documents)
                for step in rng.integers(
                        4, size=int(rng.integers(60, 120))).tolist():
                    w = nxt[w][step]
                    walk.append(w)
                yield " ".join(map(base.__getitem__, walk))

        return gen

    return PartitionedDataset([make_partition(i) for i in range(num_partitions)])


def wikipedia_dump(
    path: str,
    *,
    num_partitions: int = 8,
    min_chars: int = 64,
) -> PartitionedDataset:
    """Real Wikipedia text → document RDD (VERDICT r1 missing-#3, config 3).

    Accepts the three on-disk shapes Wikipedia pretraining corpora come in:

    - a **mediawiki XML dump** (``*.xml`` / ``*.xml.bz2``, the enwiki
      download): streamed with stdlib ``iterparse`` (constant memory), one
      document per ``<page>``'s ``<text>``, redirects skipped, wikitext
      lightly cleaned (markup → plain-ish text — the same level of cleaning
      the reference-era BERT pipelines applied);
    - a **wikiextractor output tree** (``AA/wiki_00`` files of ``<doc>``
      blocks): one document per ``<doc>`` element;
    - **plain text**: one document per line (or per blank-line-separated
      paragraph group when lines are short), matching this module's
      synthetic corpus shape.

    Documents stream lazily per partition (files are dealt round-robin;
    a single big XML file is read by every partition with stride — cheap
    relative to tokenization, and keeps partition boundaries deterministic).
    """
    import glob as _glob

    if os.path.isdir(path):
        files = sorted(
            f for f in _glob.glob(os.path.join(path, "**", "*"), recursive=True)
            if os.path.isfile(f) and not os.path.basename(f).startswith(".")
        )
    else:
        files = [path]
    if not files:
        raise FileNotFoundError(f"no corpus files under {path}")

    def open_maybe_bz2(fname: str):
        if fname.endswith(".bz2"):
            import bz2

            return bz2.open(fname, "rt", encoding="utf-8", errors="replace")
        return open(fname, "rt", encoding="utf-8", errors="replace")

    def iter_xml_docs(fname: str) -> Iterator[str]:
        from xml.etree import ElementTree

        with open_maybe_bz2(fname) as f:
            # namespace-agnostic: match on the tag's local name
            for _, elem in ElementTree.iterparse(f, events=("end",)):
                tag = elem.tag.rsplit("}", 1)[-1]
                if tag == "page":
                    text_el = None
                    redirect = False
                    for child in elem.iter():
                        ctag = child.tag.rsplit("}", 1)[-1]
                        if ctag == "redirect":
                            redirect = True
                        elif ctag == "text":
                            text_el = child
                    if not redirect and text_el is not None and text_el.text:
                        doc = clean_wikitext(text_el.text)
                        if len(doc) >= min_chars:
                            yield doc
                    elem.clear()  # constant memory

    def iter_docfile(fname: str) -> Iterator[str]:
        """wikiextractor '<doc ...> text </doc>' blocks or plain text."""
        with open_maybe_bz2(fname) as f:
            first = f.readline()
            if first.lstrip().startswith("<doc"):
                buf: list[str] = []
                for line in f:
                    if line.startswith("</doc>"):
                        doc = "\n".join(buf[1:] if buf and not buf[0].strip() else buf)
                        if len(doc) >= min_chars:
                            yield doc.strip()
                        buf = []
                    elif line.startswith("<doc"):
                        buf = []
                    else:
                        buf.append(line.rstrip("\n"))
            else:
                # plain text: a line per doc; short lines merge into paragraphs
                para: list[str] = []
                for line in [first] + list(f):
                    s = line.strip()
                    if not s:
                        if para:
                            doc = " ".join(para)
                            if len(doc) >= min_chars:
                                yield doc
                            para = []
                    elif len(s) >= min_chars:
                        yield s
                    else:
                        para.append(s)
                if para and len(" ".join(para)) >= min_chars:
                    yield " ".join(para)

    def iter_file(fname: str) -> Iterator[str]:
        base = fname[:-4] if fname.endswith(".bz2") else fname
        if base.endswith(".xml"):
            yield from iter_xml_docs(fname)
        else:
            yield from iter_docfile(fname)

    def make_partition(pidx: int):
        def gen() -> Iterator[str]:
            if len(files) >= num_partitions:
                for fname in files[pidx::num_partitions]:
                    yield from iter_file(fname)
            else:
                # few big files: stride documents across partitions
                for fname in files:
                    for i, doc in enumerate(iter_file(fname)):
                        if i % num_partitions == pidx:
                            yield doc

        return gen

    return PartitionedDataset([make_partition(i) for i in range(num_partitions)])


_WIKI_PATTERNS: "list[tuple[re.Pattern, str]] | None" = None


def clean_wikitext(text: str) -> str:
    """Light wikitext → plain text (the BERT-era preprocessing level).

    Drops templates/tables/refs/files, unwraps [[links|label]] and quotes,
    strips headings and html tags. Not a full parser — the goal is clean
    *training prose*, not rendering fidelity.
    """
    global _WIKI_PATTERNS
    if _WIKI_PATTERNS is None:
        _WIKI_PATTERNS = [
            (re.compile(r"<ref[^>]*/>|<ref[^>]*>.*?</ref>", re.S), " "),
            (re.compile(r"<!--.*?-->", re.S), " "),
            (re.compile(r"\{\|.*?\|\}", re.S), " "),            # tables
            (re.compile(r"\[\[(?:File|Image|Category):[^\]]*\]\]"), " "),
            (re.compile(r"\[\[[^\]|]*\|([^\]]*)\]\]"), r"\1"),  # [[a|b]] → b
            (re.compile(r"\[\[([^\]]*)\]\]"), r"\1"),           # [[a]] → a
            (re.compile(r"\[https?://\S*\s([^\]]*)\]"), r"\1"),
            (re.compile(r"\[https?://\S*\]"), " "),
            (re.compile(r"'{2,}"), ""),                          # bold/italics
            (re.compile(r"^=+.*?=+\s*$", re.M), " "),            # headings
            (re.compile(r"<[^>]+>"), " "),                       # html tags
            (re.compile(r"^\s*[*#:;]+\s*", re.M), ""),           # list markers
            (re.compile(r"[ \t]+"), " "),
            (re.compile(r"\n{3,}"), "\n\n"),
        ]
    # templates {{...}} nest; peel iteratively (bounded)
    for _ in range(4):
        new = re.sub(r"\{\{[^{}]*\}\}", " ", text)
        if new == text:
            break
        text = new
    for pat, repl in _WIKI_PATTERNS:
        text = pat.sub(repl, text)
    return text.strip()
