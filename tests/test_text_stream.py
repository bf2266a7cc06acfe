"""The text feed's streams, pinned byte for byte (ISSUE 25).

Every case builds a stream from ``data/text.py`` and compares a SHA-256 of
its first examples with a digest taken on the code as it stood BEFORE the
per-token Python was taken out of it. A digest covers every key, dtype,
shape and byte of every example in order, so a change that moves one random
draw, one segment id or one pad fails here and not in a loss curve.

The tokenizer tests below hold the word memo of ``WordPieceTokenizer.encode``
to ``tokenize_word``, word by word, and ``mask_tokens`` to its candidates.
"""

import hashlib

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

#: prose the synthetic corpus has none of: capitals, punctuation, digits,
#: characters outside the vocabulary, a 40-letter word, an empty document
REAL_DOCS = [
    "The River Thames (pronounced /temz/) is 346 km long; it rises at "
    "Thames Head, Gloucestershire, and flows into the North Sea.",
    "In 1815, Napoléon was exiled to Saint Helena — 1,950 km from the "
    "coast of Africa. 漢字 and кириллица are not in the vocabulary!",
    "",
    "pneumonoultramicroscopicsilicovolcanocon is a 40-letter word, and "
    "xxx is not; 3.14159 26535 89793 23846 are digits of pi.",
    "History of the world: the state, the university and the government "
    "of the island in the 20th century (see also: war, music & film).",
    "a",
    "Species of the genus Quercus grow on every mountain of the island, "
    "said the theory; the system of the language says otherwise?",
]


def _synthetic(n, parts, seed):
    return text_lib.synthetic_wikipedia(n, num_partitions=parts, seed=seed)


def _trained(docs, pieces=512):
    return text_lib.WordPieceTokenizer.train(docs.collect(), vocab_size=pieces)


def bert_like_tokenizer(pieces: int = 30522) -> text_lib.WordPieceTokenizer:
    """A vocabulary laid out like the stock ``vocab.txt``: ``[PAD]`` at 0,
    99 unused entries, then ``[UNK] [CLS] [SEP] [MASK]`` at 100-103: the
    special tokens are NOT a contiguous prefix."""
    vocab = ["[PAD]"] + [f"[unused{i}]" for i in range(99)]
    vocab += ["[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    for ch in "abcdefghijklmnopqrstuvwxyz0123456789.,;:!?()/&-":
        vocab += [ch, "##" + ch]
    words = sorted({w for d in _synthetic(64, 1, 0).collect() for w in d.split()})
    vocab += words + ["thames", "##es", "##ing", "##ed", "micro", "##scopic",
                      "##volcano", "north", "sea", "##land"]
    vocab += [f"w{i}" for i in range(pieces - len(vocab))]
    assert len(set(vocab)) == len(vocab) == pieces
    return text_lib.WordPieceTokenizer({t: i for i, t in enumerate(vocab)})


def _docs_case(parts, seed):
    def build():
        ds = _synthetic(96, parts, seed)
        return [{"doc": np.frombuffer(d.encode(), np.uint8)}
                for d in ds.collect()]
    return build


def _mlm_case(n, *, seq_len=128, num_docs=48, parts=2, repeat=False, **kw):
    def build():
        docs = _synthetic(num_docs, parts, 3)
        ds = text_lib.mlm_dataset(docs, _trained(docs), seq_len=seq_len,
                                  seed=11, **kw)
        if repeat:
            ds = ds.repeat()
        return ds.take(n)
    return build


def _lm_case(**kw):
    def build():
        docs = _synthetic(40, 2, 5)
        return text_lib.lm_dataset(docs, _trained(docs), seq_len=64,
                                   **kw).collect()
    return build


def _real_mlm():
    docs = PartitionedDataset.parallelize(REAL_DOCS * 3, 2)
    return text_lib.mlm_dataset(docs, bert_like_tokenizer(), seq_len=64,
                                seed=2, max_predictions=12,
                                segment_ids=True).collect()


def _real_lm():
    docs = PartitionedDataset.parallelize(REAL_DOCS * 2, 3)
    return text_lib.lm_dataset(docs, bert_like_tokenizer(), seq_len=48,
                               segment_ids=True).collect()


def _bert_vocab_mlm():
    docs = _synthetic(64, 2, 9)
    return text_lib.mlm_dataset(docs, bert_like_tokenizer(), seq_len=512,
                                seed=4, max_predictions=80).collect()


#: name -> builder of a list of example dicts
CASES = {
    "docs_seed0_1part": _docs_case(1, 0),
    "docs_seed7_4parts": _docs_case(4, 7),
    "mlm_packed_p80": _mlm_case(24, seq_len=512, num_docs=192,
                                max_predictions=80),
    "mlm_packed_p80_segids": _mlm_case(24, seq_len=512, num_docs=192,
                                       max_predictions=80, segment_ids=True),
    "mlm_padded": _mlm_case(60, seq_len=64, pack=False),
    "mlm_full_labels": _mlm_case(30, max_predictions=None),
    # one partition of 12 documents is 7 windows of 128, the last partial:
    # 20 examples cross the epoch boundary twice, tail window included
    "mlm_repeat_across_epochs": _mlm_case(20, num_docs=12, parts=1,
                                          repeat=True, max_predictions=20,
                                          segment_ids=True),
    "lm_eos": _lm_case(),
    "lm_segids_no_eos": _lm_case(segment_ids=True, eos_between_docs=False),
    "mlm_bert_vocab_30522": _bert_vocab_mlm,
    "mlm_real_text_segids": _real_mlm,
    "lm_real_text_segids": _real_lm,
}

#: SHA-256 of each case's stream on the parent commit 637ceaf, taken before
#: ``data/text.py`` was touched
PARENT_DIGESTS = {
    "docs_seed0_1part":
        "0345b3f8cb568803808c4f6b375d983d867a33f4f874fb31c602fe45bb3bb1ca",
    "docs_seed7_4parts":
        "3f1162193b1730e21d88b21a2fc5d7497453fee45893f64f62ffd12ec30876a3",
    "mlm_packed_p80":
        "8a902e60acef49e6d2c30b62ffe9fbb7ad3d9ec15c0ab94569395c4982182a76",
    "mlm_packed_p80_segids":
        "5d237d2a6cbc32a1e812e9a6d0b235716d032149f91fbe49b469ecd2b31b1ce7",
    "mlm_padded":
        "836b863556452d9f216423134308058cf2b3d9cbacd87a95c6ead9a2e974e870",
    "mlm_full_labels":
        "dd72570cab8ac71555e9757bf7caa2a0218ac99532c4c4f205c228077d1235bc",
    "mlm_repeat_across_epochs":
        "e69ede9172d6e47ec41dd5d5b196a0f71c13ef48427995ca19be70432aa97304",
    "lm_eos":
        "d436cd313fc67ed287c5a37ea02628a5e0b12ef5e7a8caf226fd94daaad5a5d0",
    "lm_segids_no_eos":
        "361767dfed2de2d66948dffd3de6422003ec04607a6896e49cf92b8e9747cefe",
    "mlm_bert_vocab_30522":
        "a27a3f8afe72ab28367b6813b47659a790c0444445b2a3cf3df09bea00329e05",
    "mlm_real_text_segids":
        "6ebba56bc01f2c65c38aa941837b264ed05e11014c34a05b56cafc2d6fc32495",
    "lm_real_text_segids":
        "5c12dec4c0953c01b6faf21715c2876bba068f21f52749a2d7a85198ae5c8ba0",
}


def stream_digest(examples) -> str:
    h = hashlib.sha256()
    for ex in examples:
        for key in sorted(ex):
            arr = np.asarray(ex[key])
            h.update(f"{key}:{arr.dtype.str}:{arr.shape};".encode())
            h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CASES))
def test_stream_is_the_parents_byte_for_byte(name):
    examples = CASES[name]()
    assert len(examples) > 4
    assert stream_digest(examples) == PARENT_DIGESTS[name]


def _small_tokenizer():
    return _trained(_synthetic(64, 2, 1))


TOKENIZERS = {"trained_on_corpus": _small_tokenizer,
              "bert_like_30522": bert_like_tokenizer}


def _word_by_word(tok, text):
    return [i for w in text_lib._WORD_RE.findall(text.lower())
            for i in tok.tokenize_word(w)]


@pytest.mark.parametrize("make", sorted(TOKENIZERS))
def test_encode_is_tokenize_word_word_by_word(make):
    tok = TOKENIZERS[make]()
    texts = REAL_DOCS + _synthetic(4, 1, 2).collect() + [
        # every kind of whitespace the memo's split and _WORD_RE must agree
        # on, and a zero-width space, which is none
        "no\xa0break em\u2003space file\x1csep next\x85line zero\u200bwidth "
        "tab\there\r\nİstanbul's (re)tokenized,twice;(re)tokenized,twice"]
    for _ in range(2):  # first against an empty memo, then from it
        for text in texts:
            ids = tok.encode(text)
            assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
            assert ids.tolist() == _word_by_word(tok, text)
    assert tok.unk_id in tok.encode("漢字 кириллица")
    assert len(tok.tokenize_word(REAL_DOCS[3].split()[0])) > 1  # 40 letters
    assert tok.encode("").shape == (0,)


def test_word_memo_stays_inside_its_bound(monkeypatch):
    monkeypatch.setattr(text_lib, "_WORD_MEMO_WORDS", 8)
    tok = _small_tokenizer()
    text = " ".join(REAL_DOCS)
    distinct = len(set(text.lower().split()))
    assert distinct > 8
    for _ in range(3):
        assert tok.encode(text).tolist() == _word_by_word(tok, text)
        assert tok.stats()["memo_words"] == 8
    # the words that found no room are tokenized on every occurrence
    s = tok.stats()
    assert s["words"] - s["memo_hits"] >= 3 * (distinct - 8)


def test_word_memo_counts_hits_and_misses():
    docs = _synthetic(256, 2, 1).collect()
    tok = text_lib.WordPieceTokenizer.train(docs, vocab_size=512)
    assert tok.stats() == {"words": 0, "memo_hits": 0, "memo_words": 0}
    for doc in docs:
        tok.encode(doc)
    s = tok.stats()
    assert s["words"] == sum(len(d.split()) for d in docs)
    assert s["memo_words"] == len({w for d in docs for w in d.split()})
    assert s["words"] - s["memo_hits"] == s["memo_words"]  # one miss a word
    assert s["memo_hits"] / s["words"] > 0.99
    tok.encode("quercus robur quercus")  # two fresh words, one of them twice
    after = tok.stats()
    assert after["words"] - s["words"] == 3
    assert after["memo_hits"] - s["memo_hits"] == 1
    assert after["memo_words"] - s["memo_words"] == 2


def test_mask_tokens_never_replaces_by_a_special_id_at_30522_pieces():
    tok = bert_like_tokenizer()
    assert sorted(tok.special_ids) == [0, 100, 101, 102, 103]
    np.testing.assert_array_equal(
        tok.replacement_ids,
        np.setdiff1d(np.arange(30522), sorted(tok.special_ids)))
    rng = np.random.default_rng(0)
    body = np.random.default_rng(1).choice(tok.replacement_ids, (200, 126))
    replaced = 0
    for row in body:
        ids = np.concatenate(([tok.cls_id], row, [tok.sep_id])).astype(np.int32)
        ex = text_lib.mask_tokens(ids, tok, rng)
        changed = ex["input_ids"] != ids
        drawn = ex["input_ids"][changed & (ex["input_ids"] != tok.mask_id)]
        assert not np.isin(drawn, sorted(tok.special_ids)).any()
        assert ex["mlm_weights"][[0, -1]].tolist() == [0.0, 0.0]
        assert (ex["mlm_weights"][changed] == 1.0).all()
        replaced += len(drawn)
    # 200 windows x 126 tokens x 15% x 10%: a few hundred replacements, of
    # ids up to the last piece
    assert replaced > 200
