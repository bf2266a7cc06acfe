"""The token feed of ``examples/train_sparse_moe_lm.py``: a seeded
PRE-tokenized corpus written once per seed as record shards
(``write_array_records``), read back through ``array_records`` and packed
back to back into full causal windows by the program's
``packed_token_windows``, repeated. No tokenizer runs: LM pre-training reads
token arrays. Traffic parameters: ``num_docs``, ``doc_len_median``,
``doc_len_sigma``, ``doc_len_min``, ``doc_len_max`` (log-normal document
lengths, clipped), ``zipf_exponent`` (ids over the configuration's
vocabulary slice, under a seeded permutation of it, so that frequency is
not tied to the id's value), ``eos_id`` (kept out of the documents, written
between them), ``seq_len``, ``vocab_size`` (the slice the ids are drawn from; the
configuration's ``build`` refuses a traffic whose slice is not its own)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import seedcache


def documents(seed: int, traffic: dict, vocab: int):
    """The corpus, a document at a time: ``{"tokens": int32[n]}``."""
    rng = np.random.default_rng([seed, 0x70C5])
    lens = np.clip(
        np.exp(rng.normal(np.log(traffic["doc_len_median"]),
                          traffic["doc_len_sigma"], traffic["num_docs"])),
        traffic["doc_len_min"], traffic["doc_len_max"]).astype(np.int64)
    ids = np.setdiff1d(np.arange(vocab), [traffic["eos_id"]])
    ids = rng.permutation(ids).astype(np.int32)   # rank -> id
    weight = 1.0 / np.arange(1, len(ids) + 1) ** traffic["zipf_exponent"]
    cdf = np.cumsum(weight / weight.sum())
    for n in lens:
        ranks = np.searchsorted(cdf, rng.random(int(n)), "right")
        yield {"tokens": ids[np.minimum(ranks, len(ids) - 1)]}


def build(spark, traffic: dict, seed: int) -> dict:
    from distributeddeeplearningspark_tpu.data import records
    from distributeddeeplearningspark_tpu.data import text as text_lib

    parts = max(spark.default_parallelism, 1)
    vocab = traffic["vocab_size"]
    shape = {k: traffic[k] for k in (
        "num_docs", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent", "eos_id")}
    shape.update(vocab=vocab, num_shards=8)
    key = seedcache.key(seed, [__file__, records.__file__], shape)
    rec_dir, hit = seedcache.ensure(
        key, "token_records", lambda d: records.write_array_records(
            documents(seed, traffic, vocab), d, num_shards=shape["num_shards"]))
    docs = records.array_records(rec_dir)
    ds = text_lib.packed_token_windows(
        docs, seq_len=traffic["seq_len"], eos_id=traffic["eos_id"],
        num_partitions=parts)
    nbytes = sum(os.path.getsize(os.path.join(rec_dir, f))
                 for f in os.listdir(rec_dir))
    return {"dataset": ds.repeat(), "sample_from": ds,
            "facts": {"seed_cache_hit": hit, "records_bytes": nbytes,
                      "vocab": vocab, "partitions": parts}}
