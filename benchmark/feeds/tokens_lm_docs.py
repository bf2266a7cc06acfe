"""The token feed of ``examples/train_hybrid_moe_lm.py``: the corpus of
``tokens_lm.py`` (its generator, imported: seeded PRE-tokenized documents,
log-normal lengths, Zipf ids under a seeded permutation) written once per
seed as record shards, read back through ``array_records`` and packed back
to back by the program's ``packed_token_windows(segment_ids=True)``: every
window carries the document of each position, for a model whose operators
stop at document boundaries. Traffic parameters as ``tokens_lm.py``. The
sample the harness compares with the reference on is the stream's first
window that holds at least ``SAMPLE_DOCUMENTS`` documents: a window that is
one long document (one in twenty is) has no boundary for a fault to cross."""

from __future__ import annotations

import inspect
import os

from benchmark.harness import runner, seedcache

SAMPLE_DOCUMENTS = 4


def build(spark, traffic: dict, seed: int) -> dict:
    from distributeddeeplearningspark_tpu.data import records
    from distributeddeeplearningspark_tpu.data import text as text_lib

    if "segment_ids" not in inspect.signature(
            text_lib.packed_token_windows).parameters:
        raise runner.Refused([
            "this checkout's packed_token_windows emits no segment ids: the "
            "program cannot feed packed documents to a model that must not "
            "cross their boundaries"])
    tokens_lm = runner.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tokens_lm.py"))
    parts = max(spark.default_parallelism, 1)
    vocab = traffic["vocab_size"]
    shape = {k: traffic[k] for k in (
        "num_docs", "doc_len_median", "doc_len_sigma", "doc_len_min",
        "doc_len_max", "zipf_exponent", "eos_id")}
    shape.update(vocab=vocab, num_shards=8)
    key = seedcache.key(seed, [tokens_lm.__file__, records.__file__], shape)
    rec_dir, hit = seedcache.ensure(
        key, "token_records", lambda d: records.write_array_records(
            tokens_lm.documents(seed, traffic, vocab), d,
            num_shards=shape["num_shards"]))
    ds = text_lib.packed_token_windows(
        records.array_records(rec_dir), seq_len=traffic["seq_len"],
        eos_id=traffic["eos_id"], num_partitions=parts, segment_ids=True)
    nbytes = sum(os.path.getsize(os.path.join(rec_dir, f))
                 for f in os.listdir(rec_dir))
    return {"dataset": ds.repeat(),
            "sample_from": ds.filter(
                lambda ex: ex["segment_ids"][-1] >= SAMPLE_DOCUMENTS - 1),
            "facts": {"seed_cache_hit": hit, "records_bytes": nbytes,
                      "vocab": vocab, "partitions": parts}}
