"""The text feed of ``examples/train_bert.py``: seeded pseudo-Wikipedia ->
WordPiece tokenizer trained on it -> packed masked-LM windows, repeated.
Traffic parameters: ``num_docs``, ``vocab_pieces``, ``seq_len``,
``max_predictions``. The vocabulary is trained once per seed and cached."""

from __future__ import annotations

import os

from benchmark.harness import seedcache


def build(spark, traffic: dict, seed: int) -> dict:
    from distributeddeeplearningspark_tpu.data import text as text_lib

    parts = max(spark.default_parallelism, 1)
    docs = text_lib.synthetic_wikipedia(traffic["num_docs"],
                                        num_partitions=parts, seed=seed)
    key = seedcache.key(seed, [__file__, text_lib.__file__], {
        "num_docs": traffic["num_docs"], "pieces": traffic["vocab_pieces"],
        "partitions": parts})

    def train(out_dir: str) -> None:
        text_lib.WordPieceTokenizer.train(
            docs.collect(), vocab_size=traffic["vocab_pieces"]).save(
                os.path.join(out_dir, "vocab.txt"))

    vocab_dir, hit = seedcache.ensure(key, "vocab", train)
    tok = text_lib.WordPieceTokenizer.load(os.path.join(vocab_dir, "vocab.txt"))
    ds = text_lib.mlm_dataset(docs, tok, seq_len=traffic["seq_len"],
                              max_predictions=traffic["max_predictions"],
                              pack=True, seed=seed)
    return {"dataset": ds.repeat(), "sample_from": ds,
            "facts": {"seed_cache_hit": hit, "vocab_pieces": tok.vocab_size,
                      "partitions": parts}}
