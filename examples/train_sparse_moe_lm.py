"""Driver script: long-context pre-training of a sparse-attention,
routed-expert decoder from PRE-tokenized record shards, one expert-parallel
rank's step.

The model is ``models/sparse_decoder.py`` (grouped-query attention over a
learned top-k selection of keys, routed experts with none dropped, the
three-term loss ``losses.sparse_moe_lm``). ``--variant share`` is one chip's
share of an eight-chip deployment of the widths of Keye-VL-2.0-30B-A3B's
language model (``benchmark/configs/keye_vl2_30b_a3b.json`` says how it is
cut: 16 of 128 experts, an eighth of the vocabulary, 4 of 48 layers);
``--variant tiny`` is the CPU size::

    dlsubmit examples/train_sparse_moe_lm.py -- --steps 100
    python examples/train_sparse_moe_lm.py --variant tiny --seq-len 256 \\
        --steps 6 --batch-size 2 --master "local[1]"

Feed: token arrays in record shards (``--records-dir``, one ``{"tokens":
int32[n]}`` a document, as ``data/records.write_array_records`` writes them;
a synthetic Zipf corpus is written to a temporary directory if unset) ->
``array_records`` -> ``packed_token_windows``: documents back to back with
one EOS between them, cut into full windows of ``--seq-len``, no padding,
causal across the whole window.
"""

import argparse
import logging
import tempfile

import numpy as np

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import records
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models.sparse_decoder import (
    SparseDecoderConfig,
    SparseDecoderLM,
    sparse_decoder_rules,
)
from distributeddeeplearningspark_tpu.train import losses, optim

VARIANTS = {
    # one chip's share of 8: 16 experts of the 128 the router scores, an
    # eighth of the 151,936-row vocabulary, 4 of the 48 layers
    "share": SparseDecoderConfig(
        vocab_size=18992, hidden_size=2048, num_layers=4, num_heads=32,
        num_kv_heads=4, head_dim=128, num_experts=128, experts_per_token=8,
        expert_size=768, experts_held=(0, 16), index_heads=16,
        index_head_dim=64, index_topk=2048),
    "tiny": SparseDecoderConfig.tiny(),
}


def synthetic_token_records(out_dir: str, *, vocab: int, num_docs: int,
                            median_len: int, seed: int = 0) -> None:
    """A Zipf(1) corpus with log-normal document lengths, id 0 kept as EOS."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, vocab)).astype(np.int32)
    cdf = np.cumsum(1.0 / np.arange(1, vocab))
    cdf /= cdf[-1]
    lens = np.clip(np.exp(rng.normal(np.log(median_len), 1.0, num_docs)),
                   8, 16 * median_len).astype(int)
    records.write_array_records(
        ({"tokens": ids[np.minimum(np.searchsorted(cdf, rng.random(n)),
                                   vocab - 2)]} for n in lens),
        out_dir, num_shards=8)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default=None)
    p.add_argument("--variant", default="share", choices=sorted(VARIANTS))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=2,
                   help="windows a step, over all chips")
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--records-dir", default=None,
                   help="record shards of {'tokens': int32[n]} documents")
    p.add_argument("--eos-id", type=int, default=0)
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    spark = Session.builder.master(args.master or "auto").appName(
        "sparse-moe-lm").getOrCreate()
    print(spark)

    cfg = VARIANTS[args.variant]
    rec_dir = args.records_dir
    if rec_dir is None:
        rec_dir = tempfile.mkdtemp(prefix="token_records_")
        synthetic_token_records(rec_dir, vocab=cfg.vocab_size, num_docs=256,
                                median_len=max(args.seq_len * 3 // 4, 16))
    ds = text_lib.packed_token_windows(
        records.array_records(rec_dir), seq_len=args.seq_len,
        eos_id=args.eos_id,
        num_partitions=max(spark.default_parallelism, 1)).repeat()

    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(args.lr, args.warmup,
                                        max(args.steps, args.warmup + 1))), 1.0)
    trainer = Trainer(spark, SparseDecoderLM(cfg), losses.sparse_moe_lm, tx,
                      rules=sparse_decoder_rules(cfg))
    state, summary = trainer.fit(
        ds, batch_size=args.batch_size, steps=args.steps,
        tokens_per_example=args.seq_len, log_every=5)
    print(f"done: step={int(state.step)} {summary}")
    spark.stop()


if __name__ == "__main__":
    main()
