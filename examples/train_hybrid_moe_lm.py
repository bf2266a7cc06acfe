"""Driver script: long-context pre-training of a hybrid decoder (gated short
convolutions among causal attention layers, a dense layer before the expert
layers, a sigmoid router balanced by a bias outside the gradient) from
PRE-tokenized record shards packed with documents ISOLATED, one
expert-parallel rank's step.

The model is ``models/hybrid_decoder.py``; its loss ``losses.hybrid_moe_lm``
(next-token cross-entropy, no router term). ``--variant share`` is one chip's
share of an eight-chip deployment of the widths of LFM2-24B-A2B
(``benchmark/configs/lfm2_24b_a2b.json`` says how it is cut: 8 of 64 experts,
an eighth of the vocabulary, 5 of 40 layers); ``--variant published`` is the
whole published depth and width (it fits no single chip: for a mesh, or to
count its parameters); ``--variant tiny`` is the CPU size::

    dlsubmit examples/train_hybrid_moe_lm.py -- --steps 100
    python examples/train_hybrid_moe_lm.py --variant tiny --seq-len 256 \\
        --steps 6 --batch-size 2 --master "local[1]"

Feed: token arrays in record shards (``--records-dir``, one ``{"tokens":
int32[n]}`` a document, as ``data/records.write_array_records`` writes them;
a synthetic Zipf corpus is written to a temporary directory if unset) ->
``array_records`` -> ``packed_token_windows(segment_ids=True)``: documents
back to back with one EOS between them, cut into full windows of
``--seq-len``, no padding, every position tagged with its document. The
convolution's taps, attention and the rotary positions stop at document
boundaries. Not here: a vision tower, any serving path, the exchange of
tokens over an ``expert`` mesh axis.
"""

import argparse
import logging
import tempfile

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import records
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    ATTENTION,
    CONV,
    HybridDecoderConfig,
    HybridDecoderLM,
    hybrid_decoder_rules,
)
from distributeddeeplearningspark_tpu.train import losses, optim
from train_sparse_moe_lm import synthetic_token_records

VARIANTS = {
    # one chip's share of 8: published layers 1-5 (one dense layer, one
    # period), 8 experts of the 64 the router scores, an eighth of the
    # 65,536-row vocabulary. A share trained without its exchange does not
    # train its router (RoutedExperts says why); the bias still moves
    "share": HybridDecoderConfig(
        vocab_size=8192, layer_types=(CONV, ATTENTION, CONV, CONV, CONV),
        num_dense_layers=1, experts_held=(0, 8), train_router=False),
    "published": HybridDecoderConfig(),
    "tiny": HybridDecoderConfig.tiny(),
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default=None)
    p.add_argument("--variant", default="share", choices=sorted(VARIANTS))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=1,
                   help="windows a step, over all chips")
    p.add_argument("--seq-len", type=int, default=32768)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--records-dir", default=None,
                   help="record shards of {'tokens': int32[n]} documents")
    p.add_argument("--eos-id", type=int, default=0)
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    spark = Session.builder.master(args.master or "auto").appName(
        "hybrid-moe-lm").getOrCreate()
    print(spark)

    cfg = VARIANTS[args.variant]
    rec_dir = args.records_dir
    if rec_dir is None:
        rec_dir = tempfile.mkdtemp(prefix="token_records_")
        synthetic_token_records(rec_dir, vocab=cfg.vocab_size, num_docs=256,
                                median_len=max(args.seq_len // 5, 16))
    ds = text_lib.packed_token_windows(
        records.array_records(rec_dir), seq_len=args.seq_len,
        eos_id=args.eos_id, segment_ids=True,
        num_partitions=max(spark.default_parallelism, 1)).repeat()

    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(args.lr, args.warmup,
                                        max(args.steps, args.warmup + 1))), 1.0)
    trainer = Trainer(spark, HybridDecoderLM(cfg), losses.hybrid_moe_lm, tx,
                      rules=hybrid_decoder_rules(cfg))
    state, summary = trainer.fit(
        ds, batch_size=args.batch_size, steps=args.steps,
        tokens_per_example=args.seq_len, log_every=5)
    print(f"done: step={int(state.step)} {summary}")
    spark.stop()


if __name__ == "__main__":
    main()
