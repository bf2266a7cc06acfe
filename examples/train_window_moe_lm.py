"""Driver script: long-context pre-training of a Laguna-XS.2-shaped decoder
(sliding-window attention layers of 512 with 64 query heads among full
attention layers of 48, 8 key-value heads of 128 in both; YaRN on the
leading half of the full layers' heads, plain rotary on the sliding ones;
a per-head output gate on both; a dense first layer, then 256-way softmax
routing of 8 experts a token scaled by 2.5 beside an ungated shared expert;
an untied head) from PRE-tokenized record shards packed with documents
ISOLATED, one expert-parallel rank's step.

The model is ``models/hybrid_decoder.py`` with the layer kinds
``"full_attention"`` and ``"sliding_attention"`` (the flash kernels' band
regime walks only the band's blocks); its loss ``losses.hybrid_moe_lm``
(next-token cross-entropy plus Switch's balance term at 0.001).
``--variant share`` is one chip's share of a 16-chip deployment of the
widths of poolside's Laguna-XS.2 (``benchmark/configs/laguna_xs2.json`` says
how it is cut: 16 of 256 experts, an eighth of the vocabulary, the first 5
of 40 layers); ``--variant published`` is the whole published depth and
width (it fits no single chip: for a mesh, or to count its parameters);
``--variant tiny`` is the CPU size::

    dlsubmit examples/train_window_moe_lm.py -- --steps 100
    python examples/train_window_moe_lm.py --variant tiny --seq-len 256 \\
        --steps 6 --batch-size 2 --master "local[1]"

Feed: token arrays in record shards (``--records-dir``, one ``{"tokens":
int32[n]}`` a document; a synthetic Zipf corpus is written to a temporary
directory if unset) -> ``array_records`` ->
``packed_token_windows(segment_ids=True)``: documents back to back with one
EOS between them, cut into full windows of ``--seq-len``, no padding, every
position tagged with its document; both attention kinds stop at document
boundaries and positions restart with each. Not here: any serving path (a
cache of two sizes, the window's and the row's), the band in the ring and
Ulysses paths, the exchange of tokens over an ``expert`` mesh axis.
"""

import argparse
import logging
import tempfile

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import records
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    ATTENTION,
    SLIDING,
    HybridDecoderConfig,
    HybridDecoderLM,
    hybrid_decoder_rules,
)
from distributeddeeplearningspark_tpu.train import losses, optim
from train_sparse_moe_lm import synthetic_token_records

PERIOD = (ATTENTION, SLIDING, SLIDING, SLIDING)
PUBLISHED = dict(
    vocab_size=100352, hidden_size=2048, layer_types=PERIOD * 10,
    num_dense_layers=1, num_heads=48, sliding_num_heads=64, num_kv_heads=8,
    head_dim=128, rope_theta=5e5, partial_rotary_factor=0.5,
    yarn_factor=64.0, yarn_original_max_position=4096, yarn_beta_fast=64.0,
    yarn_beta_slow=1.0, rope_attention_factor=1.4158883083359672,
    sliding_window=512, sliding_rope_theta=1e4, qk_norm=False,
    attention_gate=True, rms_eps=1e-6, max_position=262144,
    intermediate_size=8192, num_experts=256, experts_per_token=8,
    expert_size=512, router_score="softmax", router_aux_loss_coef=0.001,
    use_expert_bias=False, routed_scaling_factor=2.5,
    shared_expert_size=512, tie_embeddings=False)
VARIANTS = {
    # one chip's share of 16: published layers 0-4 (the dense full layer,
    # then sliding, sliding, sliding, full with experts), experts 0-15 of
    # the 256 the router scores, an eighth of the vocabulary
    "share": HybridDecoderConfig(**{
        **PUBLISHED, "vocab_size": 12544, "layer_types": PERIOD + PERIOD[:1],
        "experts_held": (0, 16)}),
    "published": HybridDecoderConfig(**PUBLISHED),
    "tiny": HybridDecoderConfig.tiny_window(),
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default=None)
    p.add_argument("--variant", default="share", choices=sorted(VARIANTS))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=1,
                   help="windows a step, over all chips")
    p.add_argument("--seq-len", type=int, default=16384)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--records-dir", default=None,
                   help="record shards of {'tokens': int32[n]} documents")
    p.add_argument("--eos-id", type=int, default=0)
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    spark = Session.builder.master(args.master or "auto").appName(
        "window-moe-lm").getOrCreate()
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
