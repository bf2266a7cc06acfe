"""Driver script: long-context pre-training of a Nemotron-H-shaped decoder
(blocks of ONE sublayer in a published pattern: Mamba-2 state-space layers
whose scan and convolution stop at document boundaries, relu² experts behind
a sigmoid router scaled by 2.5 beside a wider shared expert, attention
layers without any positional embedding; an untied head) from PRE-tokenized
record shards packed with documents ISOLATED, one expert-parallel rank's
step.

The model is ``models/hybrid_decoder.py`` with the layer kinds ``"mamba2"``,
``"experts"`` and ``"bare_attention"``; its loss ``losses.hybrid_moe_lm``
(next-token cross-entropy, no router term: the bias balances it).
``--variant share`` is one chip's share of a 16-chip deployment of the widths
of NVIDIA-Nemotron-3-Nano-30B-A3B
(``benchmark/configs/nemotron3_nano_30b_a3b.json`` says how it is cut: 8 of
128 experts, an eighth of the vocabulary, the first 7 of 52 layers);
``--variant published`` is the whole published depth and width (it fits no
single chip: for a mesh, or to count its parameters); ``--variant tiny`` is
the CPU size::

    dlsubmit examples/train_ssm_moe_lm.py -- --steps 100
    python examples/train_ssm_moe_lm.py --variant tiny --seq-len 256 \\
        --steps 6 --batch-size 2 --master "local[1]"

Feed: token arrays in record shards (``--records-dir``, one ``{"tokens":
int32[n]}`` a document; a synthetic Zipf corpus is written to a temporary
directory if unset) -> ``array_records`` ->
``packed_token_windows(segment_ids=True)``: documents back to back with one
EOS between them, cut into full windows of ``--seq-len``, no padding, every
position tagged with its document. The scan's state, the convolution's taps
and attention stop at document boundaries. AdamW decays every leaf, ``A_log``,
``D`` and ``dt_bias`` among them (``mamba_ssm``'s own scripts exempt those
three; this model's recipe is not published). Not here: any serving path (a
cache for the scan's and the convolution's state), the scan on more than one
device along the sequence, the exchange of tokens over an ``expert`` mesh
axis.
"""

import argparse
import logging
import tempfile

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import records
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    BARE_ATTENTION,
    EXPERTS,
    MAMBA,
    HybridDecoderConfig,
    HybridDecoderLM,
    hybrid_decoder_rules,
)
from distributeddeeplearningspark_tpu.train import losses, optim
from train_sparse_moe_lm import synthetic_token_records

KINDS = {"M": MAMBA, "E": EXPERTS, "*": BARE_ATTENTION}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PUBLISHED = dict(
    vocab_size=131072, hidden_size=2688,
    layer_types=tuple(KINDS[k] for k in PATTERN), num_dense_layers=0,
    num_heads=32, num_kv_heads=2, head_dim=128, rms_eps=1e-5,
    max_position=262144, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
    ssm_state_size=128, ssm_conv_taps=4, ssm_chunk=128, num_experts=128,
    experts_per_token=6, expert_size=1856, shared_expert_size=3712,
    expert_form="relu2", routed_scaling_factor=2.5, tie_embeddings=False)
VARIANTS = {
    # one chip's share of 16: published layers 0-6 (three Mamba-2, three
    # expert and one attention layer), experts 0-7 of the 128 the router
    # scores, an eighth of the vocabulary. A share trained without its
    # exchange does not train its router (RoutedExperts says why)
    "share": HybridDecoderConfig(**{
        **PUBLISHED, "vocab_size": 16384,
        "layer_types": tuple(KINDS[k] for k in PATTERN[:7]),
        "experts_held": (0, 8), "train_router": False}),
    "published": HybridDecoderConfig(**PUBLISHED),
    "tiny": HybridDecoderConfig.tiny_ssm(),
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
        "ssm-moe-lm").getOrCreate()
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
