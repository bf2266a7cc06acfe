"""Driver script: long-context pre-training of a DeepSeek-V3-shaped decoder
(latent attention in every layer, a dense layer before expert layers with a
shared expert beside sigmoid routing scaled by 2.5, an untied head and a
multi-token-prediction module that shares embedding and head) from
PRE-tokenized record shards, one expert-parallel rank's step.

The model is ``models/hybrid_decoder.py`` with ``"latent_attention"`` layers;
its loss ``losses.latent_moe_lm`` (next-token cross-entropy plus 0.1 times
the module's cross-entropy two tokens on; no router term: the bias balances
it). ``--variant share`` is one chip's share of a 32-chip deployment of the
widths of JoyAI-LLM-Flash (``benchmark/configs/joyai_llm_flash.json`` says how
it is cut: 8 of 256 experts, an eighth of the vocabulary, 5 of 40 layers and
the module); ``--variant published`` is the whole published depth and width
(it fits no single chip: for a mesh, or to count its parameters); ``--variant
tiny`` is the CPU size::

    dlsubmit examples/train_latent_moe_lm.py -- --steps 100
    python examples/train_latent_moe_lm.py --variant tiny --seq-len 256 \\
        --steps 6 --batch-size 2 --master "local[1]"

Feed: token arrays in record shards (``--records-dir``, one ``{"tokens":
int32[n]}`` a document; a synthetic Zipf corpus is written to a temporary
directory if unset) -> ``array_records`` -> ``packed_token_windows``:
documents back to back with one EOS between them, cut into full causal
windows of ``--seq-len``, no padding and, as this family trains, no mask
between documents (``--segment-ids`` isolates them instead). Not here: any
serving path (the compressed cache, the absorbed decode form), the exchange
of tokens over an ``expert`` mesh axis.
"""

import argparse
import logging
import tempfile

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import records
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
    LATENT,
    HybridDecoderConfig,
    HybridDecoderLM,
    hybrid_decoder_rules,
)
from distributeddeeplearningspark_tpu.train import losses, optim
from train_sparse_moe_lm import synthetic_token_records

PUBLISHED = dict(
    vocab_size=129280, hidden_size=2048, layer_types=(LATENT,) * 40,
    num_dense_layers=1, num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=32e6, rms_eps=1e-6,
    max_position=131072, intermediate_size=7168, num_experts=256,
    experts_per_token=8, expert_size=768, shared_expert_size=768,
    routed_scaling_factor=2.5, tie_embeddings=False, mtp_layers=1)
VARIANTS = {
    # one chip's share of 32: published layers 0-4 (the dense layer, four
    # expert layers) and the module, experts 0-7 of the 256 the router
    # scores, an eighth of the vocabulary. A share trained without its
    # exchange does not train its router (RoutedExperts says why)
    "share": HybridDecoderConfig(**{
        **PUBLISHED, "vocab_size": 16160, "layer_types": (LATENT,) * 5,
        "experts_held": (0, 8), "train_router": False}),
    "published": HybridDecoderConfig(**PUBLISHED),
    "tiny": HybridDecoderConfig.tiny_latent(),
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
    p.add_argument("--segment-ids", action="store_true",
                   help="isolate the documents of a window")
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    spark = Session.builder.master(args.master or "auto").appName(
        "latent-moe-lm").getOrCreate()
    print(spark)

    cfg = VARIANTS[args.variant]
    rec_dir = args.records_dir
    if rec_dir is None:
        rec_dir = tempfile.mkdtemp(prefix="token_records_")
        synthetic_token_records(rec_dir, vocab=cfg.vocab_size, num_docs=256,
                                median_len=max(args.seq_len // 5, 16))
    ds = text_lib.packed_token_windows(
        records.array_records(rec_dir), seq_len=args.seq_len,
        eos_id=args.eos_id, segment_ids=args.segment_ids,
        num_partitions=max(spark.default_parallelism, 1)).repeat()

    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(args.lr, args.warmup,
                                        max(args.steps, args.warmup + 1))), 1.0)
    trainer = Trainer(spark, HybridDecoderLM(cfg), losses.latent_moe_lm, tx,
                      rules=hybrid_decoder_rules(cfg))
    state, summary = trainer.fit(
        ds, batch_size=args.batch_size, steps=args.steps,
        tokens_per_example=args.seq_len, log_every=5)
    print(f"done: step={int(state.step)} {summary}")
    spark.stop()


if __name__ == "__main__":
    main()
