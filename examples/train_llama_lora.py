"""Config-5 driver script: Llama-2 LoRA fine-tune, FSDP-sharded.

Reference shape (BASELINE.json config 5): load Llama-2 7B base weights,
attach LoRA adapters, FSDP-shard across Spark executors on a v4-32, train
adapters only. Here: same driver surface — HF safetensors import, LoRA via
the optimizer mask, FSDP(+optional TP) via GSPMD sharding rules::

    dlsubmit examples/train_llama_lora.py -- --variant tiny --steps 50
    dlsubmit examples/train_llama_lora.py -- \
        --variant 7b --weights /data/llama-2-7b-hf --fsdp 8 --tensor 4
"""

import argparse
import dataclasses
import logging

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import text as text_lib
from distributeddeeplearningspark_tpu.models import (
    LlamaConfig,
    LlamaForCausalLM,
    llama_rules,
    lora_trainable,
)
from distributeddeeplearningspark_tpu.models import llama_io
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.train import losses, optim


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default=None)
    p.add_argument("--variant", default="tiny", choices=["7b", "13b", "tiny"])
    p.add_argument("--weights", default=None, help="HF safetensors file/dir for the base model")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--lora-rank", type=int, default=8)
    p.add_argument("--sample-tokens", type=int, default=0,
                   help="after training, sample this many tokens from the "
                        "tuned model (KV-cached decode)")
    p.add_argument("--lora-alpha", type=float, default=16.0)
    p.add_argument("--fsdp", type=int, default=-1, help="FSDP axis size (-1: all devices)")
    p.add_argument("--tensor", type=int, default=1, help="tensor-parallel axis size")
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="context-parallel axis size (shards the sequence "
                        "over the mesh seq axis)")
    p.add_argument("--cp-impl", choices=["ring", "ulysses"], default="ring",
                   help="context-parallel strategy when --seq-parallel > 1: "
                        "ring (blockwise K/V rotation, O(S/n) memory, no "
                        "head constraint) or ulysses (all-to-all head "
                        "scatter, 2 collectives, heads must divide by the "
                        "CP degree)")
    p.add_argument("--pipeline", type=int, default=1,
                   help="pipeline-parallel axis size (GPipe stages over scanned layers)")
    p.add_argument("--microbatches", type=int, default=0,
                   help="pipeline microbatches per step (default: the pipe degree)")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient-accumulation micro-steps per optimizer step")
    p.add_argument("--fused-head-loss", action="store_true",
                   help="fuse the LM-head matmul into the loss: the [B,S,V] "
                        "f32 logits never materialize (train/fused_ce.py)")
    p.add_argument("--segment-ids", action="store_true",
                   help="packed-document isolation: lm_dataset emits doc "
                        "ids and attention never crosses document "
                        "boundaries (flash/ring stream them natively); "
                        "default is GPT-style packing")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="swap each layer's FFN for a top-2-routed MoE "
                        "expert bank sharded over the expert mesh axis "
                        "(models/moe.py); 0 = dense")
    p.add_argument("--base-quant", default=None, choices=["int8"],
                   help="QLoRA-style int8 frozen-base storage (per-output-"
                        "channel scales): the 7B base drops ~12.6 to ~6.3 "
                        "GiB; --weights are quantized after import. "
                        "Requires --lora-rank > 0")
    p.add_argument("--expert", type=int, default=1,
                   help="expert-parallel axis size (with --moe-experts)")
    p.add_argument("--corpus", default=None, help="text file (one doc per line); synthetic if unset")
    p.add_argument("--tokenizer", default=None,
                   help="HF tokenizer dir matching --weights (required with --weights: "
                        "token ids must index the pretrained embedding rows)")
    args = p.parse_args()
    if args.segment_ids and args.pipeline > 1:
        p.error("--segment-ids is not supported with --pipeline (the stage "
                "forward does not thread them; packed batches would "
                "silently attend across documents)")
    if args.moe_experts:
        if args.pipeline > 1:
            p.error("--moe-experts is not supported with --pipeline "
                    "(the stage forward drops the load-balance aux loss)")
        if args.weights:
            p.error("--moe-experts cannot load dense --weights: the "
                    "checkpoint's mlp/{gate,up,down} kernels have no "
                    "counterpart in the moe/w_* expert tree and "
                    "load_pretrained would silently leave every expert "
                    "randomly initialized")
        if args.expert > 1 and args.moe_experts % args.expert:
            p.error(f"--moe-experts {args.moe_experts} must divide by "
                    f"--expert {args.expert} (expert-dim sharding)")
    elif args.expert > 1:
        p.error("--expert > 1 without --moe-experts just replicates the "
                "dense model over extra chips; drop --expert or add "
                "--moe-experts")
    if args.base_quant and not args.lora_rank:
        p.error("--base-quant requires --lora-rank > 0 (the quantized base "
                "is frozen; adapters carry the training)")
    if args.base_quant and args.moe_experts:
        p.error("--base-quant is not supported with --moe-experts (the "
                "expert bank trains from scratch in f32)")
    if args.weights and not args.tokenizer:
        p.error("--weights requires --tokenizer (the checkpoint's own vocab); "
                "a corpus-trained WordPiece vocab would index unrelated embedding rows")

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    # config 5 is FSDP-dominant: batch splits over (data, fsdp) so FSDP workers
    # are the "executors"; `--tensor` peels off chips for TP within each.
    spark = (
        Session.builder.master(args.master or "auto").appName("llama-lora")
        .config("mesh.data", 1).config("mesh.fsdp", args.fsdp)
        .config("mesh.tensor", args.tensor).config("mesh.seq", args.seq_parallel)
        .config("mesh.pipe", args.pipeline)
        .config("mesh.expert", args.expert)
        .getOrCreate()
    )
    print(spark)

    if args.corpus:
        with open(args.corpus) as f:
            lines = [ln.rstrip("\n") for ln in f if ln.strip()]
        docs = PartitionedDataset.parallelize(lines, spark.default_parallelism)
    else:
        docs = text_lib.synthetic_wikipedia(1024, num_partitions=max(spark.default_parallelism, 1))
    if args.tokenizer:
        tok = text_lib.HFTokenizerAdapter.load(args.tokenizer)
    else:
        tok = text_lib.WordPieceTokenizer.train(docs.collect(), vocab_size=2048)

    if args.variant in ("7b", "13b"):
        factory = (LlamaConfig.llama2_7b if args.variant == "7b"
                   else LlamaConfig.llama2_13b)
        cfg = factory(lora_rank=args.lora_rank, lora_alpha=args.lora_alpha)
        if tok.vocab_size > cfg.vocab_size:
            # nn.Embed's take() silently clamps out-of-range ids under jit —
            # fail loudly instead of training on a wrong embedding row
            raise SystemExit(
                f"tokenizer vocab ({tok.vocab_size}) exceeds model vocab "
                f"({cfg.vocab_size}); use the checkpoint's original tokenizer")
    else:
        cfg = LlamaConfig.tiny(
            vocab_size=max(tok.vocab_size, 512),
            lora_rank=args.lora_rank, lora_alpha=args.lora_alpha,
        )
    if args.seq_parallel > 1:
        cfg = dataclasses.replace(cfg, attention_impl=args.cp_impl)
    if args.fused_head_loss:
        if args.pipeline > 1:
            p.error("--fused-head-loss is not supported with --pipeline "
                    "(the GPipe forward emits real logits)")
        cfg = dataclasses.replace(cfg, fused_head_loss=True)
    if args.moe_experts:  # incompatibilities rejected at parse time above
        cfg = dataclasses.replace(cfg, moe_experts=args.moe_experts)
    if args.base_quant:
        cfg = dataclasses.replace(cfg, base_quant=args.base_quant)
    model = LlamaForCausalLM(cfg)

    ds = text_lib.lm_dataset(docs, tok, seq_len=args.seq_len,
                             segment_ids=args.segment_ids).repeat()

    # clip INSIDE the mask: the norm must be over adapter grads only, or the
    # frozen base weights' grads dominate it and shrink the LoRA updates
    tx = optim.masked(
        optim.with_grad_clip(
            optim.adamw(optim.warmup_cosine(
                args.lr, min(10, max(args.steps // 10, 1)), args.steps)),
            1.0,
        ),
        lora_trainable,
    )
    trainer = Trainer(
        spark, model,
        losses.causal_lm_fused if args.fused_head_loss else losses.causal_lm,
        tx,
        rules=llama_rules(cfg, pipeline=args.pipeline > 1),
        context_parallel=args.seq_parallel > 1,
        accum_steps=args.accum_steps,
        pipeline_microbatches=args.microbatches or None,
        # base weights leave autodiff entirely (no dW matmuls, no stacked
        # f32 grad buffers): measured +30% tokens/s on the bench shape
        trainable=lora_trainable,
    )
    trainer.init(trainer._sample_batch(ds, args.batch_size))
    if args.weights:
        pretrained = llama_io.load_llama_safetensors(args.weights, cfg)
        if args.base_quant:
            # per-output-channel absmax int8 — shapes then match the
            # quantized model's own tree (llama_io.quantize_base_int8)
            pretrained = llama_io.quantize_base_int8(pretrained)
        trainer.load_pretrained(pretrained)
    state, summary = trainer.fit(
        ds, batch_size=args.batch_size, steps=args.steps,
        tokens_per_example=args.seq_len, log_every=10,
    )
    print({k: round(float(v), 4) for k, v in summary.items()})
    if args.sample_tokens:
        import jax.numpy as jnp
        import numpy as np

        from distributeddeeplearningspark_tpu.models.llama_gen import generate

        prompt = jnp.asarray(
            np.tile(np.arange(8, dtype=np.int32)[None] % cfg.vocab_size, (2, 1)))
        out = generate(state.params, prompt, cfg=cfg,
                       max_new_tokens=args.sample_tokens, temperature=0.8,
                       top_k=40, seed=0)
        print("sampled continuations:", np.asarray(out).tolist())
    spark.stop()


if __name__ == "__main__":
    main()
