"""Device time a step of the expert layer: routing, sort, grouped products,
combine, forward and backward. All of it is XLA's, so it is found by what
only it produces: the grouped products by their opcode's name
(``ragged-dot``: XLA's own grouped-matmul kernel), the rest by result
shapes: anything with ``tokens x k`` rows (the buffer of assignments in
expert order, its gathers, the argsort over the assignments) and the
router's ``[tokens, router width]`` arrays (logits, probabilities, top-k),
``tokens = per_chip_batch x seq_len``."""

import re

from benchmark.harness import stage_time


def read(ctx):
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if "num_experts_per_tok" not in cfg or "seq_len" not in traffic:
        return None
    tokens = traffic["per_chip_batch"] * traffic["seq_len"]
    rows = tokens * cfg["num_experts_per_tok"]
    width = cfg["num_local_experts"]
    shapes = re.compile(rf"\[{rows}[,\]]|\[{tokens},{width}\]"
                        rf"|\[{tokens},{cfg['num_experts_per_tok']}")

    def belongs(name, info):
        if info.get("op") == "while":
            return False
        return "ragged-dot" in name or shapes.search(info.get("result", ""))

    return stage_time.union_ms_per_step(ctx, belongs, "moe_ms_per_step")
