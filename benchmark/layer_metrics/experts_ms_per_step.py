"""Device time a step of the expert layers behind the sigmoid router:
routing, sort, grouped products, combine, forward and backward, found as
``moe_ms_per_step.py`` finds them (all of it is XLA's): the grouped products
by their opcode's name (``ragged-dot``), the rest by result shapes: anything
with ``tokens x k`` rows and the router's ``[tokens, router width]`` and
``[tokens, k]`` arrays, ``tokens = per_chip_batch x seq_len``. For a
configuration that names its router's width ``router_width``."""

import re

from benchmark.harness import stage_time


def read(ctx):
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if "router_width" not in cfg or "seq_len" not in traffic:
        return None
    tokens = traffic["per_chip_batch"] * traffic["seq_len"]
    k = cfg["num_experts_per_tok"]
    shapes = re.compile(rf"\[{tokens * k}[,\]]|\[{tokens},{cfg['router_width']}\]"
                        rf"|\[{tokens},{k}[,\]]")

    def belongs(name, info):
        if info.get("op") == "while":
            return False
        return "ragged-dot" in name or shapes.search(info.get("result", ""))

    return stage_time.union_ms_per_step(ctx, belongs, "experts_ms_per_step")
