"""Device time a step of the indexer's scores and the selection, forward and
backward: the kernels ``dsa_index_fwd``, ``dsa_index_select`` and
``dsa_index_bwd`` by name, and the part of the selection that is XLA's (the
mask from threshold and cut, the log-sum-exp of the selected scores), by the
result shapes only it produces: any ``[batch, seq, seq]`` or ``[seq, seq]``
array (scores, sorted scores, masks, iotas) that is not an output of one of
the attention's kernels (``dsa_kl_target`` writes such an array too, and
belongs to ``dsa_attend_ms_per_step``)."""

from benchmark.harness import stage_time


def read(ctx):
    b, s = ctx["traffic"]["per_chip_batch"], ctx["traffic"].get("seq_len")
    if not s:
        return None

    def belongs(name, info):
        if "dsa_index" in name:
            return True
        if "dsa_" in name or info.get("op") == "while":
            return False
        result = info.get("result", "")
        return (stage_time.has_shape(result, b, s, s)
                or stage_time.has_shape(result, s, s))

    return stage_time.union_ms_per_step(ctx, belongs, "dsa_index_ms_per_step")
