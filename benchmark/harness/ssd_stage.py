"""How a trace finds the state-space scan (``ops/ssd.py`` of the program),
which XLA runs and which therefore has no name of its own: by the result
shapes that only it produces (``ops/ssd.py``'s docstring says so too).

Each pass of one layer's scan is ONE ``while`` over the groups of chunks
whose carried tuple begins, after the counter, with the state a chunk hands
on, ``f32[B, G, H / G, P, N]``, followed by arrays stacked ``[groups, B,
chunks a group, chunk, ...]`` over the window, HOWEVER MANY chunks the
program puts in a group (nothing here reads ``ops/ssd.GROUP``: the two
counts are any numbers, the fourth dimension is the configuration's
``chunk_size``). The inner loop that passes states inside a group carries
the same state, but its arrays are ``[chunks a group, B, G, H / G, ...]``:
it runs inside the outer loop's interval and is not counted. A
BACKWARD pass carries, right after the state, the two ``f32[G, H / G]``
accumulators of ``A``'s and ``D``'s gradients; a forward pass (the step's
own, or its replay under remat) does not. XLA also hoists what depends on
the segment ids alone (the masks' predicates of every layer and pass,
computed once a step) into a loop of its own, whose tuple holds only
``pred`` arrays stacked over the groups: part of the stage's time, no
execution of it.

A later KERNEL for the scan has a name, and by ``ops/ssd.py``'s word it
starts ``ssd_``: such events are the stage's too (:func:`scan_kernels`), so
``ssd_ms_per_step`` goes on reading the whole stage, loops and kernels,
without an edit here. ``ssd_roofline`` counts executions from the loops and
gives nothing once kernels run part of a pass; what it needs then is in
``PERF.md`` section 7."""

from __future__ import annotations

import re

from benchmark.harness import stage_time, trace_reduce


def scan_loops(ctx):
    """``(forward passes, backward passes, the masks' loops)``: the events
    of device 0 that are the scan's outer loops, or ``None`` for a cell or
    a program without the scan."""
    shapes = getattr(ctx["cell"]["config_mod"], "ssd_shapes", None)
    if shapes is None:
        return None
    sh = shapes(ctx["cfg"], ctx["traffic"])
    g, r = sh["groups"], sh["heads"] // sh["groups"]
    state = (rf"\(s32\[\], f32\[{sh['batch']},{g},{r},{sh['head_dim']},"
             rf"{sh['state_size']}\], ")
    stacked = rf"\[\d+,{sh['batch']},\d+,"
    chunked = stacked + rf"{sh['chunk']},"
    accumulators = rf"f32\[{g},{r}\], f32\[{g},{r}\], "
    backward = re.compile(state + accumulators)
    forward = re.compile(state + rf"(?!{accumulators})[a-z0-9]+" + chunked)
    masks = re.compile(r"^\(s32\[\](, pred" + stacked + r"[\d,]*\])+\)?$")

    def found(rx):
        return stage_time.device0_events(
            ctx, lambda name, info: info.get("op") == "while"
            and rx.search(info.get("result", "")) is not None)

    return found(forward), found(backward), found(masks)


def scan_kernels(ctx):
    """The events of device 0 whose name starts ``ssd_``: a kernel's, where
    the program runs the scan, or a part of it, as one (none today)."""
    return stage_time.device0_events(
        ctx, lambda name, info: name.startswith("ssd_"))


def union_ms(events) -> float:
    """The time the events cover, in ms (a loop spans its body's ops, and
    XLA may overlap a loop's tail with the next op: a union, not a sum)."""
    return trace_reduce.length(trace_reduce.union(
        trace_reduce.intervals(events))) / 1e6
