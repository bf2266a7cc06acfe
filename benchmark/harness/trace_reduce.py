"""From a profiler trace to numbers: the benchmark's own reduction.

``jax.profiler.ProfileData.from_file`` reads the ``.xplane.pb`` with nothing
but jax. A v5e trace (looked at by hand, PR 22) holds one plane per device,
``/device:TPU:<n>``, with these lines: ``XLA Ops``, one event per executed HLO
op, start and duration in nanoseconds, never overlapping one another;
``Async XLA Ops``, the spans of asynchronous ops from their ``-start`` to their
``-done`` (copies, slices, collectives), which do overlap the ops;
``XLA Modules`` and ``Steps``, one event per executed program. An event's
name is the op's whole HLO text (``%fusion.12 = bf16[..] fusion(..), kind=..``)
and it carries no stat that names it, so :func:`extract` cuts the text into a
short name, a kind, a result type and, for a custom call, its target. A Mosaic
kernel is ``custom_call_target="tpu_custom_call"``. The host plane
(``/host:CPU``) has one line per thread with the runtime's own spans.

Everything below works on the plain extract :func:`extract` makes (dicts,
lists, numbers), so the arithmetic is tested on a recorded extract and on
hand-made events without a profiler:

* busy time is the UNION of the op intervals, never their sum: ops on a TPU
  core may overlap (async collectives, DMA);
* a device with no op line, or an op line with no events, reduces to nothing,
  never to "the busiest line anywhere";
* exposed time of a set of ops (the all-reduces) is the part of their union
  during which no other op runs on that device.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
HLO_TEXT = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<result>.*?) "
                      r"(?P<op>[a-z][a-z0-9\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
LAYOUT = re.compile(r"\{[^{}]*\}")
MAX_HOST_EVENTS = 20_000  # kept per host thread: the longest ones
TOP = 10  # entries in each list of the last line's ``breakdown``

Interval = tuple[float, float]


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(xplane_path: str) -> dict:
    """The trace as plain data: ``{"devices": {id: {line: [event]}},
    "host": {thread: [event]}}``, an event being ``[name, start_ns, dur_ns,
    info]``. A host thread that wrote more than ``MAX_HOST_EVENTS`` spans
    keeps its longest ones (one feed thread writes 70,000 ``Transpose`` spans
    a step)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out: dict = {"devices": {}, "host": {}, "planes": []}
    for plane in data.planes:
        lines = list(plane.lines)
        out["planes"].append([plane.name, [ln.name for ln in lines]])
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(m.group(1), {})
            for ln in lines:
                dev[ln.name] = [_event(e) for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in lines:
                evs = [[e.name[:80], float(e.start_ns), float(e.duration_ns),
                        {}] for e in ln.events if e.duration_ns > 0]
                if len(evs) > MAX_HOST_EVENTS:
                    evs = sorted(sorted(evs, key=lambda e: -e[2])
                                 [:MAX_HOST_EVENTS], key=lambda e: e[1])
                if evs:
                    out["host"][ln.name] = evs
    return out


def describe(text: str) -> tuple[str, dict]:
    """An op event's name as the trace prints it (its HLO text) ->
    ``(short name, {"kind", "result", "op", "target"})``. ``kind`` is the
    short name without its number (``fusion``, ``attention``); ``result`` is
    the result type without layouts; ``op`` the HLO opcode."""
    m = HLO_TEXT.match(text)
    if not m:
        return text[:120], {}
    name = m.group("name")
    info = {"kind": re.sub(r"\.\d+$", "", name), "op": m.group("op"),
            "result": LAYOUT.sub("", m.group("result"))[:120]}
    target = TARGET.search(text)
    if target:
        info["target"] = target.group(1)
    return name, info


def _event(e) -> list:
    name, info = describe(e.name)
    return [name, float(e.start_ns), float(e.duration_ns), info]


def save_extract(ex: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ex, f)


def load_extract(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic ------------------------------------------------------

def intervals(events: list) -> list[Interval]:
    return [(e[1], e[1] + e[2]) for e in events if e[2] > 0]


def union(ivs: list[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering exactly what ``ivs`` cover."""
    out: list[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(ivs: list[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out: list[Interval] = []
    b = union(b)
    j = 0
    for lo, hi in union(a):
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- the reduction ------------------------------------------------------------

def device_ops(ex: dict, device: str) -> list:
    """The op events of one device, or [] (never another line's events)."""
    return ex["devices"].get(device, {}).get(OP_LINE, [])


def busy_s(ex: dict, device: str) -> float:
    return length(union(intervals(device_ops(ex, device)))) / 1e9


def by_kind(ex: dict, device: str) -> dict[str, list[float]]:
    """``kind result-type`` -> [summed seconds, count] on one device: the
    twelve layers' copies of one fusion count as one row."""
    out: dict[str, list[float]] = {}
    for name, _, dur, info in device_ops(ex, device):
        key = f"{info.get('kind', name)} {info.get('result', '')}".strip()
        acc = out.setdefault(key, [0.0, 0])
        acc[0] += dur / 1e9
        acc[1] += 1
    return out


def select(ex: dict, device: str, pattern: str, line: str = OP_LINE) -> list:
    """Events of ``line`` whose name, kind, opcode or custom-call target
    matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return [e for e in ex["devices"].get(device, {}).get(line, [])
            if rx.search(" ".join([e[0]] + [str(e[3].get(k, "")) for k in
                                            ("kind", "op", "target")]))]


def summed_s(events: list) -> float:
    return sum(e[2] for e in events) / 1e9


def exposed_s(ex: dict, device: str, pattern: str) -> float:
    """Seconds in which an op matching ``pattern`` ran (as an op, or as an
    asynchronous span from its start to its done) and no other op did."""
    hit = select(ex, device, pattern)
    ids = {id(e) for e in hit}
    others = [e for e in device_ops(ex, device) if id(e) not in ids]
    spans = intervals(hit) + intervals(select(ex, device, pattern, ASYNC_LINE))
    return length(subtract(spans, intervals(others))) / 1e9


def steps_traced(ex: dict, device: str, module_pattern: str) -> int:
    """How many times the program matching ``module_pattern`` ran."""
    rx = re.compile(module_pattern)
    return sum(1 for e in ex["devices"].get(device, {}).get(MODULE_LINE, [])
               if rx.search(e[0]))


def top_ops(ex: dict, device: str) -> list[list]:
    rows = sorted(by_kind(ex, device).items(), key=lambda kv: -kv[1][0])
    return [[name, secs] for name, (secs, _) in rows[:TOP]]


def idle_gaps(ex: dict, device: str) -> list[list]:
    """The longest idle gaps between the first and last op of ``device``,
    each named by the shortest host span covering at least half of it, else
    by the one overlapping it most (``unattributed`` when none does)."""
    busy = union(intervals(device_ops(ex, device)))
    if not busy:
        return []
    found = sorted(subtract([(busy[0][0], busy[-1][1])], busy),
                   key=lambda g: g[0] - g[1])[:TOP]
    host = [(e[1], e[1] + e[2], e[0]) for evs in ex["host"].values()
            for e in evs]
    out = []
    for lo, hi in found:
        # the shortest host span that covers at least half of the gap (one
        # that merely contains everything, the whole fit call, explains
        # nothing); failing that, the one that overlaps it most
        covering = [(min(b, hi) - max(a, lo), b - a, name)
                    for a, b, name in host if min(b, hi) > max(a, lo)]
        half = [c for c in covering if c[0] >= 0.5 * (hi - lo)]
        if half:
            best = min(half, key=lambda c: c[1])[2]
        else:
            best = max(covering)[2] if covering else "unattributed"
        out.append([best, (hi - lo) / 1e9])
    return out


def summarize(ex: dict, *, window_s: float) -> dict:
    """What the last line's ``device`` and ``breakdown`` need."""
    devs = sorted(ex["devices"], key=int)
    busy = {d: busy_s(ex, d) for d in devs}
    live = [d for d in devs if busy[d] > 0]
    out = {"devices": devs, "busy_s_by_device": busy, "window_s": window_s}
    if live:
        out["busy_s"] = sum(busy[d] for d in live) / len(live)
        out["device_ops"] = top_ops(ex, live[0])
        out["idle_gaps"] = idle_gaps(ex, live[0])
    return out
