"""Reduction of a profiler trace to the device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: the device's operation and module events and the host's spans,
all in nanoseconds on one clock. The rest works on a ``Trace`` alone, so a
test can build one by hand:

- ``busy`` unions the intervals in which an operation ran on the device;
- ``idle_share`` is 1 - busy / window;
- ``time_by_name`` sums the device time of events by name, and
  ``self_time`` gives each event its time less that of the events nested
  in it (a loop's body runs inside the loop's own event);
- ``gaps`` finds the device's idle intervals and names each by the harness
  span the host spent the most of it in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    ops: List[Event]                  # device operations (one chip)
    modules: List[Event]              # device programs (XLA modules)
    spans: List[Event]                # host spans (TraceAnnotation)


def base_name(name: str) -> str:
    """``jit_chunk(123)`` -> ``jit_chunk``: module ids change per run."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``: a device
    operation's event is named by its whole HLO instruction."""
    return name.split(" = ", 1)[0]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


DEVICE = "/device:TPU:0"        # the one chip a cell's program runs on
SPAN_PREFIX = "bench."           # the harness's own host spans
TOP = 10                         # entries of each breakdown list


def load(path: str) -> Trace:
    """Events of the chip (operations under their short names) and the
    harness's host spans from an xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name == DEVICE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((_op_label(ev), int(ev.start_ns),
                                    int(ev.duration_ns)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((ev.name, int(ev.start_ns),
                                        int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    return Trace(ops=ops, modules=modules, spans=spans)


KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
KERNEL_MARK = " tpu_custom_call"


def _op_label(ev) -> str:
    """Short name of a device operation; a Pallas kernel (a Mosaic custom
    call, its instruction named after the ``pallas_call``'s ``name``) is
    marked ``tpu_custom_call``: ``%paged_attention.10 tpu_custom_call``."""
    label = op_name(ev.name)
    if KERNEL_TARGET in ev.name:
        return label + KERNEL_MARK
    return label


def kernel_calls(events: Sequence[Event], name: str) -> List[Event]:
    """The calls of the Pallas kernel ``name``: custom calls whose
    instruction is ``%<name>`` or ``%<name>.<n>``, and no other kernel."""
    pat = re.compile(rf"%?{re.escape(name)}(\.\d+)?{KERNEL_MARK}")
    return [ev for ev in events if pat.fullmatch(ev[0])]


def self_time(events: Sequence[Event]) -> List[Event]:
    """Each event with its duration less that of the events nested in it
    on the same line (events nest, or do not overlap)."""
    out, stack = [], []               # stack: [name, start, end, child_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= s:
            n, a, b, c = stack.pop()
            out.append((n, a, (b - a) - c))
        if stack:
            stack[-1][3] += d
        stack.append([name, s, s + d, 0])
    while stack:
        n, a, b, c = stack.pop()
        out.append((n, a, (b - a) - c))
    return out


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_intervals(tr: Trace, window: Tuple[int, int]):
    events = tr.ops or tr.modules
    return clip(union([(s, s + d) for _, s, d in events]), *window)


def busy_s(tr: Trace, window: Tuple[int, int]) -> float:
    return sum(e - s for s, e in busy_intervals(tr, window)) / 1e9


def idle_share(tr: Trace, window: Tuple[int, int]) -> float:
    span = (window[1] - window[0]) / 1e9
    return 1.0 - busy_s(tr, window) / span


def span_window(tr: Trace, name: str) -> Optional[Tuple[int, int]]:
    """[start, end) of the host span ``name`` (the first one)."""
    for n, s, d in tr.spans:
        if n == name:
            return s, s + d
    return None


def in_window(events: Sequence[Event], window: Tuple[int, int]):
    lo, hi = window
    return [ev for ev in events if ev[1] >= lo and ev[1] + ev[2] <= hi]


def inside(events: Sequence[Event], outer: Sequence[Event]) -> List[Event]:
    """The events that lie within one of the ``outer`` events (an
    operation inside a program's run)."""
    spans = sorted((s, s + d) for _, s, d in outer)
    out, i = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] < ev[1] + ev[2]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1]:
            out.append(ev)
    return out


def time_by_name(events: Sequence[Event], key=base_name) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in events:
        k = key(name)
        out[k] = out.get(k, 0.0) + d / 1e9
    return out


def matching(events: Sequence[Event], needle: str) -> List[Event]:
    return [ev for ev in events if needle in ev[0]]


def gaps(tr: Trace, window: Tuple[int, int],
         labels: Sequence[str]) -> List[List]:
    """The ``TOP`` longest idle intervals of the device in ``window``, each
    ``[label, seconds]``, where the label is the host span among ``labels``
    that covers most of the gap (``"none"`` where none does)."""
    busy = busy_intervals(tr, window)
    idle, t = [], window[0]
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < window[1]:
        idle.append((t, window[1]))
    spans = [(n, s, s + d) for n, s, d in tr.spans if n in labels]
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:TOP]:
        cover: Dict[str, int] = {}
        for n, a, b in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[n] = cover.get(n, 0) + ov
        label = max(cover, key=cover.get) if cover else "none"
        out.append([label, (e - s) / 1e9])
    return out


def top_ops(events: Sequence[Event]) -> List[List]:
    """[name, seconds] of the operations with the most self time."""
    tot = time_by_name(self_time(events), key=lambda n: n)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])
            [:TOP]]
