"""What the metric files in ``bench/metrics`` read: one run's ``Record``,
and the arithmetic they share. Each reader returns None where its run has
nothing to read; a share of a roofline or a peak is never 0 for want of
data.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List, Optional, Sequence, Tuple

from benchlib import roofline, trace as trace_lib

# names of the program's device work, as its trace shows them: the jitted
# decode chunk and prefill programs, and the ``pallas_call`` name of the
# paged decode-attention kernel that the decode chunk calls
DECODE_PROGRAM = "jit_chunk"
PREFILL_PROGRAM = "jit_refill"
DECODE_KERNEL = "paged_attention"


@dataclasses.dataclass
class Record:
    window: Tuple[float, float]           # host monotonic seconds
    end: float                            # when the run stopped waiting
    reqs: list                            # serve.RunReq, every request
    boundaries: list                      # serve.Boundary, every boundary
    counted: list                         # the requests the window judges
    dims: object                          # the block's Dims
    rows: int
    page_size: int
    kv_bytes: int                         # bytes per cached element
    kind: str                             # device_kind
    trace: Optional[trace_lib.Trace] = None
    trace_window: Optional[Tuple[int, int]] = None   # ns, trace clock
    notes: List[str] = dataclasses.field(default_factory=list)

    def in_window(self) -> list:
        lo, hi = self.window
        return [b for b in self.boundaries if b.t0 >= lo and b.t1 <= hi]

    def stat_delta(self, key: str) -> int:
        bs = self.in_window()
        if not bs:
            return 0
        i = self.boundaries.index(bs[0])
        before = self.boundaries[i - 1].stats[key] if i else 0
        return bs[-1].stats[key] - before


def p90(values: Sequence[float]) -> Optional[float]:
    """The 90th percentile (``statistics.quantiles``, exclusive method)."""
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=10)[8]


# ------------------------------------------------------------- host clock
def ttft_s(rec: Record) -> List[float]:
    """Due -> first token of every counted request; one that never had a
    token (refused, or unanswered when the run stopped waiting) counts
    with the whole wait, due -> the run's end."""
    return [(r.first if r.first is not None else rec.end) - r.due
            for r in rec.counted]


def tpot_s(rec: Record) -> List[float]:
    return [(r.last - r.first) / (len(r.out) - 1) for r in rec.counted
            if len(r.out) >= 2]


def queue_wait_s(rec: Record) -> List[float]:
    """Due -> admission of the counted requests admitted in the window (a
    traced run's trace export stalls the loop just after it closes)."""
    return [r.admit - r.due for r in rec.counted
            if r.admit is not None and r.admit <= rec.window[1]]


def window_tokens(rec: Record) -> int:
    return sum(b.tokens for b in rec.in_window())


# ------------------------------------------------------- program counters
def admitted_prompt_tokens(rec: Record) -> int:
    return sum(sum(b.admitted) for b in rec.in_window())


def admissions(rec: Record) -> int:
    return sum(len(b.admitted) for b in rec.in_window())


def chunk_rows(rec: Record) -> List[int]:
    """Live rows at the first step of each decode chunk in the window."""
    return [len(b.steps[0]) for b in rec.in_window() if b.steps]


# ---------------------------------------------------------- device trace
def _events(rec: Record, which: str, name: str):
    """Events of the traced window: the programs (``"modules"``) whose name
    holds ``name``, or the calls of the kernel ``name`` (``"kernels"``)."""
    if rec.trace is None:
        return []
    evs = (trace_lib.kernel_calls(rec.trace.ops, name) if which == "kernels"
           else trace_lib.matching(rec.trace.modules, name))
    return trace_lib.in_window(evs, rec.trace_window)


def device_s(rec: Record, which: str, needle: str) -> Optional[float]:
    evs = _events(rec, which, needle)
    return sum(d for _, _, d in evs) / 1e9 if evs else None


def decode_steps(rec: Record) -> List[List[int]]:
    return [s for b in rec.in_window() if b.steps for s in b.steps]


def step_mfu(rec: Record) -> Optional[float]:
    pk = roofline.peaks(rec.kind)
    dec = device_s(rec, "modules", DECODE_PROGRAM)
    pre = device_s(rec, "modules", PREFILL_PROGRAM) or 0.0
    if not dec:
        return None
    flops = sum(roofline.decode_step_flops(rec.dims, s)
                for s in decode_steps(rec) if s)
    flops += roofline.prefill_flops(
        rec.dims, [n for b in rec.in_window() for n in b.admitted])
    return 100.0 * flops / ((dec + pre) * pk.bf16_flops)


def paged_attention_roofline(rec: Record) -> Optional[float]:
    """Least time of the kernel's calls in the window over their device
    time. Each decode step calls it once per paged layer, over the live
    rows' resident lengths at that step."""
    pk = roofline.peaks(rec.kind)
    calls = trace_lib.inside(_events(rec, "kernels", DECODE_KERNEL),
                             _events(rec, "modules", DECODE_PROGRAM))
    spent = sum(d for _, _, d in calls) / 1e9
    steps = [s for s in decode_steps(rec) if s]
    if not spent or not steps:
        return None
    least, bounds = 0.0, {}
    for s in steps:
        t, bound = roofline.least_time(
            roofline.paged_attention_flops(rec.dims, s),
            roofline.paged_attention_bytes(rec.dims, s, rec.page_size,
                                           rec.kv_bytes), pk)
        least += rec.dims.paged_layers * t
        bounds[bound] = bounds.get(bound, 0) + 1
    rec.notes.append("paged_attention roofline bound: " + ", ".join(
        f"{k} in {v} of {len(steps)} steps" for k, v in sorted(bounds.items()))
        + f"; {len(calls)} kernel calls in the trace of "
        f"{len(steps) * rec.dims.paged_layers} steps x paged layers, "
        f"{spent!r} s")
    return 100.0 * least / spent


def idle_share(rec: Record) -> Optional[float]:
    if rec.trace is None:
        return None
    return 100.0 * trace_lib.idle_share(rec.trace, rec.trace_window)
