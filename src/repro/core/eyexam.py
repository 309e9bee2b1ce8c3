"""Eyexam (paper Appendix A) — performance-bound analysis + TPU roofline.

Two halves:

1. ``seven_steps`` — the paper's step-by-step tightening of the performance
   bound (workload → dataflow → #PEs → array shape → storage → avg bandwidth),
   used by ``benchmarks/scaling.py`` to reproduce Fig. 14 and Fig. 27.

2. ``roofline_from_compiled`` — the three-term TPU roofline extracted from the
   multi-pod dry-run's compiled artifact:

       compute    = HLO_FLOPs  / (peak_FLOP/s per chip)
       memory     = HLO_bytes  / (HBM GB/s per chip)
       collective = Σ collective operand bytes / (ICI link GB/s per chip)

   ``cost_analysis`` supplies FLOPs/bytes; collective bytes are parsed from the
   post-SPMD HLO text (the compiled module is the per-chip program, so all
   three terms are already per-chip).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

# ------------------------------------------------------------- chip peaks
@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # bytes
    ici_link_bw: float       # bytes/s per chip-to-chip link
    source: str


# Keyed by JAX's ``device_kind``. A kind missing here is an error on the
# chip path, never a silent default.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI "
               "over 4 links"),
}

# The chip the analytic planners (dry-run, Eyexam rooflines) model.
TARGET_KIND = "TPU v5 lite"
PEAK_FLOPS = CHIP_PEAKS[TARGET_KIND].bf16_flops
HBM_BW = CHIP_PEAKS[TARGET_KIND].hbm_bw
ICI_BW = CHIP_PEAKS[TARGET_KIND].ici_link_bw
HBM_CAP = CHIP_PEAKS[TARGET_KIND].hbm_bytes


def device_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: JAX's first device). A TPU whose kind
    is not in :data:`CHIP_PEAKS` raises; the CPU backend, which runs the
    kernels interpreted for tests, plans against the target chip."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return CHIP_PEAKS[TARGET_KIND]
    try:
        return CHIP_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device.device_kind!r}; "
            "add its entry, with its source, to core.eyexam.CHIP_PEAKS"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"([\w\-]+)(\(.*)$")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective in (post-SPMD, per-chip) HLO text.

    Builds a name→shape symbol table line by line, then for each collective
    instruction sums the shapes of its operands.
    """
    shapes: Dict[str, str] = {}
    totals: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    counts: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_str, op, rest = m.groups()
        shapes[name] = shape_str
        op_base = op.rstrip("0123456789.")
        # strip -start/-done variants (async collectives)
        for c in COLLECTIVE_OPS:
            if op_base == c or op_base == c + "-start":
                # operand names: %foo.123 inside the parens
                operands = re.findall(r"%([\w.\-]+)", rest)
                b = 0
                for o in operands:
                    if o in shapes:
                        b += _shape_bytes(shapes[o])
                if b == 0:  # fall back to result shape
                    b = _shape_bytes(shape_str)
                totals[c] += b
                counts[c] += 1
                break
    totals["_counts"] = counts  # type: ignore
    return totals


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    per_op_coll: Dict[str, int]
    chips: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bound(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)  # type: ignore

    @property
    def t_total(self) -> float:
        """Optimistic fully-overlapped step time."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def fraction_of_roofline(self, useful_flops: float) -> float:
        """useful_flops (per chip) / peak over the bound-implied time."""
        if self.t_total <= 0:
            return 0.0
        return (useful_flops / self.t_total) / PEAK_FLOPS

    def summary(self) -> Dict[str, float]:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bound": self.bound,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
        }


def roofline_from_compiled(compiled, chips: int,
                           hlo_text: Optional[str] = None) -> Roofline:
    """Three-term roofline from the compiled per-chip module.

    Uses core.hloparse (call-graph walk with while-loop trip-count
    multiplication) because ``cost_analysis()`` counts scan bodies once —
    see hloparse module docstring for the traffic model.
    """
    from repro.core import hloparse
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hloparse.analyze(text)
    return Roofline(flops=cost.flops, hbm_bytes=cost.hbm_bytes,
                    coll_bytes=cost.total_coll_bytes,
                    per_op_coll={**{k: int(v) for k, v in
                                    cost.coll_bytes.items()},
                                 "counts": {k: int(v) for k, v in
                                            cost.coll_counts.items()}},
                    chips=chips)


# =============================================================== seven steps
@dataclasses.dataclass
class AcceleratorModel:
    """Abstract accelerator for the analytical model (paper Fig. 23).

    noc: 'broadcast' (Eyeriss v1: one value/cycle/type from GLB regardless of
    scale) or 'hmnoc' (Eyeriss v2: one value/cycle/type *per cluster*).
    """
    n_pes: int
    array_h: int
    array_w: int
    noc: str = "hmnoc"
    cluster_size: int = 16        # PEs per cluster (v2: 4×4 in §III-D)
    macs_per_pe: int = 1
    spad_weights: int = 192       # max weights resident per PE (§IV)
    glb_bw_words: float = 1.0     # words/cycle/data-type from GLB source

    @property
    def n_clusters(self) -> int:
        return max(self.n_pes // self.cluster_size, 1)


def seven_steps(shape, acc: AcceleratorModel) -> List[Dict]:
    """Performance bound (MACs/cycle) after each Eyexam step for one layer.

    Row-stationary-flavored mapping: spatial dims are (C·R groups) × (M, E·F).
    Returns a list of dicts with the bound after steps 1..6.
    """
    steps = []
    macs = shape.macs
    # Step 1: layer size — all-parallel bound
    b1 = macs
    steps.append({"step": 1, "name": "layer shape", "bound": b1})
    # Step 2: dataflow (RS): parallelism across M·E·F·G·C·R (row-level)
    dataflow_par = shape.G * shape.M * shape.E * shape.F * shape.C * shape.R
    b2 = min(b1, dataflow_par)
    steps.append({"step": 2, "name": "dataflow", "bound": b2})
    # Step 3: finite PEs
    b3 = min(b2, acc.n_pes * acc.macs_per_pe)
    steps.append({"step": 3, "name": "#PEs", "bound": b3})
    # Step 4: physical array shape — fold (G·E·F) onto width, (M·C·R) onto height
    w_par = shape.G * shape.E * shape.F
    h_par = shape.M * shape.C * shape.R
    active_w = min(acc.array_w, w_par)
    active_h = min(acc.array_h, h_par)
    b4 = min(b3, active_w * active_h * acc.macs_per_pe)
    steps.append({"step": 4, "name": "array dims", "bound": b4,
                  "active_pes": active_w * active_h})
    # Step 5: storage — weights resident per PE cap (paper Table III)
    w_per_pe = shape.weight_count / max(active_w * active_h, 1)
    if w_per_pe > acc.spad_weights:
        b5 = b4  # needs temporal passes; bound unchanged, utilization later
    else:
        b5 = b4
    steps.append({"step": 5, "name": "storage", "bound": b5})
    # Step 6: average NoC bandwidth
    r = {"weight": macs / max(shape.weight_count, 1),
         "iact": macs / max(shape.iact_count, 1)}
    if acc.noc == "broadcast":
        src_bw = acc.glb_bw_words                   # does NOT scale (v1)
    else:
        src_bw = acc.glb_bw_words * acc.n_clusters  # scales with clusters (v2)
    # deliverable MACs/cycle limited by each data type: bw · reuse
    bw_bound = min(src_bw * r["weight"], src_bw * r["iact"])
    b6 = min(b5, bw_bound)
    steps.append({"step": 6, "name": "NoC bandwidth", "bound": b6})
    return steps


def layer_cycles(shape, acc: AcceleratorModel) -> float:
    """Cycles for one layer under the step-6 bound (the Fig. 14 model)."""
    bound = seven_steps(shape, acc)[-1]["bound"]
    return shape.macs / max(bound, 1e-9)


def network_performance(layers_: List, acc: AcceleratorModel) -> float:
    """End-to-end MACs/cycle over a whole network (batch already in shapes)."""
    total_macs = sum(s.macs for s in layers_)
    total_cycles = sum(layer_cycles(s, acc) for s in layers_)
    return total_macs / max(total_cycles, 1e-9)
