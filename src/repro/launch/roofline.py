"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), from the peak rates of the
device the program was compiled for (:data:`PEAKS`, keyed by JAX's
``device_kind``):

    compute    = HLO_FLOPs_per_device   / peak FLOP/s (bf16)
    memory     = HLO_bytes_per_device   / peak HBM B/s
    collective = coll_bytes_per_device  / ICI B/s per link

``compiled.cost_analysis()`` reports the per-device SPMD program, so the
per-device form above equals the brief's global/(chips × peak) form.
Collective bytes are parsed from the optimized HLO: operand bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (they are not in cost_analysis).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Peaks:
    flops: float     # bf16 FLOP/s per chip
    hbm_bw: float    # HBM B/s per chip
    ici_bw: float    # ICI B/s per link
    source: str


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of inter-chip interconnect over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    """Peak rates of ``device_kind``; a device not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device_kind={device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64|c64|c128)\[([0-9,]*)\]")
_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclass
class CollectiveStats:
    bytes_by_op: dict = field(default_factory=dict)
    count_by_op: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective op in (optimized) HLO text."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or "=" not in s:
            continue
        # `%name = <out shapes> <op>(<operands>), ...`
        rhs = s.split("=", 1)[1]
        op = None
        for c in _COLLECTIVES:
            # match the op name at call position (avoid metadata mentions)
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        if re.search(rf"\b{op}-done\(", rhs):
            continue  # bytes already counted at the -start op
        paren = rhs.index("(")
        operand_text = rhs[paren:]
        out_text = rhs[:paren]
        operand_bytes = sum(
            _shape_bytes(m.group(1), m.group(2))
            for m in _SHAPE_RE.finditer(operand_text)
        )
        if operand_bytes == 0:  # older HLO w/o inline operand types
            operand_bytes = sum(
                _shape_bytes(m.group(1), m.group(2))
                for m in _SHAPE_RE.finditer(out_text)
            )
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0) + operand_bytes
        stats.count_by_op[op] = stats.count_by_op.get(op, 0) + 1
    return stats


@dataclass
class Roofline:
    flops_per_dev: float
    hbm_bytes_per_dev: float
    coll_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_global: float
    useful_ratio: float
    coll_detail: dict
    peak_mem_bytes: float

    def to_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "hbm_bytes_per_dev": self.hbm_bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops_global": self.model_flops_global,
            "useful_ratio": self.useful_ratio,
            "coll_detail": self.coll_detail,
            "peak_mem_bytes": self.peak_mem_bytes,
        }


def analyze(
    compiled, num_devices: int, model_flops_global: float, device_kind: str
) -> Roofline:
    """Trip-count-weighted roofline terms from the compiled SPMD module.

    ``device_kind`` names the chip the terms are for (the dry runs
    compile on placeholder host devices that stand in for it).  Raw
    ``cost_analysis()`` counts scan bodies once; the HLO walk in
    ``hlo_analysis`` re-weights by ``known_trip_count`` so scanned layer
    units / flash key-blocks / CE chunks are charged per execution.
    """
    from repro.launch.hlo_analysis import analyze_hlo

    peaks = peaks_for(device_kind)
    cost = compiled.cost_analysis()
    raw_flops = float(cost.get("flops", 0.0))
    raw_hbm = float(cost.get("bytes accessed", 0.0))
    hlo = analyze_hlo(compiled.as_text())
    flops = max(hlo.dot_flops, raw_flops)
    hbm = max(hlo.hbm_bytes, raw_hbm)
    compute_s = flops / peaks.flops
    memory_s = hbm / peaks.hbm_bw
    collective_s = hlo.coll_bytes / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mem_stats = compiled.memory_analysis()
    peak = float(
        getattr(mem_stats, "temp_size_in_bytes", 0)
        + getattr(mem_stats, "argument_size_in_bytes", 0)
        + getattr(mem_stats, "output_size_in_bytes", 0)
        - getattr(mem_stats, "alias_size_in_bytes", 0)
    )
    useful = model_flops_global / max(flops * num_devices, 1.0)
    return Roofline(
        flops_per_dev=flops,
        hbm_bytes_per_dev=hbm,
        coll_bytes_per_dev=float(hlo.coll_bytes),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops_global=model_flops_global,
        useful_ratio=useful,
        coll_detail=hlo.coll_detail,
        peak_mem_bytes=peak,
    )


def model_flops(cfg, shape_spec, active_params: int) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (prefill) / 2·N·B (decode)."""
    B, S = shape_spec.global_batch, shape_spec.seq_len
    if shape_spec.kind == "train":
        return 6.0 * active_params * B * S
    if shape_spec.kind == "prefill":
        return 2.0 * active_params * B * S
    return 2.0 * active_params * B  # decode: one token per sequence
