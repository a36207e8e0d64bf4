"""Roofline accounting from compiled dry-run artifacts (assignment §ROOFLINE).

All quantities are PER-DEVICE: the compiled module of an SPMD program is the
per-device program, so ``cost_analysis()`` flops/bytes and the collective
bytes parsed from ``compiled.as_text()`` are per-chip numbers.

    compute_s    = HLO_flops / peak_flops
    memory_s     = HLO_bytes / hbm_bw
    collective_s = collective_bytes / link_bw

with the peaks of the chip named by its ``device_kind`` (:data:`PEAKS`).

The dominant term is the step-time lower bound; MODEL_FLOPS/HLO_FLOPs
measures how much compiled compute is "useful" (remat/dispatch waste).
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    link_bw: float  # bytes/s per ICI link


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI over
# four links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip; a kind not in :data:`PEAKS` raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

# e.g.  %x = bf16[16,512]{1,0} all-gather(bf16[1,512]{1,0} %p), ...
_INSTR_RE = re.compile(
    r"=\s*(?P<out>\([^=]*?\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<start>-start)?\("
    r"(?P<operands>[^)]*)\)"
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: dict[str, int]
    count_by_op: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand sizes of every collective op in optimized HLO text."""
    bytes_by_op: dict[str, int] = {}
    count_by_op: dict[str, int] = {}
    for m in _INSTR_RE.finditer(hlo_text):
        op = m.group("op")
        # operand types appear inline in HLO text: "bf16[8,16]{1,0} %arg"
        nbytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(m.group("operands"))
        )
        if nbytes == 0:  # fall back to the output shape
            nbytes = sum(
                _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(m.group("out"))
            )
        bytes_by_op[op] = bytes_by_op.get(op, 0) + nbytes
        count_by_op[op] = count_by_op.get(op, 0) + 1
    return CollectiveStats(bytes_by_op, count_by_op)


@dataclasses.dataclass
class Roofline:
    flops: float  # per device
    hbm_bytes: float  # per device
    collective_bytes: float  # per device
    chips: int
    peaks: ChipPeaks
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float  # global useful flops (6 N D)
    useful_ratio: float  # model_flops / (flops * chips)

    def bound_step_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """useful-compute time / bound step time (the score axis)."""
        t_useful = self.model_flops / self.chips / self.peaks.flops
        b = self.bound_step_time()
        return t_useful / b if b > 0 else 0.0


def analyze(
    compiled, chips: int, model_flops: float, device_kind: str
) -> Roofline:
    """Roofline terms from the compiled artifact, on ``device_kind`` chips.

    Uses the trip-count-aware HLO analyzer (utils/hlo.py): XLA's own
    ``cost_analysis()`` counts scan bodies once, which would undercount every
    layer-stacked model here by its depth.
    """
    from repro.utils import hlo as hlo_mod

    costs = hlo_mod.analyze_compiled(compiled)
    flops = costs.flops
    hbm = costs.bytes
    coll = CollectiveStats(
        dict(costs.coll_by_op),
        {k: int(v) for k, v in costs.coll_count.items()},
    )
    chip = peaks(device_kind)
    compute_s = flops / chip.flops
    memory_s = hbm / chip.hbm_bw
    collective_s = coll.total_bytes / chip.link_bw
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", collective_s)],
        key=lambda kv: kv[1],
    )[0]
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=float(coll.total_bytes),
        chips=chips,
        peaks=chip,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=model_flops / (flops * chips) if flops else 0.0,
    )


def freq_transform_model(
    n_pts: int, n: int, m: int, d: int, nblocks: int
) -> dict:
    """Flops/bytes/arithmetic-intensity model of the two frequency operators.

    Dense projection: one ``(N, n) @ (n, m)`` matmul — ``2·N·n·m`` flops
    moving ``4·(N·n + n·m + N·m)`` bytes.  Structured projection: per block,
    three Kronecker-factored WHTs (``H_d = H_a ⊗ H_b``; two dense
    contractions of ``2·N·d·(a+b)`` flops each) plus the diagonal and radial
    elementwise stages — ``O(N·m·sqrt(d))`` total, moving only
    ``4·(N·d + O(m) operator leaves + N·m)`` bytes.  The flops here count
    dot-issued work only (matching ``utils.hlo.analyze_compiled``'s cost
    model, which is how the benchmark cross-checks this model against the
    compiled HLO); elementwise trig/diagonals are excluded on both sides.
    """
    a = 1 << (((d.bit_length() - 1) + 1) // 2) if d > 1 else 1
    b = max(d // a, 1)
    dense_flops = 2.0 * n_pts * n * m
    structured_flops = 3.0 * nblocks * 2.0 * n_pts * d * (a + b)
    dense_bytes = 4.0 * (n_pts * n + n * m + n_pts * m)
    structured_bytes = 4.0 * (n_pts * d + 4 * nblocks * d + n_pts * m)
    return {
        "dense_flops": dense_flops,
        "structured_flops": structured_flops,
        "flops_ratio": dense_flops / max(structured_flops, 1.0),
        "dense_bytes": dense_bytes,
        "structured_bytes": structured_bytes,
        "dense_intensity": dense_flops / dense_bytes,
        "structured_intensity": structured_flops / structured_bytes,
    }


def train_model_flops(param_count: int, tokens: int) -> float:
    """6 N D (N = active params)."""
    return 6.0 * param_count * tokens


def decode_model_flops(param_count: int, batch: int) -> float:
    """One token per sequence: 2 N per token forward (decode has no backward)."""
    return 2.0 * param_count * batch
