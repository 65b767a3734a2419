"""Extract roofline inputs from a lowered/compiled XLA program.

``cost_analysis()`` gives FLOPs and HBM traffic; collective bytes are NOT
reported there, so we parse the (optimized, partitioned) HLO text and sum
the operand sizes of every collective op.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# e.g. "bf16[2048,1024]{1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# HLO instruction line: "%name = <shape-or-tuple> op-name(...)"
_INSTR_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s+(" + "|".join(COLLECTIVE_OPS) + r")(-start|-done)?\("
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: dict[str, int] = field(default_factory=dict)
    count_by_op: dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum output-shape bytes of every collective in (optimized) HLO text.

    We count each logical collective once: the async "-start" op is counted,
    the matching "-done" is skipped; synchronous forms are counted directly.
    Output shape is used as the byte proxy (for all-gather it's the gathered
    size, for reduce-scatter the scattered size, both reasonable one-pass
    traffic proxies at the per-device level).
    """
    stats = CollectiveStats()
    for m in _INSTR_RE.finditer(hlo_text):
        shape_txt, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        b = _shape_bytes(shape_txt)
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0) + b
        stats.count_by_op[op] = stats.count_by_op.get(op, 0) + 1
    return stats


def cost_summary(compiled) -> dict:
    """Pull flops/bytes out of ``compiled.cost_analysis()`` (a dict)."""
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "raw_keys": sorted(ca)[:8]}


def memory_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        if hasattr(ma, k):
            out[k] = int(getattr(ma, k))
    out["total_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0)
    )
    return out


def model_flops_per_step(n_params_active: float, tokens: float) -> float:
    """Standard 6·N·D estimate (training). For inference use 2·N·D."""
    return 6.0 * n_params_active * tokens
