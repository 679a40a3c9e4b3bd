"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch × shape × mesh) cell:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs          [s]
    memory term     = HLO_bytes_per_device / HBM_bw              [s]
    collective term = collective_bytes_per_device / link_bw      [s]

``compiled.cost_analysis()`` runs on the SPMD-*partitioned* module, so its
flops/bytes are per-device (verified in tests/test_roofline.py) and include
padding waste from uneven head sharding — which is exactly what we want to
report honestly; the MODEL_FLOPS/HLO_FLOPs ratio exposes it.

collective_bytes is not in cost_analysis: we parse the optimized HLO text and
sum per-op traffic with standard ring estimates:
    all-gather:          result_bytes               (each device receives ~N-1/N)
    reduce-scatter:      operand_bytes ~ result*G   (sends ~N-1/N of input)
    all-reduce:          2 * result_bytes           (RS + AG phases)
    all-to-all:          result_bytes
    collective-permute:  result_bytes
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

# TPU v5e hardware constants (protocol-fixed)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s
LINK_BW = 50e9  # bytes/s/link (ICI)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.MULTILINE,
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _replica_group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)  # iota form [G,n]
    if m:
        return int(m.group(2))
    return 2


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum estimated per-device wire bytes per collective kind."""
    out: Dict[str, float] = {}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if "-done" in line.split("=")[1][:60]:
            continue  # async pair: count the -start only
        nbytes = _shape_bytes(shape_str)
        if op == "all-reduce":
            traffic = 2.0 * nbytes
        elif op == "reduce-scatter":
            traffic = nbytes * _replica_group_size(line)
        else:
            traffic = float(nbytes)
        out[op] = out.get(op, 0.0) + traffic
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # per device
    hlo_bytes: float  # per device
    coll_bytes: float  # per device
    model_flops: float  # 6*N*D (global, per step)
    bytes_per_device: Optional[float] = None  # peak HBM from memory_analysis

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline (no-overlap upper... lower bound): max of the three."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips): remat/padding/dispatch waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline step time."""
        denom = self.step_time_s * self.chips * PEAK_FLOPS
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        for k in ("compute_s", "memory_s", "collective_s", "bottleneck",
                  "useful_flops_frac", "mfu", "step_time_s"):
            d[k] = getattr(self, k)
        return d


def model_flops_for(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) per step; decode: D = global_batch
    new tokens; train adds nothing (the 6x already covers fwd+bwd); prefill
    uses the 2·N·D forward-only factor."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def from_dryrun_json(path: str) -> Roofline:
    with open(path) as f:
        d = json.load(f)
    return Roofline(
        arch=d["arch"], shape=d["shape"], mesh=d["mesh"], chips=d["chips"],
        hlo_flops=d["flops"], hlo_bytes=d["bytes_accessed"],
        coll_bytes=d["collectives"]["total"], model_flops=d["model_flops"],
        bytes_per_device=d.get("memory", {}).get("argument_size_in_bytes"),
    )
