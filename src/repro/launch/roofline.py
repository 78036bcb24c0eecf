"""Roofline-term derivation from a compiled dry-run artifact.

Hardware model: TPU v5e-class chip — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s per ICI link.  The three terms (seconds, per step):

  compute    = HLO_FLOPs / (chips x 197e12)
  memory     = HLO_bytes / (chips x 819e9)
  collective = collective_bytes / (chips x 50e9)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
parsed from ``compiled.as_text()`` (post-partitioning HLO) by summing the
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (all-reduce counted twice: ring
reduce-scatter + all-gather phases).  ``cost_analysis`` on a
SPMD-partitioned module reports the per-device program; we therefore
normalise by dividing global quantities consistently (see
``RooflineReport.from_compiled``).
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g. `bf16[16,512,128]{2,1,0}` or `f32[]`
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of every `dtype[dims]` shape found in the string
    (handles tuple shapes: commas inside dims don't confuse findall)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        nbytes = _DTYPE_BYTES.get(dt)
        if nbytes is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * nbytes
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes per collective kind from post-SPMD HLO."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result instruction lines look like:
        #   %all-gather.3 = bf16[2048,512]{1,0} all-gather(...)
        m = re.match(r"%?[\w.\-]+ = \(?([^)]+?)\)? (\S+)\(", s)
        if not m:
            continue
        shapes_str, opname = m.groups()
        kind = None
        for c in _COLLECTIVES:
            if opname == c or opname.startswith(c + "."):
                kind = c
                break
        if kind is None:
            continue
        out[kind] += _shape_bytes(shapes_str)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    peak_memory_per_device: float
    model_flops: float            # 6*N*D (train) or 2*N_active*B (decode)
    collective_breakdown: dict[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline estimate: dominant term (perfect overlap bound)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global) — remat/redundancy waste."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: model-flops time at peak / step time."""
        ideal = self.model_flops / (self.chips * PEAK_FLOPS)
        return ideal / self.step_time_s if self.step_time_s else 0.0

    def row(self) -> str:
        return (f"{self.arch},{self.shape},{self.mesh},"
                f"{self.compute_s:.4e},{self.memory_s:.4e},"
                f"{self.collective_s:.4e},{self.dominant},"
                f"{self.useful_flops_fraction:.3f},"
                f"{self.roofline_fraction:.3f},"
                f"{self.peak_memory_per_device / 2**30:.2f}")

    HEADER = ("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
              "useful_flops_frac,roofline_frac,peak_mem_GiB")


def from_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                  chips: int, model_flops: float) -> RooflineReport:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    coll_bytes = sum(v for k, v in coll.items()) \
        + coll.get("all-reduce", 0)          # AR counted twice (RS+AG)
    ma = compiled.memory_analysis()
    peak = float(getattr(ma, "temp_size_in_bytes", 0)
                 + getattr(ma, "argument_size_in_bytes", 0)
                 + getattr(ma, "output_size_in_bytes", 0)
                 - getattr(ma, "alias_size_in_bytes", 0))
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byt,
        collective_bytes_per_device=coll_bytes,
        peak_memory_per_device=peak, model_flops=model_flops,
        collective_breakdown=coll)


def count_params(params_shape) -> int:
    import jax
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params_shape)
               if hasattr(l, "size"))


def model_flops_train(cfg, n_params: int, tokens: int) -> float:
    """6*N*D with N = active params for MoE."""
    n_active = active_params(cfg, n_params)
    return 6.0 * n_active * tokens


def model_flops_decode(cfg, n_params: int, batch: int) -> float:
    n_active = active_params(cfg, n_params)
    return 2.0 * n_active * batch


def active_params(cfg, n_params: int) -> float:
    if cfg.moe.num_experts <= 0:
        return float(n_params)
    # expert params activate at top_k / num_experts
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    expert_layers = cfg.num_layers // cfg.moe_layer_period
    expert_params = expert_layers * e * 3 * cfg.d_model * cfg.d_ff
    dense = n_params - expert_params
    return dense + expert_params * (k / e)
