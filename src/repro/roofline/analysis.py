"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from `compiled.cost_analysis()` (whole-program,
already per-partition under SPMD). collective_bytes is parsed from the
compiled/optimised HLO text: we sum the RESULT sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (result size
== wire payload for all-reduce/permute; for all-gather it upper-bounds the
per-device payload by the gathered size — documented approximation).

Hardware peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``
of the chip the terms describe; a kind missing from the table is an error,
never a default.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    link_bw: float      # bytes/s per inter-chip link
    source: str


# Keyed by ``jax.Device.device_kind``.
PEAKS: dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 ICI links.
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=1600e9 / 8 / 4,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The :data:`PEAKS` entry of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}) — add them, with their source, to "
            f"repro.roofline.analysis.PEAKS") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.5 = f32[8,128]{1,0} all-reduce(...)
#       ROOT %tuple = (bf16[2,4]{1,0}, f32[]) all-to-all(...)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes per collective kind over the HLO module."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # match the opcode (sync or async -start; -done carries no
            # payload of its own and would double count)
            if re.search(rf"\)?\s{kind}(?:-start)?\(", " " + rhs) or \
                    rhs.startswith(f"{kind}("):
                # result type is the prefix before the opcode
                type_part = rhs.split(kind)[0]
                out[kind] += _shape_bytes(type_part)
                break
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device bytes accessed
    coll_bytes: float             # per-device collective payload bytes
    chips: int
    device_kind: str              # the chip kind whose peaks apply
    coll_breakdown: Optional[dict] = None

    def __post_init__(self):
        self.peaks = peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / self.peaks.link_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "device_kind": self.device_kind,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "coll_breakdown": self.coll_breakdown,
        }


def terms_from_compiled(compiled, chips: int,
                        device_kind: str) -> RooflineTerms:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:           # pragma: no cover - backend specific
        hlo = ""
    coll = collective_bytes(hlo)
    return RooflineTerms(flops=flops, hbm_bytes=hbm,
                         coll_bytes=float(sum(coll.values())), chips=chips,
                         device_kind=device_kind, coll_breakdown=coll)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (useful-work reference): 6*N*D train, 2*N*D inference;
# MoE uses N_active.
# ---------------------------------------------------------------------------

def count_params(params_tree, active_expert_fraction: float = 1.0) -> dict:
    """Returns {'total': N, 'active': N_active} from an (abstract) tree.

    Expert leaves (path containing 'experts') count toward 'active' only
    at `active_expert_fraction` = (top_k + n_shared*E_share...) / E.
    """
    import jax

    total = 0
    active = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params_tree):
        n = 1
        for d in leaf.shape:
            n *= d
        parts = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        total += n
        if "experts" in parts and "router" not in parts:
            active += n * active_expert_fraction
        else:
            active += n
    return {"total": int(total), "active": int(active)}


def model_flops(n_params_active: int, tokens: int, kind: str) -> float:
    """6*N*D for training (fwd+bwd), 2*N*D for inference forward."""
    factor = 6.0 if kind == "train" else 2.0
    return factor * n_params_active * tokens
