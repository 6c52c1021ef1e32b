"""Three-term roofline report from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
    memory term     = HLO_bytes / HBM_bw                 (per chip)
    collective term = collective_traffic / ICI_link_bw   (per chip)

``cost_analysis()`` runs on the post-SPMD per-device module, so its FLOPs /
bytes are already per-chip; dividing by per-chip peaks is equivalent to the
assignment's global/(chips x peak) formulation. Collective traffic comes
from :mod:`repro.roofline.hlo`.

Also reported: MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE, 2·N·D for
inference) and the usefulness ratio MODEL_FLOPS / HLO_FLOPs — remat and
dispatch waste show up here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from repro.configs.base import ArchConfig, InputShape
from repro.models.registry import effective_seq
from repro.roofline.hlo import parse_collectives
from repro.roofline.hw import HW, TPUv5e


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int

    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float

    compute_seconds: float
    memory_seconds: float
    collective_seconds: float
    dominant: str

    model_flops_global: float
    useful_flops_ratio: float        # model flops / compiled flops (global)

    collectives_by_kind: Dict[str, Any]
    has_while: bool

    # memory_analysis fields (bytes, per device)
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0

    lower_seconds: float = 0.0
    compile_seconds: float = 0.0

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @property
    def bound(self) -> str:
        return self.dominant


def model_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """MODEL_FLOPS per step: 6·N·D for training, 2·N·D for inference
    (N = active params, D = tokens processed)."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * effective_seq(cfg, shape)
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * effective_seq(cfg, shape)
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def analyze_compiled(
    compiled,
    *,
    cfg: ArchConfig,
    shape: InputShape,
    mesh_desc: str,
    n_devices: int,
    hw: TPUv5e = HW,
    lower_seconds: float = 0.0,
    compile_seconds: float = 0.0,
) -> RooflineReport:
    ca = compiled.cost_analysis()
    flops_pd = float(ca.get("flops", 0.0))
    bytes_pd = float(ca.get("bytes accessed", 0.0))

    text = compiled.as_text()
    coll = parse_collectives(text, default_group=n_devices)
    coll_pd = float(coll.total_traffic)

    compute_s = flops_pd / hw.peak_flops_bf16
    memory_s = bytes_pd / hw.hbm_bandwidth
    coll_s = coll_pd / hw.ici_link_bandwidth
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    compiled_global = flops_pd * n_devices
    ratio = mf / compiled_global if compiled_global else 0.0

    mem: Dict[str, int] = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0)
            ),
        }
    except Exception:
        pass

    return RooflineReport(
        arch=cfg.arch_id,
        shape=shape.name,
        mesh=mesh_desc,
        n_devices=n_devices,
        flops_per_device=flops_pd,
        hbm_bytes_per_device=bytes_pd,
        collective_bytes_per_device=coll_pd,
        compute_seconds=compute_s,
        memory_seconds=memory_s,
        collective_seconds=coll_s,
        dominant=dominant,
        model_flops_global=mf,
        useful_flops_ratio=ratio,
        collectives_by_kind={k: list(v) for k, v in coll.by_kind().items()},
        has_while=coll.has_while,
        lower_seconds=lower_seconds,
        compile_seconds=compile_seconds,
        **mem,
    )
