"""Three-term roofline from compiled dry-run artifacts.

  compute    = HLO_FLOPs            / (peak_FLOP/s)          [per device]
  memory     = HLO_bytes            / (HBM_bw)               [per device]
  collective = sum over collective ops of ring-model time    [per device]

cost_analysis() is per-device after SPMD partitioning (verified empirically).
Collective bytes are NOT in cost_analysis — we parse the compiled HLO text and
sum operand sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, attributing each to the mesh axis it runs over via its
replica_groups size.
"""
from __future__ import annotations

import math
import re
from dataclasses import asdict, dataclass, field

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (per direction)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string like 'bf16[256,1024]' or a tuple thereof."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveOp:
    kind: str
    bytes: int
    group_size: int


@dataclass
class RooflineTerms:
    flops: float                   # per-device HLO flops
    hbm_bytes: float               # per-device HLO bytes accessed
    collectives: list[CollectiveOp] = field(default_factory=list)
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW

    @property
    def collective_bytes(self) -> int:
        return sum(c.bytes for c in self.collectives)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def t_collective(self) -> float:
        """Ring model per op: all-reduce 2(n-1)/n, ag/rs (n-1)/n, a2a (n-1)/n,
        permute 1 hop.  bytes are the (per-device) operand bytes."""
        t = 0.0
        for c in self.collectives:
            n = max(c.group_size, 1)
            if n == 1:
                continue
            if c.kind == "all-reduce":
                f = 2 * (n - 1) / n
            elif c.kind in ("all-gather", "reduce-scatter", "all-to-all"):
                f = (n - 1) / n
            else:  # collective-permute: single hop
                f = 1.0
            t += f * c.bytes / self.ici_bw
        return t

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    def summary(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "n_collectives": len(self.collectives),
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
        }


def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    """Sum operand sizes of every collective in compiled HLO text."""
    ops: list[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:   # async pair: count the -start only
            continue
        shape_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        if kind == "all-gather":
            # operand (input) bytes are output/group_size; ring cost uses the
            # full gathered bytes — use output shape (what the wire carries).
            pass
        gsize = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            first = gm.group(1).split("}")[0].split("{")[-1]
            gsize = len([x for x in first.split(",") if x.strip() != ""])
        else:
            gm2 = _GROUPS_V2_RE.search(line)
            if gm2:
                gsize = int(gm2.group(2))
        ops.append(CollectiveOp(kind=kind, bytes=nbytes, group_size=gsize))
    return ops


def machine_constants(machine) -> dict:
    """Roofline constants from any machine-model flavor, for RooflineTerms.

    Accepts a ``characterize.FittedMachineModel`` (measured: ``peak_flops``
    / ``hbm_bw`` properties), a ``core.machine_model.HardwareSpec``
    (documented: outermost level ``read_bw`` + ``link_bw``), or a registry
    name string (``core.machine_model.get_spec``).  Constants the model
    does not know (None = undocumented/unmeasured) keep the v5e defaults —
    callers can see which were overridden in the returned dict.
    """
    if machine is None:
        return {}
    if isinstance(machine, str):
        from repro.core.machine_model import get_spec
        machine = get_spec(machine)
    out = {}
    peak = getattr(machine, "peak_flops", None)
    if peak:
        out["peak_flops"] = float(peak)
    hbm = getattr(machine, "hbm_bw", None)      # FittedMachineModel (measured)
    if hbm is None:                             # HardwareSpec (documented)
        levels = getattr(machine, "levels", ())
        if levels:
            hbm = getattr(levels[-1], "read_bw", None)
    if hbm:
        out["hbm_bw"] = float(hbm)
    ici = getattr(machine, "link_bw", None)
    if ici:
        out["ici_bw"] = float(ici)
    return out


def analyze(compiled, model_flops: float | None = None,
            machine=None) -> dict:
    """Full §Roofline record for one compiled (arch x shape x mesh) cell.

    ``machine`` (optional) replaces the static v5e constants with a machine
    model's — pass the ``FittedMachineModel`` that ``repro.characterize``
    measured on this very machine, a documented ``HardwareSpec``, or a spec
    registry name; see ``machine_constants``."""
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    mc = machine_constants(machine)
    terms = RooflineTerms(flops=flops, hbm_bytes=hbm, collectives=colls,
                          **mc)
    mem = compiled.memory_analysis()
    out = {
        **terms.summary(),
        "arg_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_device_bytes": int(mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes),
        "collective_breakdown": _breakdown(colls),
    }
    if model_flops is not None:
        out["model_flops"] = model_flops
        out["useful_flop_ratio"] = model_flops / flops if flops else 0.0
    if machine is not None:
        out["machine_model"] = getattr(machine, "name", str(machine))
        out["machine_constants"] = mc
    return out


def _breakdown(colls: list[CollectiveOp]) -> dict:
    agg: dict[str, dict] = {}
    for c in colls:
        a = agg.setdefault(c.kind, {"count": 0, "bytes": 0})
        a["count"] += 1
        a["bytes"] += c.bytes
    return agg
