"""Compiled-HLO analysis: roofline terms from the dry-run artifact.

Sources:
  * ``compiled.cost_analysis()``  -> HLO FLOPs + bytes accessed (per-device
    program after SPMD partitioning).
  * ``compiled.as_text()``        -> post-partitioning HLO; we sum the
    *bytes-on-wire per chip* of every collective (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute), applying standard
    bidirectional-ring factors per op kind and the replica-group size parsed
    from the op.

Chip peaks live in ``CHIPS``, keyed by ``jax.Device.device_kind``.  The
planner and cost model plan for ``PLANNING_TARGET`` (a v5e chip); the
module-level constants below are that row.  ``chip(device)`` resolves the
row for the device a program will run on: a CPU plans for the target, and a
TPU whose kind has no row is an error, never a silent default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Chip:
    peak_flops: float        # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    ici_bw: float            # chip-to-chip bytes/s per link
    vmem_bytes: int          # on-core vector memory
    source: str


CHIPS = {
    # 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI per chip (taken as
    # ~50 GB/s per link of the 2-D torus), 128 MiB VMEM per core
    "TPU v5 lite": Chip(197e12, 819e9, 50e9, 128 * 2 ** 20,
                        "Google Cloud documentation, 'TPU v5e'"),
}
PLANNING_TARGET = "TPU v5 lite"


def chip(device=None) -> Chip:
    """Peaks of ``device`` (default: the first JAX device).  CPU devices
    plan for ``PLANNING_TARGET``; any other kind must have a row."""
    import jax
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return CHIPS[PLANNING_TARGET]
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak row for device kind {device.device_kind!r}; add it to "
            "hlo_analysis.CHIPS with its source") from None


_TARGET = CHIPS[PLANNING_TARGET]
PEAK_FLOPS = _TARGET.peak_flops
HBM_BW = _TARGET.hbm_bw
ICI_BW = _TARGET.ici_bw
VMEM_BYTES = _TARGET.vmem_bytes
RIDGE = PEAK_FLOPS / HBM_BW  # ~240 flop/byte

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "tuple": 0, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all shapes in an HLO result-type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        g = m.group(1).strip()
        return len(g.split(",")) if g else default
    return default


@dataclasses.dataclass
class CollectiveStats:
    bytes_on_wire: float = 0.0          # per chip
    by_kind: dict = dataclasses.field(default_factory=dict)
    count: int = 0

    def add(self, kind: str, b: float):
        self.bytes_on_wire += b
        self.by_kind[kind] = self.by_kind.get(kind, 0.0) + b
        self.count += 1


def collective_bytes(hlo_text: str, n_devices: int) -> CollectiveStats:
    """Per-chip bytes-on-wire summed over all collectives in the module.

    Ring factors (n = replica-group size):
      all-gather        out_bytes * (n-1)/n      (each chip receives the rest)
      reduce-scatter    in_bytes  * (n-1)/n
      all-reduce        2 * size  * (n-1)/n      (RS + AG)
      all-to-all        size      * (n-1)/n
      collective-permute  size                    (send + recv one hop)
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        # "%name = TYPE[dims] op-name(...)" — the register name may itself
        # contain the op name, so split on ' = ' first.
        if " = " not in s:
            continue
        rhs = s.split(" = ", 1)[1]
        kind = None
        for k in COLLECTIVE_KINDS:
            i = rhs.find(f" {k}(")
            j = rhs.find(f" {k}-start(")
            if i >= 0 or j >= 0:
                kind = k
                rhs_shape = rhs[: i if i >= 0 else j]
                break
        if kind is None:
            continue
        size = _shape_bytes(rhs_shape)
        if size == 0:
            continue
        n = _group_size(s, n_devices)
        if n <= 1:
            continue
        frac = (n - 1) / n
        if kind == "all-reduce":
            b = 2.0 * size * frac
        elif kind == "collective-permute":
            b = float(size)
        else:
            b = size * frac
        stats.add(kind, b)
    return stats


def _collectives(hlo_text: str):
    """(kind, operand types, result types) of each collective
    (``COLLECTIVE_KINDS``) in a compiled module, in every computation: a
    loop body once, and an asynchronous ``-start`` / ``-done`` pair
    once."""
    types, found = {}, []
    for line in hlo_text.splitlines():
        name, eq, rhs = line.strip().removeprefix("ROOT ").partition(" = ")
        op = re.search(r" ([a-z][\w\-]*)\(", rhs) if eq else None
        if not op:
            continue
        res = set(re.findall(r"(\w+)\[", rhs[:op.start()]))
        types[name.lstrip("%")] = res
        kind = op.group(1).removesuffix("-start")
        if kind in COLLECTIVE_KINDS:
            args = rhs[op.end():].split(")")[0]
            found.append((kind, re.findall(r"%([\w.\-]+)", args), res))
    for kind, args, res in found:
        yield kind, set().union(*(types.get(a, ()) for a in args)), res


def collective_counts(hlo_text: str) -> dict:
    """How many collectives of each kind a compiled module holds."""
    counts = dict.fromkeys(COLLECTIVE_KINDS, 0)
    for kind, _, _ in _collectives(hlo_text):
        counts[kind] += 1
    return counts


def collective_dtypes(hlo_text: str) -> list:
    """``"<operand types>-><result types>"`` of the collectives of a
    compiled module, each side's element types sorted and joined by
    ``,``: ``["f32->bf16"]`` where every collective reads fp32 and writes
    bf16."""
    return sorted({",".join(sorted(ins)) + "->" + ",".join(sorted(out))
                   for _, ins, out in _collectives(hlo_text)})


@dataclasses.dataclass
class Roofline:
    flops: float                 # per chip
    bytes_hbm: float             # per chip
    coll_bytes: float            # per chip, on-wire
    n_devices: int
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    coll_by_kind: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.t_compute = self.flops / PEAK_FLOPS
        self.t_memory = self.bytes_hbm / HBM_BW
        self.t_collective = self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Best-case step time assuming perfect overlap of the three engines."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def summary(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_hbm,
            "coll_bytes_per_chip": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "t_bound_s": self.t_bound,
            "coll_by_kind": self.coll_by_kind,
        }


def analyze_compiled(compiled, n_devices: int) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    stats = collective_bytes(compiled.as_text(), n_devices)
    r = Roofline(flops=flops, bytes_hbm=byts, coll_bytes=stats.bytes_on_wire,
                 n_devices=n_devices)
    r.coll_by_kind = stats.by_kind
    return r


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                      # CPU backend may not support
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_bytes"] = (out.get("argument_size_in_bytes", 0)
                              + out.get("output_size_in_bytes", 0)
                              + out.get("temp_size_in_bytes", 0)
                              - out.get("alias_size_in_bytes", 0))
    return out
