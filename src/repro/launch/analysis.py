"""Compiled-artifact analysis: cost, memory, collective schedule, roofline.

The container is CPU-only, so the "profile" is the compiled HLO itself:

- ``compiled.cost_analysis()``  -> per-device HLO FLOPs / bytes accessed
- ``compiled.memory_analysis()``-> per-device argument/output/temp/peak bytes
- ``compiled.as_text()``        -> post-SPMD HLO; we parse every collective
  op's *per-device* operand bytes and classify it ICI (in-pod) vs DCN
  (crosses the pod axis, replica stride >= chips-per-pod).

Scan bodies appear once in HLO, so rolled-scan numbers undercount by the
trip count. The dry-run therefore lowers shallow (1- and 2-unit) configs
with all scans unrolled and extrapolates linearly over depth:
``f(U) = f1 + (f2 - f1) * (U - 1)`` — exact for depth-homogeneous stacks
(f1 = fixed + unit, f2 = fixed + 2*unit).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

from repro.launch.mesh import HardwareSpec, V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# one HLO op result, e.g.:  %all-gather.3 = bf16[16,512,128]{...} all-gather(
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s*"
    r"(all-reduce-start|all-gather-start|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute-start|collective-permute)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\}|replica_groups=\[")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_stride(line: str) -> int:
    """Smallest stride between consecutive ranks in the first replica group
    (1 = neighbours on the fastest mesh dim; >= chips/pod = crosses pods)."""
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if not m:
        return 1
    ranks = [int(x) for x in m.group(1).split(",") if x.strip()]
    if len(ranks) < 2:
        return 1
    return min(abs(b - a) for a, b in zip(ranks, ranks[1:]))


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes_per_device: int
    stride: int
    count: int = 1
    f32: bool = False


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Sum per-device operand bytes of every collective in post-SPMD HLO."""
    out: Dict[tuple, CollectiveOp] = {}
    for line in hlo_text.splitlines():
        if not any(k in line for k in _COLL_KINDS):
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        tuple_body, dtype, dims, kind = m.groups()
        kind = kind.replace("-start", "")
        if tuple_body is not None:
            nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tuple_body))
            if kind in ("all-reduce", "collective-permute"):
                nbytes //= 2  # start-op tuples carry (operand, result) aliases
            f32 = "f32[" in tuple_body
        else:
            nbytes = _shape_bytes(dtype, dims)
            f32 = dtype == "f32"
        stride = _group_stride(line)
        key = (kind, nbytes, stride, f32)
        if key in out:
            out[key].count += 1
        else:
            out[key] = CollectiveOp(kind, nbytes, stride, f32=f32)
    return list(out.values())


def collective_bytes(ops: List[CollectiveOp], chips_per_pod: int = 256
                     ) -> Dict[str, float]:
    """Per-device collective bytes, split ICI/DCN.

    ``*_bf16eq`` halves fp32 ops: XLA:CPU upcasts every bf16 dot operand to
    f32 *before* the SPMD collectives (the model's large tensors are all
    bf16), so raw f32 collective bytes are ~2x what the TPU build moves.
    Genuinely-f32 reductions (scalars, norms stats) are negligible at these
    sizes. Raw numbers are kept alongside.
    """
    ici = dcn = ici_eq = dcn_eq = 0.0
    by_kind: Dict[str, float] = {}
    for op in ops:
        b = op.bytes_per_device * op.count
        beq = b * (0.5 if op.f32 else 1.0)
        by_kind[op.kind] = by_kind.get(op.kind, 0) + b
        if op.stride >= chips_per_pod:
            dcn += b
            dcn_eq += beq
        else:
            ici += b
            ici_eq += beq
    return {"ici": float(ici), "dcn": float(dcn), "by_kind": by_kind,
            "ici_bf16eq": float(ici_eq), "dcn_bf16eq": float(dcn_eq),
            "total": float(ici + dcn)}


_CONVERT_RE = re.compile(
    r"^\s*(?:ROOT )?%(wrapped_convert[\w.]*|convert[\w.]*) = (\w+)\[([\d,]*)\]"
    r"[^ ]* (?:fusion|convert)\(")


def cpu_upcast_bytes(hlo_text: str, min_bytes: int = 1 << 26) -> int:
    """XLA:CPU has no native bf16 dot — it converts operands to f32 and
    hoists the converted weight/KV-cache copies out of the layer loop. A TPU
    build keeps them bf16, so these buffers are pure CPU-backend overhead in
    the memory analysis. Sums large f32 convert results (deduped by name;
    fusion-ROOT converts are excluded — their buffer is the fusion op's)."""
    seen = set()
    total = 0
    for line in hlo_text.splitlines():
        m = _CONVERT_RE.match(line)
        if not m:
            continue
        name, dtype, dims = m.groups()
        if dtype != "f32" or name in seen:
            continue
        if line.lstrip().startswith("ROOT %convert"):
            continue  # fusion-internal ROOT: buffer owned by the fusion op
        b = _shape_bytes(dtype, dims)
        if b >= min_bytes:
            seen.add(name)
            total += b
    return total


# -------------------------------------------------------------- extraction
def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def memory_summary(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    get = lambda k: float(getattr(ma, k, 0) or 0)
    return {
        "argument_bytes": get("argument_size_in_bytes"),
        "output_bytes": get("output_size_in_bytes"),
        "temp_bytes": get("temp_size_in_bytes"),
        "generated_code_bytes": get("generated_code_size_in_bytes"),
        "alias_bytes": get("alias_size_in_bytes"),
    }


def extrapolate(f1: float, f2: float, units: int) -> float:
    """fixed + unit*U given samples at U=1 and U=2 (exact for linear)."""
    unit = f2 - f1
    fixed = f1 - unit
    return fixed + unit * units


# ---------------------------------------------------- analytic HBM model
def analytic_memory_bytes(cfg, shape, mesh_shape: Dict[str, int],
                          accum: int, kind: str, params_bytes: int,
                          cache_bytes_dev: float = 0.0,
                          remat: bool = True) -> float:
    """Per-device HBM traffic per step under TPU-like fusion (the CPU
    backend's `bytes accessed` is an unfusable upper bound — see
    EXPERIMENTS.md §Dry-run). Terms:

    - weights: FSDP re-gathers each layer per microbatch; every device
      reads the model-axis shard of the FULL weight set per pass
      (fwd + bwd + remat-recompute for train; once for prefill; the
      resident TP shard once per token for decode),
    - optimizer: m/v fp32 read+write, param read+write, grad read (train),
    - activations: K boundary tensors of (tokens_dev x d_model) x 2B per
      layer per pass (K~14 covers q/kv/mlp partials at their sharded
      widths, norms, residual r/w),
    - KV cache: decode reads the full per-device cache + writes one slot
      (masked-update writes the cache once more: 2x read-equivalent).
    """
    model_n = mesh_shape.get("model", 1)
    chips = 1
    for v in mesh_shape.values():
        chips *= v
    dp_n = chips // model_n

    L = cfg.num_layers
    d = cfg.d_model
    tokens = shape.global_batch * shape.seq_len

    if kind == "decode":
        w = params_bytes / model_n            # TP-resident, read once/token
        acts = 24 * L * (shape.global_batch / max(1, dp_n)) * d * 2
        return w + 2 * cache_bytes_dev + acts
    passes = (3 if remat else 2) if kind == "train" else 1
    w_gathered = params_bytes / model_n       # per device after FSDP gather
    weights = passes * accum * w_gathered
    if kind == "train":
        weights += 24 * params_bytes / 2 / chips  # opt: 24B/param, sharded
    tokens_dev = tokens / max(1, dp_n)
    acts = passes * 14 * L * tokens_dev * d * 2
    return weights + acts + cache_bytes_dev


# -------------------------------------------------------------- roofline
@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dcn_s: float
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    model_flops: float          # 6*N*D (active) — "useful" FLOPs, global
    chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s + self.dcn_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s + self.dcn_s)

    @property
    def mfu(self) -> float:
        """MODEL_FLOPS / (chips * peak * step_time) — roofline fraction."""
        denom = self.chips * V5E.peak_flops_bf16 * max(self.step_time_s, 1e-12)
        return self.model_flops / denom

    @property
    def useful_frac(self) -> float:
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / max(hlo_global, 1.0)


def roofline(flops_dev: float, bytes_dev: float, coll: Dict[str, float],
             model_flops: float, chips: int, hw: HardwareSpec = V5E) -> Roofline:
    return Roofline(
        compute_s=flops_dev / hw.peak_flops_bf16,
        memory_s=bytes_dev / hw.hbm_bw,
        collective_s=coll.get("ici", 0.0) / hw.ici_bw,
        dcn_s=coll.get("dcn", 0.0) / hw.dcn_bw,
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        coll_bytes_per_device=coll.get("total", 0.0),
        model_flops=model_flops,
        chips=chips,
    )
