"""Roofline analysis from compiled (SPMD-partitioned, per-device) HLO.

XLA's ``cost_analysis`` counts while-loop bodies ONCE (trip counts are
ignored), which under-counts scan-over-layers models by ~L x.  This module
does structural accounting instead:

  1. parse the HLO text into computations,
  2. find ``while`` ops and their ``known_trip_count``,
  3. walk the call graph multiplying each computation's cost by the product
     of enclosing trip counts,
  4. count FLOPs from ``dot``/``convolution`` ops (2 * prod(out) * K),
     HBM traffic as operands+outputs of surviving (unfused) instructions,
     and collective bytes per kind.

Fusion bodies are costed at their call site (operands + output only -- the
internal traffic stays on-chip), which matches how TPUs see memory.

Terms (per chip, seconds), v5e constants from launch.mesh:
    T_compute    = flops / 197e12
    T_memory     = hbm_bytes / 819e9
    T_collective = wire_bytes / 50e9      (all-reduce counts 2x)
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

from repro.launch import mesh as mesh_lib

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "reshape",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\-\.]+)")
_INSTR = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w\-\.]+)\s*=\s*(\(?)([a-z0-9]+)?(?:\[([\d,]*)\])?[^=]*?\s([a-z][a-z0-9\-]*)\("
)
_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims_str: str) -> int:
    n = _DTYPE_BYTES.get(dtype, 4)
    for d in dims_str.split(","):
        if d:
            n *= int(d)
    return n


def _prod(dims_str: str) -> int:
    out = 1
    for d in dims_str.split(","):
        if d:
            out *= int(d)
    return out


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: dict | None = None
    while_calls: list | None = None     # (body, cond, trips)
    calls: list | None = None           # (comp_name, kind)  kind: fusion|call|cond


def _parse_operand_shapes(line: str, shapes: dict[str, tuple[str, str]]):
    """Operand names from the first parenthesized group after the opcode."""
    m = re.search(r"[a-z][a-z0-9\-]*\(([^)]*)\)", line)
    if not m:
        return []
    ops = []
    for tok in m.group(1).split(","):
        tok = tok.strip().lstrip("%")
        if tok in shapes:
            ops.append(shapes[tok])
    return ops


def parse_hlo(text: str) -> tuple[dict[str, CompCost], str | None]:
    comps: dict[str, CompCost] = {}
    cur: str | None = None
    entry: str | None = None
    shapes: dict[str, tuple[str, str]] = {}
    cost: CompCost | None = None

    comment_re = re.compile(r"/\*.*?\*/")
    for line in text.splitlines():
        line = comment_re.sub("", line)
        stripped = line.rstrip()
        if (
            not line.startswith(" ")
            and stripped.endswith("{")
            and ("->" in line or stripped.startswith(("ENTRY", "%")))
            and not stripped.startswith("HloModule")
        ):
            m = _COMP_HEADER.match(stripped)
            if m:
                cur = m.group(2)
                if m.group(1):
                    entry = cur
                cost = comps.setdefault(cur, CompCost(collectives={}, while_calls=[], calls=[]))
                shapes = {}
                continue
        if cur is None or cost is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        im = _INSTR.match(line)
        if not im:
            continue
        name, tuple_open, dtype, dims, opcode = im.groups()
        is_tuple = tuple_open == "("
        if not is_tuple and dtype is not None and dims is not None:
            shapes[name] = (dtype, dims)
        out_bytes = 0 if is_tuple or dtype is None else _shape_bytes(dtype, dims or "")

        if opcode in _FREE_OPS:
            continue

        if opcode == "while":
            bm = re.search(r"body=%?([\w\-\.]+)", line)
            cm = re.search(r"condition=%?([\w\-\.]+)", line)
            tm = re.search(r'known_trip_count[^\d]*(\d+)', line)
            trips = int(tm.group(1)) if tm else 1
            if bm:
                cost.while_calls.append((bm.group(1), cm.group(1) if cm else None, trips))
            continue

        if opcode == "conditional":
            for br in re.findall(r"branch_computations=\{([^}]*)\}", line):
                for c in br.split(","):
                    cost.calls.append((c.strip().lstrip("%"), "cond"))
            for c in re.findall(r"(?:true_computation|false_computation)=%?([\w\-\.]+)", line):
                cost.calls.append((c, "cond"))
            continue

        if opcode in ("fusion", "call", "custom-call"):
            operands = _parse_operand_shapes(line, shapes)
            cost.bytes += out_bytes + sum(_shape_bytes(d, s) for d, s in operands)
            fm = re.search(r"(?:calls|to_apply)=%?([\w\-\.]+)", line)
            if fm:
                cost.calls.append((fm.group(1), "fusion"))
            continue

        if opcode in _COLLECTIVES:
            bucket = cost.collectives.setdefault(opcode, {"count": 0, "bytes": 0.0})
            bucket["count"] += 1
            bucket["bytes"] += out_bytes
            cost.bytes += out_bytes  # collectives also touch HBM
            continue

        if opcode in ("dot", "convolution"):
            operands = _parse_operand_shapes(line, shapes)
            k = 1
            cm2 = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
            if cm2 and operands:
                lhs_dims = operands[0][1].split(",")
                for ci in cm2.group(1).split(","):
                    if ci:
                        k *= int(lhs_dims[int(ci)])
            cost.flops += 2.0 * _prod(dims or "") * k
            cost.bytes += out_bytes + sum(_shape_bytes(d, s) for d, s in operands)
            continue

        # remaining real ops (copy, slice, dus, reduce, transpose, ...)
        operands = _parse_operand_shapes(line, shapes)
        cost.bytes += out_bytes + sum(_shape_bytes(d, s) for d, s in operands)
        fm = re.search(r"(?:calls|to_apply)=%?([\w\-\.]+)", line)
        if fm:
            cost.calls.append((fm.group(1), "fusion"))

    return comps, entry


def _fusion_flops(comps: dict[str, CompCost]) -> None:
    """Dots fused into fusion bodies: attribute their flops to the call
    site (bytes stay call-site-only)."""
    # comps for fusion bodies already have .flops from their dot lines; the
    # multiplier walk handles attribution -- nothing to do here.  Kept for
    # clarity.
    return


def aggregate(comps: dict[str, CompCost], entry: str | None = None) -> dict[str, Any]:
    """Walk the call graph from the entry computation applying trip-count
    multipliers.  Fusion bodies contribute FLOPs (their dots) but not bytes
    (on-chip traffic)."""
    if entry is None:
        # heuristically: computation that is not referenced by anyone
        referenced = set()
        for c in comps.values():
            referenced.update(b for b, _, _ in c.while_calls)
            referenced.update(cc for cc, _ in c.calls)
        candidates = [n for n in comps if n not in referenced and n.startswith("main")]
        entry = candidates[0] if candidates else next(
            (n for n in comps if n not in referenced), next(iter(comps))
        )

    total = {"flops": 0.0, "bytes": 0.0, "collectives": {}}

    def visit(name: str, mult: float, in_fusion: bool) -> None:
        c = comps.get(name)
        if c is None:
            return
        total["flops"] += c.flops * mult
        if not in_fusion:
            total["bytes"] += c.bytes * mult
            for kind, b in (c.collectives or {}).items():
                bucket = total["collectives"].setdefault(kind, {"count": 0.0, "bytes": 0.0})
                bucket["count"] += b["count"] * mult
                bucket["bytes"] += b["bytes"] * mult
        for body, cond, trips in c.while_calls or []:
            visit(body, mult * trips, in_fusion)
            if cond:
                visit(cond, mult * trips, in_fusion)
        for callee, kind in c.calls or []:
            visit(callee, mult, in_fusion or kind == "fusion")

    visit(entry, 1.0, False)
    return total


def analyze_hlo(text: str) -> dict[str, Any]:
    comps, entry = parse_hlo(text)
    return aggregate(comps, entry)


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS per (arch x shape)
# ---------------------------------------------------------------------------

def model_flops(cfg, cell) -> float:
    """Global useful FLOPs for one step: 6*N*D for train (4x with remat
    excluded -- this is the *useful* count), 2*N*D for fwd-only, plus exact
    attention terms.  MoE uses active params."""
    from repro.models.api import model_specs
    from repro.models.common import param_count
    import jax

    specs = model_specs(cfg)
    total = param_count(specs)
    embed_rows = cfg.vocab_size * cfg.d_model
    if cfg.family == "encoder":
        matmul_params = total
    elif cfg.tie_embeddings:
        matmul_params = total          # single table, used in the unembed matmul
    else:
        matmul_params = total - embed_rows  # input gather is FLOP-free

    if cfg.family == "moe":
        per_expert = 3 * cfg.d_model * cfg.d_ff
        inactive = cfg.num_layers * (cfg.num_experts - cfg.num_experts_per_token) * per_expert
        matmul_params -= inactive

    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        tokens = B * S
        mult = 6.0
    elif cell.kind == "prefill":
        tokens = B * S
        mult = 2.0
    else:  # decode: one token per sequence
        tokens = B
        mult = 2.0

    flops = mult * matmul_params * tokens

    # attention score/value matmuls (full-attention families)
    Dh = cfg.resolved_head_dim
    H = cfg.num_heads
    if cfg.family in ("dense", "moe", "encoder"):
        L_attn = cfg.num_layers
    elif cfg.family == "hybrid":
        L_attn = math.ceil(cfg.num_layers / max(cfg.attn_every, 1))
    else:
        L_attn = 0
    if L_attn:
        if cell.kind == "decode":
            # one new token attends over the full cache: QK^T + PV
            flops += 4.0 * B * H * Dh * S * L_attn
        else:
            causal = 0.5 if cfg.causal else 1.0
            fwd_attn = 4.0 * B * H * Dh * S * S * causal * L_attn
            flops += fwd_attn * (3.0 if cell.kind == "train" else 1.0)

    # SSM/linear-attention state math (mamba2 / rwkv6)
    if cfg.family == "hybrid":
        mcfg = cfg.mamba_config()
        per_tok = 3 * 2 * mcfg.d_inner * mcfg.d_state  # h update + y readout
        flops += mult / 2.0 * per_tok * (B * S if cell.kind != "decode" else B) * cfg.num_layers
    if cfg.family == "rwkv":
        C = cfg.rwkv_head_dim
        per_tok = 3 * 2 * cfg.d_model * C
        flops += mult / 2.0 * per_tok * (B * S if cell.kind != "decode" else B) * cfg.num_layers

    return flops


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

_WIRE_FACTOR = {
    "all-reduce": 2.0,       # reduce-scatter + all-gather equivalent
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def roofline_terms(analysis: dict, *, chips: int) -> dict:
    """Per-chip seconds for each roofline term.  ``analysis`` comes from the
    per-device (partitioned) module, so flops/bytes are already per chip."""
    peak = mesh_lib.peaks(mesh_lib.TARGET_DEVICE_KIND)
    t_compute = analysis["flops"] / peak["flops_bf16"]
    t_memory = analysis["bytes"] / peak["hbm_bw"]
    wire = 0.0
    for kind, b in analysis.get("collectives", {}).items():
        wire += b["bytes"] * _WIRE_FACTOR.get(kind, 1.0)
    t_coll = wire / peak["ici_link_bw"]
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "wire_bytes": wire,
    }


def summarize_cell(result: dict, cfg, cell) -> dict:
    chips = 512 if result.get("multi_pod") else 256
    analysis = result["analysis"]
    terms = roofline_terms(analysis, chips=chips)
    mf = model_flops(cfg, cell)
    hlo_flops_global = analysis["flops"] * chips
    terms.update(
        model_flops_global=mf,
        hlo_flops_global=hlo_flops_global,
        useful_ratio=(mf / hlo_flops_global) if hlo_flops_global else float("nan"),
        # roofline fraction: useful compute time / total modeled time
        step_time_s=max(terms["t_compute_s"], terms["t_memory_s"], terms["t_collective_s"]),
    )
    terms["roofline_fraction"] = (
        (mf / chips / mesh_lib.peaks(mesh_lib.TARGET_DEVICE_KIND)["flops_bf16"])
        / terms["step_time_s"]
        if terms["step_time_s"] > 0
        else float("nan")
    )
    return terms
