"""Roofline derivation from compiled dry-run artifacts (TPU v5e targets).

Three terms per (arch x shape x mesh), all in seconds/step:

  compute    = HLO_FLOPs        / peak_FLOPs_per_chip     (197e12 bf16)
  memory     = HLO_bytes        / HBM_bw_per_chip         (819e9)
  collective = wire_bytes       / ICI_link_bw_per_chip    (50e9)

`cost_analysis()` of a GSPMD-partitioned module reports *per-device*
FLOPs/bytes (verified empirically), so no chip division is needed. Wire
bytes are parsed from the compiled HLO text with ring-collective costing
on local shard shapes:

  all-reduce(N)        -> 2*(k-1)/k * N
  all-gather(N_out)    ->   (k-1)/k * N_out
  reduce-scatter(N_out)->   (k-1)   * N_out      (input = k*N_out)
  all-to-all(N)        ->   (k-1)/k * N
  collective-permute(N)->              N

Scan trip-count correction: XLA's HloCostAnalysis visits a while body ONCE
(measured), so a depth-L scanned layer stack under-reports by ~L. We lower
the same cell at n_blocks=1 and n_blocks=2; per-block cost = C(2) - C(1);
corrected = C(1) + (n_blocks - 1) * per_block. The same differencing
corrects collective bytes inside the body. Residual under-count from
sequence-chunk scans inside a layer (blockwise attention / mamba chunks /
rwkv token scan) is corrected analytically via `inner_scan_flops`.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

# --- TPU v5e constants (per chip) ---
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?P<type>\([^=]*?\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|"
                       r"pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^}]*\}|\[[0-9,]+\]<="
                        r"\[[0-9]+\])")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return 2
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    # iota format [G,k]<=[N]
    dims = g[1:g.index("]")].split(",")
    return int(dims[-1])


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    result_bytes: dict
    wire_bytes: float

    def total(self) -> float:
        return self.wire_bytes


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict = {}
    rbytes: dict = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        if "-done" in line.split("=")[1][:40]:
            continue
        nb = _shape_bytes(m.group("type"))
        k = _group_size(line)
        if k <= 1:
            continue
        if op == "all-reduce":
            w = 2.0 * (k - 1) / k * nb
        elif op == "all-gather":
            w = (k - 1) / k * nb
        elif op == "reduce-scatter":
            w = float(k - 1) * nb
        elif op == "all-to-all":
            w = (k - 1) / k * nb
        else:  # collective-permute
            w = float(nb)
        counts[op] = counts.get(op, 0) + 1
        rbytes[op] = rbytes.get(op, 0.0) + nb
        wire += w
    return CollectiveStats(counts, rbytes, wire)


@dataclasses.dataclass
class CellCost:
    flops: float               # per device
    bytes_accessed: float      # per device
    wire_bytes: float          # per device
    coll_counts: dict
    arg_bytes: float = 0.0
    temp_bytes: float = 0.0
    out_bytes: float = 0.0

    @property
    def device_bytes(self) -> float:
        return self.arg_bytes + self.temp_bytes + self.out_bytes


def cost_from_compiled(compiled) -> CellCost:
    ca = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    ma = compiled.memory_analysis()
    return CellCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=coll.wire_bytes,
        coll_counts=coll.counts,
        arg_bytes=float(ma.argument_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        out_bytes=float(ma.output_size_in_bytes),
    )


def scan_corrected(c1: CellCost, c2: CellCost, n_blocks: int,
                   full: Optional[CellCost] = None) -> CellCost:
    """Trip-count correction via depth differencing.

    c1/c2: costs lowered at n_blocks=1/2. Memory fields come from `full`
    (the real-depth compile) when given."""
    per = CellCost(
        flops=max(c2.flops - c1.flops, 0.0),
        bytes_accessed=max(c2.bytes_accessed - c1.bytes_accessed, 0.0),
        wire_bytes=max(c2.wire_bytes - c1.wire_bytes, 0.0),
        coll_counts={})
    out = CellCost(
        flops=c1.flops + (n_blocks - 1) * per.flops,
        bytes_accessed=c1.bytes_accessed + (n_blocks - 1) * per.bytes_accessed,
        wire_bytes=c1.wire_bytes + (n_blocks - 1) * per.wire_bytes,
        coll_counts=(full or c2).coll_counts,
        arg_bytes=(full or c2).arg_bytes,
        temp_bytes=(full or c2).temp_bytes,
        out_bytes=(full or c2).out_bytes,
    )
    return out


def inner_scan_flops(cfg, shape, n_devices: int) -> float:
    """Analytic per-device FLOPs hidden inside *sequence* scans (counted
    once by HloCostAnalysis): blockwise-attention KV loop, mamba chunk
    loop, rwkv token loop. Returns the missing amount to ADD."""
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    missing = 0.0
    if kind == "decode":
        return 0.0   # decode has no sequence scans (single token)
    toks = float(B) * S
    n_attn = n_mamba = n_rwkv = 0
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
        n_mamba = cfg.n_layers - n_attn
    elif cfg.family == "ssm_rwkv":
        n_rwkv = cfg.n_layers
    elif cfg.n_heads:
        n_attn = cfg.n_layers

    fb = 3.0 if kind == "train" else 1.0   # fwd+bwd vs fwd
    # blockwise attention is a q-block map around a kv-block scan: the HLO
    # counts 1 of (nq * nkv) block pairs
    if n_attn and S > cfg.attn_block_threshold \
            and S % cfg.attn_block_size == 0:
        att = 2.0 * toks * S * cfg.n_heads * cfg.d_head  # causal halved
        nb = S // cfg.attn_block_size
        pairs = nb * nb
        missing += n_attn * att * fb * (pairs - 1) / pairs
    if n_mamba:
        Di = cfg.mamba.expand * cfg.d_model
        N = cfg.mamba.d_state
        ssm = 6.0 * toks * Di * N      # assoc-scan combine ~3 mul-add
        chunks = max(S // cfg.mamba.chunk, 1)
        missing += n_mamba * ssm * fb * (chunks - 1) / chunks
    if n_rwkv:
        D = cfg.d_model
        dh = cfg.rwkv.head_size
        wkv = 3.0 * toks * D * dh      # state update + readout per head
        missing += n_rwkv * wkv * fb * (S - 1) / S
    return missing / n_devices


@dataclasses.dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    step: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    device_gb: float
    coll_counts: dict

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops_global, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the dominant-term-bound step achieves on its
        *useful* model FLOPs: (model_flops / chips / peak) / bound."""
        bound = max(self.compute_s, self.memory_s, self.collective_s)
        return (self.compute_s / max(bound, 1e-30))


def make_row(arch: str, shape_cfg, mesh_name: str, step: str,
             cost: CellCost, model_flops: float, n_devices: int
             ) -> RooflineRow:
    return RooflineRow(
        arch=arch, shape=shape_cfg.name, mesh=mesh_name, step=step,
        compute_s=cost.flops / PEAK_FLOPS,
        memory_s=cost.bytes_accessed / HBM_BW,
        collective_s=cost.wire_bytes / ICI_BW,
        model_flops=model_flops,
        hlo_flops_global=cost.flops * n_devices,
        device_gb=cost.device_bytes / 1e9,
        coll_counts=cost.coll_counts)


@dataclasses.dataclass
class DecodeAttnRow:
    """Analytic roofline for one fused flash-decode attention step.

    Decode attention is HBM-bound at any realistic arena length: the
    kernel streams the whole K and V arena once (the dominant term, grows
    with context) plus the current token's cache write and q/out
    activations, against 2 MACs per streamed element (QK^T + PV) — an
    arithmetic intensity of ~2 flops/byte at bf16 caches, far under the
    v5e ridge (~240). The interesting number is therefore attained HBM
    bandwidth vs the 819 GB/s roof, not FLOP utilization."""
    batch: int
    ctx: float                 # mean valid cache length over the decode
    bytes_hbm: float           # KV read + cache write + q/out, per step
    flops: float               # 2·S·dh·H MACs x 2 GEMMs, per step
    roof_s: float              # best-case step time at the HBM roof

    def attained_gbps(self, measured_s: float) -> float:
        """Achieved HBM bandwidth if the measured step moved only this
        row's bytes — a lower bound on the real attained bandwidth (the
        step also runs its projection GEMMs)."""
        return self.bytes_hbm / max(measured_s, 1e-12) / 1e9

    def frac_of_roof(self, measured_s: float) -> float:
        return self.roof_s / max(measured_s, 1e-12)


def decode_attn_row(batch: int, ctx: float, n_heads: int, n_kv_heads: int,
                    d_head: int, n_layers: int = 1, *,
                    cache_bytes: int = 2, act_bytes: int = 4
                    ) -> DecodeAttnRow:
    """Decode-attention roofline row (per decode step, `n_layers` attention
    sublayers).

    bytes = KV arena read (K and V, `ctx` valid rows per slot) + the
    token's cache write + q/out activations; flops = 2 GEMMs x
    2·ctx·d_head·H MACs per sequence. `ctx` is the mean valid cache
    length across the decode (ragged slots average out); pass the pruned
    `LayerShapes` head counts for sliced subnets — the arena only holds
    surviving kv heads."""
    kv_read = 2.0 * batch * ctx * n_kv_heads * d_head * cache_bytes
    cache_write = 2.0 * batch * n_kv_heads * d_head * cache_bytes
    q_out = 2.0 * batch * n_heads * d_head * act_bytes
    bytes_hbm = n_layers * (kv_read + cache_write + q_out)
    flops = n_layers * 2.0 * 2.0 * batch * ctx * n_heads * d_head
    roof_s = max(bytes_hbm / HBM_BW, flops / PEAK_FLOPS)
    return DecodeAttnRow(batch=batch, ctx=ctx, bytes_hbm=bytes_hbm,
                         flops=flops, roof_s=roof_s)


def model_flops_for(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) + attention term — global."""
    toks = float(shape.global_batch) * shape.seq_len
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        base = 6.0 * n_active * toks
        att_f = 3.0
    elif shape.kind == "prefill":
        base = 2.0 * n_active * toks
        att_f = 1.0
    else:  # decode: one token per sequence
        toks = float(shape.global_batch)
        base = 2.0 * n_active * toks
        att_f = 1.0
    n_attn = cfg.n_layers
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every
    elif cfg.family == "ssm_rwkv":
        n_attn = 0
    if n_attn and cfg.n_heads:
        ctx = shape.seq_len
        if shape.kind == "decode":
            att = 4.0 * toks * ctx * cfg.n_heads * cfg.d_head
        else:
            att = 2.0 * toks * ctx * cfg.n_heads * cfg.d_head  # causal/2
        base += n_attn * att * att_f
    return base
