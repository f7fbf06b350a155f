"""Roofline extraction from compiled dry-run artifacts (TPU v5e targets).

Per (arch x shape x mesh) cell:
    compute    = HLO_FLOPs_per_device   / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device   / HBM_bandwidth_per_chip
    collective = collective_bytes_per_device / ICI_link_bandwidth

`cost_analysis()` on the SPMD-partitioned program reports PER-DEVICE flops
and bytes, so dividing by per-chip peaks gives the per-step time bound each
resource imposes; the slowest is the bottleneck. collective bytes are NOT
in cost_analysis: they are parsed from the optimized HLO text by summing
operand bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (async '-start' variants counted once, '-done' skipped).

Caveats (documented, consistent across cells, so deltas are meaningful):
  * cost_analysis "bytes accessed" counts every HLO op's operands+outputs —
    an upper bound on HBM traffic that ignores fusion-internal reuse. XLA's
    CPU backend applies the same counting rules to every cell.
  * link bandwidth is one 50 GB/s ICI link (`repro.obs.peaks`); real v5e
    tori overlap multiple links/directions, so collective terms are
    conservative.
"""

from __future__ import annotations

import re
from typing import NamedTuple

from repro.obs.peaks import V5E, peaks_for

# the dry-run prices a TPU v5e chip, at its published peaks


def peak_flops_for(compute_dtype: str | None) -> float:
    """MXU peak for the cell's matmul operand dtype: the bf16 peak for the
    KernelOperator mixed-precision path ("bfloat16"), the HIGHEST-precision
    float32 peak (`DevicePeaks.fp32_flops`, 6 bf16 passes) for fp32
    operands, the exact GP default.

    Known coarseness: one dtype is charged for the WHOLE cell. A bf16
    gp_train cell's MLL backward is pinned to fp32 (see mll._mll_bwd), so
    its ~10-12% backward flop share (EXPERIMENTS.md §Roofline) is
    over-credited 6x: t_compute reads up to ~40% low (0.88 + 0.12 bf16
    units charged against 0.88 + 0.72 taken), consistent across cells."""
    peaks = peaks_for(V5E)
    if compute_dtype in (None, "fp32", "float32", "f32"):
        return peaks.fp32_flops
    return peaks.bf16_flops

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# shapes like f32[128,256]{1,0} or bf16[8,128] (layout optional)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# replica_groups=[num_groups,group_size]<=[...]  (iota form)
_RG_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
# replica_groups={{0,1,2},{3,4,5}}  (explicit form)
_RG_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _RG_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _RG_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective OPERAND bytes, parsed from optimized HLO.

    Post-optimization HLO prints operands as bare %names, so operand sizes
    are derived from the RESULT shape (printed after '=') and the op
    semantics: all-gather result = operand x group_size; reduce-scatter
    result = operand / group_size; the rest are size-preserving. Async
    '-start' ops are counted once; '-done' is skipped.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    wire = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        for kind in _COLLECTIVES:
            if (f" {kind}(" not in s) and (f" {kind}-start(" not in s):
                continue
            eq = s.find("= ")
            if eq < 0:
                continue
            m = _SHAPE_RE.search(s, eq)
            if not m:
                continue
            result_bytes = _shape_bytes(m.group(1), m.group(2))
            gs = max(_group_size(s), 1)
            if kind == "all-gather":
                operand_bytes = result_bytes // gs
                w = result_bytes * (gs - 1) / gs        # ring: recv ~result
            elif kind == "reduce-scatter":
                operand_bytes = result_bytes * gs
                w = result_bytes * (gs - 1)             # ring: send input once
            elif kind == "all-reduce":
                operand_bytes = result_bytes
                w = 2.0 * result_bytes * (gs - 1) / gs  # RS + AG phases
            elif kind == "all-to-all":
                operand_bytes = result_bytes
                w = result_bytes * (gs - 1) / gs
            else:  # collective-permute
                operand_bytes = result_bytes
                w = result_bytes
            out[kind] += operand_bytes
            counts[kind] += 1
            wire += w
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["wire"] = int(wire)   # ring-model per-device link traffic
    out["counts"] = counts
    return out


class Roofline(NamedTuple):
    flops: float               # per-device HLO flops
    bytes_accessed: float      # per-device HLO bytes
    coll_bytes: float          # per-device collective operand bytes
    wire_bytes: float          # ring-model per-device link traffic
    t_compute: float
    t_memory: float
    t_collective: float        # operand-bytes basis (assignment-prescribed)
    t_collective_wire: float   # ring-model basis (realistic)
    bottleneck: str
    model_flops: float         # "useful" flops per device (6ND / 2ND etc.)
    useful_ratio: float        # model_flops / HLO flops


def analyze(cost: dict, coll: dict, model_flops_global: float,
            n_devices: int, compute_dtype: str = "bf16") -> Roofline:
    flops = float(cost.get("flops", 0.0) or 0.0)
    byts = float(cost.get("bytes accessed", 0.0) or 0.0)
    cb = float(coll["total"])
    wb = float(coll.get("wire", cb))
    peaks = peaks_for(V5E)
    t_c = flops / peak_flops_for(compute_dtype)
    t_m = byts / peaks.hbm_bytes_per_s
    t_x = cb / peaks.ici_link_bytes_per_s
    t_w = wb / peaks.ici_link_bytes_per_s
    terms = {"compute": t_c, "memory": t_m, "collective": t_w}
    bott = max(terms, key=terms.get)
    mf = model_flops_global / max(n_devices, 1)
    return Roofline(flops=flops, bytes_accessed=byts, coll_bytes=cb,
                    wire_bytes=wb, t_compute=t_c, t_memory=t_m,
                    t_collective=t_x, t_collective_wire=t_w,
                    bottleneck=bott, model_flops=mf,
                    useful_ratio=(mf / flops if flops else 0.0))


def _lm_mixer_flops_fwd(cfg, batch: int, seq: int, *, decode_ctx=None) -> float:
    """Forward FLOPs of the sequence mixers (attention scores+values, SSD) —
    the context-dependent compute 6ND misses. Causal halves the S^2 term;
    sliding-window layers use min(S, W) context."""
    total = 0.0
    if cfg.n_heads:
        per_q_ctx = []
        for layer in range(cfg.n_layers):
            win = cfg.sliding_window
            if win and layer not in cfg.global_layers:
                ctx = min(seq, win) if decode_ctx is None else min(decode_ctx, win)
            else:
                ctx = (seq / 2.0) if decode_ctx is None else decode_ctx
            per_q_ctx.append(ctx)
        q_len = 1 if decode_ctx is not None else seq
        # QK^T + PV: 2 matmuls x 2 flops = 4 * B * q * ctx * hd * H
        total += sum(4.0 * batch * q_len * ctx * cfg.hd * cfg.n_heads
                     for ctx in per_q_ctx)
        if cfg.is_encdec:
            # decoder cross-attention (q tokens vs S_enc keys)
            q = 1 if decode_ctx is not None else seq
            total += cfg.n_layers * 4.0 * batch * q * seq * cfg.hd * cfg.n_heads
            # encoder self-attn (full, non-causal) runs in train/prefill only
            if decode_ctx is None:
                total += (cfg.n_enc_layers * 4.0 * batch * seq * seq *
                          cfg.hd * cfg.n_heads)
    if cfg.ssm_state:
        s_len = 1 if decode_ctx is not None else seq
        q, n_st, hp = cfg.ssm_chunk, cfg.ssm_state, cfg.ssm_heads * cfg.ssm_head_dim
        # intra-chunk (Gm + masked-decay PV) + state build/apply per token
        total += cfg.n_layers * batch * s_len * (
            2.0 * q * n_st + 2.0 * q * hp + 4.0 * n_st * hp)
    return total


def model_flops_for(cfg, cell) -> float:
    """Reference 'useful' FLOPs (global; fwd+bwd for train, fwd for serve).

    LM: parameter matmuls (6/2 x N_active x tokens) PLUS the sequence-mixer
    context compute (attention S^2 / SSD chunk terms) — without the latter
    the 32k/500k cells would read as 'waste'. Remat recompute deliberately
    stays OUT of the reference: useful_ratio surfaces it as overhead.
    GP: the CG-forward kernel MVMs, iters * 2 n^2 (d + t). The BBMM custom
    VJP adds only O(1) extra MVM sets for the whole backward (that is the
    algorithm's point); preconditioner build, CG dots and the backward
    surface land in overhead by design.
    """
    if cell.kind in ("gp_train", "gp_predict"):
        n, d = cfg.n, cfg.d
        t = 1 + (cfg.num_probes if cell.kind == "gp_train" else 0)
        iters = (cfg.train_cg_iters if cell.kind == "gp_train"
                 else cfg.pred_cg_iters)
        return iters * 2.0 * n * n * (d + t)
    from repro.models import count_active_params
    n_active = count_active_params(cfg)
    if cell.kind == "train":
        return (6.0 * n_active * cell.batch * cell.seq +
                3.0 * _lm_mixer_flops_fwd(cfg, cell.batch, cell.seq))
    if cell.kind == "prefill":
        return (2.0 * n_active * cell.batch * cell.seq +
                _lm_mixer_flops_fwd(cfg, cell.batch, cell.seq))
    # decode: one token against a seq_len-deep context
    return (2.0 * n_active * cell.batch +
            _lm_mixer_flops_fwd(cfg, cell.batch, cell.seq,
                                decode_ctx=cell.seq))


def format_row(arch, shape, mesh_name, r: Roofline) -> str:
    return (f"| {arch} | {shape} | {mesh_name} | {r.flops:.3e} | "
            f"{r.bytes_accessed:.3e} | {r.coll_bytes:.3e} | "
            f"{r.t_compute*1e3:.2f} | {r.t_memory*1e3:.2f} | "
            f"{r.t_collective*1e3:.2f} | {r.bottleneck} | "
            f"{r.useful_ratio:.2f} |")
