"""Roofline analysis from compiled dry-run artifacts.

Terms (per device, TPU v5e peaks from ``PEAKS``):
    compute    = HLO_flops / PEAK_FLOPS
    memory     = HLO_bytes / HBM_BW
    collective = collective_bytes / ICI_BW

``cost_analysis()`` reports PER-DEVICE flops/bytes post-partitioning (verified
empirically), with while-loop bodies counted ONCE — the dry-run therefore
unrolls layer scans. Collective bytes are parsed from the optimized HLO
(``compiled.as_text()``): per-shard operand shapes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field

# Per-chip peaks keyed by ``jax.Device.device_kind``; FLOP/s and B/s.
# "TPU v5 lite" is TPU v5e. Source: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4 links
# (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

# the dry-run plans placements on the v5e production meshes
PEAK_FLOPS = PEAKS["TPU v5 lite"]["flops"]
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bw"]
ICI_BW = PEAKS["TPU v5 lite"]["ici_bw"]

_PEAK_ENV = {"flops": "REPRO_PEAK_FLOPS", "hbm_bw": "REPRO_PEAK_HBM_BW",
             "ici_bw": "REPRO_PEAK_ICI_BW"}


def peaks(device_kind: str | None = None) -> dict:
    """The peaks every achieved-vs-peak gauge divides by, per device: the
    ``PEAKS`` entry for ``device_kind`` (default: the first device's), with
    any positive ``REPRO_PEAK_FLOPS`` / ``REPRO_PEAK_HBM_BW`` /
    ``REPRO_PEAK_ICI_BW`` on top. A device missing from the table
    contributes nothing: the result then holds only the overridden keys,
    and callers emit no gauge for a peak they do not know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    out = dict(PEAKS.get(device_kind, {}))
    for key, name in _PEAK_ENV.items():
        try:
            v = float(os.environ.get(name, "") or 0)
        except ValueError:
            v = 0.0
        if v > 0:
            out[key] = v
    return out

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# result may be a single shape or a tuple of shapes; sum every shape
_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[\d,]*\]\S*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    bytes_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device bytes moved by each collective op.

    Approximation (documented): bytes-moved-per-device ~ result-shape bytes
    for AG/RS/A2A/permute; 2x for all-reduce (reduce + broadcast phases of a
    ring). The (k-1)/k factor is dropped (<7% at k=16).
    """
    stats = CollectiveStats()
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        b = sum(_shape_bytes(dt, dims)
                for dt, dims in _SHAPE_RE.findall(shapes))
        if kind == "all-reduce":
            b *= 2
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
    return stats


_DOT_RE = re.compile(r"=\s+\S+\s+(?:dot|convolution)\(")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+)\s*=")
_REF_RE = re.compile(r"%([\w.-]+)")


def overlap_evidence(hlo_text: str) -> dict:
    """Evidence that exchange collectives interleave with backward compute.

    The double-buffered ``overlap="buckets"`` schedule puts microbatch
    *i-1*'s reduce-scatter inside the scan/while body next to microbatch
    *i*'s backward dots (serialized exchange lives after the loop, so no
    single computation mixes the two). Two signals per computation:

    - **order**: a collective printed before the computation's last dot
      (on TPU the latency-hiding scheduler hoists the async ``-start``);
    - **independence**: a collective whose transitive operand closure
      contains no dot of the same computation — it consumes only
      loop-carried state, so it is *issuable* before the first backward
      dot regardless of how a synchronous backend (CPU) ordered the text.
    """
    blocks, cur = [], []
    for line in hlo_text.splitlines():
        cur.append(line)
        if line.startswith("}"):
            blocks.append(cur)
            cur = []
    if cur:
        blocks.append(cur)
    n_mixed = 0
    ordered = independent = False
    for blk in blocks:
        coll_idx = [i for i, l in enumerate(blk) if _COLL_RE.search(l)]
        dot_idx = [i for i, l in enumerate(blk) if _DOT_RE.search(l)]
        if not (coll_idx and dot_idx):
            continue
        n_mixed += 1
        if min(coll_idx) < max(dot_idx):
            ordered = True
        deps, dots = {}, set()
        dot_set = set(dot_idx)
        for i, l in enumerate(blk):
            m = _DEF_RE.match(l)
            if not m:
                continue
            name = m.group(1)
            deps[name] = [r for r in _REF_RE.findall(l.split("=", 1)[1])]
            if i in dot_set:
                dots.add(name)
        for i in coll_idx:
            m = _DEF_RE.match(blk[i])
            if not m:
                continue
            seen, stack = set(), list(deps.get(m.group(1), []))
            while stack:
                r = stack.pop()
                if r in seen:
                    continue
                seen.add(r)
                stack.extend(deps.get(r, []))
            if not (seen & dots):
                independent = True
                break
    return {"rs_before_last_dot": ordered or independent,
            "comm_independent_of_dots": independent,
            "computations_mixing_comm_and_dots": n_mixed}


@dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    model_flops: float = 0.0     # analytic 6ND (per device)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
        }


def analyze(compiled, *, model_flops_per_device: float = 0.0) -> dict:
    """Full analysis of one compiled executable."""
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    colls = parse_collectives(txt)
    rl = Roofline(flops, hbm, colls.total_bytes,
                  model_flops=model_flops_per_device)
    ma = compiled.memory_analysis()
    return {
        "roofline": rl.as_dict(),
        "collectives": {"counts": colls.counts,
                        "bytes_by_kind": colls.bytes_by_kind},
        "memory": {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(ma, "temp_size_in_bytes", 0))
            + int(getattr(ma, "argument_size_in_bytes", 0)),
        },
    }


def model_flops_6nd(n_active_params: int, tokens: int, kind: str) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D inference (fwd only)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens


def attention_flops_bytes(*, batch: int, q_len: int, kv_len: int,
                          heads: int, kv_heads: int, head_dim_k: int,
                          head_dim_v: int = 0, window: int = 0,
                          causal: bool = True, q_start: int = 0,
                          kind: str = "fwd", dtype_bytes: int = 2) -> dict:
    """Analytic FLOPs and minimal HBM bytes for (windowed-)causal
    attention — the roofline an exact fused kernel can at best achieve.

    ``pairs`` counts surviving (q, k) interactions: query at absolute
    position ``q_start + i`` sees ``min(pos+1, kv_len)`` keys, clipped to
    ``window`` when one is set — so windowed layers get a *linear* (not
    quadratic) compute term and a caller can report achieved-vs-roofline
    per masking mode. FLOPs: 2·(Dk+Dv) per pair per head forward (QK^T +
    PV); the backward recomputes the score tile and runs the dQ/dK/dV
    matmuls (3·Dk + 2·Dv dots of 2 FLOPs each). Bytes: one q/k/v read +
    one out write at ``dtype_bytes`` (+ the fp32 lse/di residual rows and
    a re-read of everything for ``fwd+bwd``) — no (S, S) term at all,
    which is exactly what separates flash from the dense XLA path."""
    import numpy as np
    Dk = head_dim_k
    Dv = head_dim_v or head_dim_k
    if causal:
        pos = q_start + np.arange(q_len, dtype=np.int64)
        per_q = np.minimum(pos + 1, kv_len)
        if window > 0:
            per_q = np.minimum(per_q, window)
        pairs = int(per_q.sum())
    else:
        pairs = q_len * kv_len
    f_fwd = 2.0 * batch * heads * pairs * (Dk + Dv)
    f_bwd = 2.0 * batch * heads * pairs * (3 * Dk + 2 * Dv)
    flops = f_fwd + (f_bwd if kind != "fwd" else 0.0)
    qo_bytes = batch * q_len * heads * (Dk + Dv) * dtype_bytes
    kv_bytes = batch * kv_len * kv_heads * (Dk + Dv) * dtype_bytes
    hbm = qo_bytes + kv_bytes
    if kind != "fwd":
        hbm += 2 * (qo_bytes + kv_bytes)          # re-read + grad writes
        hbm += batch * q_len * heads * 2 * 4      # lse + di, fp32
    return {"flops": flops, "hbm_bytes": float(hbm), "pairs": pairs,
            "intensity": flops / max(hbm, 1.0)}
