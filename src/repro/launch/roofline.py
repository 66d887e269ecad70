"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (the dry-run plans
for TPU v5e, so ``roofline_terms`` defaults to the ``V5E`` peaks):

    compute    = HLO_FLOPs_per_device / 197e12      (bf16 MXU peak)
    memory     = HLO_bytes_per_device / 819e9       (HBM bandwidth)
    collective = collective_bytes_per_device / 50e9 (ICI link)

FLOPs/bytes come from ``compiled.cost_analysis()`` (the post-SPMD module
is per-partition, so these are already per-device). Collective bytes are
parsed from the optimized HLO text: we sum the *result-shape* bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (all-reduce counted twice for the ring's
reduce+broadcast phases). Shapes in the partitioned module are
per-device, so this approximates per-device link traffic.

MODEL_FLOPS uses the 6*N*D (train) / 2*N*D (inference) convention with
N = active non-embedding params, so the ratio MODEL_FLOPS / HLO_FLOPs
exposes remat recompute, causal-mask waste, routing overhead, and
padding.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

__all__ = ["HW", "PEAKS", "V5E", "collective_bytes", "roofline_terms",
           "model_flops", "active_params"]


@dataclass(frozen=True)
class HW:
    """Published per-chip peaks of one accelerator generation."""
    peak_flops: float                 # bf16 FLOP/s
    peak_int8_ops: float              # int8 OP/s
    hbm_bw: float                     # bytes/s
    link_bw: float                    # bytes/s ICI per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect per
# chip (4 links of 50 GB/s).
V5E = HW(peak_flops=197e12, peak_int8_ops=393e12, hbm_bw=819e9,
         link_bw=50e9)

#: peaks by ``jax.Device.device_kind``. A kind missing here has no
#: modeled roofline — no other chip's numbers stand in for it.
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}



_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

# NOTE: the op-result signature may be a combiner-fused TUPLE whose
# elements are separated by /*index=N*/ comments — '=' must be in the
# class or the match silently truncates to the tuple's tail.
_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}\s/_:#*\.=]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        b = _DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * b
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind (result-shape accounting;
    all-reduce x2). '-start' variants counted, '-done' skipped."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in _COLL_RE.finditer(hlo_text):
        sig, kind = m.group(1), m.group(2).lower()
        nbytes = _shape_bytes(sig)
        mult = 2 if kind == "all-reduce" else 1
        out[kind] += nbytes * mult
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


def active_params(cfg) -> tuple:
    """(n_active, n_total) non-embedding params; MoE counts top-k experts."""
    from repro.models.lm import model_param_specs
    from repro.models.nn import np_prod
    import jax

    specs = model_param_specs(cfg)
    total = active = 0
    emb = np_prod(specs["embed"].shape)
    leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes"))[0]
    for path, s in leaves:
        name = jax.tree_util.keystr(path)
        n = np_prod(s.shape)
        if "embed'" in name and "blocks" not in name:
            continue
        if "head" in name and "blocks" not in name and "tail" not in name:
            continue
        total += n
        if "experts" in s.axes:
            frac = cfg.n_experts_per_token / max(cfg.n_experts, 1)
            active += int(n * frac)
        else:
            active += n
    del emb
    return active, total


def model_flops(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """6*N*D (train) / 2*N*D (inference forward) with N=active params."""
    n_active, _ = active_params(cfg)
    if shape_kind == "train":
        tokens = batch * seq
        return 6.0 * n_active * tokens
    if shape_kind == "prefill":
        tokens = batch * seq
        return 2.0 * n_active * tokens
    # decode: one token per sequence per step
    return 2.0 * n_active * batch


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float, hw: HW = V5E) -> dict:
    t_c = flops_per_dev / hw.peak_flops
    t_m = bytes_per_dev / hw.hbm_bw
    t_l = coll_bytes_per_dev / hw.link_bw
    dom = max((t_c, "compute"), (t_m, "memory"), (t_l, "collective"))[1]
    bound = max(t_c, t_m, t_l)
    return {
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_l,
        "dominant": dom,
        "roofline_fraction": (t_c / bound if bound > 0 else 0.0),
    }
