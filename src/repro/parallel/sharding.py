"""Logical-axis sharding rules (MaxText-style), per-arch overridable.

Model code annotates tensors with *logical* axis names; a ``ShardingRules``
object maps logical names to physical mesh axes and applies
``with_sharding_constraint``. Rules are the primary hillclimbing knob:
changing the mapping re-lowers the whole model under a different
distribution without touching model code.

Physical axes: ('pod', 'data', 'model') on the multi-pod mesh or
('data', 'model') on one pod. 'pod' composes with 'data' for data
parallelism everywhere.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "DEFAULT_RULES", "zero_shard_spec",
           "make_abstract_mesh", "shard_map_unchecked"]

def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_abstract_mesh(axis_sizes, axis_names):
    """``jax.sharding.AbstractMesh`` of the given axis sizes and names."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))

# logical axis -> mesh axis name(s) or None. 'dp' expands to the mesh's
# data-parallel axes (('pod','data') or ('data',)).
DEFAULT_RULES = {
    "batch": "dp",
    "seq": None,            # activation sequence (context parallelism knob)
    "seq_res": None,        # residual-stream sequence (Megatron-SP knob)
    "seq_kv": None,         # KV-cache sequence (long-context decode knob)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "layers": None,
    "conv": None,
    "state": None,          # SSM state dim
    "codebooks": None,
}


@dataclass(frozen=True)
class ShardingRules:
    """Binds a mesh to a logical->physical mapping."""
    mesh: Optional[Mesh]
    mapping: dict = field(default_factory=lambda: dict(DEFAULT_RULES))

    def with_overrides(self, **kv):
        m = dict(self.mapping)
        m.update(kv)
        return replace(self, mapping=m)

    # -- resolution ----------------------------------------------------------
    def _dp_axes(self):
        if self.mesh is None:
            return ("data",)
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    def resolve(self, logical: Optional[str]):
        """Logical name -> mesh axis name / tuple / None. Physical axes
        absent from the bound mesh are dropped (a pure-DP mesh has no
        'model' axis, but the default rules mention it)."""
        if logical is None:
            return None
        phys = self.mapping.get(logical, None)
        if phys == "dp":
            return self._dp_axes()
        elif phys == "dpm":  # everything: pure-DP layouts for small models
            phys = self._dp_axes() + ("model",)
        if phys is None or self.mesh is None:
            return phys
        names = self.mesh.axis_names
        if isinstance(phys, tuple):
            phys = tuple(a for a in phys if a in names)
            return phys or None
        return phys if phys in names else None

    def pspec(self, *logical_axes) -> P:
        used = set()
        out = []
        for ax in logical_axes:
            phys = self.resolve(ax)
            # drop duplicate physical axes (a mesh axis may appear once)
            if phys is None:
                out.append(None)
                continue
            flat = phys if isinstance(phys, tuple) else (phys,)
            flat = tuple(a for a in flat if a not in used)
            used.update(flat)
            out.append(flat if len(flat) > 1 else (flat[0] if flat else None))
        return P(*out)

    def sharding(self, *logical_axes) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(*logical_axes))

    def _divisible_axes(self, shape, logical_axes):
        """Drop logical axes whose physical size doesn't divide the dim
        (e.g. 14 attention heads on a 16-way model axis)."""
        out = []
        for dim, ax in zip(shape, logical_axes):
            phys = self.resolve(ax)
            flat = phys if isinstance(phys, tuple) else ((phys,) if phys else ())
            if not flat:  # unmapped -> replicated either way; keep the name
                out.append(ax)
                continue
            size = 1
            for a in flat:
                size *= self.mesh.shape[a]
            out.append(ax if dim % size == 0 else None)
        return tuple(out)

    def sharding_for(self, shape, logical_axes) -> Optional[NamedSharding]:
        """NamedSharding with ragged-dim fallback to replication."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(*self._divisible_axes(shape, logical_axes)))

    def pspec_for(self, shape, logical_axes):
        if self.mesh is None:
            return P()
        return self.pspec(*self._divisible_axes(shape, logical_axes))

    def shard(self, x, *logical_axes):
        """Apply a sharding constraint (no-op without a mesh)."""
        if self.mesh is None:
            return x
        if x.ndim != len(logical_axes):
            raise ValueError(f"rank {x.ndim} != axes {logical_axes}")
        # divisibility guard: fall back to None on ragged dims (GSPMD would
        # pad, but an explicit constraint with ragged dims is rejected)
        axes = []
        for dim, ax in zip(x.shape, logical_axes):
            phys = self.resolve(ax)
            flat = phys if isinstance(phys, tuple) else ((phys,) if phys else ())
            size = 1
            for a in flat:
                size *= self.mesh.shape[a]
            axes.append(ax if (size > 0 and dim % max(size, 1) == 0) else None)
        return jax.lax.with_sharding_constraint(x, self.sharding(*axes))


def zero_shard_spec(rules: ShardingRules, pspec: P, shape, start: int = 0) -> P:
    """ZeRO-1/FSDP: additionally shard the first divisible, unsharded dim
    (from ``start``; pass 1 to keep a stacked-layers dim whole so scan
    slices stay local) over the data-parallel axes."""
    if rules.mesh is None:
        return pspec
    dp = rules._dp_axes()
    dp_size = 1
    for a in dp:
        dp_size *= rules.mesh.shape[a]
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    for i, (dim, cur) in enumerate(zip(shape, entries)):
        if i < start:
            continue
        if cur is None and dim % dp_size == 0 and dim >= dp_size:
            entries[i] = dp if len(dp) > 1 else dp[0]
            return P(*entries)
    return pspec
