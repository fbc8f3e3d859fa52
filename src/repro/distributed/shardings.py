"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Model code annotates tensors with *logical* axes ("batch", "embed", "ff",
"experts", ...); a :class:`ShardingRules` table maps those to mesh axes.
Inside ``jit`` the annotations become ``with_sharding_constraint``s; outside
a mesh context they are no-ops, so the same model code runs single-device.

The default table implements:

  * data parallelism over ("pod", "data") on the batch axis
    (the DCN-crossing "pod" axis only ever carries data parallelism);
  * Megatron tensor parallelism over "model" on heads / ff / vocab;
  * expert parallelism over "model" for MoE experts;
  * optional sequence parallelism ("sp") — activations between blocks are
    sharded over "model" on the sequence axis, turning TP all-reduces into
    reduce-scatter + all-gather pairs (used by the perf hillclimb).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


# logical axis -> mesh axes (None = replicated)
DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "qkv": ("model",),          # fused qkv output dim
    "ff": ("model",),
    "experts": ("model",),
    "expert_group": ("pod", "data"),
    "vocab": ("model",),
    "kv_seq": None,             # decode KV cache sequence axis
    "ssm_heads": ("model",),
    "conv_ch": ("model",),
    "stage": None,
}

# sequence-parallel overlay: activations sharded over model on seq between
# blocks; KV-cache seq sharded when kv_heads cannot fill the model axis.
SP_OVERLAY = {
    "seq": ("model",),
}


@dataclasses.dataclass
class ShardingRules:
    """Maps logical axes to mesh axes and applies activation constraints.

    All spec construction is *shape-guarded*: a mesh axis is only assigned
    to a tensor dim it divides (longest prefix of the mapped axes whose
    size product divides the dim), so unusual head counts / tiny batches
    degrade to replication instead of GSPMD padding blowups.
    """

    table: Dict[str, Optional[Tuple[str, ...]]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))
    mesh_axes: Tuple[str, ...] = ()          # axes present in the mesh
    mesh_shape: Dict[str, int] = dataclasses.field(default_factory=dict)
    mesh: Optional[object] = None            # concrete Mesh: act() binds
                                             # NamedShardings (a bare
                                             # PartitionSpec constraint
                                             # needs an ambient mesh and
                                             # silently cannot apply here)
    enabled: bool = True

    @classmethod
    def for_mesh(cls, mesh, *, sequence_parallel: bool = False,
                 overrides: Optional[Dict] = None) -> "ShardingRules":
        table = dict(DEFAULT_RULES)
        if sequence_parallel:
            table.update(SP_OVERLAY)
        if overrides:
            table.update(overrides)
        return cls(table=table, mesh_axes=tuple(mesh.axis_names),
                   mesh_shape={a: int(n) for a, n in
                               zip(mesh.axis_names, mesh.devices.shape)},
                   mesh=mesh)

    @classmethod
    def disabled(cls) -> "ShardingRules":
        return cls(enabled=False)

    # ------------------------------------------------------------------
    def _axes_for(self, logical: Optional[str],
                  dim: Optional[int]) -> Optional[Tuple[str, ...]]:
        if logical is None:
            return None
        mesh_axes = self.table.get(logical)
        if mesh_axes is None:
            return None
        present = tuple(a for a in mesh_axes if a in self.mesh_axes)
        if not present:
            return None
        if dim is None:
            return present
        # longest prefix whose size product divides the dim
        out = []
        prod = 1
        for a in present:
            n = self.mesh_shape.get(a, 1)
            if dim % (prod * n) == 0:
                out.append(a)
                prod *= n
            else:
                break
        return tuple(out) or None

    def _mk_spec(self, logical, shape=None) -> P:
        cands = []
        for i, ax in enumerate(logical):
            dim = None if shape is None else shape[i]
            cands.append(self._axes_for(ax, dim) or ())
        # a mesh axis may appear at most once per spec: resolve conflicts
        # right-to-left so inner, more specific dims win (e.g. under
        # sequence parallelism the q/k/v head dim keeps "model" and the
        # seq dim drops it — Megatron-SP semantics)
        used: set = set()
        parts: list = [None] * len(cands)
        for i in range(len(cands) - 1, -1, -1):
            axes = tuple(a for a in cands[i] if a not in used)
            used.update(axes)
            parts[i] = None if not axes else (
                axes[0] if len(axes) == 1 else axes)
        return P(*parts)

    def spec(self, *logical: Optional[str]) -> P:
        """PartitionSpec for a tensor whose dims carry these logical axes."""
        return self._mk_spec(logical)

    def spec_for_shape(self, shape, *logical: Optional[str]) -> P:
        assert len(shape) == len(logical), (shape, logical)
        return self._mk_spec(logical, shape)

    def act(self, x, *logical: Optional[str]):
        """Annotate an activation; no-op when rules are disabled."""
        if not self.enabled or not self.mesh_axes:
            return x
        spec = self.spec_for_shape(x.shape, *logical)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))
        try:
            return jax.lax.with_sharding_constraint(x, spec)
        except (ValueError, RuntimeError):
            return x                      # no mesh context (eager/offload path)


NO_RULES = ShardingRules.disabled()
