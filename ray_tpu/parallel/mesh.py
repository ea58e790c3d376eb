"""Device mesh construction with named parallelism axes.

This replaces the reference's process-group plumbing (Train's
``torch/config.py`` NCCL rendezvous) with the JAX-native structure: a
``jax.sharding.Mesh`` whose axes are the parallelism strategies of
SURVEY.md §2.3 —

  dp    data parallel (gradient all-reduce over ICI)
  fsdp  sharded data parallel (weight all-gather / grad reduce-scatter)
  pp    pipeline parallel (microbatch ppermute ring)
  tp    tensor parallel (Megatron-style within-layer sharding)
  sp    sequence/context parallel (ring attention neighbor exchange)
  ep    expert parallel (MoE all-to-all dispatch)

Axis order matters on hardware: the innermost (fastest-varying) axes should
map to the closest ICI neighbors. We order axes (pp, dp, fsdp, sp, tp, ep)
outer→inner by default so tp/ep collectives ride the shortest links, matching
the scaling-book recipe.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# outer -> inner hardware order
DEFAULT_AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "tp", "ep")


@dataclass(frozen=True)
class MeshSpec:
    """Sizes for each named parallelism axis (1 = unused but present)."""

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.fsdp * self.pp * self.tp * self.sp * self.ep

    def sizes(self) -> Dict[str, int]:
        return {"pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
                "sp": self.sp, "tp": self.tp, "ep": self.ep}

    @staticmethod
    def auto(num_devices: int, *, tp: int = 1, pp: int = 1, sp: int = 1,
             ep: int = 1, fsdp: int = 1) -> "MeshSpec":
        """Fill dp with whatever is left after the explicit axes."""
        used = tp * pp * sp * ep * fsdp
        if num_devices % used != 0:
            raise ValueError(
                f"{num_devices} devices not divisible by tp*pp*sp*ep*fsdp="
                f"{used}")
        return MeshSpec(dp=num_devices // used, fsdp=fsdp, pp=pp, tp=tp,
                        sp=sp, ep=ep)


def build_mesh(spec: MeshSpec,
               devices: Optional[Sequence] = None,
               axis_order: Tuple[str, ...] = DEFAULT_AXIS_ORDER) -> Mesh:
    """Build a Mesh with all six named axes (size-1 axes included).

    Keeping unused axes (size 1) in the mesh means model sharding rules can
    always reference the full axis vocabulary; XLA elides trivial axes.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if spec.num_devices != n:
        raise ValueError(
            f"mesh spec needs {spec.num_devices} devices "
            f"(={spec.sizes()}), got {n}")
    sizes = spec.sizes()
    shape = tuple(sizes[a] for a in axis_order)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_order)


def mesh_from_string(desc: str, devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from 'dp=2,tp=2,sp=2' style descriptions."""
    kwargs: Dict[str, int] = {}
    for part in desc.replace(" ", "").split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        kwargs[k] = int(v)
    return build_mesh(MeshSpec(**kwargs), devices)


# ---------------------------------------------------------------------------
# Logical axis rules: map tensor-dimension names to mesh axes.
# ---------------------------------------------------------------------------

# Megatron-style sharding vocabulary for transformer weights/activations.
DEFAULT_RULES: Dict[str, Optional[object]] = {
    # activations
    "batch": ("dp", "fsdp"),   # batch dim sharded over data axes
    "seq": "sp",               # sequence dim sharded for context parallelism
    "embed": None,             # activation embed dim replicated
    "heads": "tp",             # attention heads over tensor axis
    "kv_heads": "tp",
    "head_dim": None,
    # weights
    "embed_in": "fsdp",        # weight embed dim sharded for ZeRO/FSDP
    "mlp": "tp",               # FFN hidden over tensor axis
    "vocab": "tp",             # embedding/LM-head vocab over tensor axis
    "experts": "ep",           # MoE expert dim
    "stages": "pp",            # stacked pipeline stage dim
}


def logical_to_spec(names: Sequence[Optional[str]],
                    rules: Optional[Dict] = None) -> PartitionSpec:
    """('batch','seq','embed') -> PartitionSpec(('dp','fsdp'),'sp',None)."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    out = []
    for name in names:
        if name is None:
            out.append(None)
        else:
            if name not in rules:
                raise KeyError(f"no sharding rule for logical axis {name!r}")
            out.append(rules[name])
    return PartitionSpec(*out)


def named_sharding(mesh: Mesh, *names: Optional[str],
                   rules: Optional[Dict] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(names, rules))


def shard_constraint(x, mesh: Mesh, *names: Optional[str],
                     rules: Optional[Dict] = None):
    """with_sharding_constraint by logical axis names."""
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, *names, rules=rules))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def local_mesh_devices(n: Optional[int] = None) -> List:
    """Devices for a mesh; n=None -> all."""
    devs = jax.devices()
    return devs if n is None else devs[:n]
