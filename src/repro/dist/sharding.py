"""Logical-axis sharding policy + mesh context (GSPMD distribution layer).

Model code annotates tensors with *logical* axis names ("batch", "heads",
"mlp", ...) via `constraint`; a `ShardingPolicy` maps those names onto the
physical mesh axes ("pod", "data", "model"), dropping any assignment that
does not divide the dimension or would reuse a mesh axis twice. With no
active policy every `constraint` annotation is a no-op, so single-device
tests run unchanged.

Who consumes what (these are live call paths, not future plans):

- `constraint` lands in the layer stack at the mixer/FFN seams —
  `models/blocks.py::apply_layer` pins the residual stream to
  ("batch", "sp_seq", None) after every block, and the attention/FFN
  bodies in `models/layers.py` annotate activations at their head/mlp
  splits. Active only under `use_policy` (the dry-run launcher and
  mesh-sharded serving both enter it).
- `param_specs` is called by `launch/inputs.py::input_specs` (dry-run
  lowering: eval-shaped params get NamedShardings attached),
  `launch/train.py` (real params `device_put` onto the mesh), and
  `serve/engine.py` (FSDP-at-load for `ModelConfig.fsdp` configs served
  with `ExecConfig.mesh` — command-r-35B / mixtral-8x22B-class trees
  resolve without fitting one device).
- `MeshSpec` is the *declarative, hashable* mesh shape that rides on
  `ExecConfig.mesh` and therefore in the `resolve_plan` lru-cache key;
  the tensor-parallel attention backends (`exec/sharded.py`) call
  `MeshSpec.build()` to materialize the concrete `jax.sharding.Mesh`
  and `repro.dist.shard_map` to run per-shard kernel bodies over it.

Also hosts `make_mesh` (Auto axis types) and a `shard_map` whose
replication check is off by default.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "MeshContext", "MeshSpec", "ShardingPolicy", "constraint",
    "current_policy", "named_sharding_tree", "param_specs", "use_policy",
    "shard_map", "make_mesh",
]

_DP_AXES = ("pod", "data")


# --------------------------------------------------------------------------
# mesh + shard_map
# --------------------------------------------------------------------------

def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map`, replication check off unless asked for (the kernel
    bodies' pallas_calls carry no varying-axis annotations)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(shape, axis_names, devices=None):
    """`jax.make_mesh` with Auto axis types: the model stack places
    activations with `with_sharding_constraint` and lets GSPMD propagate
    the rest, which Explicit axes (jax's default) refuse."""
    return jax.make_mesh(shape, axis_names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


# --------------------------------------------------------------------------
# declarative mesh shape (plan-cache safe)
# --------------------------------------------------------------------------

_BUILT_MESHES: dict = {}


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: what `ExecConfig.mesh` carries.

    A frozen, hashable value object — `resolve_plan` is lru-cached over
    `(ModelConfig, ExecConfig)`, so the config must carry the mesh *shape*
    (which determines backend capability: divisibility, model_size), never
    the live `jax.sharding.Mesh` (device handles don't belong in a cache
    key). Backends materialize the concrete mesh via `build()` at trace
    time; capability predicates stay purely structural so plans resolve —
    and `plan_audit` exercises the catalog x mesh matrix — on a one-device
    process with no `XLA_FLAGS` set.

    ``axes`` is an ordered tuple of ``(name, size)`` pairs, e.g.
    ``(("data", 2), ("model", 4))``. `parse` accepts the launcher
    ``--mesh`` forms: ``"4"`` / ``"model=4"`` / ``"data=2,model=4"``.
    """

    axes: tuple = ()

    def __post_init__(self):
        seen = set()
        for entry in self.axes:
            name, size = entry
            if name in seen:
                raise ValueError(f"duplicate mesh axis {name!r} in {self.axes}")
            seen.add(name)
            if not isinstance(size, int) or size < 1:
                raise ValueError(f"mesh axis {name!r} needs a positive int "
                                 f"size, got {size!r}")

    @classmethod
    def parse(cls, text: str) -> "MeshSpec":
        """``"4"`` (model=4) / ``"model=4"`` / ``"data=2,model=4"``."""
        axes = []
        for part in str(text).split(","):
            part = part.strip()
            if not part:
                continue
            name, eq, size = part.partition("=")
            if not eq:
                name, size = "model", part
            try:
                axes.append((name.strip(), int(size)))
            except ValueError:
                raise ValueError(f"--mesh entries are axis=size, got {part!r}")
        return cls(axes=tuple(axes))

    @property
    def axis_names(self) -> tuple:
        return tuple(name for name, _ in self.axes)

    @property
    def n_devices(self) -> int:
        return int(np.prod([size for _, size in self.axes], dtype=np.int64)) \
            if self.axes else 1

    @property
    def model_size(self) -> int:
        return dict(self.axes).get("model", 1)

    def describe(self) -> str:
        return ",".join(f"{n}={s}" for n, s in self.axes) or "1"

    def build(self):
        """The concrete `jax.sharding.Mesh` (cached per spec).

        Raises with a run-it hint when the process has fewer devices than
        the spec asks for — structural predicates never call this, so a
        plan naming a TP backend resolves anywhere; only actually *running*
        it needs the devices (simulated ones count:
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
        """
        cached = _BUILT_MESHES.get(self)
        if cached is None:
            have = len(jax.devices())
            if self.n_devices > have:
                raise RuntimeError(
                    f"mesh {self.describe()} needs {self.n_devices} devices "
                    f"but the process has {have}; run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count="
                    f"{self.n_devices} (or on that many real devices)")
            cached = _BUILT_MESHES[self] = make_mesh(
                tuple(size for _, size in self.axes), self.axis_names)
        return cached

    def context(self) -> "MeshContext":
        return MeshContext(self.build())


# --------------------------------------------------------------------------
# mesh context
# --------------------------------------------------------------------------

@dataclasses.dataclass
class MeshContext:
    """Physical mesh + the conventional axis roles used by the model stack."""

    mesh: object = None

    @property
    def axis_names(self) -> tuple:
        return tuple(self.mesh.axis_names) if self.mesh is not None else ()

    def _size(self, name: str) -> int:
        return int(self.mesh.shape[name]) if name in self.axis_names else 1

    @property
    def model_axis(self) -> Optional[str]:
        return "model" if "model" in self.axis_names else None

    @property
    def model_size(self) -> int:
        return self._size("model")

    @property
    def present_dp_axes(self) -> tuple:
        return tuple(a for a in _DP_AXES if a in self.axis_names)

    @property
    def dp_size(self) -> int:
        return int(np.prod([self._size(a) for a in self.present_dp_axes],
                           dtype=np.int64))


# --------------------------------------------------------------------------
# policy
# --------------------------------------------------------------------------

def _default_axis_map(mesh) -> dict:
    names = tuple(mesh.axis_names) if mesh is not None else ()
    dp = tuple(a for a in _DP_AXES if a in names)
    model = ("model",) if "model" in names else ()
    return {
        "batch": dp,
        "seq": (),            # caches replicate over seq unless make_policy remaps
        "sp_seq": model,      # Megatron-SP residual stream
        "heads": model,
        "mlp": model,
        "vocab": model,
        "model": model,
        "chunks": model,      # SSD chunk dim fallback when heads don't divide
        "headdim": (),
    }


class ShardingPolicy:
    """Maps logical axis names onto mesh axes with divisibility checks."""

    def __init__(self, mesh, axis_map: Optional[dict] = None):
        self.mesh = mesh
        self.axis_map = dict(axis_map) if axis_map is not None \
            else _default_axis_map(mesh)

    def mesh_axes(self, name: Optional[str]) -> tuple:
        if name is None:
            return ()
        return tuple(self.axis_map.get(name, ()))

    def axes_size(self, axes) -> int:
        if isinstance(axes, str):
            axes = (axes,)
        return int(np.prod([int(self.mesh.shape[a]) for a in axes],
                           dtype=np.int64)) if axes else 1

    def spec_for(self, shape: tuple, names: tuple) -> P:
        """PartitionSpec for `shape`, one logical name (or None) per dim.

        A mesh axis is used at most once; an assignment that does not divide
        the dimension is dropped (replicated) rather than erroring.
        """
        used: set = set()
        entries = []
        for dim, name in zip(shape, names):
            picked = []
            for ax in self.mesh_axes(name):
                size = int(self.mesh.shape[ax])
                if ax in used or size <= 0:
                    continue
                if dim % (self.axes_size(tuple(picked)) * size) != 0:
                    continue
                picked.append(ax)
            used.update(picked)
            if not picked:
                entries.append(None)
            elif len(picked) == 1:
                entries.append(picked[0])
            else:
                entries.append(tuple(picked))
        return P(*entries)


# --------------------------------------------------------------------------
# active-policy context (thread of execution, not thread-safe by design)
# --------------------------------------------------------------------------

_ACTIVE: list = []


@contextlib.contextmanager
def use_policy(policy: ShardingPolicy, mesh_ctx: Optional[MeshContext] = None):
    _ACTIVE.append((policy, mesh_ctx))
    try:
        yield policy
    finally:
        _ACTIVE.pop()


def current_policy() -> Optional[ShardingPolicy]:
    return _ACTIVE[-1][0] if _ACTIVE else None


def constraint(x, *names):
    """Annotate `x` with logical axis names; no-op without an active policy."""
    pol = current_policy()
    if pol is None or pol.mesh is None:
        return x
    spec = pol.spec_for(x.shape, names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol.mesh, spec))


# --------------------------------------------------------------------------
# parameter sharding rules
# --------------------------------------------------------------------------

# logical axes per weight leaf, keyed by leaf name and aligned to the
# *trailing* dims of the leaf — leading scan/expert dims replicate, so one
# rule covers both a plain layer's (d_model, H, hd) wq and the scanned
# stack's (n_layers, d_model, H, hd). Megatron split: qkv/up projections
# shard their output (heads/mlp), wo/down their input, embeddings the vocab.
_PARAM_RULES = {
    "wq": (None, "heads", None),
    "wk": (None, "heads", None),
    "wv": (None, "heads", None),
    "bq": ("heads", None),
    "bk": ("heads", None),
    "bv": ("heads", None),
    "wo": ("heads", None, None),
    "w1": (None, "mlp"),
    "w3": (None, "mlp"),
    "w2": ("mlp", None),
    "tok_emb": ("vocab", None),
    "unembed": (None, "vocab"),
    "w_z": (None, "heads"),
    "w_x": (None, "heads"),
    "w_B": (None, "heads"),
    "w_C": (None, "heads"),
    "w_dt": (None, "heads"),
    "out_proj": ("heads", None),
}


def _leaf_axes(path: str, shape: tuple) -> tuple:
    name = path.split("/")[-1]
    rule = _PARAM_RULES.get(name)
    if rule is None or len(rule) > len(shape):
        return tuple(None for _ in shape)
    return tuple(None for _ in range(len(shape) - len(rule))) + tuple(rule)


def _path_str(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keypath)


def param_specs(params_shapes, cfg, policy: ShardingPolicy):
    """PartitionSpec pytree for a parameter (shape) pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for keypath, leaf in flat:
        shape = tuple(getattr(leaf, "shape", ()))
        axes = _leaf_axes(_path_str(keypath), shape)
        specs.append(policy.spec_for(shape, axes))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_sharding_tree(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
