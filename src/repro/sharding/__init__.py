"""Logical-axis sharding rules → PartitionSpecs.

Models annotate activations with *logical* axis names; params get specs
from path-based rules.  Logical names resolve to mesh axes through
``LOGICAL_RULES`` and are silently dropped when the current mesh lacks
the axis or the dimension is not divisible — this is what makes one
model definition run unchanged on the single-pod (data, model) mesh,
the multi-pod (pod, data, model) mesh, a tiny 8-device test mesh, and a
single CPU device.
"""
from __future__ import annotations

import contextlib
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# logical axis -> preferred mesh axes (first match that exists wins; for
# composite entries every present axis is used).
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),       # data parallel over pod × data
    "fsdp": ("data",),              # ZeRO-3 parameter sharding
    "fsdp_pod": ("pod", "data"),
    "model": ("model",),            # TP: heads / ff / vocab
    "expert": ("model",),           # EP: expert dim of MoE weights
    "moe_fsdp": ("data",),          # ZeRO-3 on MoE weights specifically
    "moe_ff": (),                   # TP within expert (small-E MoE)
    "moe_cap": (),                  # capacity dim of dispatch buffers
    "kv_seq": ("data",),            # long-context decode: shard KV seq
    "none": (),
}


def mesh_context(mesh):
    """Context manager putting ``mesh`` in scope for PartitionSpec
    resolution."""
    return jax.set_mesh(mesh)


@contextlib.contextmanager
def logical_rules(**over):
    """Temporarily override LOGICAL_RULES (perf experiments)."""
    old = {k: LOGICAL_RULES[k] for k in over}
    LOGICAL_RULES.update({k: tuple(v) for k, v in over.items()})
    try:
        yield
    finally:
        LOGICAL_RULES.update(old)


def current_mesh():
    """The (abstract) mesh in scope."""
    return jax.sharding.get_abstract_mesh()


def _mesh_axis_sizes() -> dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def resolve(logical: str | None, dim: int | None = None,
            used: set | None = None):
    """Logical name -> mesh axes tuple (or None), respecting presence,
    divisibility of ``dim``, and axes already used by other dims."""
    if logical is None or logical == "none":
        return None
    sizes = _mesh_axis_sizes()
    axes = [a for a in LOGICAL_RULES.get(logical, ()) if a in sizes
            and (used is None or a not in used)]
    if not axes:
        return None
    if dim is not None:
        total = 1
        kept = []
        for a in axes:
            if dim % (total * sizes[a]) == 0:
                kept.append(a)
                total *= sizes[a]
        axes = kept
    if not axes:
        return None
    if used is not None:
        used.update(axes)
    return tuple(axes) if len(axes) > 1 else axes[0]


def spec(*logical: str | None, dims: Sequence[int] | None = None) -> P:
    parts = []
    used: set = set()
    for i, name in enumerate(logical):
        d = None if dims is None else dims[i]
        parts.append(resolve(name, d, used))
    return P(*parts)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Annotate an activation with logical axes (no-op off-mesh)."""
    if not _mesh_axis_sizes():
        return x
    s = spec(*logical, dims=x.shape)
    return jax.lax.with_sharding_constraint(x, s)


# ---------------------------------------------------------------------------
# Parameter specs by path rules
# ---------------------------------------------------------------------------

# (path-substring, logical names per dim). First match wins; matched
# against "/".join(path). Entries cover every param family in
# repro/models. Stacked (scan-over-layers) params get a leading None.
PARAM_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    ("embed/tok", ("model", "fsdp")),          # vocab × d
    ("embed/pos", (None, "fsdp")),
    ("embed/unembed", ("fsdp", "model")),
    ("attn/wq", ("fsdp", "model", None)),      # d × Hq × hd
    ("attn/wk", ("fsdp", "model", None)),
    ("attn/wv", ("fsdp", "model", None)),
    ("attn/wo", ("model", None, "fsdp")),      # Hq × hd × d
    ("moe/wg", ("fsdp", None)),                        # d × E router
    ("moe/w_gate", ("expert", "moe_fsdp", "moe_ff")),  # E × d × ff
    ("moe/w_up", ("expert", "moe_fsdp", "moe_ff")),
    ("moe/w_down", ("expert", "moe_ff", "moe_fsdp")),  # E × ff × d
    ("mlp/w_gate", ("fsdp", "model")),
    ("mlp/w_up", ("fsdp", "model")),
    ("mlp/w_down", ("model", "fsdp")),
    ("ssm/in_proj", ("fsdp", "model")),        # d × d_in_all
    ("ssm/out_proj", ("model", "fsdp")),       # d_inner × d
    ("ssm/conv", (None, "model")),             # width × channels
    ("ssm/", (None,)),                         # A_log, D, dt_bias, norm
    ("norm", (None,)),
]


def param_spec_for(path: str, shape: tuple[int, ...]) -> P:
    for sub, names in PARAM_RULES:
        if sub in path:
            # align rule names to trailing dims (leading scan dims None)
            k = len(names)
            if len(shape) >= k:
                lead = (None,) * (len(shape) - k)
                dims = shape[len(shape) - k:]
                used: set = set()
                parts = [resolve(n, d, used)
                         for n, d in zip(names, dims)]
                return P(*lead, *parts)
            return P(*([None] * len(shape)))
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    out = []
    for p in path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                out.append(str(getattr(p, attr)))
                break
        else:
            out.append(str(p))
    return "/".join(out)


def param_specs(params) -> dict:
    """PartitionSpec pytree matching a param pytree (call inside
    ``mesh_context`` so divisibility is checked against the actual
    mesh)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec_for(_path_str(path), leaf.shape),
        params)


def named_shardings(params, mesh) -> dict:
    from jax.sharding import NamedSharding
    with mesh_context(mesh):
        specs = param_specs(params)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
