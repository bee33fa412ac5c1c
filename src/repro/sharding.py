"""Sharding rules: map model/activation tensors onto the mesh axes.

Mesh axes (see launch/mesh.py):
  - "pod"   : CoRS client axis (multi-pod mesh only). No gradient sync here.
  - "data"  : batch / FSDP axis.
  - "model" : tensor-parallel axis (heads / d_ff / vocab / experts).

All helpers degrade gracefully: a dimension is only sharded when divisible by
the axis size, otherwise left replicated (GSPMD would fail to partition
non-divisible dims cleanly; we keep the dry-run deterministic instead).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(shape: Sequence[int], names: Sequence[str]) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`. The placement API
    and the launch shardings are written for GSPMD (jit in/out shardings
    plus `with_sharding_constraint`); jax >= 0.7 defaults new meshes to
    `Explicit` axes, under which `vmap` refuses inputs whose mapped axis is
    sharded on some arguments and replicated on others."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def client_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh with a "clients" axis for the vectorized collab
    engine (vec_collab.py): the stacked client axis is sharded over it and
    the prototype merge becomes a psum. Defaults to all local devices."""
    n = n_devices or len(jax.devices())
    return auto_mesh((n,), ("clients",))


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def dp_axes(mesh: Mesh) -> tuple:
    """Axes over which the batch is sharded ("pod" folds into batch)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def maybe(axis: Optional[str], dim: int, size: int):
    """Return `axis` if `dim` is divisible by `size`, else None."""
    return axis if (axis is not None and size > 1 and dim % size == 0) else None


def batch_spec(mesh: Mesh, batch: int, *rest) -> P:
    """Shard the leading batch dim over (pod, data) as far as divisible."""
    axes = []
    for a in dp_axes(mesh):
        if batch % (mesh.shape[a] * _prod(mesh, axes)) == 0:
            axes.append(a)
    lead = tuple(axes) if axes else None
    return P(lead, *rest)


def _prod(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def head_axis_plan(num_heads: int, head_dim: int, tp: int) -> str:
    """Which per-head axis the model axis shards: 'heads' | 'head_dim' | 'none'."""
    if tp <= 1:
        return "none"
    if num_heads % tp == 0:
        return "heads"
    if head_dim % tp == 0:
        return "head_dim"
    return "none"


def shard(mesh: Mesh, x, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    """Every-device-holds-it sharding (GSPMD P())."""
    return NamedSharding(mesh, P())


def leading_axis(mesh: Mesh, axis: str) -> NamedSharding:
    """Leading-dim-over-`axis` sharding (GSPMD P(axis)); the client axis of
    the collaborative engines ("clients" on a `client_mesh`, "pod" on the
    LM launch mesh). GSPMD pads non-divisible leading dims, so uneven
    client counts (hetero buckets) shard without a divisibility assert."""
    return NamedSharding(mesh, P(axis))


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------
def param_spec(path: str, shape: tuple, mesh: Mesh, *, fsdp: bool) -> P:
    """Heuristic parameter sharding from the param-tree path.

    Conventions used by the model code (nn/ + models/):
      - 'embed'               : (vocab, d_model)        -> vocab over model
      - 'lm_head' / 'w_out'   : (d_model, vocab)        -> vocab over model
      - 'wq','wk','wv'        : (d_model, heads*hd)     -> out dim over model
      - 'wo'                  : (heads*hd, d_model)     -> in dim over model
      - 'w_gate','w_up'       : (d_model, d_ff)         -> d_ff over model
      - 'w_down'              : (d_ff, d_model)         -> d_ff over model
      - experts '..._e'       : (E, d, f)               -> f over model
      - everything else       : replicated (biases, norms, small projs)
    FSDP additionally shards the *other* matrix dim over data when divisible.
    """
    tp = axis_size(mesh, "model")
    dp = axis_size(mesh, "data")
    leaf = path.split("/")[-1]
    ndim = len(shape)
    spec = [None] * ndim

    model_dim = None  # index sharded by "model"
    if ndim >= 2:
        if leaf in ("embed", "proto"):
            model_dim = 0
        elif leaf in ("lm_head", "w_out"):
            model_dim = ndim - 1
        elif leaf in ("wq", "wk", "wv", "w_gate", "w_up", "wkv_b", "wq_b",
                      "w_in", "w_qkv"):
            model_dim = ndim - 1
        elif leaf in ("wo", "w_down"):
            model_dim = ndim - 2
        elif leaf.endswith("_e"):      # stacked expert weights (E, d, f)
            # "tp": shard the per-expert ffn dim; "ep": shard the expert dim
            model_dim = 0 if _HINTS.get("moe_ep") else ndim - 1

    if model_dim is not None and maybe("model", shape[model_dim], tp):
        spec[model_dim] = "model"

    if fsdp and ndim >= 2:
        # shard one remaining large dim over data
        for d in range(ndim - 1, -1, -1):
            if spec[d] is None and shape[d] % dp == 0 and shape[d] >= dp:
                spec[d] = "data"
                break
    return P(*spec)


def tree_param_specs(params, mesh: Mesh, *, fsdp: bool):
    """PartitionSpec pytree matching `params` (dict-of-dict pytree)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        specs[path] = param_spec(path, leaf.shape, mesh, fsdp=fsdp)
    # rebuild tree
    def build(subtree, prefix):
        if isinstance(subtree, dict):
            return {k: build(v, prefix + [_plain(k)]) for k, v in subtree.items()}
        return specs["/".join(prefix)]
    return build(params, [])


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def _plain(k) -> str:
    return str(k)


# ---------------------------------------------------------------------------
# Sharding hints: knobs the launcher sets before lowering so deep layers
# (e.g. the MoE dispatch buffers) can apply mesh-aware constraints without
# threading the mesh through every call signature. Used by §Perf variants.
# ---------------------------------------------------------------------------
_HINTS = {"mesh": None, "moe_ep": False}


def set_hints(**kw):
    _HINTS.update(kw)


def hint(name: str):
    return _HINTS.get(name)


def constrain(x, *spec):
    """with_sharding_constraint against the hinted mesh (no-op without)."""
    m = _HINTS.get("mesh")
    if m is None:
        return x
    cleaned = []
    for s in spec:
        if s is not None and isinstance(s, str) and s not in m.axis_names:
            cleaned.append(None)
        else:
            cleaned.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(*cleaned)))
