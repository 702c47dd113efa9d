"""Divisibility-aware logical-axis sharding rules.

The production mesh is ('data', 'model') = (16, 16) per pod, with an extra
leading 'pod' axis multi-pod. Rules are name+shape based over the params
pytree; a dimension is sharded on an axis only when divisible (whisper's 20
heads, kv_heads ∈ {1,2,4,8}, odd vocabs all fall back to replication of
that dim rather than failing).

Layout summary (DESIGN.md §6):
  * expert weights [E, d, F]: E over 'data' x F over 'model' (2-D expert
    sharding — the only way Kimi-K2's 2 TB of experts fit 16 GB/chip)
  * dense/attention matrices: output-feature dim over 'model', wo/w_down
    transposed accordingly (Megatron-style tensor parallel)
  * embeddings: vocab over 'model'
  * batch dims of inputs over ('pod','data'); long_500k (batch=1) shards
    the KV-cache sequence over 'data' instead (context parallelism)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------------- #
# Perf-iteration options (§Perf hillclimbing, EXPERIMENTS.md):
# globally-gated optimization paths so baseline and optimized variants of
# the SAME model code can be lowered and compared. Launchers set these from
# --opts; CPU tests leave them empty (no mesh context -> no constraints).
# --------------------------------------------------------------------- #

OPTIONS: set = set()
_CONTEXT_MESH = [None]


def set_options(names, mesh=None):
    OPTIONS.clear()
    OPTIONS.update(names or [])
    _CONTEXT_MESH[0] = mesh


def opt(name: str) -> bool:
    return name in OPTIONS


def constrain(x, *spec_entries):
    """with_sharding_constraint that is a no-op when no launcher mesh is
    registered (CPU tests), and drops axis names absent from the mesh
    (e.g. 'pod' on the single-pod mesh)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = _CONTEXT_MESH[0]
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def fix(e):
        if e is None or (isinstance(e, str) and e in names):
            return e
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None

    spec = PartitionSpec(*(fix(e) for e in spec_entries))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(mesh.shape)  # works for Mesh and AbstractMesh


def data_axes(mesh: Mesh):
    """The (composite) batch-parallel axis: ('pod','data') when multi-pod."""
    names = [n for n in ("pod", "data") if n in mesh.axis_names]
    return tuple(names) if len(names) > 1 else names[0]


def axis_size(mesh: Mesh, axes) -> int:
    sizes = mesh_axis_sizes(mesh)
    if isinstance(axes, str):
        return sizes[axes]
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def _div(dim: int, mesh: Mesh, axes) -> Optional[Any]:
    """Return `axes` if dim divides evenly over them, else None."""
    if axes is None:
        return None
    return axes if dim % axis_size(mesh, axes) == 0 else None


# --------------------------------------------------------------------- #
# Parameter rules
# --------------------------------------------------------------------- #

def _param_rule(path: Tuple[str, ...], shape: Tuple[int, ...],
                mesh: Mesh) -> P:
    """Decide a PartitionSpec for one parameter from its tree path + shape."""
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    # leading L dim from the layer-stack vmap
    stacked = "blocks" in path or "dense_blocks" in path
    dims = shape[1:] if stacked else shape
    lead = (None,) if stacked else ()

    m = "model"
    dp = data_axes(mesh)

    def spec(*entries):
        return P(*(lead + tuple(entries)))

    if len(dims) == 0:
        return spec()

    # ---- Adafactor factored second-moment states mirror their parameter's
    # spec minus the reduced dim (path = param_path + ('row'|'col',)) ----
    if name == "row" and len(path) >= 2:
        parent_spec = _param_rule(path[:-1], shape + (128,), mesh)
        return P(*tuple(parent_spec)[:-1])
    if name == "col" and len(path) >= 2:
        parent_spec = _param_rule(path[:-1], shape[:-1] + (128, shape[-1]),
                                  mesh)
        ps = tuple(parent_spec)
        return P(*(ps[:-2] + ps[-1:]))

    # ---- embeddings ----
    if name == "embedding":                       # [V, d]
        return spec(_div(dims[0], mesh, m), None)
    if name == "unembed":                         # [d, V]
        return spec(None, _div(dims[1], mesh, m))

    # ---- MoE experts ----
    if parent == "moe" and name in ("w_gate", "w_up") and len(dims) == 3:
        return spec(_div(dims[0], mesh, dp), None, _div(dims[2], mesh, m))
    if parent == "moe" and name == "w_down" and len(dims) == 3:
        return spec(_div(dims[0], mesh, dp), _div(dims[1], mesh, m), None)
    if name == "router":                          # [d, E]
        return spec(None, None)

    # ---- MLA ----
    if name in ("w_qb", "w_uk", "w_uv") and len(dims) == 3:  # [r, H, hd]
        return spec(None, _div(dims[1], mesh, m), None)
    if name in ("w_qa", "w_kva", "w_kr"):
        return spec(None, None)

    # ---- attention / generic matrices ----
    if name in ("wq", "wk", "wv", "wg", "wr", "wk", "w_in", "w_gate",
                "w_up", "w_a", "w_x"):
        if len(dims) == 2:                        # [d_in, d_out]
            return spec(None, _div(dims[1], mesh, m))
    if name in ("wo", "w_down", "w_out", "wv") and len(dims) == 2:
        # output projections contract over the model-sharded dim
        if parent == "cmix" and name == "wv":     # rwkv cmix [F, d]
            return spec(_div(dims[0], mesh, m), None)
        if name == "wv":                          # attention value proj
            return spec(None, _div(dims[1], mesh, m))
        return spec(_div(dims[0], mesh, m), None)
    if name == "conv_w":                          # [cw, dr]
        return spec(None, _div(dims[1], mesh, m))

    # ---- everything small (norms, biases, mixes, loras, u) ----
    return spec(*(None,) * len(dims))


def param_shardings(cfg, params_shapes, mesh: Mesh):
    """params_shapes: pytree of ShapeDtypeStruct (jax.eval_shape output).
    Returns matching pytree of NamedSharding."""
    def fn(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "idx", "")) for p in path)
        keys = tuple(str(k) for k in keys)
        spec = _param_rule(keys, tuple(leaf.shape), mesh)
        if len(spec) != len(leaf.shape):
            spec = P(*(tuple(spec) + (None,) * (len(leaf.shape) - len(spec))))
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(fn, params_shapes)


# --------------------------------------------------------------------- #
# Input / cache rules
# --------------------------------------------------------------------- #

def batch_sharding(mesh: Mesh, batch: int, ndim: int) -> NamedSharding:
    dp = data_axes(mesh)
    lead = _div(batch, mesh, dp)
    return NamedSharding(mesh, P(lead, *(None,) * (ndim - 1)))


def cache_shardings(cfg, cache_shapes, mesh: Mesh, batch: int):
    """Shard the KV cache: batch over data axes when divisible, otherwise
    the ring/sequence dim over 'data' (context parallelism, long_500k)."""
    dp = data_axes(mesh)
    m = "model"
    batch_ok = batch % axis_size(mesh, dp) == 0

    def fn(path, leaf):
        keys = tuple(str(getattr(p, "key", "")) for p in path)
        name = keys[-1] if keys else ""
        shp = tuple(leaf.shape)
        if name in ("k", "v"):            # [L,B,R,Hkv,hd]
            b_ax = dp if batch_ok else None
            seq_ax = None if batch_ok else _div(shp[2], mesh, "data")
            h_ax = _div(shp[3], mesh, m)
            hd_ax = None if h_ax else _div(shp[4], mesh, m)
            if opt("cache-seq-shard") and h_ax is None and seq_ax is None:
                # §Perf: when kv-heads don't divide the model axis, shard
                # the cache sequence instead of head_dim — attention then
                # all-reduces small score/output partials instead of
                # all-gathering the whole cache every layer
                seq_ax, hd_ax = _div(shp[2], mesh, m), None
            return NamedSharding(mesh, P(None, b_ax, seq_ax, h_ax, hd_ax))
        if name in ("ckv", "krope"):      # [L,B,R,r]
            b_ax = dp if batch_ok else None
            seq_ax = None if batch_ok else _div(shp[2], mesh, "data")
            return NamedSharding(mesh, P(None, b_ax, seq_ax, None))
        if name in ("enc_k", "enc_v"):    # [L,B,S_enc,H,hd]
            b_ax = dp if batch_ok else None
            return NamedSharding(mesh, P(None, b_ax, None,
                                         _div(shp[3], mesh, m), None))
        if name == "pos":                 # [B,R]
            b_ax = dp if batch_ok else None
            seq_ax = None if batch_ok else _div(shp[1], mesh, "data")
            return NamedSharding(mesh, P(b_ax, seq_ax))
        if name == "wkv":                 # [L,B,H,N,N]
            b_ax = dp if batch_ok else None
            return NamedSharding(mesh, P(None, b_ax,
                                         _div(shp[2], mesh, m), None, None))
        if name in ("sx_att", "sx_ffn"):  # [L,B,d]
            b_ax = dp if batch_ok else None
            return NamedSharding(mesh, P(None, b_ax, _div(shp[2], mesh, m)))
        if name == "h":                   # [L,B,dr]
            b_ax = dp if batch_ok else None
            return NamedSharding(mesh, P(None, b_ax, _div(shp[2], mesh, m)))
        if name == "conv":                # [L,B,cw-1,dr]
            b_ax = dp if batch_ok else None
            return NamedSharding(mesh, P(None, b_ax, None,
                                         _div(shp[3], mesh, m)))
        # length scalar and anything else: replicated
        return NamedSharding(mesh, P(*(None,) * len(shp)))

    return jax.tree_util.tree_map_with_path(fn, cache_shapes)


def replicated(mesh: Mesh, tree):
    return jax.tree.map(
        lambda l: NamedSharding(mesh, P(*(None,) * len(l.shape))), tree)
