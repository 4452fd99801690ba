"""Per-family sharding rules (PartitionSpec trees) for the production mesh.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod (launch/mesh.py).  Batch always shards over ``("pod", "data")``;
weights shard over ``"model"`` following Megatron-style tensor parallelism
where divisibility allows, with documented fallbacks:

  * attention heads shard over model iff ``n_heads % model == 0`` (and KV
    heads shard with GSPMD padding whenever ``n_kv_heads >= 2``); archs
    with 12/15 heads keep attention weights replicated and rely on FFN TP
    (recorded per arch in EXPERIMENTS.md §Dry-run).
  * MoE experts shard over model on the expert axis (EP): 60 routed
    experts are padded to 64 slots (qwen2-moe) so EP=16 divides.
  * embedding/lm_head shard the vocab over model.
  * ZeRO-1: optimizer moments/master shard over "data" along each param's
    largest model-unsharded divisible axis (zero_opt_specs).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.transformer import TransformerConfig


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: every caller in
    this repo wants the check disabled (psum-carrying train steps)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def batch_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else None


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P) or x is None)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def lm_param_specs(cfg: TransformerConfig, mesh: Mesh) -> dict:
    # 4-D projections (L, d, H, dh): shard the HEAD axis when it divides
    # the model axis.  jit *arguments* must shard evenly (unlike
    # intermediates, which GSPMD pads), so odd head counts (smollm: 15,
    # qwen2-1.5b: 12 q / 2 kv) keep the (small) attention weights
    # replicated — the per-rank compute slicing still happens through the
    # attn_head_axis constraint on the q/k/v activations.
    m = axis_size(mesh, "model")
    q_ok = cfg.n_heads % m == 0
    kv_ok = cfg.n_kv_heads % m == 0
    attn_col_q = P(None, None, "model", None) if q_ok else P(None, None, None, None)
    attn_col_kv = P(None, None, "model", None) if kv_ok else P(None, None, None, None)
    attn_row = P(None, "model", None, None) if q_ok else P(None, None, None, None)
    attn_bias_q = P(None, "model", None) if q_ok else P(None, None, None)
    attn_bias_kv = P(None, "model", None) if kv_ok else P(None, None, None)

    layers: dict = {
        "attn_norm_scale": P(None, None),
        "ffn_norm_scale": P(None, None),
        "wq": attn_col_q, "wk": attn_col_kv, "wv": attn_col_kv,
        "wo": attn_row,
    }
    if cfg.norm == "layernorm":
        layers["attn_norm_bias"] = P(None, None)
        layers["ffn_norm_bias"] = P(None, None)
    if cfg.qkv_bias:
        layers["bq"] = attn_bias_q
        layers["bk"] = attn_bias_kv
        layers["bv"] = attn_bias_kv
    if cfg.moe:
        layers["router"] = P(None, None, None)
        layers["we_gate"] = P(None, "model", None, None)   # expert parallel
        layers["we_up"] = P(None, "model", None, None)
        layers["we_down"] = P(None, "model", None, None)
        if cfg.n_shared_experts:
            layers["ws_gate"] = P(None, None, "model")
            layers["ws_up"] = P(None, None, "model")
            layers["ws_down"] = P(None, "model", None)
            if cfg.shared_expert_gate:
                layers["shared_gate"] = P(None, None, None)
    else:
        layers["w_gate"] = P(None, None, "model")
        layers["w_up"] = P(None, None, "model")
        layers["w_down"] = P(None, "model", None)

    specs: dict = {
        "embed": P("model", None),
        "final_norm_scale": P(None),
        "layers": layers,
    }
    if cfg.norm == "layernorm":
        specs["final_norm_bias"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    return specs


def lm_batch_spec(mesh: Mesh) -> P:
    return P(batch_axes(mesh), None)


def lm_cache_specs(cfg: TransformerConfig, mesh: Mesh, batch: int) -> dict:
    """KV cache: stacked [L, B, Smax, Hkv, dh] (scan mode) or a tuple of
    per-layer [B, Smax, Hkv, dh] buffers (unrolled mode)."""
    b_axes = batch_axes(mesh)
    total_b = 1
    for a in (b_axes or ()):
        total_b *= axis_size(mesh, a)
    b_ax = b_axes if (b_axes and batch % total_b == 0) else None
    # the cache is a jit *argument*: head axis shards only when it divides
    kv_ax = "model" if cfg.n_kv_heads % axis_size(mesh, "model") == 0 else None
    if cfg.unroll_layers:
        layer = P(b_ax, None, kv_ax, None)
        kv = tuple(layer for _ in range(cfg.n_layers))
    else:
        kv = P(None, b_ax, None, kv_ax, None)
    return {"k": kv, "v": kv, "len": P()}


# ---------------------------------------------------------------------------
# Streamed CSR shards (data/graph_stream.py)
# ---------------------------------------------------------------------------

def host_submesh(mesh: Optional[Mesh], process_index: int = 0,
                 process_count: int = 1) -> Optional[Mesh]:
    """One process's contiguous slice of the ``"data"`` axis.

    Multi-host streaming places each host's shards on the devices that
    host actually drives: the data axis is cut into ``process_count``
    equal runs and process ``i`` gets run ``i``.  Falls back to the full
    mesh when the axis is absent or does not divide (every process then
    places onto the shared mesh, which is also what the single-device
    in-process simulator exercises).
    """
    if mesh is None or process_count <= 1:
        return mesh
    if not 0 <= process_index < process_count:
        raise ValueError(
            f"process_index {process_index} not in [0, {process_count})")
    if "data" not in mesh.axis_names:
        return mesh
    d = axis_size(mesh, "data")
    if d % process_count:
        return mesh
    axis = list(mesh.axis_names).index("data")
    per = d // process_count
    idx = [slice(None)] * mesh.devices.ndim
    idx[axis] = slice(process_index * per, (process_index + 1) * per)
    return Mesh(mesh.devices[tuple(idx)], mesh.axis_names)


def stream_shard_placement(mesh: Optional[Mesh], n_edges: int, *,
                           process_index: int = 0, process_count: int = 1
                           ) -> tuple[Optional[NamedSharding],
                                      Optional[NamedSharding]]:
    """(neighbors, offsets) shardings for one streamed CSR partition.

    Neighbor arrays are the bulk payload and shard over the ``"data"`` axis
    (edge-parallel consumers: gather/segment-sum in models/gnn); the small
    per-partition offset arrays replicate.  device_put needs evenly
    divisible shards, so a partition whose edge count does not divide the
    data axis falls back to replication — the streaming loader pads to
    STREAM_GRANULE_IDS buckets precisely so the common case divides.

    ``process_index``/``process_count`` restrict placement to the calling
    host's :func:`host_submesh` slice of the data axis.
    """
    mesh = host_submesh(mesh, process_index, process_count)
    if mesh is None:
        return None, None
    axis = "data" if "data" in mesh.axis_names else None
    d = axis_size(mesh, axis) if axis else 1
    nbr_spec = P(axis) if axis and d > 1 and n_edges % d == 0 else P(None)
    return (NamedSharding(mesh, nbr_spec), NamedSharding(mesh, P(None)))


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def gnn_specs(mesh: Mesh, batch_like: dict) -> dict:
    """Edge arrays shard over every axis (flattened); node/feature arrays
    shard rows over ("data", "model"); small per-graph arrays replicate."""
    all_axes = tuple(mesh.axis_names)
    node_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)

    def spec_for(key: str, arr) -> P:
        if key.startswith("edge_") or key.startswith("triplet_"):
            return P(all_axes) if arr.ndim == 1 else P(all_axes, None)
        if key in ("x", "pos"):
            return P(node_axes, None)
        if key in ("labels", "label_mask", "graph_id", "node_mask"):
            return P(node_axes)
        if key == "targets":
            return P(None, None) if arr.ndim == 2 else P(None)
        return P(*([None] * arr.ndim))

    return {k: spec_for(k, v) for k, v in batch_like.items()
            if hasattr(v, "ndim")}


# ---------------------------------------------------------------------------
# Recsys (DIN)
# ---------------------------------------------------------------------------

def din_param_specs(mesh: Mesh) -> dict:
    return {
        "item_table": P("model", None),   # 10M rows row-sharded
        "cate_table": P("model", None),
        "attn": None,                     # small MLPs replicated (filled below)
        "mlp": None,
    }


def din_specs(params_like: dict, mesh: Mesh) -> dict:
    specs = {
        "item_table": P("model", None),
        "cate_table": P("model", None),
        "attn": jax.tree.map(lambda x: P(*([None] * x.ndim)), params_like["attn"]),
        "mlp": jax.tree.map(lambda x: P(*([None] * x.ndim)), params_like["mlp"]),
    }
    return specs


def din_batch_specs(mesh: Mesh, batch_like: dict) -> dict:
    b_axes = batch_axes(mesh)
    all_axes = tuple(mesh.axis_names)

    def spec_for(key: str, arr) -> P:
        if key.startswith("cand_") and arr.ndim == 1 and arr.shape[0] >= 1024:
            # retrieval: candidate axis shards over everything
            return P(all_axes)
        if key.startswith("hist_") and arr.ndim == 1:
            # retrieval: one user's history, replicated
            return P(None)
        lead = b_axes
        return P(lead, *([None] * (arr.ndim - 1)))

    return {k: spec_for(k, v) for k, v in batch_like.items() if hasattr(v, "ndim")}


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding
# ---------------------------------------------------------------------------

def zero_spec(shape: tuple, spec: P, mesh: Mesh) -> P:
    """Add "data" sharding on the largest axis not already sharded."""
    d = axis_size(mesh, "data")
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best, best_size = None, 0
    for i, (s, n) in enumerate(zip(entries, shape)):
        if s is None and n % d == 0 and n > best_size:
            best, best_size = i, n
    if best is not None:
        entries[best] = "data"
    return P(*entries)


def zero_opt_specs(params_like: Any, param_specs: Any, mesh: Mesh) -> dict:
    """Optimizer-state spec tree for optim.adamw state (step/m/v/master)."""
    flat_p, treedef = jax.tree.flatten(params_like)
    flat_s = jax.tree.flatten(param_specs,
                              is_leaf=lambda x: isinstance(x, P))[0]
    assert len(flat_p) == len(flat_s), (len(flat_p), len(flat_s))
    mv = treedef.unflatten(
        [zero_spec(p.shape, s, mesh) for p, s in zip(flat_p, flat_s)])
    return {"step": P(), "m": mv, "v": mv, "master": mv}
