"""Sharding rules: logical model axes → mesh axes (MaxText-style, by path).

Meshes: single-pod ``("data", "model") = (16, 16)``; multi-pod adds a leading
``"pod"`` axis that joins the data-parallel group. Rules are
divisibility-aware: a dim that doesn't divide by the candidate axis size falls
back to the next candidate (or replication), so the same rules drive every
(arch × shape) cell, including awkward ones (e.g. 8 KV heads on a 16-way
model axis → the cache shards its sequence dim instead).

Three parameter modes:
  * tp        — weights TP-sharded over "model", replicated over data
  * fsdp      — additionally shard the largest replicated dim over "data"
                (ZeRO-3 for params; required for ≥ 17B assigned archs)
Optimizer state always gets the fsdp treatment (ZeRO-1 minimum).
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return dim % axis_size(mesh, axes) == 0


def shard_dim(dim: int, mesh: Mesh, candidates) -> Optional[Any]:
    """First candidate axis (or axis tuple) whose size divides ``dim``."""
    for c in candidates:
        if c is None:
            return None
        if _fits(dim, mesh, c):
            return c
    return None


# --------------------------------------------------------------------- params
# (regex on the param path, per-dim logical role). Roles: "model" candidates
# try TP; "fsdp" dims are where ZeRO sharding lands.
_PARAM_RULES: list[tuple[str, tuple[str, ...]]] = [
    (r"embed/table$", ("model", "fsdp")),          # (V, d): vocab-TP
    (r"unembed/table$", ("model", "fsdp")),
    (r"(attn|cross)/(q|k|v)/w$", ("fsdp", "model")),   # (d, H*hd): head-TP
    (r"(attn|cross)/(q|k|v)/b$", ("model",)),
    (r"(attn|cross)/o/w$", ("model", "fsdp")),         # (H*hd, d)
    (r"(attn|cross)/o/b$", (None,)),
    # --- MLA
    (r"attn/q_down/w$", ("fsdp", None)),
    (r"attn/q_up/w$", (None, "model")),
    (r"attn/kv_down/w$", ("fsdp", None)),
    (r"attn/(k_up|v_up)$", ("model", None, None)),     # (H, r, hd)
    # --- FFN / MoE
    (r"ffn/(gate|up)/w$", ("fsdp", "model")),
    (r"ffn/down/w$", ("model", "fsdp")),
    (r"ffn/(gate|up|down)/b$", (None,)),
    (r"ffn/router/w$", (None, None)),
    (r"ffn/(gate|up)$", ("model", "fsdp", None)),      # (E, d, ff): EP
    (r"ffn/down$", ("model", "fsdp", None)),           # (E, ff, d)
    # --- Mamba
    (r"mixer/in_proj/w$", ("fsdp", "model")),
    (r"mixer/conv_w$", (None, "model")),
    (r"mixer/conv_b$", ("model",)),
    (r"mixer/x_proj/w$", ("model", None)),
    (r"mixer/dt_proj/w$", (None, "model")),
    (r"mixer/dt_bias$", ("model",)),
    (r"mixer/A_log$", ("model", None)),
    (r"mixer/D$", ("model",)),
    (r"mixer/out_proj/w$", ("model", "fsdp")),
    # --- RWKV
    (r"mixer/(r|k|v|g)/w$", ("fsdp", "model")),
    (r"mixer/o/w$", ("model", "fsdp")),
    (r"mixer/(cm_k|cm_r)/w$", ("fsdp", "model")),
    (r"mixer/cm_v/w$", ("model", "fsdp")),
    (r"mixer/wA$", ("fsdp", None)),
    (r"mixer/wB$", (None, "model")),
    (r"mixer/(w0|u)$", ("model",)),
    (r"mixer/ln_scale$", ("model", None)),
    (r"mixer/(mu|cm_mu)$", (None, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _spec_for(path: str, shape: tuple[int, ...], mesh: Mesh, *,
              fsdp: bool, stacked: bool) -> P:
    roles: Optional[tuple] = None
    for pat, r in _PARAM_RULES:
        if re.search(pat, path):
            roles = r
            break
    ndim = len(shape)
    offset = 1 if stacked else 0         # leading n_periods axis
    spec: list = [None] * ndim
    if roles is not None:
        used_data = False
        for i, role in enumerate(roles):
            di = i + offset
            if di >= ndim or role is None:
                continue
            if role == "model":
                if _fits(shape[di], mesh, "model"):
                    spec[di] = "model"
            elif role == "fsdp" and fsdp and not used_data:
                dax = batch_axes(mesh)
                if dax and _fits(shape[di], mesh, dax):
                    spec[di] = dax if len(dax) > 1 else dax[0]
                    used_data = True
    return P(*spec)


def param_pspecs(params: PyTree, mesh: Mesh, *, fsdp: bool = False) -> PyTree:
    """PartitionSpec tree matching ``params`` (works on ShapeDtypeStructs)."""

    def fn(path, leaf):
        ps = _path_str(path)
        stacked = "blocks" in ps
        return _spec_for(ps, leaf.shape, mesh, fsdp=fsdp, stacked=stacked)

    return jax.tree_util.tree_map_with_path(fn, params)


def zero_pspecs(params: PyTree, mesh: Mesh) -> PyTree:
    """Optimizer-state sharding: params rules + forced fsdp (ZeRO)."""
    return param_pspecs(params, mesh, fsdp=True)


# --------------------------------------------------------------------- batch
def batch_pspecs(batch: PyTree, mesh: Mesh) -> PyTree:
    bax = batch_axes(mesh)

    def fn(leaf):
        if leaf.ndim == 0:
            return P()
        b = leaf.shape[0]
        ax = shard_dim(b, mesh, [bax, bax[-1:] if bax else None, None])
        if ax is not None and not isinstance(ax, str) and len(ax) == 1:
            ax = ax[0]
        return P(ax, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(fn, batch)


# --------------------------------------------------------------------- cache
def cache_pspecs(cache: PyTree, mesh: Mesh) -> PyTree:
    """Decode-cache sharding: batch over data axes; heads over model when
    divisible, else the sequence (page) dim; SSM states shard their channel
    dim. Leaves have a leading n_periods stack axis."""
    bax = batch_axes(mesh)

    def fn(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape           # (n_periods, B, ...)
        spec: list = [None] * len(shape)
        b = shape[1]
        ax = shard_dim(b, mesh, [bax, bax[-1:] if bax else None, None])
        if ax is not None and not isinstance(ax, str) and len(ax) == 1:
            ax = ax[0]
        spec[1] = ax
        if re.search(r"(^|/)(k|v|xk|xv)$", ps):
            # (L, B, Hkv, hd, S): models/attention.py's cache layout
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
            elif _fits(shape[4], mesh, "model"):
                spec[4] = "model"
        elif re.search(r"/(c|kr)$", ps):           # MLA latent (L, B, S, r)
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
        elif ps.endswith("/ssm"):                  # (L, B, di, ds)
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
        elif ps.endswith("/conv"):                 # (L, B, K-1, di)
            if _fits(shape[3], mesh, "model"):
                spec[3] = "model"
        elif ps.endswith("/S"):                    # rwkv (L, B, H, N, N)
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
        elif ps.endswith(("/tm_x", "/cm_x")):      # (L, B, d)
            if _fits(shape[2], mesh, "model"):
                spec[2] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(fn, cache)


def to_shardings(pspecs: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------- activation constraints
# §Perf iteration: without explicit constraints XLA's sharding propagation
# all-gathers layer activations across the model axis (TB/step at the 4k
# train shapes). The launchers opt in via set_activation_mesh(mesh); model
# code calls constrain(x, "batch", None, "model") with logical roles that
# degrade to replication when a dim doesn't divide.
_ACT_MESH: Optional[Mesh] = None


def set_activation_mesh(mesh: Optional[Mesh]) -> None:
    global _ACT_MESH
    _ACT_MESH = mesh


MIN_CONSTRAIN_ELEMS = 1 << 22   # don't pin small (decode-sized) tensors


def constrain(x, *roles):
    """Apply with_sharding_constraint by logical dim roles.

    Roles: "batch" → ("pod","data"); "model" → "model"; None / non-divisible
    dims stay UNCONSTRAINED (never force replication — forcing P(None) on a
    non-divisible head dim was a measured regression: whisper prefill 2.5×
    worse, §Perf iteration 2 postmortem). Tensors under ~4M elements are left
    alone (single-token decode paths must not be re-sharded per layer).
    No-op outside an activation mesh (tests, single-device runs).
    """
    mesh = _ACT_MESH
    if mesh is None or x.ndim != len(roles) or x.size < MIN_CONSTRAIN_ELEMS:
        return x
    spec = []
    pinned = False
    for dim, role in zip(x.shape, roles):
        ax = P.UNCONSTRAINED
        if role == "batch":
            cand = [batch_axes(mesh), batch_axes(mesh)[-1:], None]
            got = shard_dim(dim, mesh, [c for c in cand if c])
            if got is not None:
                ax = got[0] if len(got) == 1 else got
                pinned = True
        elif role == "model" and _fits(dim, mesh, "model"):
            ax = "model"
            pinned = True
        spec.append(ax)
    if not pinned:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
