"""Sharding rule engine: assigns PartitionSpecs to every parameter / cache /
activation from path-based logical rules with divisibility-checked fallbacks.

Strategy (DESIGN.md §5):
- TP over the ``model`` axis: attention heads, MLP hidden, vocab, experts (EP
  when the expert count divides the axis).
- FSDP (ZeRO-3) over the ``data`` axis: after TP assignment, the largest
  still-unsharded dimension that the data-axis size divides is sharded; XLA
  inserts the per-layer all-gathers (params) and reduce-scatters (grads).
- ``pod`` is an outer pure-DP axis: params replicated across pods, gradient
  all-reduce crosses pod links.
- Fallbacks are explicit: e.g. whisper (20 heads) and llama4-scout (40 heads)
  don't divide a 16-way model axis -> attention stays FSDP-only while the FFN
  is TP; decode KV caches whose kv-head count doesn't divide shard the
  *sequence* dim over ``model`` (flash-decoding style, XLA inserts the
  softmax-reduction collectives).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)        # works for Mesh and AbstractMesh


def dp_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_spec(mesh: Mesh):
    axes = dp_axes_of(mesh)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def tp_size(mesh: Mesh) -> int:
    return mesh_axes(mesh).get("model", 1)


def data_size(mesh: Mesh) -> int:
    return mesh_axes(mesh).get("data", 1)


# --------------------------------------------------------------------------- #
# parameter rules
# --------------------------------------------------------------------------- #

def _tp_col(cfg, mesh, n_heads_like: int) -> bool:
    """May a flattened heads*hd (or mlp/vocab) column dim go on `model`?"""
    return n_heads_like % tp_size(mesh) == 0


def param_spec(path: str, shape: Tuple[int, ...], cfg, mesh: Mesh,
               fsdp: bool = True) -> P:
    tp = tp_size(mesh)
    dsz = data_size(mesh)
    spec: list = [None] * len(shape)

    def put(dim: int, axis: str) -> bool:
        if dim < 0:
            dim += len(shape)
        if spec[dim] is None and shape[dim] % {"model": tp}.get(axis, 1) == 0:
            spec[dim] = axis
            return True
        return False

    heads_ok = cfg.num_heads % tp == 0
    kv_ok = cfg.num_kv_heads % tp == 0 if cfg.num_kv_heads else False
    ep = cfg.num_experts > 0 and cfg.num_experts % tp == 0

    if re.search(r"(embed|lm_head)$", path):
        put(-2, "model")                                   # vocab-sharded
    elif re.search(r"experts.*w[ug]$", path):
        # expert dim is -3 of (..., E, D, F) — layer stacking prepends dims,
        # so never index from the left (found the hard way: EP on dim 0
        # sharded the *layer* axis and forced full expert re-gathers)
        put(-3, "model") if ep else put(-1, "model")       # EP else expert TP
    elif re.search(r"experts.*wd$", path):
        put(-3, "model") if ep else put(-2, "model")
    elif re.search(r"router$", path):
        pass                                               # small, replicated
    elif re.search(r"attn.*w[q]$", path) or re.search(r"(^|/)w[rg]$", path):
        if heads_ok:
            put(-1, "model")
    elif re.search(r"attn.*w[kv]$", path):
        if kv_ok:
            put(-1, "model")
    elif re.search(r"attn.*wo$", path):
        if heads_ok:
            put(-2, "model")
    elif re.search(r"(ffn|shared_ffn|cm).*(wu|wg)$", path) or re.search(r"wu$", path):
        put(-1, "model")
    elif re.search(r"(ffn|shared_ffn|cm).*wd$", path) or re.search(r"wd$", path):
        put(-2, "model")
    elif re.search(r"out_proj$", path):
        put(-2, "model")                                   # mamba2 d_inner rows
    elif re.search(r"(^|/)(wk|wv|wo)$", path):             # rwkv time-mix
        if heads_ok:
            put(-1 if not path.endswith("wo") else -2, "model")
    # everything else (norms, conv, lora, biases, mix coeffs): replicated TP-wise

    if fsdp and dsz > 1:
        # ZeRO-3: shard the largest remaining dim divisible by the data size
        cands = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in cands:
            if spec[i] is None and shape[i] % dsz == 0 and shape[i] >= dsz:
                spec[i] = "data"
                break
    return P(*spec)


def make_param_specs(params_shapes: Any, cfg, mesh: Mesh,
                     fsdp: bool = True) -> Any:
    """params_shapes: pytree of ShapeDtypeStruct (from jax.eval_shape)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(param_spec(pstr, leaf.shape, cfg, mesh, fsdp))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# --------------------------------------------------------------------------- #
# cache rules (decode)
# --------------------------------------------------------------------------- #

def cache_spec(path: str, shape: Tuple[int, ...], cfg, mesh: Mesh) -> P:
    """KV / SSM caches. Layout conventions (leading layer-stack dim):
    k,v: (L, B, S, Hkv, hd); state: (L, B, H, hd, N); conv: (L, B, W, C);
    wkv: (L, B, H, hd, hd); shift: (L, B, D); xk/xv: (L, B, P, Hkv, hd);
    paged serving pools kpool/vpool: (L, NB, BS, Hkv*hd)."""
    tp = tp_size(mesh)
    dsz = data_size(mesh)
    dp = dp_spec(mesh)
    spec: list = [None] * len(shape)
    if re.search(r"(^|/)[kv]pool$", path) and len(shape) == 4:
        # paged pool: ONLY the Hkv*hd row may split, into whole kv heads.
        # Dim 1 is the physical block id — allocation is a host-side free
        # list and any block can belong to any request, so the block axis
        # must stay whole on every device (a block-sharded pool would turn
        # each table gather into a cross-device shuffle). Seq-dim fallback is likewise unavailable:
        # dim 2 is the *intra-block* offset, not a sequence.
        if cfg.num_kv_heads % tp == 0:
            spec[3] = "model"
        return P(*spec)
    if len(shape) >= 2 and shape[1] % max(dsz, 1) == 0 and dsz > 1:
        spec[1] = dp                                        # batch over data(+pod)
    if re.search(r"(^|/)(k|v|xk|xv)$", path) and len(shape) == 5:
        if cfg.num_kv_heads % tp == 0:
            spec[3] = "model"                               # kv heads
        elif shape[2] % tp == 0:
            spec[2] = "model"                               # seq (flash-decoding)
    elif re.search(r"(state|wkv)$", path) and len(shape) == 5:
        if shape[2] % tp == 0:
            spec[2] = "model"                               # ssm heads
    return P(*spec)


def make_cache_specs(cache_shapes: Any, cfg, mesh: Mesh) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(cache_spec(pstr, leaf.shape, cfg, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


# --------------------------------------------------------------------------- #
# activation constraints
# --------------------------------------------------------------------------- #

def current_mesh():
    """The ambient mesh that ``jax.set_mesh`` established, or None — one
    resolution path for every caller (training, serving, tests)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def model_sharded(fn, in_specs, out_specs):
    """``fn`` mapped shard-locally over the ambient mesh (``jax.shard_map``),
    or ``fn`` itself when there is no mesh or its ``model`` axis is 1.

    Pallas calls are opaque to GSPMD, so a kernel under a tensor-parallel
    mesh is mapped explicitly: ``in_specs``/``out_specs`` name which of its
    operands split over ``model``; the rest are replicated."""
    mesh = current_mesh()
    if mesh is None or tp_size(mesh) <= 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_act(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that degrades to a no-op when no mesh axes of
    the spec exist (single-device smoke tests) or dims don't divide."""
    mesh = current_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    clean = []
    for dim, entry in enumerate(spec):
        if entry is None:
            clean.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in sizes)
        total = 1
        for a in axes:
            total *= sizes[a]
        if axes and x.shape[dim] % total == 0:
            clean.append(axes if len(axes) > 1 else axes[0])
        else:
            clean.append(None)
    if all(c is None for c in clean):
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))


# --------------------------------------------------------------------------- #
# serving (tensor-parallel engine)
# --------------------------------------------------------------------------- #

def make_serving_mesh(tp: int):
    """A 1-D tensor-parallel mesh over the first ``tp`` local devices.

    The serving engine has no data axis: the continuous batch is scheduled
    host-side and every device sees every request, so the only mesh axis is
    ``model`` (attention heads / FFN hidden / vocab / KV-head pool axis)."""
    devices = jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} exceeds the {len(devices)} visible devices "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"for CPU testing)")
    return Mesh(np.asarray(devices[:tp]), ("model",))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated NamedSharding — host-controlled serving state (block
    tables, seq lens, sampled tokens, sampling knobs) stays whole on every
    device so the scheduler never pays a layout shuffle for it."""
    return NamedSharding(mesh, P())


def make_paged_pool_shardings(cfg, mesh: Mesh, num_blocks: int,
                              block_size: int):
    """NamedShardings for the serving engine's paged KV pools, via the same
    ``cache_spec`` rules the training/decode caches use (kpool/vpool split
    the Hkv*hd row into whole kv heads over ``model``; the block axis stays
    whole)."""
    shape = (cfg.num_layers, num_blocks, block_size,
             cfg.num_kv_heads * cfg.resolved_head_dim)
    return {name: NamedSharding(mesh, cache_spec(name, shape, cfg, mesh))
            for name in ("kpool", "vpool")}


def serving_jit_shardings(mesh: Mesh, param_shardings, pool_shardings,
                          n_host_args: int, n_rep_outs: int) -> Dict:
    """``jax.jit`` sharding kwargs for a serving entrypoint of the canonical
    shape ``fn(params, pools, *host_args) -> (*rep_outs, pools)``.

    Params keep their TP layout, pools keep theirs (donation-compatible:
    identical in/out sharding), and everything else — block tables, seq
    lens, tokens, PRNG keys, sampling knobs in; sampled tokens / logits
    out — is replicated, so the only per-step host transfer is the sampled
    token row the engine actually reads back."""
    rep = replicated(mesh)
    return dict(
        in_shardings=(param_shardings, pool_shardings)
        + (rep,) * n_host_args,
        out_shardings=(rep,) * n_rep_outs + (pool_shardings,))


def batch_spec(ndim: int, mesh: Mesh, batch_size: int = 0) -> P:
    """Leading-dim DP sharding; falls back toward fewer axes (then replication)
    when the batch doesn't divide (e.g. long_500k's global_batch=1)."""
    axes = dp_axes_of(mesh)
    sizes = mesh_axes(mesh)
    while axes:
        total = 1
        for a in axes:
            total *= sizes[a]
        if batch_size == 0 or batch_size % total == 0:
            dp = axes if len(axes) > 1 else axes[0]
            return P(dp, *([None] * (ndim - 1)))
        axes = axes[1:]
    return P(*([None] * ndim))
