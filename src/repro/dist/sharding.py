"""Path+shape-driven sharding rule engine for the pod-scale meshes.

Maps every parameter / batch / cache leaf of the model zoo onto a
PartitionSpec over the production meshes (``("data", "model")`` single-pod,
``("pod", "data", "model")`` multi-pod — see repro.launch.mesh). Rules key
on the leaf's *path name* (the row/col-parallel naming convention of
repro.models.layers) and validate against its *shape*: an axis is only ever
assigned to a dim it divides, falling back down a per-leaf preference chain
and ultimately to replication (indivisible dims such as odd vocabs).

Conventions (documented in docs/ARCHITECTURE.md):

* Stacked leading dims (the scanned ``n_blocks`` / ``dense`` /
  ``encoder`` / ``cross`` layer stacks, and the federated per-pod stack)
  are never sharded.
* **Column-parallel** (model axis on the *output* dim, data/FSDP on the
  input dim): ``wq wk wv w_dkv w_uk w_uv w_gate w_up head router``.
* **Row-parallel** (model axis on the *input* dim, data on the output):
  ``wo w_down``.
* **SSM mixer** (``in_proj out_proj conv_w`` + conv/ssm cache): data/FSDP
  only, never the model axis — its fused channel dim is split/concatenated
  at tile-misaligned boundaries, which the jax 0.4.37 partitioner
  miscompiles (see ``_SSM_DATA_ONLY``).
* **Expert weights** (rank 3 after the stack dim): expert-parallel — model
  axis on the expert dim — when ``n_experts % model == 0``, else
  tensor-parallel inside each expert with the col/row rule above.
* ``embed`` ``(vocab, d)``: model on vocab, data on d; an indivisible vocab
  moves the model axis onto d.
* 1-D leaves (norm scales, biases, A_log/D/dt_bias) are replicated.
* Batches shard the batch dim over data; a batch of 1 (long-context) falls
  back to sequence sharding.
* Caches: the n_blocks stack dim is never sharded; batch (else sequence)
  over data; heads/state-channel dims over model.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "spec_for_leaf",
    "param_specs",
    "opt_specs",
    "batch_specs",
    "cache_specs",
    "serve_arg_specs",
    "named",
]

# Leaf names (last path component) keyed to their parallelism role.
_COL = frozenset(
    {"wq", "wk", "wv", "w_dkv", "w_uk", "w_uv", "w_gate", "w_up",
     "head", "router"}
)
_ROW = frozenset({"wo", "w_down"})
# SSM mixer leaves stay OFF the model axis (data/FSDP only): the mamba path
# splits and re-concatenates its fused channel dim (z|x|B|C|dt, then
# x|B|C around the conv) at boundaries that don't align with model-axis
# tiles, and the jax 0.4.37 SPMD partitioner miscompiles misaligned
# slices/concats of tiled operands (verified on the CPU backend: crossing
# segments return garbage). Sharding back-propagation re-tiles these
# tensors even when only a *neighbouring* leaf is model-sharded, so the
# whole mixer must be model-replicated; attention/FFN blocks carry the
# tensor parallelism. (MLA's two fused splits have no re-concat and are
# protected by the replication guard in layers._mla_qkv instead.)
_SSM_DATA_ONLY = frozenset({"in_proj", "out_proj", "conv_w"})

# Param-tree roots whose leaves carry a leading scanned-layer stack dim.
_STACKED_ROOTS = frozenset({"blocks", "dense", "encoder", "cross"})


def _sizes(mesh) -> dict:
    """Axis name -> size for Mesh and AbstractMesh alike."""
    return dict(mesh.shape)


def _assign(shape, prefs, sizes) -> P:
    """Greedy placement: each (axis, candidate dims) pair lands on the first
    free dim the axis size divides; axes absent from the mesh are skipped."""
    spec: list = [None] * len(shape)
    for ax, cands in prefs:
        size = sizes.get(ax)
        if not size:
            continue
        for d in cands:
            if spec[d] is None and shape[d] % size == 0:
                spec[d] = ax
                break
    return P(*spec)


def spec_for_leaf(path: str, shape: tuple, mesh, n_stack: int = 0) -> P:
    """PartitionSpec for one param leaf.

    path: "/"-joined pytree path (e.g. "blocks/slot0/mixer/wq").
    n_stack: number of leading stacked dims (never sharded).
    """
    sizes = _sizes(mesh)
    name = path.rsplit("/", 1)[-1]
    nd = len(shape)
    free = nd - n_stack
    if free <= 1:
        # Norm scales, biases, A_log/D/dt_bias, scalars: replicated.
        return P(*([None] * nd))
    in_pos, out_pos = nd - 2, nd - 1
    if name == "embed":
        # (vocab, d): model prefers the vocab dim; odd vocabs fall back to d.
        prefs = [("model", [in_pos, out_pos]), ("data", [out_pos])]
    elif name in _SSM_DATA_ONLY:
        # Mamba mixer: model-replicated (see _SSM_DATA_ONLY above); FSDP
        # keeps the matmul weights data-sharded on their non-fused dim.
        if name == "conv_w":
            return P(*([None] * nd))
        prefs = [("data", [in_pos if name == "in_proj" else out_pos])]
    elif name in _COL or name in _ROW:
        model_first = out_pos if name in _COL else in_pos
        model_second = in_pos if name in _COL else out_pos
        data_dim = in_pos if name in _COL else out_pos
        model_pref = [model_first, model_second]
        if free == 3:
            # MoE expert stack (E, d_in, d_out): expert-parallel when the
            # model-axis size divides the expert count (E % model == 0),
            # else tensor-parallel inside each expert.
            model_pref = [n_stack] + model_pref
        prefs = [("model", model_pref), ("data", [data_dim])]
    else:
        # Unknown >=2-D leaf: replicate rather than guess.
        return P(*([None] * nd))
    return _assign(shape, prefs, sizes)


def _key_str(k) -> str:
    return str(getattr(k, "key", getattr(k, "idx", k)))


def param_specs(params: Any, mesh, fed_axis: str | None = None) -> Any:
    """PartitionSpec pytree mirroring ``params`` leaf-for-leaf.

    fed_axis: prepend this mesh axis to every spec — the specs then address
    the *per-pod stacked* tree ``(n_pods, *leaf.shape)`` used by the
    federated gossip/train steps (callers pass the unstacked tree here).
    """

    def one(kp, leaf):
        parts = [_key_str(k) for k in kp]
        n_stack = 1 if parts and parts[0] in _STACKED_ROOTS else 0
        spec = spec_for_leaf("/".join(parts), leaf.shape, mesh, n_stack)
        if fed_axis is not None:
            spec = P(fed_axis, *tuple(spec))
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def opt_specs(params: Any, mesh, fed_axis: str | None = None) -> Any:
    """PartitionSpecs for *optimizer-state* mirrors of ``params`` (momentum
    velocities, Adam moments, fp32 master copies).

    Optimizer state joins no matmul — it is only read and written
    elementwise in the update — so it is free to shard where the params
    cannot: wherever the param rules fall back to full replication (1-D
    norm scales/biases, indivisible dims, the SSM conv weights), the state
    leaf is ZeRO-style sharded over the ``data`` axis on the first dim it
    divides (including stacked leading dims, which ARE shardable here: the
    scan-carry constraint that pins them for params does not apply to a
    zeros_like mirror). Leaves whose param spec already uses a mesh axis
    keep it unchanged, so the elementwise update stays collective-free.

    This is what lets fp32 masters + 8-bit moments (2-6x the bf16 param
    bytes) live on a mesh whose params are memory-bound: at bf16 params /
    fp32+fp32 momentum state, replicated state would triple the replicated
    footprint.
    """
    sizes = _sizes(mesh)

    def one(kp, leaf):
        parts = [_key_str(k) for k in kp]
        n_stack = 1 if parts and parts[0] in _STACKED_ROOTS else 0
        spec = spec_for_leaf("/".join(parts), leaf.shape, mesh, n_stack)
        if all(ax is None for ax in spec):
            dsize = sizes.get("data")
            if dsize:
                upgraded: list = [None] * len(leaf.shape)
                for d, dim in enumerate(leaf.shape):
                    if dim % dsize == 0:
                        upgraded[d] = "data"
                        break
                spec = P(*upgraded)
        if fed_axis is not None:
            spec = P(fed_axis, *tuple(spec))
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def batch_specs(batch: Any, mesh, fed_axis: str | None = None) -> Any:
    """Batch leaves shard dim 0 over data; batch=1 long-context falls back
    to sequence sharding (dim 1). With ``fed_axis`` the leading federated
    group dim is sharded over that axis first."""
    sizes = _sizes(mesh)

    def one(leaf):
        shape = tuple(leaf.shape)
        lead: list = []
        if fed_axis is not None:
            ok = sizes.get(fed_axis) and shape and shape[0] % sizes[fed_axis] == 0
            lead = [fed_axis if ok else None]
            shape = shape[1:]
        spec: list = [None] * len(shape)
        dsize = sizes.get("data")
        if dsize and shape:
            if shape[0] % dsize == 0:
                spec[0] = "data"
            elif len(shape) > 1 and shape[1] % dsize == 0:
                spec[1] = "data"
        return P(*lead, *spec)

    return jax.tree_util.tree_map(one, batch)


# Decode-cache rules: absolute dim positions (incl. the n_blocks stack dim
# at 0, which is never sharded) per leaf name — shapes per models/layers.py.
# Dims that RoPE splits in half (head_dim, k_rope) and MLA's latent rank are
# never model-sharded: tiled split/concat + scatter on those dims is
# miscompiled by the jax 0.4.37 partitioner (see _SSM_DATA_ONLY) — model
# parallelism on caches lives on the kv-heads dim only.
_CACHE_PREFS = {
    # (n_blocks, B, S, kv_heads, head_dim)
    "k": [("data", (1, 2)), ("model", (3,))],
    "v": [("data", (1, 2)), ("model", (3,))],
    # (n_blocks, B, S, rank)
    "c_kv": [("data", (1, 2))],
    "k_rope": [("data", (1, 2))],
    # (n_blocks, B, d_conv-1, conv_channels) — channels never model-sharded:
    # they are the fused x|B|C concat (see _SSM_DATA_ONLY).
    "conv": [("data", (1,))],
    # (n_blocks, B, n_heads, head_dim, state) — model-replicated with the
    # rest of the SSM mixer.
    "ssm": [("data", (1,))],
}


def cache_specs(cache: Any, mesh) -> Any:
    """PartitionSpecs for a decode cache pytree (see T.init_cache).

    The batch (slot) dim rides ``data``; the sequence-dim fallback is taken
    ONLY for batch==1 (the long-context dry-run/analysis shapes): the serve
    engine scatters new k/v at runtime slots along S, and scatter/concat on
    a tiled dim is miscompiled by the 0.4.37 partitioner (see
    ``_SSM_DATA_ONLY``) — an indivisible multi-slot batch replicates
    instead."""
    sizes = _sizes(mesh)

    def one(kp, leaf):
        name = _key_str(kp[-1]) if kp else ""
        shape = tuple(leaf.shape)
        if name == "enc_out":  # (B, enc_len, d)
            return _assign(shape, [("data", (0,)), ("model", (2,))], sizes)
        prefs = _CACHE_PREFS.get(name)
        if prefs is None or not shape:  # "pos" scalar and unknown leaves
            return P(*([None] * len(shape)))
        batch = shape[1] if len(shape) > 1 else 0
        prefs = [(ax, [d for d in dims if d < len(shape)
                       and not (ax == "data" and d == 2 and batch != 1)])
                 for ax, dims in prefs]
        return _assign(shape, prefs, sizes)

    return jax.tree_util.tree_map_with_path(one, cache)


def serve_arg_specs(args: Any, mesh) -> Any:
    """Specs for the serve engine's per-step host arrays (token (B,1),
    positions/n_valid/active/temps (B,)): the slot dim rides the ``data``
    axis — matching the cache's batch-dim sharding, so slot-indexed
    scatters stay local — and replicates when it does not divide."""
    sizes = _sizes(mesh)

    def one(leaf):
        shape = tuple(leaf.shape)
        spec: list = [None] * len(shape)
        dsize = sizes.get("data")
        if dsize and shape and shape[0] % dsize == 0:
            spec[0] = "data"
        return P(*spec)

    return jax.tree_util.tree_map(one, args)


def named(specs: Any, mesh) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree over ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
