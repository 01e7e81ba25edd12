"""Layouts: first-class `LayoutSpec` objects + the layout registry.

A *layout* fixes, for every switchable tensor, which `model`-axis rank owns
which slice. All layouts compute the same function over byte-identical
global state (paper §3). Non-switchable tensors (embeddings, dense MLP,
norms) keep one layout-independent sharding.

A `LayoutSpec` owns the three contracts a layout must define:
  * batch/slot geometry  — are decode slots replicated over the model axis
    (TP-style) or rank-sharded (EP-style); prefill batch width; the rounding
    quantum of the decode batch-size ladder;
  * KV ownership         — which unified-buffer view KV lives in ("ep":
    per-rank page pools with `owner_rank >= 0`; "tp": one pooled,
    head-sliced pool with `owner_rank == -1`) and the resulting `kv_rep`
    capacity penalty;
  * expert sharding      — packing rule ("tp" width-slices every expert,
    "ep" gives each rank whole experts) and the mesh extent of the expert
    shard (the switch group vs the FULL data x model mesh).

The engine, page allocator, step builders, and switch executor dispatch
through these spec attributes; a switch is planned between *any ordered
pair* of registered specs (core/switch.py). `TP`/`EP`/`TPEP` are the
registered specs themselves — `LayoutSpec` subclasses `str`, so legacy
string call sites (`layout == "tp"`, dict keys, json) keep working.

Key helpers:
  * GroupInfo        — head/replication arithmetic for the G-rank group
  * param_specs      — PartitionSpec pytree for a layout (GSPMD path)
  * pack_params      — global init params -> layout-specific stored form
                       (rank-major experts; padded vocab)
  * attn_rank_major  — decode-path attention weights expanded to (G, ...) with
                       head-block replication when heads < G (wo pre-scaled by
                       1/q_rep so the group psum is exact)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.moe import (ExpertLayout, make_expert_layout, pack_experts,
                              pack_w13)


# ---------------------------------------------------------------------------
# LayoutSpec + registry
# ---------------------------------------------------------------------------

class LayoutSpec(str):
    """Frozen first-class layout description.

    A `str` subclass: the spec *is* its registered name, so it drops into
    every legacy call site (dict keys, `json.dumps`, `make_expert_layout`)
    unchanged, while new code dispatches on the attributes below instead of
    string compares. Instances are immutable after construction and interned
    in the registry, so identity checks (`spec is TP`) are valid once a name
    has been resolved through `get_layout`.
    """

    # NOTE: no __slots__ — CPython forbids nonempty __slots__ on str
    # subclasses; immutability is enforced by the __setattr__ override.
    _FIELDS = ("slots_sharded", "kv_view", "dense_tp", "expert_kind",
               "expert_full_mesh", "world", "description")

    def __new__(cls, name: str, *, slots_sharded: bool, kv_view: str,
                dense_tp: bool, expert_kind: str, expert_full_mesh: bool,
                world: int | None = None, description: str = ""):
        if kv_view not in ("ep", "tp"):
            raise ValueError(f"kv_view must be 'ep' or 'tp', got {kv_view!r}")
        if expert_kind not in ("ep", "tp"):
            raise ValueError(f"expert_kind must be 'ep' or 'tp', "
                             f"got {expert_kind!r}")
        if world is not None and int(world) < 1:
            raise ValueError(f"world must be a positive device count, "
                             f"got {world!r}")
        self = super().__new__(cls, name)
        object.__setattr__(self, "slots_sharded", slots_sharded)
        object.__setattr__(self, "kv_view", kv_view)
        object.__setattr__(self, "dense_tp", dense_tp)
        object.__setattr__(self, "expert_kind", expert_kind)
        object.__setattr__(self, "expert_full_mesh", expert_full_mesh)
        object.__setattr__(self, "world",
                           int(world) if world is not None else None)
        object.__setattr__(self, "description", description)
        return self

    def __setattr__(self, key, value):
        raise AttributeError("LayoutSpec is frozen")

    def __repr__(self) -> str:  # the name; attrs via vars-like helper
        return f"LayoutSpec({str.__repr__(self)})"

    # -- world (device-count) dimension -------------------------------------
    @property
    def base_name(self) -> str:
        """Registered name without the `@world` size suffix."""
        return str(self).partition("@")[0]

    @property
    def base(self) -> "LayoutSpec":
        """The registered unsized spec this layout derives from. Sized specs
        are distinct str values ("tp@4" != "tp"), so code that compares
        layouts against `TP`/`EP` must normalize through this first."""
        return self if self.world is None else get_layout(self.base_name)

    def sized(self, world: int | None) -> "LayoutSpec":
        """This layout pinned to a device count: `TP.sized(4)` is `tp@4`.

        `world=None` (or the spec's own world) returns the spec unchanged;
        anything else resolves through the registry so sized variants stay
        interned value objects like their bases."""
        if world is None or world == self.world:
            return self
        return get_layout(f"{self.base_name}@{int(world)}")

    # -- batch/slot geometry ------------------------------------------------
    @property
    def kv_per_rank(self) -> bool:
        """True when each model rank owns a private page pool (EP view)."""
        return self.kv_view == "ep"

    def batch_quantum(self, G: int) -> int:
        """Decode batch-slot count must be a multiple of this. Rank-sharded
        slots need G | B; full-mesh experts split the replicated token set
        1/G per rank before dispatch, which also needs G | B."""
        return G if (self.slots_sharded or self.expert_full_mesh) else 1

    def decode_ladder(self, ladder: tuple, G: int) -> tuple:
        """Round a requested batch ladder to this layout's quantum."""
        q = self.batch_quantum(G)
        if q <= 1:
            return tuple(ladder)
        return tuple(sorted({max(q, -(-b // q) * q) for b in ladder}))

    def prefill_quantum(self, G: int) -> int:
        """Tokens-per-chunk multiple required by the prefill step (full-mesh
        experts split the chunk's token set 1/G per rank)."""
        return G if self.expert_full_mesh else 1

    # -- KV ownership -------------------------------------------------------
    def kv_capacity_tokens(self, cfg: ModelConfig, G: int,
                           ep_capacity_tokens: int) -> int:
        """Group token capacity under this layout given the EP-view capacity
        (same byte budget; the pooled view replicates each KV head kv_rep
        times — the paper's capacity penalty)."""
        if self.kv_view == "ep":
            return ep_capacity_tokens
        return ep_capacity_tokens // group_info(cfg, G).kv_rep

    # -- expert sharding ----------------------------------------------------
    def expert_group(self, G: int, chips: int | None = None) -> int:
        """Rank count of the expert shard: the switch group, or the full
        mesh for full-mesh layouts."""
        return (chips or G) if self.expert_full_mesh else G

    def expert_axes(self, data_axes=("data",),
                    model_axis: str = "model") -> tuple:
        """Mesh axes the rank-major expert dim is sharded over."""
        if self.expert_full_mesh:
            return tuple(data_axes) + (model_axis,)
        return (model_axis,)

    def expert_layout(self, cfg: ModelConfig, G: int,
                      chips: int | None = None) -> ExpertLayout:
        return make_expert_layout(cfg.num_experts,
                                  self.expert_group(G, chips),
                                  self.expert_kind)


_REGISTRY: dict[str, LayoutSpec] = {}


def register_layout(spec: LayoutSpec) -> LayoutSpec:
    """Intern a spec. Re-registering the same name is an error (specs are
    value objects; redefinition would silently change switch semantics)."""
    if str(spec) in _REGISTRY:
        raise ValueError(f"layout {str(spec)!r} already registered")
    _REGISTRY[str(spec)] = spec
    return spec


def get_layout(name) -> LayoutSpec:
    """Resolve a layout name (or spec) to the registered spec instance.

    Sized names (`"tp@4"`) resolve lazily: the first lookup derives a spec
    from the registered base layout with `world=4` and interns it, so the
    registry can hold the same parallelism scheme at several device counts
    (`tp@8`, `tp@4`, ...) — world is a layout dimension, not a constant.
    """
    if isinstance(name, LayoutSpec):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        pass
    base_name, at, w = str(name).rpartition("@")
    if at and base_name in _REGISTRY:
        try:
            world = int(w)
        except ValueError:
            world = 0
        if world >= 1:
            base = _REGISTRY[base_name]
            fields = {f: getattr(base, f) for f in LayoutSpec._FIELDS}
            fields["world"] = world
            return register_layout(LayoutSpec(str(name), **fields))
    raise KeyError(f"unknown layout {name!r}; registered: "
                   f"{tuple(_REGISTRY)}") from None


def registered_layouts() -> tuple[LayoutSpec, ...]:
    return tuple(_REGISTRY.values())


def world_of(layout, default_G: int) -> int:
    """Device count a layout runs on: its own `world`, else the launch
    group size. Every geometry derivation goes through this instead of
    reading a module-global G or `len(jax.devices())`."""
    w = getattr(get_layout(layout), "world", None)
    return int(w) if w else int(default_G)


TP = register_layout(LayoutSpec(
    "tp", slots_sharded=False, kv_view="tp", dense_tp=True,
    expert_kind="tp", expert_full_mesh=False,
    description="Megatron TP: heads + expert widths sharded over the group; "
                "batch replicated; pooled head-sliced KV."))
EP = register_layout(LayoutSpec(
    "ep", slots_sharded=True, kv_view="ep", dense_tp=False,
    expert_kind="ep", expert_full_mesh=False,
    description="DP attention + expert parallelism: slots and whole experts "
                "per rank; per-rank KV page pools."))
# TPEP: TP attention + experts sharded over the FULL (data x model) mesh —
# the v5e-HBM-feasible high-throughput layout for >=100B MoE (DESIGN.md: on
# 16GB chips the paper's DP-attention assumption breaks for big attention
# stacks; the switch group generalizes from 8 GPUs to 256 chips).
TPEP = register_layout(LayoutSpec(
    "tpep", slots_sharded=False, kv_view="tp", dense_tp=True,
    expert_kind="ep", expert_full_mesh=True,
    description="Hybrid: TP attention within the group, whole experts "
                "sharded over the full data x model mesh."))
LAYOUTS = (TP, EP, TPEP)


# ---------------------------------------------------------------------------
# Group arithmetic
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GroupInfo:
    """Facts about how heads/experts split over the switchable G-rank group."""
    G: int
    cfg_heads: int
    cfg_kv_heads: int

    @property
    def q_local(self) -> int:
        return max(1, self.cfg_heads // self.G)

    @property
    def q_rep(self) -> int:
        return max(1, self.G // self.cfg_heads)

    @property
    def kv_local(self) -> int:
        return max(1, self.cfg_kv_heads // self.G)

    @property
    def kv_rep(self) -> int:
        """TP KV replication factor == the paper's KV-capacity penalty."""
        return max(1, self.G // self.cfg_kv_heads)

    def q_block(self, rank: int) -> int:
        """First global q-head of `rank`'s block."""
        return (rank // self.q_rep) * self.q_local

    def kv_block(self, rank: int) -> int:
        return (rank // self.kv_rep) * self.kv_local


def group_info(cfg: ModelConfig, G: int) -> GroupInfo:
    return GroupInfo(G=G, cfg_heads=cfg.num_heads, cfg_kv_heads=cfg.num_kv_heads)


def expert_layout(cfg: ModelConfig, G: int, layout: str) -> ExpertLayout:
    return make_expert_layout(cfg.num_experts, G, layout)


def padded_vocab(V: int, multiple: int = 256) -> int:
    return -(-V // multiple) * multiple


# ---------------------------------------------------------------------------
# Param packing: global init -> layout-specific stored form
# ---------------------------------------------------------------------------

def _pack_moe(moe: dict, lay: ExpertLayout) -> dict:
    """Stacked (L, E, ...) expert weights -> rank-major (L, G, E_loc, ...)."""
    out = dict(moe)
    out["w13"] = jax.vmap(lambda w: pack_w13(w, lay))(moe["w13"])
    out["w2"] = jax.vmap(lambda w: pack_experts(w, lay, width_axis=2))(moe["w2"])
    return out


def _pad_vocab_tables(params: dict, V: int, Vp: int) -> dict:
    def padv(x):
        if x.ndim >= 2 and x.shape[0] == V:
            return jnp.pad(x, ((0, Vp - V),) + ((0, 0),) * (x.ndim - 1))
        return x
    out = dict(params)
    for k in ("embed", "lm_head"):
        if k in out:
            out[k] = padv(out[k])
    return out


def pack_params(cfg: ModelConfig, params: dict, layout: str, G: int,
                expert_G: int | None = None) -> dict:
    """Init-time global params -> stored form for `layout` on a G-rank group.

    expert_G overrides the expert-sharding group size (full-mesh layouts:
    the total chip count).
    """
    spec = get_layout(layout)
    params = _pad_vocab_tables(params, cfg.vocab_size,
                               padded_vocab(cfg.vocab_size))
    if cfg.is_moe and "layers" in params and "moe" in params["layers"]:
        eg = expert_G or G
        lay = make_expert_layout(cfg.num_experts, eg, spec.expert_kind)
        params = dict(params)
        params["layers"] = dict(params["layers"])
        params["layers"]["moe"] = _pack_moe(params["layers"]["moe"], lay)
    return params


# ---------------------------------------------------------------------------
# PartitionSpec rules (GSPMD train/prefill path)
# ---------------------------------------------------------------------------

def _spec_last(ndim: int, axis: str) -> P:
    return P(*([None] * (ndim - 1)), axis)


def _spec_dim(ndim: int, dim: int, axis: str) -> P:
    spec = [None] * ndim
    spec[dim] = axis
    return P(*spec)


def _leaf_spec(cfg: ModelConfig, spec: LayoutSpec, path: str, leaf,
               m: str, exp_ax=None) -> P:
    """Sharding rule for one param leaf. `path` is '/'-joined key path.
    exp_ax: expert-sharding axes (full-mesh layouts: data x model)."""
    nd = leaf.ndim
    name = path.split("/")[-1]
    rep = P()  # replicated
    tp_like = spec.dense_tp      # shard dense/attention/vocab TP-style

    # rank-major experts: (L, G_exp, ...) or (G_exp, ...)
    if name in ("w13", "w2") and nd >= 4:
        return _spec_dim(nd, nd - 4, exp_ax or m)
    # vocab tables: TP-like layouts shard the vocab; DP attention replicates
    # them within the model group (the paper's "+12.7 GB/GPU: DP attention
    # replicates the attention stack and per-rank embedding/LM head")
    if name in ("embed", "lm_head"):
        return _spec_dim(nd, 0, m) if tp_like else rep
    if name == "dec_pos":
        return rep
    # norms and small vectors
    if name in ("scale", "bias", "norm", "q_norm", "k_norm", "router",
                "shared_gate", "A_log", "Dskip", "dt_bias"):
        return rep
    # attention projections
    if name in ("wq", "wk", "wv"):
        if tp_like or "xattn" in path or "encoder" in path:
            # encoder/cross attention has no DP-vs-TP switch state; keep TP
            return _spec_last(nd, m)
        return rep
    if name == "wo":
        if tp_like or "xattn" in path or "encoder" in path:
            return _spec_dim(nd, nd - 2, m)
        return rep
    # dense MLP: always TP (Megatron) — not switch state
    if name in ("w_gate", "w_up"):
        return _spec_last(nd, m)
    if name == "w_down":
        return _spec_dim(nd, nd - 2, m)
    # shared experts: width-sharded in TP-like layouts, replicated under DP
    if name in ("shared_wg", "shared_wu"):
        return _spec_dim(nd, nd - 2, m) if tp_like else rep
    if name == "shared_w2":
        return _spec_last(nd, m) if tp_like else rep
    # SSM: TP shards inner channels/heads; DP replicates
    if name in ("wz", "wx"):
        return _spec_last(nd, m) if tp_like else rep
    if name in ("wB", "wC", "conv_B", "conv_C"):
        return rep
    if name == "wdt":
        return _spec_last(nd, m) if tp_like else rep
    if name == "conv_x":
        return _spec_last(nd, m) if tp_like else rep
    if name == "out_proj":
        return _spec_dim(nd, nd - 2, m) if tp_like else rep
    return rep


def param_specs(cfg: ModelConfig, params: dict, layout: str,
                model_axis: str = "model", data_axes=("data",)) -> Any:
    """PartitionSpec pytree matching `params` for `layout`."""
    spec = get_layout(layout)
    exp_ax = (spec.expert_axes(data_axes, model_axis)
              if spec.expert_full_mesh else None)
    def rule(path, leaf):
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        return _leaf_spec(cfg, spec, "/".join(str(k) for k in keys), leaf,
                          model_axis, exp_ax)
    return jax.tree_util.tree_map_with_path(rule, params)


def batch_specs(layout: str, dp_axes=("data",), model_axis: str = "model"):
    """Token-batch sharding: slot-sharded layouts additionally split the
    batch over `model`."""
    dp = tuple(dp_axes)
    if get_layout(layout).slots_sharded:
        return P(dp + (model_axis,), None)
    return P(dp, None)


# ---------------------------------------------------------------------------
# Decode-path rank-major attention weights
# ---------------------------------------------------------------------------

def attn_rank_major(cfg: ModelConfig, ap: dict, G: int) -> dict:
    """Stacked attention params (L, ...) -> TP rank-major (L?, G, ...).

    Head blocks replicate when heads < G; wo is pre-scaled by 1/q_rep so the
    model-group psum of partial outputs is exact.
    """
    gi = group_info(cfg, G)
    dh = cfg.dh
    H, K = cfg.num_heads, cfg.num_kv_heads
    ql, kl = gi.q_local, gi.kv_local
    has_L = ap["wq"].ndim == 3

    def blocks_for(w, heads, local, head_axis):
        """Slice head-blocks per rank -> (G, ...) stacked (replicated when
        heads < G)."""
        shp = list(w.shape)
        shp[head_axis:head_axis + 1] = [heads, dh]
        wh = w.reshape(shp)
        rep = max(1, G // heads)
        outs = []
        for r in range(G):
            start = (r // rep) * local
            outs.append(jax.lax.dynamic_slice_in_dim(wh, start, local,
                                                     head_axis))
        out = jnp.stack(outs, axis=0)
        mg = list(out.shape)
        mg[head_axis + 1:head_axis + 3] = [local * dh]
        out = out.reshape(mg)
        # (G, L, ...) -> (L, G, ...) when stacked
        return jnp.moveaxis(out, 0, 1) if has_L else out

    ha = 2 if has_L else 1          # head axis of (L?, D, H*dh)
    oa = 1 if has_L else 0          # head axis of (L?, H*dh, D)
    out = {
        "wq": blocks_for(ap["wq"], H, ql, ha),
        "wk": blocks_for(ap["wk"], K, kl, ha),
        "wv": blocks_for(ap["wv"], K, kl, ha),
        "wo": blocks_for(ap["wo"] / gi.q_rep, H, ql, oa),
    }
    if cfg.qk_norm:
        out["q_norm"] = _bcast_g(ap["q_norm"], G)
        out["k_norm"] = _bcast_g(ap["k_norm"], G)
    return out


def _bcast_g(x: jax.Array, G: int) -> jax.Array:
    """(L?, dh) -> (L?, G, dh) replicated."""
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (G, x.shape[-1]))
