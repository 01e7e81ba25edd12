"""Architecture `shared_moe`, for the test that a new architecture enters
the benchmark by new files alone (test_arch_contract.py copies this file
to bench/arch/shared_moe.py of a copy of the benchmark): `topk_moe`'s
block with shared experts beside the routed ones, as Qwen2-MoE has them.

The shared experts are one SwiGLU MLP of width
`shared_expert_intermediate_size` (a whole number of routed expert widths,
which is how the program sizes it) whose output is scaled by a sigmoid gate
of the layer's input, sigmoid(h . shared_gate), and added to the routed
experts' output. The program draws their weights from the fourth of the
expert layer's keys, split four ways: gate, up, down, gate vector.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from arch import topk_moe as base
from benchlib import flops
from benchlib import reference as ref


def model_config(conf: dict):
    I = conf["moe_intermediate_size"]
    F = conf["shared_expert_intermediate_size"]
    if F % I:
        raise ValueError("the program sizes shared experts in whole routed "
                         "expert widths")
    return base.model_config(conf).replace(num_shared_experts=F // I)


@dataclass(frozen=True)
class Dims(base.Dims):
    F: int          # shared experts' width


def dims(conf: dict) -> Dims:
    return Dims(**base.dims(conf).__dict__,
                F=conf["shared_expert_intermediate_size"])


def shared_mlp(m: Dims, rows) -> float:
    """FLOPs of the shared experts and their gate, all layers."""
    return m.L * flops.tokens(rows) * (6.0 * m.D * m.F + 2.0 * m.D)


def forward(m: Dims, rows) -> float:
    return base.forward(m, rows) + shared_mlp(m, rows)


costs = base.costs


def init(conf: dict, key) -> dict:
    w = base.init(conf, key)
    m = dims(conf)
    dt = ref.DTYPES[conf["torch_dtype"]]
    ks = list(jax.random.split(key, 8))
    km = list(jax.random.split(ks[3], 5))
    kg, ku, kd, kk = jax.random.split(km[3], 4)
    w["layers"].update(
        shared_wg=ref.normal(kg, (m.L, m.F, m.D), m.D, dt),
        shared_wu=ref.normal(ku, (m.L, m.F, m.D), m.D, dt),
        shared_w2=ref.normal(kd, (m.L, m.D, m.F), m.F, dt),
        shared_gate=ref.normal(kk, (m.L, m.D), m.D, dt))
    return w


def make_weights(conf: dict, seed: int, devices) -> dict:
    return ref.place(init, conf, seed, devices)


@partial(jax.jit, static_argnames=("conf_key", "quant", "qblock"))
def _layer(x, lw, li, pos, seg, *, conf_key, quant, qblock=256):
    conf = dict(conf_key)
    m = dims(conf)
    g = base.own(lw, li)
    x = x + base.attention(x, g, pos, seg, conf, m, quant, qblock)
    h = ref.rms(x, g["mlp_norm"], conf["rms_norm_eps"])
    hg = ref.mm(h, g["shared_wg"].T, quant)
    hu = ref.mm(h, g["shared_wu"].T, quant)
    s = ref.mm(jax.nn.silu(hg) * hu, g["shared_w2"].T, quant)
    gate = jax.nn.sigmoid(jnp.matmul(h, g["shared_gate"].astype(jnp.float32),
                                     precision=ref.HIGHEST))
    return x + base.routed(h, g, lw, li, m, quant) + s * gate[:, None]


def key(conf: dict) -> tuple:
    return base.key(conf, base.KEYS + ("shared_expert_intermediate_size",))


def hidden(conf: dict, w: dict, tokens, seg, out_idx, quant=None):
    return ref.stack(conf, w, tokens, seg, out_idx, partial(
        _layer, conf_key=key(conf), quant=quant))


head = base.head
