import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch x shape x mesh x layout)
# cell on placeholder devices; record memory analysis, cost analysis, HLO
# collective counts, and analytic roofline terms.
#
# Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
#           --shape decode_32k --mesh pod1 --layout ep
#       PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod1|pod2]
# Results land in results/dryrun/<arch>__<shape>__<mesh>__<layout>.json.
# NOTE: the XLA_FLAGS line above MUST stay the first statement — jax locks
# the device count at first init (so no `from __future__` here).
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, ShapeSpec, cell_applicable
from repro.core.layouts import EP, TP, TPEP, expand_kv_heads, group_info
from repro.launch.mesh import data_axes_of, make_production_mesh
from repro.models.common import ModelConfig
from repro.serving.kvcache import CacheConfig

RESULTS = Path(os.environ.get("REPRO_RESULTS", "results/dryrun"))

# roofline hardware constants (TPU v5e)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*\(")
_SHAPE_RE = re.compile(r"^\s*%?\S+\s*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def parse_hlo_collectives(hlo: str) -> dict:
    """Count collective ops + sum their result bytes from HLO text. Ops in
    while bodies appear once; the analytic model (scan-aware) is primary."""
    counts: dict[str, int] = {}
    bytes_: dict[str, int] = {}
    for line in hlo.splitlines():
        mm = _COLL_RE.search(line)
        if not mm:
            continue
        kind = mm.group(1)
        counts[kind] = counts.get(kind, 0) + 1
        sm = _SHAPE_RE.match(line)
        if sm and sm.group(1) in _DTYPE_BYTES:
            dims = [int(d) for d in sm.group(2).split(",") if d]
            b = _DTYPE_BYTES[sm.group(1)] * int(np.prod(dims)) if dims else 0
            bytes_[kind] = bytes_.get(kind, 0) + b
    return {"counts": counts, "result_bytes": bytes_}


def cc_for(cfg: ModelConfig, G: int, layout: str, group_batch: int,
           seq: int, page: int = 128) -> CacheConfig:
    """Size the unified buffer so `layout` holds group_batch requests of
    `seq` tokens (+1 decode token)."""
    gi = group_info(cfg, G)
    tokens = group_batch * (seq + page)
    if layout == EP:
        per_rank = -(-group_batch // G) * (seq + page)
        pages_ep = per_rank // page + 2
    else:
        pages_tp = tokens // page + 2
        pages_ep = -(-pages_tp * gi.kv_local // cfg.num_kv_heads)
        pages_ep = max(pages_ep, 2)
        # keep the view shapes consistent: pages_tp = pages_ep*K//Kl >= need
        while (pages_ep * cfg.num_kv_heads) // gi.kv_local < pages_tp:
            pages_ep += 1
    maxp = seq // page + 2
    return CacheConfig(page_size=page, pages_ep=pages_ep,
                       max_pages_per_req=maxp)


# ---------------------------------------------------------------------------
# analytic FLOPs / bytes / collective bytes per cell (scan-aware; primary)
# ---------------------------------------------------------------------------

def _expert_bytes_total(cfg: ModelConfig) -> int:
    if not cfg.is_moe:
        return 0
    return cfg.num_layers * cfg.num_experts * 3 * cfg.d_model * cfg.d_expert * 2


def _expected_activated(E: int, k: int, tokens: float) -> float:
    if E == 0 or tokens <= 0:
        return 0.0
    return E * (1.0 - (1.0 - min(k, E) / E) ** max(tokens, 0.0))


def analytic_terms(cfg: ModelConfig, shape: ShapeSpec, layout: str,
                   mesh) -> dict:
    """Per-device per-step roofline terms in seconds (scan-aware, primary).

    compute  = FLOPs_dev / peak ;  memory = HBM bytes_dev / bw ;
    collective = payload bytes_dev / link bw.
    """
    from repro.distributed.collectives import (decode_collective_bytes,
                                               train_collective_bytes)
    from repro.models.registry import count_params_analytic
    G = mesh.shape["model"]
    chips = int(np.prod(list(mesh.shape.values())))
    dp = chips // G
    gi = group_info(cfg, G)
    N = count_params_analytic(cfg)
    Na = count_params_analytic(cfg, active_only=True)
    expert_b = _expert_bytes_total(cfg)              # bf16 bytes, all experts
    nonexpert_b = N * 2 - expert_b
    B, S = shape.global_batch, shape.seq_len
    Lk = _kv_layers(cfg)
    kv_tok_bytes = 2 * cfg.num_kv_heads * cfg.dh * 2 * Lk   # K+V, bf16
    window = cfg.sliding_window or 0
    ctx = min(S, window) if window else S

    if shape.kind == "train":
        tokens = B * S
        model_flops = 6 * Na * tokens
        if cfg.num_heads:
            model_flops += 3 * 2 * tokens * (min(S, window or S) / 2) \
                * cfg.num_heads * cfg.dh * 2
        flops_dev = model_flops / chips
        # fwd reads + bwd reads + grad writes of the local shard; activations
        bytes_dev = 3 * (N * 2) / G \
            + 8 * (tokens / dp) * cfg.d_model * 2 * cfg.num_layers / 1
        coll_bytes = train_collective_bytes(
            cfg, layout, tokens // dp, G, dp, N)["total"]
        useful = 6 * Na * tokens / chips
    elif shape.kind == "prefill":
        q_tokens = B * S
        model_flops = 2 * Na * q_tokens
        if cfg.num_heads:
            model_flops += 2 * q_tokens * (ctx / 2) * cfg.num_heads \
                * cfg.dh * 2
        flops_dev = model_flops / chips
        # weights once + activations + KV writes
        bytes_dev = (N * 2) / G + 4 * (q_tokens / dp) * cfg.d_model * 2 \
            + (q_tokens / chips) * kv_tok_bytes
        coll_bytes = decode_collective_bytes(
            cfg, layout, max(1, B // dp) * S, G)
        useful = 2 * Na * q_tokens / chips
    else:  # decode
        q_tokens = B
        model_flops = 2 * Na * q_tokens
        if cfg.num_heads:
            model_flops += 2 * q_tokens * ctx * cfg.num_heads * cfg.dh * 2
        if cfg.ssm_state:
            model_flops += 2 * q_tokens * cfg.num_layers * cfg.ssm_heads \
                * cfg.ssm_head_dim * cfg.ssm_state * 3
        flops_dev = model_flops / chips
        group_B = max(1, B // dp)
        if layout == TPEP:
            # TP attention + experts over the full mesh (G_exp = chips)
            from repro.models.moe import make_expert_layout
            lay = make_expert_layout(cfg.num_experts or 1, chips, EP)
            E_loc = max(1, (cfg.num_experts or 1) // lay.ep)
            routed = B * cfg.top_k / max(lay.ep, 1)
            act = _expected_activated(E_loc, cfg.top_k, routed)
            w_dev = nonexpert_b / G + (act / max(E_loc, 1)) \
                * (expert_b / chips)
            kv_dev = group_B * ctx * gi.kv_local * cfg.dh * 2 * 2 * Lk
        elif layout == TP:
            act = _expected_activated(cfg.num_experts, cfg.top_k, group_B) \
                if cfg.is_moe else 0
            w_dev = nonexpert_b / G + (act / max(cfg.num_experts, 1)) \
                * expert_b / G
            kv_dev = group_B * ctx * gi.kv_local * cfg.dh * 2 * 2 * Lk
        else:
            from repro.models.moe import make_expert_layout
            lay = make_expert_layout(cfg.num_experts or 1, G, EP)
            E_loc = (cfg.num_experts or 1) // lay.ep
            routed = group_B * cfg.top_k / lay.ep if cfg.is_moe else 0
            act = _expected_activated(E_loc, cfg.top_k, routed)
            w_dev = nonexpert_b + (act / max(cfg.num_experts, 1)) \
                * expert_b / lay.tp_inner if cfg.is_moe else nonexpert_b / \
                (G if not cfg.ssm_state else 1)
            if not cfg.is_moe and not cfg.ssm_state:
                # dense DP-attn: attention stack replicated, MLP sharded
                attn_b = cfg.num_layers * (cfg.d_model * cfg.num_heads
                                           * cfg.dh * 2 + 2 * cfg.d_model
                                           * cfg.num_kv_heads * cfg.dh) * 2
                mlp_b = N * 2 - attn_b
                w_dev = attn_b + mlp_b / G
            kv_dev = (group_B / G) * ctx * cfg.num_kv_heads * cfg.dh \
                * 2 * 2 * Lk
        if cfg.ssm_state:
            ssm_b = (group_B / (G if layout == EP else 1)) * cfg.num_layers \
                * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            kv_dev += ssm_b
        bytes_dev = w_dev + kv_dev + 4 * group_B * cfg.d_model * 2
        if layout == TPEP:
            # attn all-reduce + full-mesh dispatch a2a + model all-gather
            bpe = 2
            per_layer = (2 * (G - 1) / G * group_B * cfg.d_model * bpe
                         + 2 * (group_B / G) * cfg.top_k * cfg.d_model * bpe
                         + (G - 1) / G * group_B * cfg.d_model * bpe)
            coll_bytes = cfg.num_layers * per_layer
        else:
            coll_bytes = decode_collective_bytes(cfg, layout, group_B, G)
        useful = 2 * Na * q_tokens / chips

    return {
        "chips": chips,
        "model_flops_total": float(model_flops),
        "flops_per_dev": float(flops_dev),
        "bytes_per_dev": float(bytes_dev),
        "coll_bytes_per_dev": float(coll_bytes),
        "t_compute": float(flops_dev / PEAK_FLOPS),
        "t_memory": float(bytes_dev / HBM_BW),
        "t_collective": float(coll_bytes / LINK_BW),
        "useful_flops_per_dev": float(useful),
    }


def _kv_layers(cfg: ModelConfig) -> int:
    from repro.serving.kvcache import num_kv_layers
    return num_kv_layers(cfg)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh, layout: str,
                cc: CacheConfig | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        out = {"tokens": sds((B, S)), "labels": sds((B, S))}
        if cfg.family == "encdec":
            out["frames"] = sds((B, cfg.encoder_seq, cfg.d_model),
                                cfg.compute_dtype)
        if cfg.family == "vlm":
            out["patches"] = sds((B, cfg.num_patches, cfg.d_model),
                                 cfg.compute_dtype)
        return out
    raise ValueError("serve cells build their own specs")


def lower_cell(arch: str, shape_name: str, mesh_kind: str, layout: str,
               *, compile_: bool = True, remat: bool = True,
               grad_accum: int = 1, zero: bool = False,
               page: int = 128) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    da = data_axes_of(mesh)
    G = mesh.shape["model"]
    dp = int(np.prod([mesh.shape[a] for a in da]))
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "layout": layout, "devices": int(np.prod(list(mesh.shape.values())))}
    t0 = time.perf_counter()

    if shape.kind == "train":
        from repro.training.train_loop import build_train_step
        step, init_fn, (psh, osh, bsh) = build_train_step(
            cfg, mesh, layout, data_axes=da, grad_accum=grad_accum,
            donate=False, global_batch=shape.global_batch, remat=remat,
            zero=zero)
        pshape, oshape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        batch = input_specs(cfg, shape, mesh, layout)
        lowered = step.lower(pshape, oshape, batch)
    else:
        lowered = _lower_serve(cfg, shape, mesh, layout, da, G, dp,
                               page=page)

    rec["lower_s"] = time.perf_counter() - t0
    if compile_:
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = time.perf_counter() - t1
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))
                                and k in ("flops", "bytes accessed",
                                          "transcendentals", "utilization")}
        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
        rec["hlo_collectives"] = parse_hlo_collectives(compiled.as_text())
    rec["analytic"] = analytic_terms(cfg, shape, layout, mesh)
    rec["status"] = "ok"
    return rec


def _lower_serve(cfg, shape, mesh, layout, da, G, dp, page=128):
    """Lower a serve cell (prefill or decode)."""
    B, S = shape.global_batch, shape.seq_len
    Dd = dp
    group_B = max(1, B // dp)
    if cfg.family == "encdec":
        cfg = cfg.replace(max_positions=max(4096, S + 8))

    if shape.kind == "prefill":
        if cfg.family in ("ssm", "hybrid", "encdec", "vlm"):
            # GSPMD full-sequence forward (prefill compute; see DESIGN.md)
            from repro.core.layouts import (batch_specs, pack_params,
                                            param_specs)
            from repro.models.registry import forward, init_params
            from repro.models.moe import make_expert_layout
            from jax.sharding import NamedSharding
            lay = (make_expert_layout(cfg.num_experts, G, layout)
                   if cfg.is_moe else None)
            pshape = jax.eval_shape(lambda: pack_params(
                cfg, init_params(cfg, jax.random.PRNGKey(0)), layout, G))
            psh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                param_specs(cfg, pshape, layout))
            bspec = batch_specs(layout, da)
            # fall back to DP-only batch sharding when B !% (dp * G)
            ent = bspec[0] if len(bspec) else None
            ent = (ent,) if isinstance(ent, str) else ent
            nshard = int(np.prod([mesh.shape[a]
                                  for ax in ent for a in
                                  ((ax,) if isinstance(ax, str) else ax)])) \
                if ent else 1
            if B % nshard:
                from jax.sharding import PartitionSpec as PS
                bspec = PS(tuple(da), None)
            batch = {"tokens": sds((B, S))}
            bsh = {"tokens": NamedSharding(mesh, bspec)}
            if cfg.family == "encdec":
                batch["frames"] = sds((B, cfg.encoder_seq, cfg.d_model),
                                      cfg.compute_dtype)
                bsh["frames"] = NamedSharding(
                    mesh, jax.sharding.PartitionSpec(bspec[0], None, None))
            if cfg.family == "vlm":
                batch["patches"] = sds((B, cfg.num_patches, cfg.d_model),
                                       cfg.compute_dtype)
                bsh["patches"] = NamedSharding(
                    mesh, jax.sharding.PartitionSpec(bspec[0], None, None))
            fn = jax.jit(lambda p, b: forward(cfg, p, b, lay=lay),
                         in_shardings=(psh, bsh))
            return fn.lower(pshape, batch)
        # transformer families: true paged prefill step
        from repro.serving.steps import build_serve_step, build_decode_pack
        from repro.core.layouts import pack_params
        cc = cc_for(cfg, G, layout, group_B, S, page)
        Bp = group_B if layout == TP else max(G, -(-group_B // G) * G)
        step = build_serve_step(cfg, mesh, layout, cc, Bp, Sq=S,
                                data_axes=da, attn_backend="ref",
                                donate=False)
        return _lower_step(cfg, step, mesh, layout, cc, Bp, S, Dd, G)

    # decode cells
    window = cfg.sliding_window or 0
    eff_S = min(S, window) if window else S
    cc = (cc_for(cfg, G, TP if layout == TPEP else layout, group_B, eff_S,
                 page) if cfg.family != "ssm" else None)
    Bslot = group_B if layout != EP else max(G, -(-group_B // G) * G)
    if layout == TPEP:
        Bslot = max(G, -(-Bslot // G) * G)   # token slice needs G | Bslot
    if cfg.family == "ssm":
        from repro.serving.steps_extra import (build_ssm_serve_step,
                                               ssm_state_shapes)
        step = build_ssm_serve_step(cfg, mesh, layout, Bslot, data_axes=da,
                                    donate=False)
        shp = ssm_state_shapes(cfg, Dd, Bslot)
        dt = cfg.param_dtype
        args = (_ssm_pack_sds(cfg), sds(shp["conv_x"], dt),
                sds(shp["conv_B"], dt), sds(shp["conv_C"], dt),
                sds(shp["ssm"], jnp.float32), sds((Dd, Bslot, 1)),
                sds((Dd, Bslot)), sds((2,), jnp.uint32))
        return step.lower(*args)
    if cfg.family == "hybrid":
        from repro.serving.steps_extra import (build_hybrid_serve_step,
                                               hybrid_decode_pack,
                                               ssm_state_shapes)
        from repro.models.registry import init_params
        from repro.core.layouts import pack_params
        step = build_hybrid_serve_step(cfg, mesh, layout, cc, Bslot,
                                       data_axes=da, attn_backend="ref",
                                       donate=False)
        pk = jax.eval_shape(lambda: hybrid_decode_pack(
            cfg, pack_params(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             layout, G), layout, G))
        shp = ssm_state_shapes(cfg, Dd, Bslot)
        dt = cfg.param_dtype
        kv = (Dd, G) + cc.rank_shape(cfg, G)
        maxp = cc.max_pages_per_req
        args = (pk, sds(kv, dt), sds(shp["conv_x"], dt),
                sds(shp["conv_B"], dt), sds(shp["conv_C"], dt),
                sds(shp["ssm"], jnp.float32), sds((Dd, Bslot, 1)),
                sds((Dd, Bslot)), sds((Dd, Bslot)),
                sds((Dd, Bslot, maxp)), sds((2,), jnp.uint32))
        return step.lower(*args)
    if cfg.family == "encdec":
        from repro.serving.steps_extra import (build_encdec_serve_step,
                                               encdec_decode_pack)
        from repro.models.registry import init_params
        from repro.core.layouts import pack_params, group_info
        gi = group_info(cfg, G)
        step = build_encdec_serve_step(cfg, mesh, layout, cc, Bslot,
                                       cfg.encoder_seq, data_axes=da,
                                       attn_backend="ref", donate=False)
        pk = jax.eval_shape(lambda: encdec_decode_pack(
            cfg, pack_params(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             layout, G), layout, G))
        kv = (Dd, G) + cc.rank_shape(cfg, G)
        maxp = cc.max_pages_per_req
        Kx = G * gi.kv_local if layout == TP else cfg.num_kv_heads
        xkv = sds((Dd, Bslot, cfg.num_layers, 2, cfg.encoder_seq, Kx,
                   cfg.dh), cfg.param_dtype)
        args = (pk, sds(kv, cfg.param_dtype), xkv,
                sds((Dd, Bslot, 1)), sds((Dd, Bslot)), sds((Dd, Bslot)),
                sds((Dd, Bslot, maxp)), sds((2,), jnp.uint32))
        return step.lower(*args)
    # dense / moe / vlm text decode
    from repro.serving.steps import build_serve_step
    step = build_serve_step(cfg, mesh, layout, cc, Bslot, Sq=1,
                            data_axes=da, attn_backend="ref", donate=False)
    return _lower_step(cfg, step, mesh, layout, cc, Bslot, 1, Dd, G)


def _lower_step(cfg, step, mesh, layout, cc, Bslot, Sq, Dd, G):
    from repro.serving.steps import build_decode_pack, _params_like
    G_exp = (int(np.prod(list(mesh.shape.values())))
             if layout == TPEP else None)
    pk = jax.eval_shape(lambda p: build_decode_pack(cfg, p, layout, G),
                        _params_like(cfg, layout, G, G_exp))
    kv = (Dd, G) + cc.rank_shape(cfg, G)
    maxp = cc.max_pages_per_req
    args = (pk, sds(kv, cfg.param_dtype),
            sds((Dd, Bslot, Sq)), sds((Dd, Bslot)), sds((Dd, Bslot)),
            sds((Dd, Bslot, maxp)), sds((2,), jnp.uint32))
    return step.lower(*args)


def _ssm_pack_sds(cfg):
    from repro.models.ssm_lm import init_ssm_lm
    import jax.random as jr
    p = jax.eval_shape(lambda: init_ssm_lm(cfg, jr.PRNGKey(0)))
    from repro.core.layouts import padded_vocab
    Vp = padded_vocab(cfg.vocab_size)
    p = dict(p)
    p["embed"] = sds((Vp, cfg.d_model), cfg.param_dtype)
    p["lm_head"] = sds((Vp, cfg.d_model), cfg.param_dtype)
    return p


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cell(arch, shape, mesh_kind, layout, out_dir: Path) -> dict:
    name = f"{arch}__{shape}__{mesh_kind}__{layout}"
    out = out_dir / f"{name}.json"
    try:
        rec = lower_cell(arch, shape, mesh_kind, layout)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-3000:]}
    rec.update({"arch": arch, "shape": shape, "mesh": mesh_kind,
                "layout": layout})
    out_dir.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    status = rec.get("status")
    extra = ""
    if status == "ok" and "memory" in rec:
        extra = f" argbytes={rec['memory'].get('argument_size_in_bytes', 0)/2**30:.2f}GiB" \
            f" compile={rec.get('compile_s', 0):.1f}s"
    print(f"[dryrun] {name}: {status}{extra}", flush=True)
    return rec


def default_layouts(cfg: ModelConfig, shape: ShapeSpec) -> list[str]:
    outs = [TP, EP]
    # MoE serve cells additionally get TPEP (full-mesh expert parallelism —
    # the HBM-feasible layout for >=100B MoE on 16GB chips)
    if cfg.is_moe and shape.kind != "train":
        outs.append(TPEP)
    return outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--layout", default=None, choices=[TP, EP, TPEP])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        for arch in ARCHS:
            cfg = get_config(arch)
            for sname, sh in SHAPES.items():
                for layout in ([args.layout] if args.layout
                               else default_layouts(cfg, sh)):
                    run_cell(arch, sname, args.mesh, layout, out_dir)
        return
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for sname in shapes:
            for layout in ([args.layout] if args.layout else [TP, EP]):
                run_cell(arch, sname, args.mesh, layout, out_dir)


if __name__ == "__main__":
    main()
