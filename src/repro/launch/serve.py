"""Serving launcher: run the Moebius engine on a workload.

The engine keeps every layout named in ``--layouts`` resident and the
switch policy picks between them: the registered specs are ``tp``, ``ep``,
and the hybrid ``tpep`` (TP attention + experts over the full mesh). With
more than two layouts the coordinator scores candidates with the analytical
cost model (KV-feasibility included) behind the paper's hysteresis band.

The run is driven through the AsyncEngine streaming frontend (DESIGN.md
§7): the trace is submitted as per-request token streams, the idle
fast-forward jumps quiet periods, and the summary reports per-request
TTFT/TPOT p50/p99 from ServeMetrics.

Examples (CPU, 8 host devices):
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload rollout --scale 0.02 --mesh 1x4 --policy rollout
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload bursty --scale 0.05 --mesh 2x4
  # three-layout runtime: tpep is a reachable operating point
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload bursty --scale 0.05 --mesh 2x4 --layouts tp,ep,tpep
  # serve statically on the hybrid layout
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload rollout --scale 0.02 --mesh 2x4 --policy static-tpep \
      --layouts tp,ep,tpep
  # elastic world sizes (DESIGN.md §13): tp@2 is a 2-device operating
  # point — the policy shrinks 4->2 when quiet, grows back on bursts
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload bursty --scale 0.05 --mesh 2x4 --layouts tp,ep,tp@2
  # multi-tenant QoS trace (DESIGN.md §11), 30% tagged interactive
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.serve \
      --workload bursty --scale 0.05 --mesh 1x4 --slo-class-mix 0.3
  # HTTP/SSE frontend (POST /v1/generate, GET /v1/metrics)
  REPRO_HOST_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --mesh 1x4 --http-port 8000
"""
import os
from pathlib import Path

if "REPRO_HOST_DEVICES" in os.environ:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_HOST_DEVICES"])

REPO = Path(__file__).resolve().parents[3]


def use_compile_cache(root: str | os.PathLike = REPO) -> str:
    """Keep JAX's persistent compile cache where JAX_COMPILATION_CACHE_DIR
    says (JAX reads the variable itself), else at the fixed, git-ignored
    `<root>/.jax_cache`. Returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, *, reduced: bool = False,
                 layers: int | None = None):
    """The served configuration. `reduced` swaps in the tiny float32 CPU
    cut; `layers` cuts whole layers only — widths and dtypes stay as
    published."""
    from repro.configs import get_config
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers:
        cfg = cfg.replace(num_layers=min(layers, cfg.num_layers))
    return cfg


def build_engine(cfg, *, mesh: str = "1x4", layouts: str = "tp,ep",
                 policy: str = "interactive", t_high: int | None = None,
                 cache=None, **ecfg):
    """Mesh + layouts + switch policy + cache -> MoebiusEngine. The one
    construction path of the launcher and `chip_smoke.py`; `ecfg` holds
    the remaining EngineConfig fields."""
    from repro.core.layouts import EP, TP, get_layout
    from repro.core.policy import PolicyConfig, calibrate_threshold
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import EngineConfig, MoebiusEngine
    from repro.serving.kvcache import CacheConfig

    dd, g = (int(x) for x in mesh.split("x"))
    specs = tuple(get_layout(l.strip()) for l in layouts.split(",")
                  if l.strip())
    th = t_high or max(8, calibrate_threshold(cfg, g))
    if policy == "interactive":
        pol, start = PolicyConfig.interactive(th), TP
    elif policy == "rollout":
        pol, start = PolicyConfig.rollout(th), EP
    else:
        pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
        start = get_layout(policy.removeprefix("static-"))
    cc = cache or CacheConfig(page_size=16, pages_ep=256,
                              max_pages_per_req=64)
    ecfg.setdefault("ladder", (g, 4 * g, 16 * g))
    return MoebiusEngine(cfg, make_mesh((dd, g), ("data", "model")), cc,
                         ecfg=EngineConfig(start_layout=start, layouts=specs,
                                           policy=pol, **ecfg))


def main():
    import argparse
    import json

    from repro.serving.frontend import AsyncEngine
    from repro.serving.workloads import (BurstySpec, QosMixSpec, RolloutSpec,
                                         bursty_trace, qos_mixed_trace,
                                         replay, rollout_batch)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny float32 CPU cut of --arch; "
                         "--no-reduced serves its published widths")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to this many whole layers (widths and "
                         "dtypes unchanged)")
    ap.add_argument("--mesh", default="1x4")
    ap.add_argument("--workload", default="rollout",
                    choices=["rollout", "bursty", "qosmix"])
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--layouts", default="tp,ep",
                    help="comma-separated registered layouts the engine "
                         "keeps resident (e.g. tp,ep,tpep). A name may "
                         "carry a device count: tp@8,ep@8,tp@4 makes the "
                         "4-device tp a reachable operating point, so the "
                         "policy can shrink the serving world when the "
                         "queue is quiet and grow it back under bursts "
                         "(DESIGN.md §13)")
    ap.add_argument("--policy", default="interactive",
                    choices=["interactive", "rollout", "static-tp",
                             "static-ep", "static-tpep"])
    ap.add_argument("--t-high", type=int, default=None)
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="fuse N decode steps under one dispatch (device-"
                         "resident decode state; N=1 is the classic "
                         "per-token host loop)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prefill chunk width in tokens (rounded up to a "
                         "multiple of every resident layout's "
                         "prefill_quantum)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-iteration mixed-batch token budget (decode "
                         "tokens first, prefill chunks into the remainder); "
                         "0 = auto: the quantum-rounded prefill chunk, so "
                         "full-mesh layouts keep their 1/G-per-rank split")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page reuse (refcounted "
                         "pages + CoW; on by default)")
    ap.add_argument("--samples-per-prompt", type=int, default=1,
                    help="rollout workload: completions sampled per "
                         "distinct prompt (shared-prefix groups)")
    ap.add_argument("--qos", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="class-aware QoS scheduling + interactive-"
                         "attainment switch gating (DESIGN.md §11); "
                         "--no-qos serves class-blind")
    ap.add_argument("--slo-class-mix", type=float, default=0.0,
                    help="fraction of trace requests tagged 'interactive' "
                         "(rest 'batch'; deterministic in --seed). 0 "
                         "keeps the workload's own tags")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve the HTTP/SSE frontend on this port "
                         "instead of replaying a trace (POST /v1/generate"
                         ", GET /v1/metrics; 0 = pick a free port)")
    ap.add_argument("--attn-backend", default=None,
                    choices=["ref", "pallas", "interpret"],
                    help="paged-attention backend (default: auto — pallas "
                         "on TPU, ref elsewhere)")
    ap.add_argument("--moe-backend", default=None,
                    choices=["ref", "pallas", "interpret"],
                    help="grouped MoE GEMM backend (same auto policy)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-steps", type=int, default=5000)
    args = ap.parse_args()

    use_compile_cache()
    cfg = model_config(args.arch, reduced=args.reduced, layers=args.layers)
    eng = build_engine(cfg, mesh=args.mesh, layouts=args.layouts,
                       policy=args.policy, t_high=args.t_high,
                       prefill_chunk=args.prefill_chunk,
                       token_budget=args.token_budget,
                       decode_steps=args.decode_steps,
                       prefix_cache=not args.no_prefix_cache,
                       qos=args.qos, attn_backend=args.attn_backend,
                       moe_backend=args.moe_backend, seed=args.seed)
    if args.http_port is not None:
        # live HTTP/SSE mode: no trace — requests arrive over the wire
        import asyncio

        from repro.launch.http import serve_http
        eng.warmup()
        asyncio.run(serve_http(AsyncEngine(eng), port=args.http_port))
        return
    if args.workload == "rollout":
        reqs = rollout_batch(
            RolloutSpec(scale=args.scale,
                        samples_per_prompt=args.samples_per_prompt),
            seed=args.seed)
    elif args.workload == "qosmix":
        reqs = qos_mixed_trace(QosMixSpec(), seed=args.seed)
    else:
        reqs = bursty_trace(BurstySpec(scale=args.scale), seed=args.seed)
    if args.slo_class_mix > 0:
        import numpy as np
        mix_rng = np.random.default_rng(args.seed + 1)
        for r in reqs:
            r.slo_class = ("interactive"
                           if mix_rng.random() < args.slo_class_mix
                           else "batch")
    fe = AsyncEngine(eng)
    streams = replay(fe, reqs)
    summary = eng.run(max_steps=args.max_steps)
    summary["streams_finished"] = sum(s.finished for s in streams.values())
    summary["switches"] = len(eng.switch_records)
    summary["final_layout"] = eng.active
    summary["layouts"] = [str(l) for l in eng.layouts]
    print(json.dumps(summary, indent=1))


if __name__ == "__main__":
    main()
