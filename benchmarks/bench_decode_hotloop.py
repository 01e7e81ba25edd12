"""Decode hot loop: tokens/s and host-overhead fraction vs decode_steps.

A fixed decode-heavy workload (forced output lengths, replayed identically)
is served with ``decode_steps`` in {1, 4, 8}. N=1 is the classic per-token
host loop (rebuild + upload the batch, block on the sampled token every
step); N>1 runs the fused on-device loop over device-resident decode state,
so the per-token host work is amortized over N substeps and outputs are
fetched once per dispatch. Outputs must be byte-identical across all N.

Methodology (CPU, 2-ish cores):
  * primary section, mesh 1x1 — control-plane isolation: a deliberately
    tiny model keeps the device substep in a realistic ratio to host time
    (a real accelerator step is ~10 ms against the same host loop; CPU
    multi-device emulation would swamp it with thread-rendezvous cost);
  * timing covers the pure-decode phase only (prefill completes before the
    clock starts — the issue under test is the decode control plane);
  * configs are measured interleaved, best-of-``reps`` per config, because
    shared-box noise comes in bursts;
  * full mode adds a mesh 1x8 mechanism row: same engine on emulated SPMD
    collectives (fused wins less there — the per-substep cost is
    rendezvous-bound, which fusing cannot remove; identity still holds).

A second section decomposes one mixed-batch serving run into separately
timed ``prefill`` / ``mixed`` / ``generate`` stages (each engine iteration
attributed by the token deltas it produced — see ``_staged_rows``), so the
per-stage tokens/s of the unified dispatch path is a tracked number.

Runnable standalone: ``python benchmarks/bench_decode_hotloop.py [--smoke]``
(--smoke is the CI gate: fused(8) throughput >= single-step and identical
tokens; smaller workload, primary section only).
"""
from __future__ import annotations

import time


def _measure_section(mesh, cfg, steps_list, *, n_req, out_len, reps,
                     ladder, pages_ep, maxp, seed):
    """Best-of-``reps`` decode-phase tokens/s per decode_steps config."""
    import numpy as np
    from benchmarks.common import make_engine
    from repro.serving.request import Request

    def mkreqs(n, length, rid0):
        r = np.random.default_rng(seed)
        return [Request(rid=rid0 + i,
                        prompt=list(r.integers(5, 200, 16)),
                        max_new_tokens=length, forced_len=length,
                        arrival_s=0.0) for i in range(n)]

    engines: dict = {}

    def get_engine(n):
        if n not in engines:
            eng = make_engine(cfg, mesh, start="ep", ladder=ladder,
                              pages_ep=pages_ep, maxp=maxp,
                              prefill_chunk=16, decode_steps=n,
                              attn_backend="ref")
            eng.warmup(layouts=(eng.active,))
            for r in mkreqs(4, 8, rid0=10 ** 6):   # jit/numpy paths hot
                eng.submit(r)
            eng.run(max_steps=10000)
            engines[n] = eng
        return engines[n]

    rid = [0]

    def measure(n):
        eng = get_engine(n)
        eng.finished.clear()
        for r in mkreqs(n_req, out_len, rid0=rid[0]):
            eng.submit(r)
        rid[0] += 1000
        i = 0
        while eng.pending or eng.waiting or eng.prefilling:
            eng.step()
            i += 1
            assert i < 10000, "prefill made no progress"
        # flush fused tokens dispatched during the prefill phase so `pre`
        # counts them and the device is idle when the clock starts —
        # otherwise in-flight work would be credited to the timed window
        # for fused configs only
        eng._drain_decode()
        pre = sum(len(r.output)
                  for r in list(eng.running.values()) + eng.finished)
        t0 = time.perf_counter()
        eng.run(max_steps=500000)
        dt = time.perf_counter() - t0
        toks = sum(len(r.output) for r in eng.finished) - pre
        outs = {r.rid % 1000: tuple(r.output) for r in eng.finished}
        return toks / dt, outs, eng.metrics.decode_dispatches

    best = {n: 0.0 for n in steps_list}
    outs: dict = {}
    disp: dict = {}
    for _ in range(reps):
        for n in steps_list:
            tps, o, d = measure(n)
            best[n] = max(best[n], tps)
            outs.setdefault(n, o)
            disp[n] = d
    n0 = steps_list[0]
    identical = all(outs[n] == outs[n0] for n in steps_list)
    return best, identical, disp


def _staged_rows(seed: int = 0):
    """Stage-decomposed serving timeline under the mixed-batch engine
    (MaxText splits its serving loop the same way): every engine iteration
    is timed individually and attributed to

      * ``prefill``  — the dispatch carried only prefill chunks,
      * ``mixed``    — decode rows and prefill chunks shared one dispatch,
      * ``generate`` — decode-only,

    by the prefill/decode token deltas it produced. One batch of
    long-prompt requests naturally walks through all three stages: every
    request prefills first (prefill), early finishers decode while the
    token budget still feeds the stragglers' chunks (mixed), then the
    batch drains (generate)."""
    import numpy as np
    from benchmarks.common import make_engine
    from repro.launch.mesh import make_mesh
    from repro.serving.request import Request

    cfg = _hotloop_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = make_engine(cfg, mesh, start="ep", ladder=(8,), pages_ep=224,
                      maxp=32, prefill_chunk=32, attn_backend="ref")
    eng.warmup(layouts=(eng.active,))
    rng = np.random.default_rng(seed)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 96)),
                           max_new_tokens=64, forced_len=64, arrival_s=0.0))
    stages = {"prefill": [0.0, 0, 0], "mixed": [0.0, 0, 0],
              "generate": [0.0, 0, 0]}          # [seconds, tokens, iters]
    m = eng.metrics
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        p0, d0 = m.prefill_tokens, m.decode_tokens
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        dp, dd = m.prefill_tokens - p0, m.decode_tokens - d0
        if dp and dd:
            st = "mixed"
        elif dp:
            st = "prefill"
        elif dd:
            st = "generate"
        else:
            continue                            # idle/admission-only tick
        stages[st][0] += dt
        stages[st][1] += dp + dd
        stages[st][2] += 1
        i += 1
        assert i < 10000, "staged run made no progress"
    rows = []
    for st, (sec, toks, iters) in stages.items():
        rows.append((f"decode_hotloop.stage.{st}.tokens_per_s",
                     toks / sec if sec else 0.0,
                     f"iters={iters} tokens={toks} wall_s={sec:.3f}"))
    present = all(v[2] > 0 for v in stages.values())
    rows.append(("decode_hotloop.stage.coverage", float(present),
                 f"all_stages_present={present}"))
    return rows


def _skew_rows(smoke: bool, seed: int = 0):
    """Hot-expert imbalance row: the decode expert FFN timed under balanced
    vs skewed routing (workloads.router_weights), einsum formulation vs the
    grouped-GEMM path of kernels/moe_gemm (DESIGN.md §14).

    Shapes are static, so at a FIXED capacity bucket both formulations cost
    the same flops — the imbalance shows up as (a) dropped assignments at
    the balanced bucket and (b) the inflated bucket (C == T*k) a skewed
    router forces you to provision, which both paths then pay for. Timings
    use the serving backend (ref on CPU); grouped-vs-einsum outputs are
    checked byte-identical under fp32 on every cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import bench_cfg, time_call
    from benchmarks.workloads import router_weights, routed_dispatch
    from repro.models.moe import _grouped_ffn_local

    cfg = bench_cfg(num_layers=1, d_model=64 if smoke else 128, experts=8)
    E, D = cfg.num_experts, cfg.d_model
    W13, W2 = 2 * cfg.d_expert, cfg.d_expert
    T = 64 if smoke else 256
    rng = np.random.default_rng(seed)
    # nonzero-mean tokens: the skew hook biases a router COLUMN, which only
    # dominates the logit x @ w when x has a constant component (real
    # activations do; zero-mean noise would cancel the bias)
    x = jnp.asarray(rng.standard_normal((T, D)) + 1.0, jnp.float32)
    w13 = jnp.asarray(rng.standard_normal((E, W13, D)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, D, W2)) * 0.05, jnp.float32)

    def einsum_ffn(xd):
        # the pre-kernel inline formulation, verbatim
        h = jnp.einsum("ecd,ewd->ecw", xd, w13,
                       preferred_element_type=jnp.float32)
        hg, hu = jnp.split(h, 2, axis=-1)
        h = (jax.nn.silu(hg) * hu).astype(cfg.compute_dtype)
        return jnp.einsum("ecw,edw->ecd", h, w2,
                          preferred_element_type=jnp.float32)

    grouped_ffn = jax.jit(
        lambda xd, n: _grouped_ffn_local(cfg, w13[None], w2[None], 0, xd,
                                         n)[0])
    einsum_ffn = jax.jit(einsum_ffn)

    rows = []
    for label, skew in (("balanced", 0.0), ("hot1", 6.0)):
        rw = router_weights(cfg, skew=skew, seed=seed)
        # balC: the bucket a balanced router needs (factor 2, the usual
        # serving headroom); hotC: the worst-case bucket a hot expert
        # forces you to provision (factor E)
        for cap, capf in (("balC", 2.0), ("hotC", float(E))):
            xd, disp, _, dropped = routed_dispatch(cfg, rw, x,
                                                   cap_factor=capf)
            n = disp.sum(axis=(0, 2)).astype(jnp.int32)   # rows per expert
            t_e = time_call(einsum_ffn, xd, warmup=2, iters=5)
            t_g = time_call(grouped_ffn, xd, n, warmup=2, iters=5)
            same = bool(jnp.array_equal(einsum_ffn(xd), grouped_ffn(xd, n)))
            rows.append((
                f"decode_hotloop.skew.{label}.{cap}.grouped_us", t_g * 1e6,
                f"einsum_us={t_e*1e6:.1f} C={xd.shape[1]} "
                f"dropped_frac={dropped:.3f} identical={same}"))
            assert same, "grouped-GEMM diverged from einsum under skew"
    return rows


def _hotloop_cfg():
    """Minimal-but-real MoE (4 routed experts, top-2, swiglu) sized so the
    device substep stands in for a fast accelerator step: on ~10 ms real
    steps the host loop is the bottleneck this benchmark measures, and a
    CPU host can only reproduce that ratio with a near-trivial model."""
    import jax.numpy as jnp
    from repro.configs import get_config
    return get_config("mixtral-8x7b").reduced(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=1, head_dim=8,
        num_experts=4, top_k=2, d_expert=32, vocab_size=256,
        capacity_factor=4.0, param_dtype=jnp.float32,
        compute_dtype=jnp.float32)


def run(smoke: bool = False, seed: int = 0):
    from repro.launch.mesh import make_mesh

    cfg = _hotloop_cfg()
    steps_list = (1, 8) if smoke else (1, 4, 8)
    out_len, reps = (192, 2) if smoke else (384, 3)

    rows = []
    mesh1 = make_mesh((1, 1), ("data", "model"))
    best, identical, disp = _measure_section(
        mesh1, cfg, steps_list, n_req=8, out_len=out_len, reps=reps,
        ladder=(8,), pages_ep=224, maxp=16, seed=seed)
    for n in steps_list:
        rows.append((f"decode_hotloop.N{n}.tokens_per_s", best[n],
                     f"best_of={reps} dispatches={disp[n]}"))
    nf = steps_list[-1]
    speedup = best[nf] / best[1]
    rows.append((f"decode_hotloop.fused_speedup_N{nf}", speedup,
                 f"identical_tokens={identical} "
                 f"fused_ge_single={speedup >= 1.0 and identical}"))
    # single-step per-token time removed by amortizing the host loop
    rows.append(("decode_hotloop.host_overhead_frac_est",
                 1.0 - 1.0 / max(speedup, 1e-9),
                 "of the N=1 per-token step time"))
    rows.extend(_staged_rows(seed=seed))
    rows.extend(_skew_rows(smoke, seed=seed))

    if not smoke:
        mesh8 = make_mesh((1, 8), ("data", "model"))
        b8, id8, _ = _measure_section(
            mesh8, cfg, (1, 8), n_req=8, out_len=64, reps=1,
            ladder=(8,), pages_ep=64, maxp=16, seed=seed)
        rows.append(("decode_hotloop.mech_1x8.fused_speedup_N8",
                     b8[8] / b8[1],
                     f"identical_tokens={id8} (rendezvous-bound; "
                     "see module docstring)"))
    return rows


def main() -> None:
    import argparse
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from _bootstrap import ensure_env_and_path
    ensure_env_and_path()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI gate: fused >= single-step throughput "
                         "with byte-identical outputs")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    ok = False
    for nm, us, derived in run(smoke=args.smoke):
        print(f"{nm},{us:.2f},{derived}", flush=True)
        if "fused_ge_single=True" in derived:
            ok = True
    if args.smoke and not ok:
        raise SystemExit("decode_hotloop smoke gate FAILED "
                         "(fused < single-step or outputs diverged)")


if __name__ == "__main__":
    main()
