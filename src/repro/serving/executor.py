"""Executor / ModelRunner: everything that touches a device (DESIGN.md §7).

The Executor owns the device-resident state the Scheduler must never see:
layout packs + the single-copy expert store, the unified KV buffer, the
step-function caches (`ResidentRuntime`), `DeviceDecodeState` + the fused
one-deep dispatch pipeline, the CoW page copier, and the `SwitchExecutor`.
It consumes the Scheduler's plans/decisions (`MixedPlan`s, `CopyPages`)
and reports completions back through the scheduler callbacks
(`commit_mixed` is driven by the engine facade; fused-pipeline
retirements go through the `on_finish` hook). `run_mixed` is THE dispatch
path: one step-fn cache keyed by (layout, rung, chunk width) serves
mixed, pure-decode, and pure-prefill plans alike. It launches and returns
the step's next tokens on the device; `fetch_mixed` reads them after the
engine has launched the next step (the one-deep pipeline, DESIGN.md §10),
and decode rows take tokens still in flight from the carried buffer.

Memory discipline mirrors the paper: the control plane (attention/embed/norm
packs, compiled steps) is resident for EVERY registered layout (the
dual-mode buffer); the data plane (expert weights, KV pool) exists once, in
the active layout.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.layouts import (LayoutSpec, get_layout, group_info,
                                pack_params, world_of)
from repro.core.residency import ResidentRuntime
from repro.core.switch_exec import CrossWorldSwitcher, SwitchExecutor
from repro.models.common import ModelConfig
from repro.models.moe import unpack_experts, unpack_w13
from repro.models.registry import init_params
from repro.serving.device_state import DeviceDecodeState
from repro.serving.kvcache import COPY_W, CacheConfig, make_copy_pages
from repro.serving.metrics import ServeMetrics
from repro.serving.request import Request
from repro.serving.scheduler import MixedPlan
from repro.serving.steps import (build_decode_loop, build_decode_pack,
                                 build_mixed_step, carry_sharding,
                                 decode_pack_specs)
from repro.tracing import span


def build_weight_init(cfg: ModelConfig, mesh, specs, active: LayoutSpec,
                      Dd: int, *, data_axis: str = "data",
                      model_axis: str = "model"):
    """The start-up weight program for the resident layouts `specs` that
    share `mesh`: `init(key) -> (packs, experts)` generates the global
    parameters from the seed and packs them into each layout's stored
    form, and `shardings` are the serve step's NamedShardings for both.
    `experts` is the `active` layout's expert store (None when `active`
    lives on another mesh); inactive layouts' experts are never built, so
    under `jax.jit(init, out_shardings=shardings)` the only device copy of
    the experts is the sharded store itself."""
    w = mesh.shape[model_axis]
    ep_axes = (data_axis, model_axis)

    def init(key):
        params = init_params(cfg, key)
        packs, experts = {}, None
        for spec in specs:
            stored = pack_params(cfg, params, spec, w,
                                 expert_G=spec.expert_group(w, Dd * w))
            pk = build_decode_pack(cfg, stored, spec, w)
            if cfg.is_moe:
                moe = dict(pk["layers"]["moe"])
                ex = {"w13": moe.pop("w13"), "w2": moe.pop("w2")}
                pk["layers"] = {**pk["layers"], "moe": moe}
                if spec == active:
                    experts = ex
            packs[spec] = pk
        return packs, experts

    shapes, _ = jax.eval_shape(init, jax.random.PRNGKey(0))
    packs, ex_sh = {}, None
    for spec in specs:
        ps = decode_pack_specs(cfg, shapes[spec], spec, model_axis,
                               ep_axes=ep_axes)
        sh = jax.tree.map(lambda p: NamedSharding(mesh, p), ps,
                          is_leaf=lambda x: isinstance(x, P))
        if cfg.is_moe:
            moe = dict(sh["layers"]["moe"])
            ex = {"w13": moe.pop("w13"), "w2": moe.pop("w2")}
            sh["layers"] = {**sh["layers"], "moe": moe}
            if spec == active:
                ex_sh = ex
        packs[spec] = sh
    return init, (packs, ex_sh)


class Executor:
    """Device-side model runner for one engine instance."""

    def __init__(self, cfg: ModelConfig, mesh, cc: CacheConfig, ecfg,
                 layouts: tuple[LayoutSpec, ...], active: LayoutSpec,
                 metrics: ServeMetrics | None = None,
                 data_axis: str = "data", model_axis: str = "model"):
        self.cfg, self.mesh, self.cc, self.ecfg = cfg, mesh, cc, ecfg
        self.m, self.da = model_axis, data_axis
        self.G = mesh.shape[model_axis]
        self.Dd = mesh.shape[data_axis]
        self.chips = self.Dd * self.G
        self.gi = group_info(cfg, self.G)
        self.layouts = layouts
        self.active = active
        self.metrics = metrics if metrics is not None else ServeMetrics()
        # --- world (device count) is a layout dimension: a resident layout
        # may pin its own world w <= launch G ("tp@4"); each distinct world
        # gets a sub-mesh slicing the launch mesh along the model axis ---
        self.meshes: dict[int, object] = {self.G: mesh}
        for spec in layouts:
            w = world_of(spec, self.G)
            if w > self.G:
                raise ValueError(
                    f"layout {str(spec)!r} wants world {w} > launch "
                    f"world {self.G}")
            if w not in self.meshes:
                self.meshes[w] = self._submesh(w)
        # full-mesh layouts split each prefill chunk 1/w per rank
        q = max(s.prefill_quantum(world_of(s, self.G)) for s in layouts)
        self.prefill_chunk = -(-ecfg.prefill_chunk // q) * q
        # --- N-resident control plane; single-copy expert data plane,
        # generated from the seed straight into the stored forms at the
        # shardings the serve steps take (one jit per world) ---
        self.packs: dict[str, dict] = {}
        self._experts = None
        for w in sorted({world_of(s, self.G) for s in layouts}):
            specs = tuple(s for s in layouts if world_of(s, self.G) == w)
            init, shardings = build_weight_init(
                cfg, self.meshes[w], specs, active, self.Dd,
                data_axis=data_axis, model_axis=model_axis)
            packs, experts = jax.jit(init, out_shardings=shardings)(
                jax.random.PRNGKey(ecfg.seed))
            self.packs.update(packs)
            if experts is not None:
                self._experts = experts
        # canonical unpacked experts kept on host only when a cross-world
        # switch is possible: it re-packs from this copy instead of
        # resharding device buffers (experts are read-only in serving, so
        # the copy is never stale)
        self._moe_host = None
        if cfg.is_moe and len({world_of(s, self.G) for s in layouts}) > 1:
            self._moe_host = self._experts_to_host()

        # --- unified KV buffer (committed to its serve-step sharding up
        # front: a lazily-committed buffer would change sharding signature
        # after the first dispatch and recompile every warmed executable) ---
        # per-rank stored shape, world-independent (cc.nelems ignores G)
        self.kv_rank_shape = cc.rank_shape(cfg, self.G)
        self.kv_flat = self._zero_kv(world_of(active, self.G))
        self._copy_fns: dict = {}          # CoW page copier, per layout

        # --- resident runtimes (all layouts, ladder of decode rungs) ---
        wmin = min(world_of(s, self.G) for s in layouts)
        self.rt = ResidentRuntime(ladder=tuple(
            b for b in ecfg.ladder if b % wmin == 0 or b >= wmin
        ) or (wmin,))
        self._pack_cache: dict = {}        # assembled packs, per layout
        # fused decode (decode_steps > 1): device-resident state + the
        # one-deep dispatch pipeline (outputs consumed one iteration late)
        self._dstate: DeviceDecodeState | None = None
        self._pending: tuple | None = None
        # host staging buffers, keyed by (B, Sq): two sets a shape, reset
        # in place and used in turn (`_staging`)
        self._stage_bufs: dict = {}
        # the mixed-step pipeline: the carried token buffer (the last
        # dispatch's next tokens, on the device), the rows a rank holds in
        # that dispatch, and (plan, passes, logits) of each dispatch
        # launched and not yet fetched, oldest first
        self._carry = self._new_carry(active)
        self._carry_rows = 1
        self._launched: deque = deque()
        # same-world switch executors, lazily built per world; the
        # cross-world switcher stages through host memory (no common mesh)
        self._switchers: dict[int, SwitchExecutor] = {}
        self.xw = CrossWorldSwitcher(
            cfg, cc, self.Dd, self._moe_host,
            model_axis=model_axis, data_axis=data_axis,
            backend=ecfg.switch_backend)
        self._key = jax.random.PRNGKey(ecfg.seed + 1)
        # (rid, position) -> fp32 logits of the sampled token, filled only
        # under EngineConfig.record_logits (parity checks across layouts)
        self.logits: dict[tuple[int, int], np.ndarray] = {}
        # completion sink for fused-pipeline retirements (the engine wires
        # this to Scheduler.finish_request)
        self.on_finish = lambda r: None

    # ------------------------------------------------------------------
    # world geometry (device count as a layout dimension)
    # ------------------------------------------------------------------
    def _submesh(self, w: int):
        """Sub-mesh over the first `w` ranks of the model axis."""
        from repro.launch.mesh import submesh
        return submesh(self.mesh, w, model_axis=self.m)

    def _world(self, layout) -> int:
        return world_of(layout, self.G)

    def _mesh_for(self, layout):
        return self.meshes[self._world(layout)]

    def _zero_kv(self, w: int):
        """Fresh zero KV buffer shaped/sharded for world `w` (per-rank
        nelems is world-independent, so only the rank axis changes),
        created on its shards."""
        shape = (self.Dd, w) + self.kv_rank_shape
        sh = NamedSharding(self.meshes[w], P(self.da, self.m))
        return jax.jit(lambda: jnp.zeros(shape, self.cfg.param_dtype),
                       out_shardings=sh)()

    def _new_carry(self, layout: LayoutSpec):
        """Zero carried token buffer for `layout`: (Dd, top rung) in the
        serve step's row sharding, whatever rung a step runs."""
        shape = (self.Dd, self.ladder_for(layout)[-1])
        return jax.device_put(
            np.zeros(shape, np.int32),
            carry_sharding(self._mesh_for(layout), layout,
                           data_axes=(self.da,), model_axis=self.m))

    def _experts_to_host(self) -> dict:
        """Canonical (L, E, ...) numpy copy of the active expert store,
        fetched one layer at a time so device memory never holds a second
        full copy."""
        w = self._world(self.active)
        lay = self.active.expert_layout(self.cfg, w, self.Dd * w)
        E = self.cfg.num_experts
        unpack = {"w13": lambda x: unpack_w13(x, lay, E),
                  "w2": lambda x: unpack_experts(x, lay, 2, E)}
        with jax.default_device(jax.devices("cpu")[0]):
            return {k: np.stack([
                np.asarray(unpack[k](jnp.asarray(np.asarray(layer))))
                for layer in self._experts[k]]) for k in unpack}

    def _switcher_for(self, w: int) -> SwitchExecutor:
        sw = self._switchers.get(w)
        if sw is None:
            sw = SwitchExecutor(
                self.cfg, self.cc, self.meshes[w], model_axis=self.m,
                data_axis=self.da, direct_reshard=self.ecfg.direct_reshard,
                backend=self.ecfg.switch_backend)
            self._switchers[w] = sw
        return sw

    @property
    def switcher(self) -> SwitchExecutor:
        """Same-world switch executor for the ACTIVE layout's world."""
        return self._switcher_for(self._world(self.active))

    def _is_cross_world(self, target) -> bool:
        return self._world(target) != self._world(self.active)

    def switch_in_progress(self) -> bool:
        return (self.xw.session is not None
                or any(sw.session is not None
                       for sw in self._switchers.values()))

    # ------------------------------------------------------------------
    # step functions (resident; warmed at startup or first use)
    # ------------------------------------------------------------------
    def ladder_for(self, layout: LayoutSpec):
        spec = get_layout(layout)
        return spec.decode_ladder(self.rt.ladder, self._world(spec))

    def _mixed_fn(self, layout: LayoutSpec, B: int, Sq: int):
        """THE serve step (steps.build_mixed_step), cached by
        (layout, rung, chunk width). Sq == 1 is the decode shape;
        Sq == prefill_chunk serves mixed and pure-prefill plans."""
        return self.rt.get_or_build(
            (layout, "mixed", B, Sq),
            lambda: build_mixed_step(
                self.cfg, self._mesh_for(layout), layout, self.cc, B, Sq=Sq,
                temperature=self.ecfg.temperature, data_axes=(self.da,),
                model_axis=self.m, attn_backend=self.ecfg.attn_backend,
                moe_backend=self.ecfg.moe_backend,
                return_logits=self.ecfg.record_logits))

    def _decode_loop_fn(self, layout: LayoutSpec, B: int, N: int):
        return self.rt.get_or_build(
            (layout, "decode_loop", B, N),
            lambda: build_decode_loop(
                self.cfg, self._mesh_for(layout), layout, self.cc, B, N,
                temperature=self.ecfg.temperature, data_axes=(self.da,),
                model_axis=self.m, attn_backend=self.ecfg.attn_backend,
                moe_backend=self.ecfg.moe_backend))

    def warmup(self, layouts=None):
        """Compile every resident layout's runtime at startup (paper §4.4).

        The ACTIVE layout's step fns also run once on throwaway zero
        inputs shaped/sharded exactly like live traffic, so the XLA
        compile and the jit fast path are paid here and never inside a
        serving iteration (jax.jit alone is lazy — building the wrapper
        compiles nothing). Inactive layouts are built only; their first
        execution happens behind a switch, whose benches warm explicitly.
        """
        for lo in (self.layouts if layouts is None else layouts):
            for b in self.ladder_for(lo):
                # decode-only plans (Sq 1) and mixed plans, which pair any
                # ladder rung with the chunk width
                self._mixed_fn(lo, b, 1)
                self._mixed_fn(lo, b, self.prefill_chunk)
                if self.ecfg.decode_steps > 1:
                    self._decode_loop_fn(lo, b, self.ecfg.decode_steps)
            if self.ecfg.prefix_cache:
                # compile the CoW page copier for EVERY resident layout
                # outside the serving loop (a null plan: the reserved
                # page 0 self-copies) — the first CoW after a live switch
                # must select an executable, not build one. Layouts at a
                # different world compile on a throwaway zero buffer
                # shaped for THEIR world (self.kv_flat has the active
                # world's rank axis and is donated by the copier).
                kv = None
                if self._world(lo) != self._world(self.active):
                    kv = self._zero_kv(self._world(lo))
                self.copy_pages(0, 0, [(0, 0)], layout=lo, kv=kv)
            if lo is not self.active:
                continue
            pk = self._assemble_pack(lo)
            # the live loop's per-step key derivation compiles here too
            key = self._step_key(0)
            maxp = self.cc.max_pages_per_req
            for b in self.ladder_for(lo):
                z2 = jnp.zeros((self.Dd, b), jnp.int32)
                bt = jnp.zeros((self.Dd, b, maxp), jnp.int32)
                src = jnp.full((self.Dd, b), -1, jnp.int32)
                for sq in (1, self.prefill_chunk):
                    self._mixed_fn(lo, b, sq)(
                        pk, jnp.zeros_like(self.kv_flat),
                        jnp.zeros((self.Dd, b, sq), jnp.int32), z2, z2, bt,
                        key, self._carry, src)
                if self.ecfg.decode_steps > 1:
                    # match the live call's committed shardings exactly
                    st = DeviceDecodeState(self._mesh_for(lo), lo, self.Dd,
                                           b, maxp, da=self.da, m=self.m)
                    st.warm_scatters()
                    self._decode_loop_fn(lo, b, self.ecfg.decode_steps)(
                        pk, jnp.zeros_like(self.kv_flat), st.tokens,
                        st.positions, st.budgets, st.block_tables, key)
        if self.ecfg.warm_switches and self.ecfg.chunk_layers > 0:
            # dry-run the chunked switch movers for every active->other
            # same-world pair: the fused kv_pack/expert_reshard staging
            # kernels compile here, so the first LIVE switch selects
            # executables, never compiles (paper §4.4). Only pairs FROM
            # the active layout are warmable — the movers trace over the
            # resident expert buffers, which are stored in its layout.
            sw = self.switcher
            experts = self._experts if self.cfg.is_moe else None
            for lo in (self.layouts if layouts is None else layouts):
                if lo is self.active or self._is_cross_world(lo):
                    continue
                sw.warmup_movers(self.active, lo, experts, self.kv_flat,
                                 self.ecfg.chunk_layers)

    def _assemble_pack(self, layout: str) -> dict:
        """Assembled (control-plane pack + resident experts) pytree, cached
        per layout; invalidated when a switch reshards the expert store."""
        pk = self._pack_cache.get(layout)
        if pk is None:
            pk = self.packs[layout]
            if self.cfg.is_moe:
                pk = dict(pk)
                layers = dict(pk["layers"])
                layers["moe"] = {**layers["moe"], **self._experts}
                pk["layers"] = layers
            self._pack_cache[layout] = pk
        return pk

    def _step_key(self, step_i: int):
        return jax.random.key_data(jax.random.fold_in(self._key, step_i))

    # ------------------------------------------------------------------
    # device page copies (the Scheduler's CopyPages decisions)
    # ------------------------------------------------------------------
    def copy_pages(self, d: int, pool: int, pairs: list,
                   layout: LayoutSpec | None = None, kv=None):
        """Device page copy within the active view (the CoW mover). EP view:
        the pair applies to `pool`'s rank only; pooled views: every rank
        copies its head-slice of the page. `layout` overrides the view
        only for warmup (a null self-copy of the reserved page 0 is a
        data no-op under any view, so inactive layouts compile safely);
        `kv` overrides the buffer for cross-world warmup, where the live
        buffer has the wrong rank-axis extent."""
        spec = self.active if layout is None else get_layout(layout)
        w = self._world(spec)
        fn = self._copy_fns.get(spec)
        if fn is None:
            fn = make_copy_pages(self.cfg, self.cc, self._mesh_for(spec),
                                 spec, model_axis=self.m, data_axis=self.da)
            self._copy_fns[spec] = fn
        rows = [pool] if spec.kv_per_rank else list(range(w))
        buf = self.kv_flat if kv is None else kv
        for b in range(0, len(pairs), COPY_W):
            blk = pairs[b:b + COPY_W]
            sp = np.zeros((self.Dd, w, COPY_W), np.int32)
            dp = np.zeros((self.Dd, w, COPY_W), np.int32)
            vm = np.zeros((self.Dd, w, COPY_W), bool)
            for g in rows:
                for i, (a, bdst) in enumerate(blk):
                    sp[d, g, i], dp[d, g, i], vm[d, g, i] = a, bdst, True
            buf = fn(buf, jnp.asarray(sp), jnp.asarray(dp), jnp.asarray(vm))
        if kv is None:
            self.kv_flat = buf
        return buf

    def run_copies(self, copies: list) -> None:
        """Execute drained CopyPages decisions in emission order (the order
        encodes the free->realloc hazards the Scheduler already resolved)."""
        if not copies:
            return
        with span("exec.copies"):
            for c in copies:
                self.copy_pages(c.d, c.pool, list(c.pairs))

    # ------------------------------------------------------------------
    # mixed-batch dispatch (THE serve path)
    # ------------------------------------------------------------------
    def _staging(self, B: int, Sq: int) -> tuple:
        """(tokens, positions, valid_len, block_table, src) host buffers
        for one (rung, chunk) shape — reset in place (src to -1, the rest
        to 0) and reused. Two sets a shape take turns: a dispatch may
        still read its inputs from host memory while the next is staged,
        and the one before it has been fetched by then."""
        sets = self._stage_bufs.get((B, Sq))
        if sets is None:
            maxp = self.cc.max_pages_per_req
            sets = [tuple(np.zeros(shape, np.int32) for shape in (
                (self.Dd, B, Sq), (self.Dd, B), (self.Dd, B),
                (self.Dd, B, maxp), (self.Dd, B))) for _ in range(2)]
            self._stage_bufs[(B, Sq)] = sets
        sets.reverse()
        bufs = sets[0]
        for a in bufs[:4]:
            a.fill(0)
        bufs[4].fill(-1)
        return bufs

    def run_mixed(self, plan: MixedPlan, step_i: int) -> jax.Array:
        """Stage and launch ONE mixed-batch step: decode rows (n_tokens ==
        1) and prefill-chunk rows under a single executable. A decode row
        whose input token is still in flight (`MixedRow.src`) takes it
        from the carried token buffer on the device. Returns the step's
        (Dd, B) next tokens, still on the device: `fetch_mixed` brings
        them to the host for Scheduler.commit_mixed, after the next
        dispatch is launched."""
        B, Sq = plan.B, plan.Sq
        with span("exec.stage", B=B, Sq=Sq, slots=self.Dd * B * Sq) as sp:
            toks, pos, vl, bt, src = self._staging(B, Sq)
            n_dec = n_pref = 0
            for row in plan.rows:
                r, d, s, n = row.req, row.d, row.row, row.n_tokens
                if row.kind == "decode":
                    if row.src >= 0:
                        # its index in its rank's block of the carry
                        src[d, s] = row.src % self._carry_rows
                    else:
                        toks[d, s, 0] = r.output[-1]
                    n_dec += 1
                else:
                    toks[d, s, :n] = r.prompt_array()[row.start_pos:
                                                      row.start_pos + n]
                    n_pref += n
                pos[d, s] = row.start_pos
                vl[d, s] = n
                bt[d, s, :len(r.pages)] = r.pages
            sp.set_metadata(dec=n_dec, pre=n_pref)
            fn = self._mixed_fn(self.active, B, Sq)
            args = (self._assemble_pack(self.active), self.kv_flat,
                    jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(vl),
                    jnp.asarray(bt), self._step_key(step_i), self._carry,
                    jnp.asarray(src))
        with span("exec.launch"):
            nxt, self.kv_flat, passes, self._carry, *logits = fn(*args)
        # start the device->host copies now; the host reads them after it
        # has staged and launched the next dispatch
        lg = logits[0] if logits else None
        for a in (nxt, passes, lg):
            if hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()
        self._carry_rows = (B // self._world(self.active)
                            if self.active.slots_sharded else B)
        self._launched.append((plan, passes, lg))
        if n_pref:
            self.metrics.prefill(n_pref)
        if n_dec:
            self.metrics.decode(n_dec, 1)
        self.metrics.dispatch(mixed=bool(n_dec and n_pref))
        return nxt

    def fetch_mixed(self, nxt) -> np.ndarray:
        """Host copy of the oldest launched dispatch's next tokens (`nxt`,
        as `run_mixed` returned it); records its expert weight passes and,
        under record_logits, its logits."""
        plan, passes, lg = self._launched.popleft()
        with span("exec.fetch"):
            out, passes = jax.device_get((nxt, passes))
            self.metrics.weight_passes(passes)
            if lg is not None:
                lg = np.asarray(lg)
                for row in plan.rows:
                    # keyed by the KV position of the token these logits
                    # sample
                    key = (row.req.rid, row.start_pos + row.n_tokens)
                    self.logits[key] = lg[row.d, row.row, :self.cfg.vocab_size]
        return out

    # ------------------------------------------------------------------
    # fused decode (decode_steps > 1): device-resident state, N-step loop
    # ------------------------------------------------------------------
    def clear_slot(self, r: Request) -> None:
        """Vacate a fused-decode device slot (zero budget, null pages).
        Installed into the Scheduler as its `clear_slot` hook."""
        st = self._dstate
        if (st is not None and r.slot is not None and r.slot >= 0
                and st.slot_rid[r.data_group, r.slot] == r.rid):
            st.slot_rid[r.data_group, r.slot] = -1
            st.apply([], [(r.data_group, r.slot, 0, [])])
        r.slot = None
        r.budget_dev = 0

    def _rebuild_dstate(self, B: int, sched) -> DeviceDecodeState:
        """Fresh device state for a new rung/layout; every running request
        re-joins through the next `plan_fused` pass (requires a drained
        pipeline — callers consume in-flight outputs first)."""
        for r in sched.running.values():
            r.slot = None
            r.budget_dev = 0
        self._dstate = DeviceDecodeState(self._mesh_for(self.active),
                                         self.active, self.Dd, B,
                                         self.cc.max_pages_per_req,
                                         da=self.da, m=self.m)
        return self._dstate

    def decode_fused(self, sched, step_i: int) -> None:
        """One fused decode iteration: plan against the device state, apply
        the delta scatters, dispatch the N-step loop, pipeline the output
        fetch one iteration deep."""
        with span("exec.fused"):
            N = self.ecfg.decode_steps
            if not sched.running:
                self.drain_decode()
                return
            B = sched.fused_rung()
            st = self._dstate
            if st is None or st.B != B or st.layout is not self.active:
                self.drain_decode()        # step boundary before a rebuild
                st = self._rebuild_dstate(B, sched)
            joins, grows, plan, capped, starved = sched.plan_fused(st, N)
            self.run_copies(sched.drain_copies())
            # deltas must land even when nothing steps: plan_fused already
            # recorded the joins in the host mirror, and a budget-clamped
            # join still needs its token/position/table row on device
            st.apply(joins, grows)
            sched.resolve_fused(plan, capped, starved)
            if not plan:
                self.drain_decode()        # nothing live; flush the pipeline
                return
            fn = self._decode_loop_fn(self.active, st.B, N)
            out, self.kv_flat, tok, pos, bud, passes = fn(
                self._assemble_pack(self.active), self.kv_flat, st.tokens,
                st.positions, st.budgets, st.block_tables,
                self._step_key(step_i))
            st.advance(tok, pos, bud)
            # start the device->host copy now; the tokens are read one
            # engine iteration later, so host dispatch runs ahead of the
            # device
            for a in (out, passes):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            total = 0
            for d, s, r, steps in plan:
                r.inflight += steps
                r.budget_dev -= steps
                total += steps
            self.metrics.decode(total, N)
            self.metrics.dispatch()
            prev, self._pending = self._pending, (out, passes, plan, st)
            if prev is not None:
                self._consume(prev)

    def _consume(self, pending):
        """Fetch one fused dispatch's tokens and retire finished requests.
        Output rows are deterministic in shape: slot budgets stop a request
        exactly at its target length on device, so `steps` per slot is
        known at dispatch time."""
        out, passes, plan, st = pending
        arr, passes = jax.device_get((out, passes))
        self.metrics.weight_passes(passes)
        for d, s, r, steps in plan:
            for j in range(steps):
                r.output.append(int(arr[d, s, j]))
            r.inflight -= steps
            if r.inflight == 0 and r.done():
                self.on_finish(r)
                st.slot_rid[d, s] = -1
                r.slot = None
                r.budget_dev = 0

    def drain_decode(self) -> None:
        """Consume any in-flight fused outputs: request metadata reaches a
        decode step boundary (required before switch planning, rung/layout
        rebuilds, and at shutdown)."""
        if self._pending is not None:
            prev, self._pending = self._pending, None
            self._consume(prev)

    def suspend_fused(self, sched) -> None:
        """Drain the one-deep fused pipeline and park the device decode
        state. While a prefill chunk rides the mixed step (decode_steps > 1
        engines fall back to single-token mixed dispatches for the storm's
        duration), the fused slot mirror would go stale — positions advance
        host-side only. Every runner re-joins through `_rebuild_dstate` +
        `plan_fused` once the engine returns to pure-decode iterations."""
        self.drain_decode()
        if self._dstate is not None:
            for r in sched.running.values():
                r.slot = None
                r.budget_dev = 0
            self._dstate = None

    # ------------------------------------------------------------------
    # switch execution (device side; the engine facade orchestrates)
    # ------------------------------------------------------------------
    def _post_switch(self, target: LayoutSpec) -> None:
        # layout geometry changed: the device decode state must be rebuilt
        # and the assembled packs re-point at the resharded expert store
        self.active = target
        self._dstate = None
        self._pack_cache.clear()
        self._carry = self._new_carry(target)

    def _commit_cross_world(self, target: LayoutSpec, live: list[Request]):
        """Commit the cross-world session: device_put the staged host
        buffers onto the destination sub-mesh, swap the data plane."""
        (experts, kv, alloc, caches, st) = self.xw.commit(
            live, self.kv_flat, self._mesh_for(target))
        if self.cfg.is_moe:
            self._experts = experts
        # attention-free models have no KV to migrate: re-zero at the
        # destination world so the serve step sees the right rank axis
        self.kv_flat = kv if kv is not None else self._zero_kv(
            self._world(target))
        self._post_switch(target)
        return alloc, caches, st

    def switch_monolithic(self, target: LayoutSpec, live: list[Request],
                          alloc, caches):
        """Monolithic switch: decode paused for the whole migration.
        Returns (new_alloc, new_caches, stats)."""
        target = get_layout(target)
        if self._is_cross_world(target):
            # monolithic == the chunked cross-world path with one giant
            # chunk, driven to completion inline
            self.xw.start(self.active, target, self._world(self.active),
                          self._world(target), live, self.kv_flat,
                          chunk_layers=10 ** 9, caches=caches)
            while not self.xw.session.done:
                self.xw.advance(self.kv_flat)
            return self._commit_cross_world(target, live)
        experts = self._experts if self.cfg.is_moe else None
        (experts, self.kv_flat, alloc, caches, st) = self.switcher.monolithic(
            self.active, target, live, experts, self.kv_flat,
            cur_alloc=alloc, caches=caches)
        if self.cfg.is_moe:
            self._experts = experts
        self._post_switch(target)
        return alloc, caches, st

    def switch_start(self, target: LayoutSpec, live: list[Request],
                     chunk_layers: int, alloc, caches):
        """Open a chunked switch session (destination staged layer-chunk by
        layer-chunk while decode keeps running on the source layout)."""
        target = get_layout(target)
        if self._is_cross_world(target):
            return self.xw.start(
                self.active, target, self._world(self.active),
                self._world(target), live, self.kv_flat, chunk_layers,
                caches=caches)
        return self.switcher.start(
            self.active, target, live,
            self._experts if self.cfg.is_moe else None,
            self.kv_flat, chunk_layers, cur_alloc=alloc, caches=caches)

    def switch_advance(self) -> None:
        if self.xw.session is not None:
            self.xw.advance(self.kv_flat)
            return
        self.switcher.advance(
            self._experts if self.cfg.is_moe else None, self.kv_flat)

    def switch_abort(self):
        """Abandon the chunked session: the active layout, device decode
        state, and assembled packs are untouched — decode never left the
        source buffers — so no _post_switch runs. Returns the aborted
        attempt's SwitchStats."""
        if self.xw.session is not None:
            return self.xw.abort()
        return self.switcher.abort()

    def switch_commit(self, target: LayoutSpec, live: list[Request]):
        """Dirty-page delta + commit; returns (new_alloc, new_caches, stats)."""
        target = get_layout(target)
        if self.xw.session is not None:
            return self._commit_cross_world(target, live)
        (experts, self.kv_flat, alloc, caches,
         st) = self.switcher.commit(live, self.kv_flat)
        if self.cfg.is_moe:
            self._experts = experts
        self._post_switch(target)
        return alloc, caches, st
