"""Device-resident decode state for the fused decode loop (DESIGN.md §5).

Host scheduling still decides *which request sits in which slot*; everything
the decode loop actually reads — last token, KV position, remaining-token
budget, block-table row — lives on device in the step's sharding and is
updated by small jitted delta scatters when requests join, grow their page
list, or get their budget clamped/restored, instead of being re-materialized
from host metadata every step (the single-step path's per-token
(Dd, B, maxp) rebuild + upload).

The state is functional: `build_decode_loop` returns the advanced
tokens/positions/budgets arrays and the engine swaps them in via
`advance()`. Delta updates are chunked to a FIXED width (`SCATTER_W`, the
same fixed-plan-width idiom as the switch executor's KV_BLOCK): padding
rows carry an out-of-bounds slot index, which JAX scatter semantics drop
(`mode="drop"`), so there are exactly two scatter executables per rung —
a burst of joins can never hit a compile inside the serving loop.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# fixed row count per scatter call; wider deltas split into blocks
SCATTER_W = 8

# (mesh, row_spec, B, maxp, kind) -> jitted scatter. Module-level (like
# steps._PARAMS_CACHE) because states are recreated on every rung change
# and the executables must survive them; the key space is small — one
# mesh per process and two kinds per ladder rung.
_SCATTER_CACHE: dict = {}


def _join_fn(mesh, row_spec, B: int, maxp: int):
    """Scatter full rows: tokens, positions, budgets, block-table row."""
    key = (mesh, tuple(row_spec), B, maxp, "join")
    if key not in _SCATTER_CACHE:
        sh2 = NamedSharding(mesh, P(*row_spec))
        sh3 = NamedSharding(mesh, P(*row_spec, None))

        def fn(tok, pos, bud, bt, di, si, v_tok, v_pos, v_bud, v_bt):
            tok = tok.at[di, si].set(v_tok, mode="drop")
            pos = pos.at[di, si].set(v_pos, mode="drop")
            bud = bud.at[di, si].set(v_bud, mode="drop")
            bt = bt.at[di, si].set(v_bt, mode="drop")
            return tok, pos, bud, bt

        _SCATTER_CACHE[key] = jax.jit(
            fn, donate_argnums=(0, 1, 2, 3),
            out_shardings=(sh2, sh2, sh2, sh3))
    return _SCATTER_CACHE[key]


def _grow_fn(mesh, row_spec, B: int, maxp: int):
    """Scatter budget + block-table row only (token/position stay ahead on
    device — a grown or budget-clamped slot must not lose its loop state)."""
    key = (mesh, tuple(row_spec), B, maxp, "grow")
    if key not in _SCATTER_CACHE:
        sh2 = NamedSharding(mesh, P(*row_spec))
        sh3 = NamedSharding(mesh, P(*row_spec, None))

        def fn(bud, bt, di, si, v_bud, v_bt):
            bud = bud.at[di, si].set(v_bud, mode="drop")
            bt = bt.at[di, si].set(v_bt, mode="drop")
            return bud, bt

        _SCATTER_CACHE[key] = jax.jit(
            fn, donate_argnums=(0, 1), out_shardings=(sh2, sh3))
    return _SCATTER_CACHE[key]


@dataclass
class DeviceDecodeState:
    """One decode rung's device-resident state + its host occupancy mirror.

    Arrays live in the decode step's sharding (slot-sharded layouts split
    the B dim over the model axis). `slot_rid` is the host-side occupancy
    map (-1 = free); budgets/positions/tokens are mirrored only implicitly
    through Request bookkeeping (`budget_dev`, `inflight`).
    """
    mesh: object
    layout: object                 # LayoutSpec
    Dd: int
    B: int
    maxp: int
    da: str = "data"
    m: str = "model"
    tokens: jax.Array = field(init=False)
    positions: jax.Array = field(init=False)
    budgets: jax.Array = field(init=False)
    block_tables: jax.Array = field(init=False)
    slot_rid: np.ndarray = field(init=False)

    def __post_init__(self):
        row = ((self.da, self.m) if self.layout.slots_sharded
               else (self.da, None))
        self._row = row
        sh2 = NamedSharding(self.mesh, P(*row))
        sh3 = NamedSharding(self.mesh, P(*row, None))
        z2 = np.zeros((self.Dd, self.B), np.int32)
        z3 = np.zeros((self.Dd, self.B, self.maxp), np.int32)
        self.tokens = jax.device_put(z2, sh2)
        self.positions = jax.device_put(z2, sh2)
        self.budgets = jax.device_put(z2, sh2)
        self.block_tables = jax.device_put(z3, sh3)
        self.slot_rid = np.full((self.Dd, self.B), -1, np.int64)

    # ------------------------------------------------------------------
    def free_slot(self, d: int, lo: int, hi: int) -> int | None:
        """First free slot index in [lo, hi) of data group d."""
        for s in range(lo, hi):
            if self.slot_rid[d, s] < 0:
                return s
        return None

    def _bt_row(self, pages: list[int]) -> np.ndarray:
        row = np.zeros(self.maxp, np.int32)
        n = min(len(pages), self.maxp)
        row[:n] = pages[:n]
        return row

    def apply(self, joins: list, grows: list) -> None:
        """Apply host-side deltas to the device arrays.

        joins: (d, s, token, position, budget, pages) — new occupants;
        grows: (d, s, budget, pages) — page growth / budget updates for
        slots whose token/position are already correct on device.
        Deltas are split into fixed-width SCATTER_W blocks (padding rows
        dropped via OOB indices), so each kind dispatches one pre-compiled
        executable regardless of burst size.
        """
        W = SCATTER_W
        for b in range(0, len(joins), W):
            blk = joins[b:b + W]
            di = np.zeros(W, np.int32)
            si = np.full(W, self.B, np.int32)        # OOB -> dropped
            vt = np.zeros(W, np.int32)
            vp = np.zeros(W, np.int32)
            vb = np.zeros(W, np.int32)
            vbt = np.zeros((W, self.maxp), np.int32)
            for i, (d, s, tok, pos, bud, pages) in enumerate(blk):
                di[i], si[i], vt[i], vp[i], vb[i] = d, s, tok, pos, bud
                vbt[i] = self._bt_row(pages)
            fn = _join_fn(self.mesh, self._row, self.B, self.maxp)
            (self.tokens, self.positions, self.budgets,
             self.block_tables) = fn(
                self.tokens, self.positions, self.budgets, self.block_tables,
                di, si, vt, vp, vb, vbt)
        for b in range(0, len(grows), W):
            blk = grows[b:b + W]
            di = np.zeros(W, np.int32)
            si = np.full(W, self.B, np.int32)
            vb = np.zeros(W, np.int32)
            vbt = np.zeros((W, self.maxp), np.int32)
            for i, (d, s, bud, pages) in enumerate(blk):
                di[i], si[i], vb[i] = d, s, bud
                vbt[i] = self._bt_row(pages)
            fn = _grow_fn(self.mesh, self._row, self.B, self.maxp)
            self.budgets, self.block_tables = fn(
                self.budgets, self.block_tables, di, si, vb, vbt)

    def warm_scatters(self) -> None:
        """Compile both scatter executables with all-padding blocks (every
        row OOB-dropped): the serving loop never hits a scatter compile."""
        self.apply([(0, self.B, 0, 0, 0, [])], [(0, self.B, 0, [])])

    def advance(self, tokens, positions, budgets) -> None:
        """Swap in the arrays returned by the fused decode loop."""
        self.tokens, self.positions, self.budgets = tokens, positions, budgets
