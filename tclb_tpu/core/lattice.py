"""Lattice engine: state, streaming, per-stage step, iteration.

TPU-native re-design of the reference lattice engine (reference
src/Lattice.cu.Rt, src/LatticeContainer.inc.cpp.Rt, src/cuda.cu.Rt):

* the reference's double-buffered ``FTabs`` snapshots + 27 margin blocks
  become a single dense ``(n_storage, *shape)`` array per state; streaming is
  a functional pull (``jnp.roll`` — periodic like the reference's wrapped
  margins), so double buffering is XLA's problem (donated buffers), not ours;
* the reference's per-(operation x globals x stage) generated kernel zoo
  (src/cuda.cu.Rt:81-283) becomes ONE traced step function per stage,
  specialized by ``jax.jit``;
* per-node ``switch (NodeType & NODE_BOUNDARY)`` dispatch
  (src/d2q9/Dynamics.c.Rt:121-150) becomes mask/select algebra on the flag
  field — branchless, which is exactly what the VPU wants;
* globals accumulated with shared-memory trees + atomics
  (src/cuda.cu.Rt:176-202) become masked ``jnp.sum``/``max`` reductions
  (deterministic, unlike the reference's atomic order).

The engine is pure-functional: ``step(state, params) -> state`` is jittable,
differentiable (the adjoint path — reference Tapenade machinery, tools/makeAD)
and shardable (parallel/halo.py wraps it in ``shard_map``).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import lru_cache, partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from tclb_tpu.core import shift as ddf
from tclb_tpu.core.registry import Model
from tclb_tpu.ops.fusion import zone_plane
from tclb_tpu import telemetry

FLAG_DTYPE = jnp.uint16


@struct.dataclass
class SimParams:
    """Runtime parameters: the reference's GPU-const-memory settings
    (src/LatticeContainer.inc.cpp.Rt:32-55) + zonal setting tables (C7,
    src/ZoneSettings.h).  ``zone_table[s, z]`` is the value of setting ``s``
    in settings-zone ``z``; non-zonal settings read ``settings[s]``.

    Time-dependent zonal settings (the reference's per-(setting, zone)
    time tables, src/ZoneSettings.h:9-120) live in ``time_series``: row
    ``r`` of the ``(n_series, T)`` array is the per-iteration value of the
    (setting, zone) pair recorded in the static ``series_map`` as
    ``(setting_index, zone, r)``.  At iteration ``t`` the effective value is
    ``time_series[r, t % T]``, overriding ``zone_table``.  Gradients with
    respect to ``time_series`` are the reference's GRAD planes (control
    gradients) — free here because the whole step is differentiable."""

    settings: jnp.ndarray        # (n_settings,) real
    zone_table: jnp.ndarray      # (n_settings, zone_max) real
    time_series: Optional[jnp.ndarray] = None   # (n_series, T) real
    series_map: tuple = struct.field(pytree_node=False, default=())


@struct.dataclass
class LatticeState:
    """The complete per-step lattice state (a pytree — one pytree per
    reference ``FTabs`` snapshot)."""

    fields: jnp.ndarray          # (n_storage, *shape) real
    flags: jnp.ndarray           # (*shape) uint16 node-type bitfield
    globals_: jnp.ndarray        # (n_globals,) per-iteration integrals
    iteration: jnp.ndarray       # () int32


# --------------------------------------------------------------------------- #
# Streaming
# --------------------------------------------------------------------------- #


def pull_stream(model: Model, fields: jnp.ndarray) -> jnp.ndarray:
    """Pull-scheme streaming: plane ``i`` at node ``x`` receives the value
    stored at ``x - e_i`` (reference pull streaming,
    src/LatticeAccess.inc.cpp.Rt:182-263).  Periodic wrap — the reference's
    global domain is periodic through its margin wiring; walls are painted.

    ``jnp.roll(a, s)[x] == a[x - s]``, so rolling plane ``i`` by ``e_i``
    is exactly the pull.  Zero-vector planes are left untouched.
    """
    ndim = model.ndim
    out = []
    for i in range(model.n_storage):
        dx, dy, dz = (int(v) for v in model.ei[i])
        plane = fields[i]
        shifts, axes = [], []
        # axis layout: (..., z, y, x) — x is last (TPU lane dimension)
        for shift, axis in ((dz, -3), (dy, -2), (dx, -1)):
            if shift and (ndim >= -axis):
                shifts.append(shift)
                axes.append(axis)
        if shifts:
            plane = jnp.roll(plane, shifts, axes)
        out.append(plane)
    return jnp.stack(out)


class Streaming:
    """Streaming strategy: how pulled densities and neighbor Field loads are
    realized.  This default implements the single-device / global-array case
    (periodic roll).  The sharded engine substitutes
    :class:`tclb_tpu.parallel.halo.HaloStreaming`, which fetches halos over
    the mesh — injecting the strategy here keeps model code identical in both
    worlds (the reference achieves the same with its margin-block pointer
    rewiring, src/Lattice.cu.Rt:399-410)."""

    def __init__(self, model: Model):
        self.model = model

    def pull(self, fields: jnp.ndarray) -> jnp.ndarray:
        return pull_stream(self.model, fields)

    def make_loader(self, raw: jnp.ndarray) -> Callable:
        """Return ``load(index, dx, dy, dz)`` giving the ``x + d`` neighbor
        of storage plane ``index``."""
        ndim = self.model.ndim

        def load(index: int, dx: int, dy: int, dz: int) -> jnp.ndarray:
            plane = raw[index]
            shifts, axes = [], []
            for shift, axis in ((dz, -3), (dy, -2), (dx, -1)):
                if shift and (ndim >= -axis):
                    shifts.append(-shift)
                    axes.append(axis)
            return jnp.roll(plane, shifts, axes) if shifts else plane

        return load


# --------------------------------------------------------------------------- #
# Node context — what a model's Run()/Init() sees
# --------------------------------------------------------------------------- #


def series_overrides(params: SimParams, i: int, iteration) -> list:
    """``[(zone, value)]`` scalar overrides of setting ``i`` from its
    registered <Control> time series at ``iteration`` (mod-T wrap);
    empty without a series.  Shared by NodeCtx.setting and the fast
    engines' per-step aux planes — one implementation, no drift.

    Returned as per-zone SCALARS to be applied with
    ``jnp.where(zones == z, value, plane)`` against a loop-invariant
    base plane: modifying the zone TABLE and indexing it with the zone
    ids per step kept a (zone_max,)->(ny,nx) gather inside the iteration
    scan, which XLA could not hoist and lowered catastrophically
    (~25 ms/step at 1024^2 on v5e); masked selects against the hoisted
    base plane are free.  No such gather is left: the base plane itself
    is a chain of selects (``fusion.zone_plane``)."""
    rows = [(z, r) for (si, z, r) in params.series_map if si == i]
    if not rows or params.time_series is None:
        return []
    T = params.time_series.shape[1]
    t = jnp.mod(jnp.asarray(iteration, jnp.int32), T)
    return [(z, params.time_series[r, t]) for z, r in rows]


def series_dt_overrides(params: SimParams, i: int, iteration) -> list:
    """``[(zone, d/dt value)]`` for setting ``i``'s series: one-sided
    central differences clamped at the horizon endpoints (the finite
    control horizon is not periodic — a wrapped difference would mix the
    two ends into a spurious spike); empty without a series."""
    rows = [(z, r) for (si, z, r) in params.series_map if si == i]
    if not rows or params.time_series is None:
        return []
    ts = params.time_series
    T = ts.shape[1]
    t = jnp.mod(jnp.asarray(iteration, jnp.int32), T)
    lo = jnp.maximum(t - 1, 0)
    hi = jnp.minimum(t + 1, T - 1)
    span = jnp.maximum(hi - lo, 1).astype(ts.dtype)
    return [(z, (ts[r, hi] - ts[r, lo]) / span) for z, r in rows]


class NodeCtx:
    """The model-facing view of one lattice-wide kernel invocation.

    Plays the role of the reference's generated node object (``Node_Run`` with
    its pop'ed density locals, settings in const memory and NodeType register,
    src/cuda.cu.Rt:236-274) — but vectorized over the whole (local) lattice:
    every accessor returns full planes, and "per-node dispatch" is mask
    algebra via :meth:`nt_is` / :meth:`boundary_case`.
    """

    def __init__(self, model: Model, fields: jnp.ndarray, raw: jnp.ndarray,
                 flags: jnp.ndarray, params: SimParams,
                 loader: Optional[Callable] = None,
                 iteration: Any = 0, avg_start: Any = 0,
                 present: Optional[set] = None,
                 compute_globals: bool = True):
        self.model = model
        self._fields = fields      # pulled (streamed) storage
        self._raw = raw            # un-streamed storage (for Field loads)
        self._loader = loader or Streaming(model).make_loader(raw)
        self.flags = flags
        self.params = params
        self.iteration = iteration
        self.avg_start = avg_start
        self._globals: dict[str, jnp.ndarray] = {}
        self._zone_ids = None
        # static specialization knobs (the reference compiles its kernels
        # per model boundary set and per Globals mode, src/cuda.cu.Rt:81):
        # `present` skips boundary cases whose node types are not painted;
        # `compute_globals=False` is the NoGlobals kernel flavor
        self.present = present
        self.compute_globals = compute_globals

    def avg_samples(self) -> jnp.ndarray:
        """Iterations accumulated into the running averages since the last
        <Average> reset (reference ``iter - reset_iter``); at least 1."""
        n = jnp.asarray(self.iteration) - jnp.asarray(self.avg_start)
        return jnp.maximum(n.astype(self._fields.dtype), 1.0)

    # -- field access ------------------------------------------------------- #

    def group(self, name: str) -> jnp.ndarray:
        """Streamed stack of all densities in a group: shape (n, *shape)."""
        idx = self.model.groups[name]
        return self._fields[jnp.array(idx)] if len(idx) > 1 \
            else self._fields[idx[0]][None]

    def density(self, name: str) -> jnp.ndarray:
        return self._fields[self.model.storage_index[name]]

    def load(self, name: str, dx: int = 0, dy: int = 0, dz: int = 0
             ) -> jnp.ndarray:
        """Neighbor access to a stored Field: value at ``x + (dx,dy,dz)``
        (reference ``load_<field><DX,DY,DZ>``,
        src/LatticeAccess.inc.cpp.Rt:266-292).  Goes through the injected
        streaming strategy so sharded runs fetch across shard boundaries."""
        return self._loader(self.model.storage_index[name], dx, dy, dz)

    def store(self, groups: dict[str, jnp.ndarray]) -> dict:
        """Declare the stage's write set: group/plane name -> new stack
        (the reference's push_<Stage> writes,
        src/LatticeAccess.inc.cpp.Rt:216-225, restricted to the stage's
        ``save`` set, AddStage in src/conf.R:290).  The engine writes ONLY
        these planes back into storage; unmentioned planes keep their
        previous (un-streamed) value — which equals the streamed value for
        every zero-velocity plane, and saves the HBM write for
        never-changing planes (BC buffers, coupling fields, cut
        distances)."""
        return groups

    # -- settings ----------------------------------------------------------- #

    def setting(self, name: str) -> jnp.ndarray:
        """Scalar for plain settings; per-node plane for zonal settings
        (selected through the flag's zone bits, ``fusion.zone_plane`` —
        reference ``ZoneSetting()`` device accessor,
        src/LatticeContainer.h.Rt:89-108).  Zones with a
        registered time series (``<Control>``) read the current iteration's
        entry instead of the constant table."""
        m = self.model
        i = m.setting_index[name]
        spec = m.settings[i]
        if not spec.zonal:
            return self.params.settings[i]
        plane = zone_plane(self.params.zone_table[i], self._zones())
        for z, v in series_overrides(self.params, i, self.iteration):
            plane = jnp.where(self._zones() == z,
                              v.astype(plane.dtype), plane)
        return plane

    def setting_dt(self, name: str) -> jnp.ndarray:
        """Time derivative of a zonal setting: central difference over its
        time series (reference ``<setting>_DT`` planes, the ``set_internal``
        derivative at src/ZoneSettings.h:102-119); zero where no series.
        One-sided differences at the series endpoints — the series is a
        finite control horizon, not periodic, so a wrapped central
        difference would mix the two ends into a spurious spike."""
        m = self.model
        i = m.setting_index[name]
        plane = jnp.zeros(self.flags.shape, dtype=self._fields.dtype)
        for z, v in series_dt_overrides(self.params, i, self.iteration):
            plane = jnp.where(self._zones() == z,
                              v.astype(plane.dtype), plane)
        return plane

    def _zones(self) -> jnp.ndarray:
        if self._zone_ids is None:
            self._zone_ids = (self.flags.astype(jnp.int32)
                              >> self.model.zone_shift)
        return self._zone_ids

    # -- node types --------------------------------------------------------- #

    def nt_is(self, name: str) -> jnp.ndarray:
        """Bool plane: node's group-field equals this node type."""
        t = self.model.node_types[name]
        return (self.flags & FLAG_DTYPE(t.mask)) == FLAG_DTYPE(t.value)

    def nt_in_group(self, group: str) -> jnp.ndarray:
        m = self.model.group_masks[group]
        return (self.flags & FLAG_DTYPE(m)) != FLAG_DTYPE(0)

    def boundary_case(self, f: jnp.ndarray,
                      cases: dict[str, Callable[[jnp.ndarray], jnp.ndarray]]
                      ) -> jnp.ndarray:
        """Vectorized ``switch (NodeType & NODE_<group>)``: each case function
        maps the full stack to a modified stack; nodes whose group-field
        equals the named type select that case's result, others keep ``f``
        (each node type carries its own group mask).  Multiple names may
        share a function by passing a tuple key."""
        out = f
        for names, fn in cases.items():
            if isinstance(names, str):
                names = (names,)
            if self.present is not None:
                names = tuple(n for n in names if n in self.present)
                if not names:
                    continue   # type not painted: skip the whole case
            mask = self.nt_is(names[0])
            for n in names[1:]:
                mask = mask | self.nt_is(n)
            out = jnp.where(mask[None], fn(f), out)
        return out

    # -- globals ------------------------------------------------------------ #

    def add_global(self, name: str, plane: jnp.ndarray,
                   where: Optional[jnp.ndarray] = None) -> None:
        """Accumulate a per-node contribution to a Global (reference
        ``AddTo<Global>`` + atomic reduction, src/cuda.cu.Rt:130-202).
        ``where`` masks contributing nodes (e.g. objective node types)."""
        if not self.compute_globals:
            return
        if where is not None:
            plane = jnp.where(where, plane, jnp.zeros_like(plane))
        if name in self._globals:
            self._globals[name] = self._globals[name] + plane
        else:
            self._globals[name] = plane

    def reduce_globals(self) -> jnp.ndarray:
        m = self.model
        out = jnp.zeros((m.n_globals,),
                        dtype=self._fields.dtype)
        for name, plane in self._globals.items():
            g = m.globals_[m.global_index[name]]
            red = jnp.max(plane) if g.op == "MAX" else jnp.sum(plane)
            out = out.at[m.global_index[name]].set(red)
        return out


# --------------------------------------------------------------------------- #
# Step / iterate
# --------------------------------------------------------------------------- #


def make_stage_step(model: Model, stage_name: str,
                    streaming: Optional[Streaming] = None,
                    present: Optional[set] = None,
                    compute_globals: bool = True) -> Callable:
    """Build the pure step function for one stage (the reference compiles a
    ``Node_Run`` kernel per stage, src/cuda.cu.Rt:209-283; we trace one).

    ``streaming`` injects the streaming strategy (pull + neighbor loads):
    default is the global periodic roll; the sharded engine
    (parallel/halo.py) injects a halo-exchange strategy instead.

    ``present``/``compute_globals`` specialize the trace the way the
    reference specializes its kernel zoo (per boundary set and per
    Globals mode): absent node types skip their full-lattice boundary
    case, and the NoGlobals flavor skips every reduction."""
    stage = model.stages[stage_name]
    fn = model.stage_fns[stage.main]
    if fn is None:
        raise ValueError(f"model {model.name}: stage {stage_name} has no "
                         f"bound function {stage.main!r}")
    if streaming is None:
        streaming = Streaming(model)

    def step(state: LatticeState, params: SimParams) -> LatticeState:
        # full-f32 matmuls: on TPU, einsum/tensordot otherwise default to
        # bf16 MXU passes, and bf16's 8 mantissa bits destroy the moment
        # transforms (the d2q9 Karman case visibly diverges by iteration
        # ~100).  LBM is bandwidth-bound — exact matmuls cost nothing
        # measurable.  Scoped here, not via global config, so importing the
        # framework never changes precision for unrelated user code.
        with jax.default_matmul_precision("highest"):
            return _step_inner(state, params)

    def _step_inner(state: LatticeState, params: SimParams) -> LatticeState:
        raw = state.fields
        pulled = streaming.pull(raw) if stage.load_densities else raw
        ctx = NodeCtx(model, pulled, raw, state.flags, params,
                      loader=streaming.make_loader(raw),
                      iteration=state.iteration,
                      present=present, compute_globals=compute_globals)
        new_fields = fn(ctx)
        # A stage returns its write set as a dict (group or plane name ->
        # stack/plane): only the named planes are saved, everything else
        # keeps its UN-streamed storage — the reference's per-stage save
        # set (AddStage save=..., src/conf.R:290; e.g. d2q9_kuper's
        # CalcPhi saves only phi while reading streamed f).  This is the
        # cheap half of the 1R+1W traffic story: never-changing planes
        # (BC buffers, SynthT, cut distances) are not rewritten per step.
        # A full-array return still means "replace the whole stack".
        if isinstance(new_fields, dict):
            buf = raw
            for name, stack in new_fields.items():
                if name in model.groups:
                    idx = model.groups[name]
                    if len(idx) == 1:
                        plane = stack[0] if stack.ndim > buf.ndim - 1 \
                            else stack
                        buf = buf.at[idx[0]].set(plane)
                    else:
                        buf = buf.at[jnp.array(idx)].set(stack)
                else:
                    buf = buf.at[model.storage_index[name]].set(stack)
            new_fields = buf
        # Solid/Wall nodes keep the engine's semantics from the model's Run();
        # nothing special here — BCs are the model's job via ctx.boundary_case.
        # Globals accumulate across the stages of one action (the reference
        # clears the GPU globals buffer at iteration start and every stage's
        # kernels atomically add into it, src/Lattice.cu.Rt:383-461);
        # make_action_step zeroes the buffer before its first stage, so a
        # trailing non-global stage (e.g. kuper's CalcPhi) no longer wipes
        # the objectives the Run stage just computed.  SUM globals add;
        # MAX globals combine with max (the reference's atomicMax path,
        # src/cross.h:104-132) — adding per-stage maxima would double-count.
        if not compute_globals:
            return LatticeState(
                fields=new_fields, flags=state.flags,
                globals_=state.globals_, iteration=state.iteration)
        stage_globals = ctx.reduce_globals()
        max_rows = [i for i, g in enumerate(model.globals_) if g.op == "MAX"]
        if max_rows:
            is_max = jnp.zeros((model.n_globals,), dtype=bool
                               ).at[jnp.array(max_rows)].set(True)
            combined = jnp.where(is_max,
                                 jnp.maximum(state.globals_, stage_globals),
                                 state.globals_ + stage_globals)
        else:
            combined = state.globals_ + stage_globals
        return LatticeState(
            fields=new_fields,
            flags=state.flags,
            globals_=combined,
            iteration=state.iteration,
        )

    return step


def make_action_step(model: Model, action: str = "Iteration",
                     streaming: Optional[Streaming] = None,
                     present: Optional[set] = None,
                     compute_globals: bool = True) -> Callable:
    """Compose an action's stages into one step (reference Actions,
    src/conf.R:339 + the per-stage loop in Lattice::Iteration,
    src/Lattice.cu.Rt:414-457)."""
    steps = [make_stage_step(model, s, streaming, present=present,
                             compute_globals=compute_globals)
             for s in model.actions[action]]
    # one action == one lattice iteration (when it streams at all):
    # the counter advances once per action, not per stage
    advances = any(model.stages[s].load_densities
                   for s in model.actions[action])

    def step(state: LatticeState, params: SimParams) -> LatticeState:
        if compute_globals:
            state = state.replace(globals_=jnp.zeros_like(state.globals_))
        for s in steps:
            state = s(state, params)
        if advances:
            state = state.replace(iteration=state.iteration + 1)
        return state

    return step


def make_iterate(model: Model, action: str = "Iteration",
                 unroll: int = 1,
                 streaming: Optional[Streaming] = None,
                 present: Optional[set] = None,
                 storage_dtype: Any = None,
                 storage_shift: Optional[np.ndarray] = None) -> Callable:
    """niter-step loop as a ``lax.scan`` (reference Lattice::Iterate,
    src/Lattice.cu.Rt:780-869).  Differentiable; wrap with ``jax.checkpoint``
    policies for long-horizon adjoints (reference SnapLevel tape,
    src/Lattice.cu.Rt:34-49).

    ``iterate``'s contract is "globals_ = the LAST step's integrals"
    (each action step zeroes them), so the first niter-1 steps run the
    NoGlobals specialization — the reductions are pure waste there (the
    reference's Globals-mode template parameter, src/cuda.cu.Rt:81) —
    and only the final step reduces.

    ``storage_dtype`` (precision ladder) narrows the scan CARRY to that
    dtype: each step widens the fields to the compute dtype (taken from
    ``params.settings.dtype``), runs the action, and narrows the result
    back, so the HBM-resident state between steps is genuinely
    ``storage_dtype`` — the same round-trip truncation the Pallas
    engines apply per DMA, which is what the error-vs-f32 harness
    (tclb_tpu/precision.py) must measure.  ``None`` keeps today's exact
    path (the casts never enter the trace).

    ``storage_shift`` (DDF shifting, ``storage_repr="shifted"``) is the
    broadcastable per-plane weight block from
    :func:`tclb_tpu.core.shift.stack_shift`: the narrow carry then
    stores ``f_i - w_i`` and every widen seam restores the shift before
    the physics (f32 accumulation unchanged).  ``None`` = raw
    representation (the seam helpers reduce to pure ``astype``)."""
    step_ng = make_action_step(model, action, streaming, present=present,
                               compute_globals=False)
    step_full = make_action_step(model, action, streaming, present=present,
                                 compute_globals=True)
    sdt = None if storage_dtype is None else jnp.dtype(storage_dtype)
    sb = storage_shift if sdt is not None else None

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        if niter <= 0:
            return state
        if sdt is None:
            def body(s, _):
                return step_ng(s, params), None
            state, _ = jax.lax.scan(body, state, None, length=niter - 1,
                                    unroll=unroll)
            return step_full(state, params)

        cdt = params.settings.dtype

        def body(s, _):
            out = step_ng(
                s.replace(fields=ddf.widen_stack(s.fields, cdt, sb)),
                params)
            return out.replace(
                fields=ddf.narrow_stack(out.fields, sdt, sb)), None
        state, _ = jax.lax.scan(
            body, state.replace(fields=state.fields.astype(sdt)),
            None, length=niter - 1, unroll=unroll)
        out = step_full(
            state.replace(fields=ddf.widen_stack(state.fields, cdt, sb)),
            params)
        return out.replace(fields=ddf.narrow_stack(out.fields, sdt, sb))

    return iterate


def make_ensemble_step(model: Model, action: str = "Init",
                       present: Optional[set] = None) -> Callable:
    """Batched single-action step for an ensemble of independent cases:
    ``step(states, params) -> states`` over a leading case axis.

    Runs the cases through ``lax.map`` (a scan over the batch), NOT
    ``vmap``: a scan body is compiled as its own isolated computation, so
    the per-case arithmetic clusters exactly like the sequential
    ``jit(step)`` program and the result is bit-identical to running the
    cases one by one — the ensemble contract (serve/ensemble.py).  One
    action per run (Init, a globals-reducing final step) is cheap; the
    niter-step bulk goes through :func:`make_ensemble_iterate` instead."""
    step = make_action_step(model, action, present=present)

    def batched(states: LatticeState, params: SimParams) -> LatticeState:
        return jax.lax.map(lambda sp: step(sp[0], sp[1]), (states, params))

    return batched


def make_ensemble_iterate(model: Model, action: str = "Iteration",
                          unroll: int = 1,
                          present: Optional[set] = None,
                          mode: str = "map",
                          storage_dtype: Any = None,
                          storage_shift: Optional[np.ndarray] = None
                          ) -> Callable:
    """Batched counterpart of :func:`make_iterate`: advance N independent
    cases (stacked ``LatticeState``s + per-case ``SimParams``) in ONE
    device dispatch.

    ``mode="map"`` (default) runs each case's whole niter-step loop as a
    ``lax.map`` body: a map body is compiled as its own isolated
    computation, so the per-case arithmetic clusters exactly like the
    sequential ``jit(make_iterate(...))`` program and the output is
    **bit-identical** to N sequential runs — the ensemble contract
    (serve/ensemble.py).  The throughput win is dispatch/compile
    amortization and cross-case pipelining, not SIMD over the batch.

    ``mode="vmap"`` vmaps the NoGlobals bulk over the case axis inside
    the time scan (XLA vectorizes the whole batch per step) and runs the
    final full-globals step through ``lax.map``.  Faster where the
    per-case work underfills the vector units, but NOT parity-safe in
    general: under a batch dimension XLA:CPU re-clusters some models'
    multiply-add chains (the same re-association ``lbm.pin`` fences
    elsewhere) and drifts fields by 1 ulp — e.g. d2q9_kuper's forcing
    stage on a painted cavity.  Opt in only where throughput beats
    bit-reproducibility.

    ``storage_dtype`` narrows each case's carry between steps exactly
    like :func:`make_iterate`'s precision ladder — the serving tier's
    doubled batch caps come from genuinely bf16-resident ensemble
    state, so the per-step round trip must match the single-case
    engines' truncation.  ``storage_shift`` selects the shifted (DDF)
    representation for that carry, exactly as in :func:`make_iterate`
    (the shift block broadcasts under the leading case axis)."""
    if mode not in ("map", "vmap"):
        raise ValueError(f"ensemble mode must be 'map' or 'vmap', "
                         f"got {mode!r}")
    step_ng = make_action_step(model, action, present=present,
                               compute_globals=False)
    step_full = make_action_step(model, action, present=present,
                                 compute_globals=True)
    sdt = None if storage_dtype is None else jnp.dtype(storage_dtype)
    sb = storage_shift if sdt is not None else None

    def _wrap(step, params):
        if sdt is None:
            return step

        def stepped(st, p=params):
            cdt = p.settings.dtype
            out = step(
                st.replace(fields=ddf.widen_stack(st.fields, cdt, sb)), p)
            return out.replace(fields=ddf.narrow_stack(out.fields, sdt, sb))
        return stepped

    def iterate_map(states: LatticeState, params: SimParams, niter: int
                    ) -> LatticeState:
        if niter <= 0:
            return states

        def one(sp):
            s, p = sp
            ng, fl = _wrap(step_ng, p), _wrap(step_full, p)

            def body(st, _):
                return ng(st, p) if sdt is None else ng(st), None
            s, _ = jax.lax.scan(body, s, None, length=niter - 1,
                                unroll=unroll)
            return fl(s, p) if sdt is None else fl(s)

        return jax.lax.map(one, (states, params))

    def iterate_vmap(states: LatticeState, params: SimParams, niter: int
                     ) -> LatticeState:
        if niter <= 0:
            return states

        if sdt is None:
            def body(s, _):
                return jax.vmap(step_ng)(s, params), None
        else:
            def narrow_step(st, p):
                out = step_ng(
                    st.replace(fields=ddf.widen_stack(
                        st.fields, p.settings.dtype, sb)), p)
                return out.replace(
                    fields=ddf.narrow_stack(out.fields, sdt, sb))

            def body(s, _):
                return jax.vmap(narrow_step)(s, params), None
        states, _ = jax.lax.scan(body, states, None, length=niter - 1,
                                 unroll=unroll)

        def final(sp):
            s, p = sp
            if sdt is None:
                return step_full(s, p)
            out = step_full(
                s.replace(fields=ddf.widen_stack(
                    s.fields, p.settings.dtype, sb)), p)
            return out.replace(fields=ddf.narrow_stack(out.fields, sdt, sb))
        return jax.lax.map(final, (states, params))

    return iterate_map if mode == "map" else iterate_vmap


def make_sampled_iterate(model: Model, points: np.ndarray,
                         quantities: Sequence[str],
                         action: str = "Iteration",
                         streaming: Optional[Streaming] = None) -> Callable:
    """Like :func:`make_iterate` but also gathers the listed quantities at
    fixed lattice points after every step, returned as the scan ys —
    the functional equivalent of the reference Sampler's per-iteration GPU
    ring buffer (reference updateAllSamples, src/Lattice.cu.Rt:1212-1225).

    ``points`` is (npoints, ndim) in array index order (z, y, x / y, x).
    Returns ``iterate(state, params, niter) -> (state, (samples, its))``
    with samples shaped (niter, npoints, ncols) and ``its`` the state's
    iteration after each step; vector quantities contribute their
    components as consecutive columns.  The scan of what no fused engine
    takes (:meth:`Lattice._samples_on_engine`): every quantity is
    evaluated on the whole plane every step.
    """
    step = make_action_step(model, action, streaming)
    idx = tuple(jnp.asarray(points[:, k].astype(np.int32))
                for k in range(points.shape[1]))
    qfns = [(q, model.quantity_fns[q]) for q in quantities]

    def sample(state: LatticeState, params: SimParams,
               avg_start: Any = 0) -> jnp.ndarray:
        ctx = NodeCtx(model, state.fields, state.fields, state.flags, params,
                      iteration=state.iteration, avg_start=avg_start)
        cols = []
        for _, fn in qfns:
            with jax.default_matmul_precision("highest"):
                plane = fn(ctx)
            if plane.ndim == len(state.flags.shape):
                cols.append(plane[idx][:, None])
            else:  # vector: (ncomp, *shape) -> (npoints, ncomp)
                cols.append(plane[(slice(None),) + idx].T)
        return jnp.concatenate(cols, axis=-1)

    def iterate(state: LatticeState, params: SimParams, niter: int,
                avg_start=0):
        def body(s, _):
            s2 = step(s, params)
            return s2, (sample(s2, params, avg_start), s2.iteration)
        return jax.lax.scan(body, state, None, length=niter)

    return iterate


# --------------------------------------------------------------------------- #
# Host-side Lattice wrapper
# --------------------------------------------------------------------------- #


def _quantity_body(model: Model, name: str, cdtype: Any,
                   storage_repr: str) -> tuple[Callable, list[int]]:
    """The traced body both programs of a Quantity share, ``evaluate(
    fields, flags, params, iteration, avg_start) -> plane(s)``, and the
    count of its traces (a one-element list the body bumps: a call that
    leaves it alone reused an executable)."""
    fn = model.quantity_fns[name]
    shift_block = ddf.stack_shift(model, storage_repr)
    traces = [0]

    def evaluate(fields, flags, params, iteration, avg_start):
        traces[0] += 1
        # quantities evaluate in the compute dtype over RAW distributions
        # (no-op cast at f32; the shifted rung restores f_i = dev + w_i
        # at this widen seam, so extraction never sees the deviation)
        fields = ddf.widen_stack(fields, cdtype, shift_block)
        ctx = NodeCtx(model, fields, fields, flags, params,
                      iteration=iteration, avg_start=avg_start)
        with jax.default_matmul_precision("highest"):
            return fn(ctx)

    return evaluate, traces


@lru_cache(maxsize=None)
def quantity_program(model: Model, name: str, cdtype: Any,
                     storage_repr: str) -> tuple[Callable, list[int]]:
    """The compiled program of one Quantity, ``program(fields, flags,
    params, iteration, avg_start) -> plane(s)``, and the count of its
    traces (a one-element list the traced body bumps: a call that leaves
    it alone reused an executable).  Keyed by what the trace depends on,
    not by the Lattice, so a second lattice of the model reuses the trace
    and ``jax.jit``'s own cache handles shapes and shardings; kept, with
    its model, for the life of the process, as the registry keeps models."""
    evaluate, traces = _quantity_body(model, name, cdtype, storage_repr)
    return jax.jit(evaluate), traces


@lru_cache(maxsize=None)
def nonfinite_program(model: Model, name: str, cdtype: Any,
                      storage_repr: str) -> tuple[Callable, list[int]]:
    """What ``<Failcheck>`` runs for one Quantity: ``program(fields, flags,
    params, iteration, avg_start) -> int32 scalar``, the number of values
    of the quantity, over every node and component, that are NaN or
    infinite; beside it the count of its traces.  The same body as
    :func:`quantity_program` and the test in the same ``jax.jit``: only
    the count leaves the program, so it has no plane-sized output and
    nothing but four bytes to bring to the host (a v5e compile still
    keeps the plane as a temporary between the quantity's last fusion
    and the reduction; PERF.md), and on a mesh the state's sharding
    carries through to one replicated scalar.  Keyed and kept as
    :func:`quantity_program` is; the state is read, not donated."""
    evaluate, traces = _quantity_body(model, name, cdtype, storage_repr)

    def count(fields, flags, params, iteration, avg_start):
        plane = evaluate(fields, flags, params, iteration, avg_start)
        return jnp.sum(~jnp.isfinite(plane), dtype=jnp.int32)

    return jax.jit(count), traces


class _NeighbourRead(Exception):
    """A quantity asked :func:`taps_program`'s context for another node."""


@lru_cache(maxsize=None)
def taps_program(model: Model, quantities: tuple, cdtype: Any
                 ) -> tuple[Callable, list[int]]:
    """What turns a sampled engine's taps into ``<Sample>``'s columns:
    ``program(taps, flags, params, iteration, behind, avg_start) ->
    (samples, its)``, shaped (rows, P, ncols) and (rows,) as
    :func:`make_sampled_iterate`'s, and the count of its traces, as
    :func:`quantity_program` keeps it.  ``taps`` is a tuple of stacks
    ``(n_k, planes, P)``, the stored planes at the P sample points after
    each of an ``iterate``'s steps in turn (the fused call's, then the
    tail step's); ``flags`` the (P,) flags of the points; ``iteration``
    the state's now, ``behind`` steps after the last of the taps.  The
    model's own quantity functions run on a :class:`NodeCtx` whose
    lattice is the (rows, P) gathered nodes, so a sample is the
    arithmetic of :meth:`Lattice.get_quantity` at that node and no plane
    is evaluated; a vector quantity's components are consecutive
    columns.  A quantity that reads a neighbour
    (``ctx.load`` with an offset) raises :class:`_NeighbourRead` as it is
    traced: :meth:`Lattice._samples_on_engine` asks before dispatch."""
    fns = [model.quantity_fns[q] for q in quantities]
    traces = [0]

    def evaluate(taps, flags, params, iteration, behind, avg_start):
        traces[0] += 1
        fields = jnp.moveaxis(jnp.concatenate(taps), 1, 0).astype(cdtype)
        rows = fields.shape[1]
        its = (jnp.asarray(iteration, jnp.int32) - behind - rows + 1
               + jnp.arange(rows, dtype=jnp.int32))

        def own_node(index, dx, dy, dz):
            if dx or dy or dz:
                raise _NeighbourRead(index, dx, dy, dz)
            return fields[index]

        ctx = NodeCtx(model, fields, fields,
                      jnp.broadcast_to(flags, (rows,) + flags.shape),
                      params, loader=own_node, iteration=its[:, None],
                      avg_start=avg_start)
        cols = []
        for fn in fns:
            with jax.default_matmul_precision("highest"):
                v = fn(ctx)
            cols.append(v[..., None] if v.ndim == 2
                        else jnp.moveaxis(v, 0, -1))
        return jnp.concatenate(cols, axis=-1), its

    return jax.jit(evaluate), traces


@dataclasses.dataclass(frozen=True)
class EngineCandidate:
    """One link of the chain :meth:`Lattice._build_fast` returns: a fused
    engine that can take the lattice, and what dispatch has to know to
    try it.  ``probe``: its first call runs on a copy of the state and a
    failure steps down the chain; otherwise it is a proven engine, run on
    the real state, whose exception goes through."""

    tag: str                        # its name in events, spans, _fast_name
    build: Callable[[], Callable]   # () -> ops.engine.Engine
    probe: bool = False
    cap: int = 0   # the band cap it stands for, a rung of ``engine.probe``
    verdict: Optional[tuple] = None   # generic band engine only: the
    #                          (fuse, by_cap) to remember once it has run


@partial(jax.jit, static_argnames=("idx", "out"))
def _write_planes(fields, planes, idx, out=None):
    """``fields`` with ``planes`` written at storage indices ``idx``, as
    one program: a plane at a time, dispatched eagerly, a state of
    gigabytes has one whole copy in flight for every plane written.
    ``out`` (on a mesh: the fields' own sharding) is the result's, so
    that the planes, whatever theirs, are cut to the shards and never
    gathered."""
    for i, plane in zip(idx, planes):
        fields = fields.at[i].set(plane)
    return (fields if out is None
            else jax.lax.with_sharding_constraint(fields, out))


class Lattice:
    """Host-side convenience wrapper, mirroring the reference ``Lattice``
    class surface (src/Lattice.h.Rt:36-168): allocate, Init, Iterate,
    Get/Set densities, GetQuantity, settings, save/load."""

    def __init__(self, model: Model, shape: Sequence[int],
                 dtype: Any = jnp.float32,
                 settings: Optional[dict[str, float]] = None,
                 mesh: Any = None,
                 storage_dtype: Any = None,
                 storage_repr: Optional[str] = None,
                 device: Any = None):
        if len(shape) != model.ndim:
            raise ValueError(f"model {model.name} is {model.ndim}D; "
                             f"got shape {shape}")
        self.model = model
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        # precision ladder: ``storage_dtype`` narrows the HBM-resident
        # distribution fields only — every kernel still accumulates in
        # the compute dtype (``dtype``), settings/zone tables/globals
        # stay wide, and flags are untouched.  Strictly OPT-IN: the
        # default is the compute dtype and nothing ever narrows
        # silently.  Validated by the error-vs-reference harness
        # (tclb_tpu/precision.py), not by bit-parity.
        sdt = jnp.dtype(dtype) if storage_dtype is None \
            else jnp.dtype(storage_dtype)
        if sdt != jnp.dtype(dtype):
            if not jnp.issubdtype(sdt, jnp.floating) \
                    or sdt.itemsize > jnp.dtype(dtype).itemsize:
                raise ValueError(
                    f"storage_dtype {sdt} must be a float dtype no wider "
                    f"than the compute dtype {jnp.dtype(dtype)}")
            if mesh is not None:
                raise ValueError("narrowed storage_dtype is not supported "
                                 "on sharded (mesh) lattices: the halo "
                                 "building block is f32-only")
        self.storage_dtype = sdt
        # at-rest representation (DDF shifting): narrowed lattices with
        # a recognized velocity set default to "shifted" (store
        # f_i - w_i, Mach-independent bf16 accuracy); full-width storage
        # is always "raw" so the f32 path stays bit-identical.  The
        # repr is stamped into checkpoint manifests, serve/cache keys
        # and telemetry spans — raw and shifted layouts never mix
        # silently (core/shift.py).
        narrowed = sdt != jnp.dtype(dtype)
        self.storage_repr = ddf.resolve_repr(model, narrowed, storage_repr)
        self._shift_vec = ddf.shift_of(model, self.storage_repr)
        self._shift_block = ddf.stack_shift(model, self.storage_repr)
        self.mesh = mesh
        vec = model.settings_vector(settings)
        self._series: dict[tuple[int, int], np.ndarray] = {}
        self.params = SimParams(
            settings=jnp.asarray(vec, dtype=dtype),
            zone_table=jnp.asarray(
                np.broadcast_to(vec[:, None], (len(vec), model.zone_max)),
                dtype=dtype),
        )
        # on a mesh the arrays of the lattice's size are made in shards:
        # no device ever holds the whole lattice
        on_fields = on_flags = None
        if mesh is not None:
            from tclb_tpu.parallel.mesh import field_spec, flag_spec
            from jax.sharding import NamedSharding
            on_fields = NamedSharding(mesh, field_spec(mesh))
            on_flags = NamedSharding(mesh, flag_spec(mesh))
        self.state = LatticeState(
            fields=jnp.zeros((model.n_storage,) + self.shape, dtype=sdt,
                             device=on_fields),
            flags=jnp.zeros(self.shape, dtype=FLAG_DTYPE, device=on_flags),
            globals_=jnp.zeros((model.n_globals,), dtype=dtype),
            iteration=jnp.zeros((), dtype=jnp.int32),
        )
        if mesh is not None and device is not None:
            raise ValueError("pass either mesh= (sharded) or device= "
                             "(single-device pin), not both")
        self.device = device
        if mesh is not None:
            from tclb_tpu.parallel.mesh import shard_state
            self._place = lambda: shard_state(self.state, self.params, mesh)
            self.state, self.params = self._place()
        elif device is not None:
            # single-device pin (the fleet dispatcher's lane seam): commit
            # state+params to the named device so every downstream dispatch
            # runs there instead of on JAX's default device
            self._place = lambda: (jax.device_put(self.state, device),
                                   jax.device_put(self.params, device))
            self.state, self.params = self._place()
        else:
            self._place = None
        # the XLA engine is built lazily so its trace can specialize on
        # the PAINTED node types (the reference compiles per boundary
        # set); set_flags invalidates it.  _host_flags keeps the host-side
        # copy present_types needs — under multi-host the sharded device
        # flags span non-addressable devices and cannot be fetched back
        self._iterate_cached = None
        self._host_flags: Optional[np.ndarray] = None
        self._present: Optional[tuple] = None   # see _present_types()
        step_init = make_action_step(model, "Init")
        if narrowed:
            def _init_narrow(state, params, _step=step_init,
                             _cdt=jnp.dtype(dtype), _sdt=sdt,
                             _sb=self._shift_block):
                out = _step(state.replace(
                    fields=ddf.widen_stack(state.fields, _cdt, _sb)),
                    params)
                return out.replace(
                    fields=ddf.narrow_stack(out.fields, _sdt, _sb))
            step_init = _init_narrow
        self._init = jax.jit(step_init, donate_argnums=0)
        # <Sample>'s sampler (attach_sampler), the XLA scan of the runs no
        # fused engine takes, the flags of the sample points on the
        # device, and what the calls of the iterate under way have left
        # for the sampler: ("taps", stack) of a sampled engine, ("rows",
        # (samples, its)) of the XLA scan
        self.sampler = None
        self._iterate_sampled = None
        self._sample_flags = None
        self._sampled: list = []
        self.avg_start = 0    # iteration of the last <Average> reset
        # fused Pallas fast path: built lazily at the first iterate() so the
        # painted flags are known (the 3D kernel specializes on present node
        # types); see _fast_path()
        self._fast = None
        self._fast_name = None
        self._fast_tried = False
        self._fast_probing = False
        self._fast_chain: list = []
        # the engine of the one step a hybrid engine leaves for the
        # Globals, and its tag (None: the XLA step); see _build_tail()
        self._tail = None
        self._tail_name = None
        self._tail_probing = False

    # -- setup -------------------------------------------------------------- #

    def set_flags(self, flags: np.ndarray) -> None:
        """Overwrite the node-type field (reference Lattice::FlagOverwrite,
        src/Lattice.cu.Rt:892-905)."""
        assert flags.shape == self.shape
        self._host_flags = np.asarray(flags, dtype=np.uint16).copy()
        # on a mesh from the host straight to the shards
        self.state = dataclasses.replace(
            self.state, flags=jnp.asarray(flags, dtype=FLAG_DTYPE)
            if self.mesh is None else jax.device_put(
                self._host_flags, self.state.flags.sharding))
        if self._place is not None:
            self.state, self.params = self._place()
        self._fast_tried = False   # present node types may have changed
        self._iterate_cached = None

    def set_setting(self, name: str, value: float, zone: Optional[int] = None
                    ) -> None:
        """reference Lattice::setSetting + zonal variant
        (src/Lattice.cu.Rt:1135-1191)."""
        m = self.model
        vec = np.array(self.params.settings, dtype=np.float64)
        table = np.array(self.params.zone_table, dtype=np.float64)
        if zone is None:
            m._set_with_derived(vec, name, float(value))
            # keep un-touched zones following the scalar value
            table[m.setting_index[name], :] = vec[m.setting_index[name]]
        else:
            table[m.setting_index[name], zone] = float(value)
        self.params = self.params.replace(
            settings=jnp.asarray(vec, dtype=self.dtype),
            zone_table=jnp.asarray(table, dtype=self.dtype))
        if self._place is not None:
            self.state, self.params = self._place()

    def set_setting_series(self, name: str, values: np.ndarray, zone: int = 0
                           ) -> None:
        """Attach a per-iteration time series to a zonal setting (reference
        ``zSet.set(setting, zone, vector)`` filled by <Control>,
        src/Handlers.cpp.Rt:2213-2452).  All series share one horizon length
        (the reference's ``zSet.len``); iteration wraps modulo that length."""
        m = self.model
        i = m.setting_index[name]
        if not m.settings[i].zonal:
            raise ValueError(f"setting {name!r} is not zonal; Control time "
                             "series apply to zonal settings")
        values = np.asarray(values, dtype=np.float64).ravel()
        for old in self._series.values():
            if len(old) != len(values):
                raise ValueError(
                    f"all Control series must share one horizon: got "
                    f"{len(values)}, existing {len(old)}")
        self._series[(i, int(zone))] = values
        self._fast_tried = False   # the engine re-selects series-aware
        keys = sorted(self._series)
        series_map = tuple((si, z, r) for r, (si, z) in enumerate(keys))
        ts = np.stack([self._series[k] for k in keys])
        self.params = self.params.replace(
            time_series=jnp.asarray(ts, dtype=self.dtype),
            series_map=series_map)
        if self._place is not None:
            self.state, self.params = self._place()

    def init(self) -> None:
        """Run the model's Init action (reference Lattice::Init)."""
        self.state = self._init(self.state, self.params)

    # -- running ------------------------------------------------------------ #

    def _flags_host(self) -> np.ndarray:
        """Host-side flag field for static specialization (multi-host
        safe: sharded device flags may span non-addressable devices)."""
        if self._host_flags is not None:
            return self._host_flags
        return np.asarray(self.state.flags)

    def _present_types(self) -> set:
        """The node types painted on the lattice (a pass over the flags
        for each type of the model), remembered for the host flags it
        was read from: the XLA engine, the fused chain and the tail
        engine all specialize on it."""
        from tclb_tpu.ops.lbm import present_types
        flags = self._flags_host()
        if self._present is None or self._present[0] is not flags:
            self._present = (flags, present_types(self.model, flags))
        return self._present[1]

    @property
    def _iterate(self):
        """The XLA engine, built on demand and specialized on the painted
        node types (absent boundary cases are skipped; globals reduce on
        the final step only — iterate()'s contract)."""
        if self._iterate_cached is None:
            present = self._present_types()
            if self.mesh is not None:
                from tclb_tpu.parallel.halo import make_sharded_iterate
                self._iterate_cached = make_sharded_iterate(
                    self.model, self.mesh, present=present)
            else:
                narrowed = self.storage_dtype != jnp.dtype(self.dtype)
                self._iterate_cached = jax.jit(
                    make_iterate(self.model, present=present,
                                 storage_dtype=(self.storage_dtype
                                                if narrowed else None),
                                 storage_shift=self._shift_block),
                    static_argnames=("niter",), donate_argnums=0)
        return self._iterate_cached

    def _generic_tiles(self, fz: int, by_cap: Optional[int] = None):
        """The ``(bz, by, K)`` the generic slab engine cuts a 3D plane
        into where no whole-plane plan holds it, else None."""
        from tclb_tpu.ops import pallas_generic
        if self.model.ndim != 3:
            return None
        return pallas_generic.tile_plan_3d(
            self.model, self.shape, self.storage_dtype.itemsize, fz, by_cap)

    def _generic_cand(self, present: set, fz: int,
                      by_cap: Optional[int] = None,
                      tag: Optional[str] = None, **how) -> EngineCandidate:
        """The generic band (2D) or slab (3D) engine at ``fz`` steps a
        kernel call as a candidate; ``how`` is what dispatch has to know
        to try it (``probe``, ``cap``, ``verdict``)."""
        from tclb_tpu.ops import pallas_generic
        model, shape, sdt = self.model, self.shape, self.storage_dtype
        if tag is None:
            # a plane the engine tiles says so: the rows of its bands
            plan = self._generic_tiles(fz, by_cap)
            by = f",by={plan[1]}" if plan and plan[1] < shape[1] else ""
            tag = f"pallas_generic[{model.name},fuse={fz}{by}]"
        return EngineCandidate(
            tag, lambda: pallas_generic.make_pallas_iterate(
                model, shape, sdt, present=present, fuse=fz, by_cap=by_cap,
                shift=self._shift_vec, points=self._engine_points()),
            **how)

    def _engine_points(self) -> Optional[np.ndarray]:
        """The sample points a fused engine is built with: those of the
        attached sampler, None without one."""
        return None if self.sampler is None else self.sampler.points

    def _samples_on_engine(self) -> bool:
        """Whether a fused engine may take the run of the attached
        sampler.  Not on a mesh (the taps would be a gather across
        shards), not with storage narrower than the compute dtype (the
        taps could not be widened as the quantity's own seam does), not
        under a ``<Control>`` series, and not where a quantity reads a
        neighbour (:func:`taps_program` has the node alone): those keep
        :func:`make_sampled_iterate`."""
        if (self.mesh is not None or self.params.time_series is not None
                or self.storage_dtype != jnp.dtype(self.dtype)):
            return False
        npts, i32 = len(self.sampler.points), jnp.int32
        try:
            self._with_taps_program(lambda program: jax.eval_shape(
                program,
                (jax.ShapeDtypeStruct((1, self.model.n_storage, npts),
                                      self.storage_dtype),),
                jax.ShapeDtypeStruct((npts,), FLAG_DTYPE), self.params,
                *[jax.ShapeDtypeStruct((), i32)] * 3))
        except _NeighbourRead:
            return False
        return True

    def _with_taps_program(self, use: Callable):
        """``use(program)`` of the attached sampler's
        :func:`taps_program`, a trace of it counted as
        :meth:`_run_quantity` counts a quantity program's."""
        program, traces = taps_program(
            self.model, tuple(self.sampler.quantities), jnp.dtype(self.dtype))
        before = traces[0]
        try:
            return use(program)
        finally:
            if traces[0] != before:
                telemetry.counter("quantity.programs_built")

    def _build_fast(self) -> list:
        """The fused Pallas engines that can take this configuration, as
        a chain of :class:`EngineCandidate`: the preferred engine first,
        then what it steps down to where it does not compile; empty where
        the XLA engine runs (the reference's tuned kernel IS its engine —
        Lattice::Iteration launches it every step,
        src/Lattice.cu.Rt:414-457; this makes the Pallas kernel play the
        same role).  Auto-selected on TPU only: in interpret mode (CPU)
        the kernels are an emulation, far slower than XLA.
        ``TCLB_FASTPATH=0`` disables; ``TCLB_FASTPATH=force`` enables
        off-TPU (tests use this to exercise the dispatch in interpret
        mode)."""
        import os
        mode = os.environ.get("TCLB_FASTPATH", "auto")
        if mode == "0" or (mode != "force"
                           and jax.default_backend() != "tpu"):
            return []
        from tclb_tpu.ops import pallas_d2q9, pallas_d3q, pallas_generic
        model, shape, name = self.model, self.shape, self.model.name
        present = self._present_types()
        shift = self._shift_vec
        # a Control time series gives a step the zonal values of its own
        # iteration.  The tuned 2D band takes them as scalars beside its
        # per-call planes (pallas_d2q9.series_flavour) and the generic
        # engine as per-iteration planes, one step a call; the tuned 3D
        # family and the two VMEM-resident engines take none and are
        # left out (set_setting_series invalidates the engine so this
        # re-runs)
        has_series = self.params.time_series is not None
        # a sampler (<Sample>) needs what every step left at its points:
        # the engines that can hand that out run one step a kernel call
        # and say so in their tag; those whose call is many steps
        # on-chip (the two VMEM-resident engines) are left out, as is the
        # tuned 3D family, which has no such flavour (attach_sampler and
        # detach_sampler invalidate the engine so this re-runs)
        sampled = self.sampler is not None
        if sampled and not self._samples_on_engine():
            return []
        points = self._engine_points()
        # engines receive the STORAGE dtype: their HBM stacks and DMA
        # scratch narrow with it while their compute stays f32 (each
        # kernel family widens on read / narrows on write); f32-only
        # families (pallas_d2q9, sharded) reject it in supports() and
        # dispatch falls through to the d3q/generic families
        sdt = self.storage_dtype
        s_itemsize = jnp.dtype(sdt).itemsize

        def cand(tag, make, probe=False, cap=0, verdict=None, **kw):
            # the one place a builder is called: all share this signature
            return EngineCandidate(
                tag, lambda: make(model, shape, sdt, present=present, **kw),
                probe, cap, verdict)
        if self.mesh is not None:
            return self._sharded_chain(present)
        if pallas_d2q9.covers(model, shape, sdt):
            chain = self._band_chain(cand, sampled, points, has_series)
            if chain:
                return chain
        if not has_series and pallas_d3q.supports(model, shape, sdt):
            if sampled:
                return []
            make = pallas_d3q.make_pallas_iterate
            k3 = pallas_d3q.choose_fuse(model, shape, itemsize=s_itemsize)

            def tag3(fuse, pin=None):
                # a plane the engine tiles says so: the rows of its bands
                plan = pallas_d3q.tile_plan(model, shape, s_itemsize, pin)
                by = f",by={plan[1]}" if plan and plan[1] < shape[1] else ""
                return f"pallas_d3q[{name},fuse={fuse}{by}]"
            # no fuse given: the builder's own planner picks its plan
            chain = [cand(tag3(k3), make, probe=k3 >= 2, shift=shift)]
            if k3 >= 2:
                # K>=2 multi-step fusion (one HBM round trip per K steps)
                # compiles against the raised scoped-vmem ceiling: first
                # TPU compile may still hit Mosaic temporaries the planner
                # can't see, so the fused build is probed; the K=1 block
                # kernel is the proven engine for these models
                chain.append(cand(tag3(1, 1), make, fuse=1, shift=shift))
            else:
                # single-step demotion must never be silent: record WHY
                # the fused planner rejected every (bz, K) so a floor
                # regression can be triaged from telemetry alone
                _, why = pallas_d3q.fused_cfg_explain(
                    model, shape, itemsize=s_itemsize)
                telemetry.event(
                    "fused_rejected", engine="pallas_d3q", model=name,
                    shape=list(shape), reason=why or "unknown")
            return chain
        # the static analyzer's kernel-safety verdict gates EVERY
        # registry-driven kernel: a stage reading beyond its declared
        # stencil would make the band windows silently wrong (the XLA
        # path wraps exactly, so it stays the safe fallback); so does an
        # earlier probe of this model/shape that found nothing to compile
        from tclb_tpu import analysis
        if not (analysis.kernel_safety_ok(model)
                and pallas_generic.mosaic_ok(model, shape)):
            return []
        fits_resident = (not has_series and not sampled
                         and pallas_generic.supports_resident(
                             model, shape, sdt))
        if not (fits_resident or pallas_generic.supports(model, shape, sdt)):
            return []

        band = partial(self._generic_cand, present)
        cfg = (None if fits_resident
               else pallas_generic.get_build_cfg(model, shape))
        if cfg is not None:
            # this model/shape already proved it compiles: skip the
            # first-call probe (and its full-state copy)
            return [band(1 if sampled else cfg[0], cfg[1])]
        # temporal fusion amortizes one HBM round trip over K steps; the
        # shared planner caps K by the stencil reach fitting the halo (2D:
        # fixed 8-row block; deep-stencil models like lee at reach 6/step
        # stay fuse=1) or by the traffic model vs the K=1 engine (3D: slab
        # halos grow with K, so the win must be priced)
        fz0 = (1 if sampled
               else pallas_generic.choose_fuse_3d(model, shape,
                                                  itemsize=s_itemsize)
               if model.ndim == 3 else pallas_generic.choose_fuse(model))
        if fits_resident:
            # generic counterpart of the tuned d2q9 resident engine
            # (checked above): whole lattice VMEM-resident for ANY
            # registry model that fits the budget.  ONE kernel call
            # advances a whole iterate(n) (all but the one or two steps
            # left to the band engine's globals flavour), so the tag
            # states no fuse depth and every distinct n is a program of
            # its own (counter engine.resident_programs).
            # First call is probed; each resident flavour steps down to
            # ITS band family: here the generic band as planned
            return [cand(f"pallas_resident_generic[{name}]",
                         pallas_generic.make_resident_iterate, probe=True,
                         shift=shift), band(fz0, None)]
        # the trace probe of supports() cannot see Mosaic lowering gaps
        # (e.g. a model using arccos) or scoped-VMEM overflows — those
        # only surface at first TPU compile: probe the planner's choice,
        # then a ladder of smaller bands, then no fusion
        rungs = [(fz0, 16), (fz0, 8)]
        if fz0 >= 2:
            rungs += [(1, 16), (1, 8)]
        plan0 = self._generic_tiles(fz0)
        if model.ndim == 3 and plan0 is None:
            # last resort: smaller caps under the raised scoped-vmem
            # ceiling (a negative cap encodes it; the ceiling itself
            # costs nothing: the same (2, 32, 1) window of d3q19,
            # d3q19_kuper and d3q27_BGK read 0.830, 1.547 and 0.971 ns
            # an update under it and 0.851, 1.550 and 0.988 under
            # Mosaic's own 16 MiB, bit-equal; chip, PR 44).
            # A tiled window compiles under it from the start: its rungs
            # cap the rows and slabs of the window
            rungs += [(fz0, -16), (fz0, -8)]
        # the planner's own choice reads as the 2D band's default cap,
        # a tiled 3D window's as the rows of its bands
        cap0 = (pallas_generic._DEFAULT_BY_CAP if model.ndim == 2
                else plan0[1] if plan0 else 0)
        # a sampled run's verdict is not remembered: its one step a call
        # is not what a later lattice of the shape should run
        def verdict(fz, cap):
            return None if sampled else (fz, cap)
        return [band(fz0, None, probe=True, cap=cap0,
                     verdict=verdict(fz0, None))] + [
            band(fz, cap, f"pallas_generic[{name},fuse={fz},by<={cap}]",
                 probe=True, cap=cap, verdict=verdict(fz, cap))
            for fz, cap in rungs]

    def _sharded_chain(self, present: set) -> list:
        """:meth:`_build_fast`'s chain on a mesh.  Building the sharded
        engine IS asking whether it takes the case, so the preferred one
        is built here; a refusal says why (``fused_rejected``) and the
        sharded XLA step runs.  The 2D generic flavour is probed, with
        nothing under it.  A 3D shard's fused kernel compiles against
        the raised scoped-VMEM ceiling, which the planner cannot prove:
        it is probed, and under a plan of K >= 2 stands the same
        kernel's K = 1 plan; where that fails too the chain has run out
        (:meth:`_probe_first_call`: an ``engine_fallback`` and the
        sharded XLA step off the TPU, an error on it)."""
        from tclb_tpu.parallel import halo
        model, mesh, shape = self.model, self.mesh, self.shape

        def make(**kw):
            return halo.make_sharded_pallas_iterate(
                model, mesh, shape, self.dtype, present=present, **kw)

        def tag(fuse: int, plan: Optional[tuple]) -> str:
            # a plane the engine tiles says so: the rows of its bands
            by = f",by={plan[1]}" if plan and plan[1] < shape[1] else ""
            return f"pallas_sharded[{dict(mesh.shape)},fuse={fuse}{by}]"

        it = make()
        if it is None:
            telemetry.event(
                "fused_rejected", engine="pallas_sharded", model=model.name,
                shape=list(shape), mesh=dict(mesh.shape),
                reason=halo.why_no_sharded_pallas(model, mesh, shape,
                                                  self.dtype))
            return []
        chain = [EngineCandidate(tag(it.fuse, it.plan), lambda: it,
                                 probe=it.unproven)]
        if it.plan is not None and it.fuse >= 2:
            from tclb_tpu.ops import pallas_d3q
            local = halo.band_shards(model, mesh, shape)[2]
            chain.append(EngineCandidate(
                tag(1, pallas_d3q.tile_plan(model, local, fuse=1)),
                lambda: make(fuse=1), probe=True))
        return chain

    def _band_chain(self, cand: Callable, sampled: bool, points,
                    has_series: bool) -> list:
        """The tuned 2D family's part of :meth:`_build_fast`'s chain for a
        lattice its kernels cover: the band engine as
        ``pallas_d2q9.band_plan`` cuts it, at two steps a kernel call or
        (under a sampler) one, and over it the VMEM-resident engine where
        the lattice fits and no ``<Control>`` series is attached (its
        call is eight steps on-chip and takes none; the band engine takes
        a series at either depth, each step its own iteration's values).
        A series on a setting the band kernels read from no plane
        (``pallas_d2q9.series_rows``) leaves the family out like a shape
        without a plan.  A plan over Mosaic's own scoped-VMEM limit
        (rows of 2048 nodes and more) has not been shown to compile: its
        first call is probed, and under it stands the plan of the next
        band height down; a plan at the default limit is the proven
        engine, as it was.  A shape no plan holds must never fail at its
        first call: empty, with a ``fused_rejected`` event that says why,
        and what is under this family takes the case."""
        from tclb_tpu.ops import pallas_d2q9
        model, shape, name = self.model, self.shape, self.model.name
        plan = pallas_d2q9.band_plan(model, *shape)
        if plan is None:
            telemetry.event(
                "fused_rejected", engine="pallas_2d", model=name,
                shape=list(shape),
                reason=pallas_d2q9.why_no_plan(model, shape))
            return []
        if has_series and pallas_d2q9.series_rows(
                model, self.params.series_map) is None:
            telemetry.event(
                "fused_rejected", engine="pallas_2d", model=name,
                shape=list(shape),
                reason="a <Control> series on a setting the band kernels "
                       f"read from no plane: {self.params.series_map}")
            return []
        fuse = 1 if sampled else 2
        how = dict(fuse=fuse, points=points)
        make = pallas_d2q9.make_pallas_iterate
        rows = plan.band_rows[fuse - 1]
        unproven = plan.raised(fuse)
        chain = [cand(f"pallas_2d[{name},fuse={fuse}]", make,
                      probe=unproven, cap=rows if unproven else 0, **how)]
        under = unproven and rows > 8 and pallas_d2q9.band_plan(
            model, *shape, rows_cap=rows - 8)
        if under:
            rows = under.band_rows[fuse - 1]
            chain.append(cand(
                f"pallas_2d[{name},fuse={fuse},by<={rows}]", make,
                probe=True, cap=rows, rows_cap=rows, **how))
        if not (sampled or has_series) and pallas_d2q9.supports_resident(
                model, shape, self.storage_dtype):
            # small domains: whole lattice VMEM-resident, 8 steps per
            # kernel call — (1R+1W)/8 HBM traffic per step.  First call
            # is probed (the budget cannot see Mosaic's temporaries); the
            # band engine is the proven one under it
            chain.insert(0, cand(f"pallas_resident[{name},fuse=8]",
                                 pallas_d2q9.make_resident_iterate,
                                 probe=True))
        return chain

    def _build_tail(self) -> tuple:
        """The engine of the one step a hybrid engine (one that does not
        say ``full_globals``) leaves for the Globals, and its tag: the
        generic Pallas engine's one-step flavour, which reduces them in
        the kernel, wherever it takes the case: on the lattice, or on a
        mesh on each shard with the partial sums reduced across it
        (:meth:`_sharded_tail_cand`).  ``(None, None)``, the XLA step,
        where ``pallas_generic`` refuses the model, the shape (on a mesh:
        a shard's) or the storage dtype, where its kernel reduces no
        Globals, where its band stands on ghost rows (no multiple of its
        rows, as the 100 rows of ``karman.xml``): the call then lies
        between an XLA pad and a slice of the whole state, and beside a
        resident engine it is one more band call among the ``n % 8``
        steps that engine accounts for; and on a mesh the sharded tail
        cannot take: one split in x (in 3D: or in y), 2D shards of no
        multiple of 8 rows.  The same for every model and dimension: it
        is one
        algorithm, one step that reduces Globals.  Under a ``<Control>``
        series (the tuned 2D band's, off a mesh) the same engine runs its
        series flavour, ``call_sg``, which assembles that one step's
        zonal planes; on a mesh a series keeps the XLA engine for every
        step.  Its first call is probed (:meth:`_probe_tail`): nothing
        has shown yet that it compiles."""
        from tclb_tpu import analysis
        from tclb_tpu.ops import pallas_generic
        model = self.model
        if not analysis.kernel_safety_ok(model):
            return None, None
        if self.mesh is not None:
            cand = self._sharded_tail_cand()
        elif (pallas_generic.mosaic_ok(model, self.shape)
              # supports() without its abstract trace of the kernel
              # (seconds of every run's set-up): the probed first call
              # is that trace, and a model whose kernel does not trace
              # steps down there
              and pallas_generic.supports(model, self.shape,
                                          self.storage_dtype, probe=False)):
            cand = self._generic_cand(self._present_types(), 1)
        else:
            cand = None
        if cand is None:
            return None, None
        try:
            it = cand.build()
        except Exception as e:  # noqa: BLE001
            self._tail_failed(cand.tag, e)
            return None, None
        if it is not None and it.full_globals and not it.pad_rows:
            return it, cand.tag
        return None, None

    def _sharded_tail_cand(self) -> Optional[EngineCandidate]:
        """The tail engine on a mesh as a candidate, whose build is None
        where the builder refuses the mesh, the model or the shard's
        shape (``parallel/halo.make_sharded_pallas_tail``: building it IS
        asking); None under a ``<Control>`` series (the sharded engines
        take none, and the XLA engine runs every step) and where an
        earlier probe of the model at the shard's shape found nothing to
        compile."""
        from tclb_tpu.ops import pallas_generic
        from tclb_tpu.parallel.halo import (band_shards,
                                            make_sharded_pallas_tail)
        model, mesh, shape = self.model, self.mesh, self.shape
        shards = band_shards(model, mesh, shape)
        if (self.params.time_series is not None or shards is None
                or not pallas_generic.mosaic_ok(model, shards[2])):
            return None
        return EngineCandidate(
            f"pallas_sharded[generic,{dict(mesh.shape)},fuse=1,globals]",
            lambda: make_sharded_pallas_tail(
                model, mesh, shape, self.storage_dtype,
                present=self._present_types()))

    def _tail_failed(self, tag: str, e: Exception) -> None:
        """The tail engine cannot be built, compiled or run: the XLA
        step takes the trailing steps of this lattice from here on.  No
        verdict is remembered: ``mosaic_ok`` is the fused chain's, which
        may still need the generic engine's looped kernels, and a later
        lattice's probe costs one call."""
        from tclb_tpu.utils import log
        log.warning(f"engine: {tag} failed to compile ({e!r}); the XLA "
                    "step takes the globals")
        telemetry.engine_fallback(tag, "xla", repr(e),
                                  model=self.model.name)
        self._tail = self._tail_name = None

    def _fast_path(self):
        if not self._fast_tried:
            self._fast_tried = True
            # the preferred engine is built now (iterate() reads what it
            # advertises), those under it when _probe_first_call gets there
            with telemetry.span("engine.build") as sp:
                self._fast_chain = chain = self._build_fast()
                self._fast = chain[0].build() if chain else None
                self._fast_name = chain[0].tag if chain else None
                self._fast_probing = bool(chain) and chain[0].probe
                full = self._fast is not None and self._fast.full_globals
                self._tail, self._tail_name = (
                    self._build_tail()
                    if self._fast is not None and not full
                    else (None, None))
                sp.add(candidates=[c.tag for c in chain],
                       selected=self._fast_name or "xla",
                       tail=None if self._fast is None or full
                       else self._tail_name or "xla")
            self._tail_probing = self._tail is not None
            self._sample_flags = None
            if self._fast is not None and self.sampler is not None:
                pts = self.sampler.points
                self._sample_flags = jax.device_put(
                    self._flags_host()[tuple(pts[:, k] for k in
                                             range(pts.shape[1]))]
                    .astype(FLAG_DTYPE), self.device)
            from tclb_tpu.utils import log
            if self._fast is not None:
                suffix = "(in-kernel globals)" if full \
                    else (f"(+1 step per call on {self._tail_name or 'xla'} "
                          "for globals)")
                log.info(f"engine: {self._fast_name} fused fast path "
                         f"{suffix}")
            else:
                log.debug(f"engine: XLA path ({self.model.name} "
                          f"{self.shape})")
            telemetry.engine_selected(
                self._fast_name or "xla", model=self.model.name,
                shape=list(self.shape), backend=jax.default_backend(),
                probed=self._fast_probing)
        return self._fast

    def iterate(self, niter: int) -> None:
        """Advance ``niter`` steps on the auto-selected engine.  With
        telemetry enabled the chunk runs under an ``iterate`` span
        (block_until_ready-fenced wall time, derived MLUPS); disabled,
        the span machinery is a single boolean check."""
        if not telemetry.enabled():
            self._iterate_impl(niter)
            return
        # int(iteration) forces a device sync BEFORE the span opens, so
        # the measured wall time never bills a previous chunk's async
        # tail; what the read itself took is the span's pre_sync_s
        t = time.perf_counter()
        iteration = int(self.state.iteration)
        pre_sync_s = round(time.perf_counter() - t, 6)
        with telemetry.span(
                "iterate", iters=int(niter),
                nodes=float(np.prod(self.shape)),
                storage_dtype=np.dtype(self.state.fields.dtype).name,
                storage_repr=self.storage_repr,
                model=self.model.name,
                iteration=iteration, pre_sync_s=pre_sync_s) as sp:
            self._iterate_impl(niter)
            engine = self._fast_name or "xla"
            sp.add(engine=engine, fuse=telemetry.fuse_of(engine))
            sp.sync(self.state.fields)

    def _iterate_impl(self, niter: int) -> None:
        fast = self._fast_path()
        # an engine advertising full_globals returns the LAST step's
        # Globals itself (in-kernel accumulation, ≡ the reference's
        # src/cuda.cu.Rt:176-202) — no trailing step; the hybrid
        # engines run niter-1 fused steps + one step on the tail engine
        # (_build_tail: the generic Pallas kernel's in-kernel-globals
        # flavour where it takes the case, on a y-split 2D mesh on each
        # shard under shard_map with a psum, else the XLA step) instead.
        # Engines advertising supports_series give every step the Control
        # series' values of its own iteration themselves (the tuned 2D
        # band as scalars, the generic engines as planes): _build_fast
        # lists no other under a series, and one that did not say so
        # would leave every step to XLA here.
        full = fast is not None and fast.full_globals
        nfast = niter if full else niter - 1
        use_fast = (fast is not None and nfast >= 1
                    and (self.params.time_series is None
                         or fast.supports_series))
        done = nfast if use_fast else niter
        # dispatch_s: the jitted call has returned, the fence not begun;
        # a probed first call (compile, fallback ladder) leaves it out
        with telemetry.span("iterate.fused", iters=done) as sp:
            if not use_fast:
                self.state = self._xla_steps(niter)
                sp.mark("dispatch_s")
            elif self._fast_probing:
                with telemetry.span("engine.probe",
                                    engine=self._fast_name) as probe:
                    tried: list = []
                    done = self._probe_first_call(fast, niter, nfast, tried,
                                                  probe)
                    telemetry.counter("engine.probe_attempts", len(tried))
                    probe.add(attempts=len(tried),
                              rungs=[cap for cap in tried if cap],
                              result=self._fast_name or "xla")
                    probe.sync(self.state)
            else:
                self.state = self._run_engine(fast, self.state, nfast)
                sp.mark("dispatch_s")
            sp.add(iters=done,
                   engine=(self._fast_name if use_fast else None) or "xla")
            sp.sync(self.state)
        if done < niter:
            # the hybrid engines' trailing step, for the globals
            with telemetry.span("iterate.globals_step", iters=1) as sp:
                if self._tail_probing:
                    self._probe_tail()
                else:
                    self.state = (
                        self._run_engine(self._tail, self.state, 1)
                        if self._tail is not None
                        else self._xla_steps(1))
                    sp.mark("dispatch_s")
                if self._tail is not None:
                    telemetry.counter("engine.tail_calls")
                sp.add(engine=self._tail_name or "xla")
                sp.sync(self.state)
        if self.sampler is not None:
            self._hand_samples()

    def _xla_steps(self, niter: int) -> LatticeState:
        """The state after ``niter`` steps on the XLA engine; under a
        sampler on its sampled scan (:func:`make_sampled_iterate`), whose
        rows go where a sampled engine's taps go."""
        if self.sampler is None:
            return self._iterate(self.state, self.params, niter)
        if self._iterate_sampled is None:
            self._iterate_sampled = jax.jit(
                make_sampled_iterate(self.model, self.sampler.points,
                                     self.sampler.quantities),
                static_argnames=("niter",))
        state, rows = self._iterate_sampled(
            self.state, self.params, niter, np.int32(self.avg_start))
        self._left_samples("rows", rows, rows[0])
        return state

    def _left_samples(self, kind: str, what, stack,
                      say: Callable = telemetry.annotate) -> None:
        """A call has left ``stack.shape[0]`` steps' samples on the
        device, ``stack`` (a sampled engine's taps, or the XLA scan's
        rows): keep ``what`` for :meth:`_hand_samples`, and ``say`` so:
        on the innermost open span (``iterate.fused`` or
        ``iterate.globals_step``), or where a probe tells it to."""
        self._sampled.append((kind, what, stack.shape[0]))
        telemetry.counter("sampler.rows", stack.shape[0])
        say(sample_points=len(self.sampler.points),
            sample_rows=stack.shape[0], sample_bytes=stack.nbytes)

    def _hand_samples(self) -> None:
        """Give the sampler what the calls of this ``iterate`` left, in
        the order of the steps, as device arrays: the XLA scan's rows as
        they are, consecutive taps of sampled engines through one call
        of :func:`taps_program`.  Nothing here waits for the device; the
        sampler's flush does."""
        left, self._sampled = self._sampled, []
        behind = sum(steps for _, _, steps in left)
        for kind, calls in itertools.groupby(left, key=lambda c: c[0]):
            calls = list(calls)
            behind -= sum(steps for _, _, steps in calls)
            if kind == "rows":
                chunks = [what for _, what, _ in calls]
            else:
                chunks = [self._with_taps_program(lambda program: program(
                    tuple(taps for _, taps, _ in calls), self._sample_flags,
                    self.params, self.state.iteration, np.int32(behind),
                    np.int32(self.avg_start)))]
            for samples, its in chunks:
                self.sampler.append(its, samples)

    def _run_engine(self, engine, state: LatticeState, niter: int,
                    say: Callable = telemetry.annotate) -> LatticeState:
        """The one place a fused engine is called (the fused call, the
        tail call and both probes), and the one that reports it: with
        telemetry on, its account of the call (``Engine.account``) as
        the counters ``engine.kernel_calls``, ``engine.resident_calls``
        and ``engine.paired_calls`` and as fields ``say`` puts on a
        span, once the call has returned (under a ``<Control>`` series
        with ``series_rows``, ``series_horizon`` and
        ``series_bytes_per_step``, and the counter
        ``engine.series_steps``): the innermost open one
        (``iterate.fused`` or ``iterate.globals_step``), or the
        ``engine.probe`` whose candidate this is (its
        ``engine.probe.candidate`` is the innermost then, and times the
        run only).  A candidate that fails its probe reports nothing.
        A sampled engine's call returns its taps beside the state; they
        are kept for the sampler (:meth:`_left_samples`)."""
        out = engine(state, self.params, niter)
        if engine.samples:
            out, taps = out
            self._left_samples("taps", taps, taps, say)
        if telemetry.enabled() and engine.account is not None:
            series = self.params.time_series
            did = engine.account(niter, series is not None)
            if series is not None:
                # the series' account: the table, and the bytes of the
                # planes the engine makes and its kernel reads a step
                # because of it
                telemetry.counter("engine.series_steps", niter)
                did.update(
                    series_rows=series.shape[0],
                    series_horizon=series.shape[1],
                    series_bytes_per_step=did.pop("series_planes", 0)
                    * int(np.prod(self.shape)) * series.dtype.itemsize)
            telemetry.counter("engine.kernel_calls", did["kernel_calls"])
            if "resident_calls" in did:
                telemetry.counter("engine.resident_calls",
                                  did["resident_calls"])
            telemetry.counter("engine.paired_calls", did["paired_calls"])
            say(**did, **(engine.vmem or {}))
        return out

    def _probe_tail(self) -> None:
        """The first step of the tail engine (:meth:`_build_tail`), on
        the state itself: its program of one kernel call does not donate
        (``pallas_generic._donating_unless_one_call``, beside the generic
        engines' one schedule, ``_scheduled_engine``; on a mesh
        ``parallel/halo.make_sharded_pallas_tail``'s one program), so a
        failure leaves the state whole.  Where it does not compile, or fails as
        it runs, the XLA step takes over with one ``engine_fallback``
        event and the run goes on: unlike a fused engine's steps, this
        step in XLA is what every run paid before."""
        tag = self._tail_name
        self._tail_probing = False
        with telemetry.span("engine.probe", engine=tag) as probe:
            with telemetry.span("engine.probe.candidate", tag=tag,
                                cap=0, result="ran") as run:
                try:
                    # fenced inside the try: a failure at execution
                    # shows here
                    self.state = jax.block_until_ready(self._run_engine(
                        self._tail, self.state, 1, probe.add))
                except Exception as e:  # noqa: BLE001
                    run.add(result=type(e).__name__)
                    self._tail_failed(tag, e)
            if self._tail is None:
                self.state = self._xla_steps(1)
            telemetry.counter("engine.probe_attempts")
            probe.add(attempts=1, rungs=[],
                      result=self._tail_name or "xla")
            probe.sync(self.state)

    def _probe_first_call(self, fast, niter: int, nfast: int,
                          tried: list, probe) -> int:
        """The first call of an engine that has to be probed: run
        ``nfast`` fused steps, walking down the chain of
        :meth:`_build_fast` where an engine does not compile.  Returns
        the steps done: ``nfast``, or ``niter`` where nothing compiled and
        XLA ran the whole chunk (its last step has produced the globals).
        ``tried`` gains one entry per engine that was run: the band cap
        the candidate stands for (its rung of the ladder), 0 for an
        engine without one.  ``probe`` is the ``engine.probe`` span all
        this runs under: the engine that ran puts its account there."""
        from tclb_tpu.ops import pallas_generic
        from tclb_tpu.utils import log
        chain, selected, cause = self._fast_chain, self._fast_name, None
        for n, cand in enumerate(chain):
            tried.append(cand.cap)
            # one span a candidate run: which rung cost what
            with telemetry.span("engine.probe.candidate", tag=cand.tag,
                                cap=cand.cap, result="ran") as run:
                try:
                    # probe on a COPY of the state: the engines donate
                    # their input, and a failure that happens at
                    # execution rather than compile would otherwise
                    # leave the real state's buffers deleted.  It comes
                    # first, so that a traced run reads it off the
                    # span's start (fenced there, as the engine's first
                    # kernel would fence it anyway)
                    state = self.state
                    if cand.probe:
                        state = run.sync(jax.tree.map(jnp.copy, state))
                        run.mark("copy_s")
                    it = fast if n == 0 else cand.build()
                    self.state = self._run_engine(it, state, nfast,
                                                  probe.add)
                except Exception as e:  # noqa: BLE001
                    run.add(result=type(e).__name__)
                    if not cand.probe:
                        # a proven engine on the real state is the end
                        # of its chain: its exception is the run's
                        raise
                    if cause is None:
                        cause = e
                    log.warning(f"engine: {cand.tag} failed to compile "
                                f"({e!r}); stepping down its chain")
                    continue
            break
        else:
            if jax.default_backend() == "tpu":
                # on the chip a run that finishes in XLA under a Pallas
                # name is ~9x slower and reads as a result: fail with
                # the first exception instead
                raise RuntimeError(
                    f"engine {selected} and every smaller configuration "
                    "under it failed to compile on the TPU backend: "
                    f"{cause!r}") from cause
            log.warning(f"engine: {selected} failed to compile "
                        f"({cause!r}); XLA fallback")
            if self.mesh is None:
                # the sharded probe exercised a DIFFERENT kernel (local
                # shard shape) — never poison the single-device caches
                # from it
                pallas_generic.set_mosaic_ok(self.model, self.shape, False)
            cand = it = None
        ran = cand.tag if cand else None
        if ran != selected:
            telemetry.engine_fallback(selected, ran or "xla", repr(cause),
                                      model=self.model.name)
        self._fast, self._fast_name, self._fast_probing = it, ran, False
        if cand is None:
            self.state = self._xla_steps(niter)
            return niter
        if cand.verdict is not None:
            # the generic band engine's verdict, remembered process-wide
            pallas_generic.set_mosaic_ok(self.model, self.shape, True)
            pallas_generic.set_build_cfg(self.model, self.shape,
                                         *cand.verdict)
        return nfast

    def attach_sampler(self, sampler) -> None:
        """Register a point sampler: every subsequent step also leaves
        its quantities at the sample points (reference Sampler, C16), a
        row a step, with the sampler (``sampler.append``).  The engine is
        selected again, with the sampler in view (:meth:`_build_fast`):
        a fused engine's one-step flavour that returns the points' planes
        after every step, or the XLA scan :func:`make_sampled_iterate`
        (the global-view step, which XLA partitions over a mesh)."""
        self.sampler = sampler
        self._iterate_sampled = None
        self._fast_tried = False

    def detach_sampler(self) -> None:
        """The run goes on without its sampler: the engine is selected
        again, as for a lattice that never had one."""
        self.sampler = None
        self._iterate_sampled = None
        self._fast_tried = False

    # -- inspection --------------------------------------------------------- #

    def get_quantity(self, name: str) -> jnp.ndarray:
        """Evaluate a registered Quantity over the lattice (reference
        Lattice::GetQuantity, src/Lattice.cu.Rt:1012-1036): one compiled
        program a quantity (:func:`quantity_program`), shared by every
        lattice of the model.  The state is read, not donated; iteration
        and ``avg_start`` are array arguments, so only a new shape, dtype
        or sharding compiles again.  The innermost open span
        (``quantity.eval`` under the Solver) learns whether this call
        ``"built"`` the program or ``"reused"`` it."""
        return self._run_quantity(quantity_program, name)

    def count_nonfinite(self, name: str) -> jax.Array:
        """How many values of a registered Quantity are NaN or infinite,
        as an int32 scalar still on the device (replicated on a mesh):
        :func:`nonfinite_program`, dispatched and not waited for, so a
        caller can issue every quantity's count and fetch them together.
        Says ``"built"`` or ``"reused"`` as :meth:`get_quantity` does."""
        return self._run_quantity(nonfinite_program, name)

    def _run_quantity(self, programs: Callable, name: str) -> jax.Array:
        """One of a quantity's two compiled programs on the live state."""
        program, traces = programs(
            self.model, name, jnp.dtype(self.dtype), self.storage_repr)
        before = traces[0]
        out = program(self.state.fields, self.state.flags, self.params,
                      self.state.iteration, np.int32(self.avg_start))
        built = traces[0] != before
        if built:
            telemetry.counter("quantity.programs_built")
        telemetry.annotate(program="built" if built else "reused")
        return out

    def reset_average(self) -> None:
        """Zero the ``average=True`` storage planes and restart the sample
        counter (reference Lattice::resetAverage,
        src/Lattice.cu.Rt:1193-1201: CudaMemset of each averaged plane +
        ``reset_iter = iter``)."""
        m = self.model
        idx = [i for i, d in enumerate(m.densities) if d.average]
        if idx:
            fields = self.state.fields
            for i in idx:
                fields = fields.at[i].set(0.0)
            self.state = dataclasses.replace(self.state, fields=fields)
            if self._place is not None:
                self.state, self.params = self._place()
        self.avg_start = int(self.state.iteration)

    def _plane_w(self, idx: int):
        """Per-plane shift for the density accessors: the lattice weight
        under the shifted representation, falsy (``None``) otherwise."""
        if self._shift_vec is None:
            return None
        w = float(self._shift_vec[idx])
        return w or None

    def get_density(self, name: str) -> jnp.ndarray:
        """One storage plane in RAW distribution values (the shifted
        rung widens + restores ``w_i``; raw storage returns the plane
        untouched, exactly the pre-shift behavior)."""
        idx = self.model.storage_index[name]
        w = self._plane_w(idx)
        if w is None:
            return self.state.fields[idx]
        return ddf.widen_plane(self.state.fields[idx], self.dtype, w)

    def set_density_planes(self, values: dict) -> None:
        """Write several storage planes with ONE device placement (a
        per-plane set_density would re-shard the whole state each time).
        Values are RAW distributions; the shifted rung removes ``w_i``
        in the compute dtype before narrowing."""
        idxs, planes = [], []
        for name, value in values.items():
            idx = self.model.storage_index[name]
            w = self._plane_w(idx)
            if w is None:
                plane = jnp.asarray(value, dtype=self.storage_dtype)
            else:
                plane = ddf.narrow_plane(
                    jnp.asarray(value, dtype=self.dtype),
                    self.storage_dtype, w)
            idxs.append(idx)
            planes.append(plane)
        self.state = dataclasses.replace(self.state, fields=_write_planes(
            self.state.fields, tuple(planes), tuple(idxs),
            None if self.mesh is None else self.state.fields.sharding))
        if self._place is not None:
            self.state, self.params = self._place()

    def set_density(self, name: str, value: np.ndarray) -> None:
        self.set_density_planes({name: value})

    def fields_raw(self) -> np.ndarray:
        """At-rest field stack as a host float64 array in the RAW
        representation — the representation-independent view the
        precision harness and state digests compare against (the
        arithmetic runs in f64, so it is exact for either storage
        layout)."""
        return ddf.convert_fields_host(
            np.asarray(self.state.fields), self.storage_repr, "raw",
            ddf.storage_shift(self.model), np.float64)

    def get_globals(self) -> dict[str, float]:
        """reference Lattice::getGlobals (src/Lattice.cu.Rt:1093-1106)."""
        vals = np.asarray(self.state.globals_)
        return {g.name: float(vals[i]) for i, g in enumerate(self.model.globals_)}

    def get_objective(self) -> float:
        """Weighted objective from <Global>InObj settings (reference
        Lattice::calcGlobals, src/Lattice.cu.Rt:1113-1129)."""
        m = self.model
        obj = 0.0
        vals = np.asarray(self.state.globals_)
        svec = np.asarray(self.params.settings)
        for i, g in enumerate(m.globals_):
            obj += float(svec[m.setting_index[g.name + "InObj"]]) * float(vals[i])
        return obj

    # -- checkpoint --------------------------------------------------------- #

    def save(self, path: str) -> None:
        """Full-state dump (reference Lattice::save, src/Lattice.cu.Rt:592-626),
        including any Control time series.  Legacy ``.npz`` format, written
        atomically (temp + fsync + rename) through the checkpoint
        subsystem's writer so a kill mid-save never corrupts an existing
        copy; the manifest-verified directory format lives in
        :mod:`tclb_tpu.checkpoint`."""
        from tclb_tpu.checkpoint.restore import npy_safe
        from tclb_tpu.checkpoint.writer import atomic_path, with_suffix
        extra = {}
        if self.params.time_series is not None:
            extra["time_series"] = np.asarray(self.params.time_series)
            extra["series_map"] = np.asarray(self.params.series_map,
                                             dtype=np.int64)
        target = with_suffix(path, ".npz")
        with telemetry.span("checkpoint.save", mode="legacy_npz",
                            path=target) as sp:
            sp.sync(self.state.fields)
            with atomic_path(target) as tmp:
                with open(tmp, "wb") as f:
                    np.savez(f,
                             fields=npy_safe(np.asarray(self.state.fields)),
                             flags=np.asarray(self.state.flags),
                             iteration=int(self.state.iteration),
                             settings=np.asarray(self.params.settings),
                             zone_table=np.asarray(self.params.zone_table),
                             storage_dtype=str(
                                 np.dtype(self.storage_dtype)),
                             storage_repr=self.storage_repr,
                             **extra)

    def load(self, path: str) -> None:
        from tclb_tpu.checkpoint.restore import npy_restore
        from tclb_tpu.checkpoint.writer import resolve_npz
        d = np.load(resolve_npz(path))
        self._fast_tried = False   # restored flags may paint new types
        self._iterate_cached = None
        self._host_flags = np.asarray(d["flags"], dtype=np.uint16)
        # files older than the storage_repr stamp are raw by definition;
        # a cross-representation load converts on the host in f64 (an
        # unknown stamp raises rather than loading garbage)
        src_repr = (str(d["storage_repr"]) if "storage_repr" in d
                    else "raw")
        src_sdt = (str(d["storage_dtype"]) if "storage_dtype" in d
                   else str(np.dtype(self.dtype)))
        raw_fields = npy_restore(d["fields"], src_sdt)
        if src_repr == self.storage_repr:
            fields = jnp.asarray(raw_fields, dtype=self.storage_dtype)
        else:
            fields = jnp.asarray(ddf.convert_fields_host(
                raw_fields, src_repr, self.storage_repr,
                ddf.storage_shift(self.model), self.storage_dtype))
        self.state = LatticeState(
            fields=fields,
            flags=jnp.asarray(d["flags"], dtype=FLAG_DTYPE),
            globals_=self.state.globals_,
            iteration=jnp.asarray(d["iteration"], dtype=jnp.int32),
        )
        self._series = {}
        ts, smap = None, ()
        if "time_series" in d:
            ts = jnp.asarray(d["time_series"], dtype=self.dtype)
            smap = tuple(tuple(int(v) for v in row) for row in d["series_map"])
            for si, z, r in smap:
                self._series[(si, z)] = np.asarray(d["time_series"][r])
        self.params = SimParams(
            settings=jnp.asarray(d["settings"], dtype=self.dtype),
            zone_table=jnp.asarray(d["zone_table"], dtype=self.dtype),
            time_series=ts, series_map=smap)
        if self._place is not None:
            self.state, self.params = self._place()
