"""Sharded lattice stepping: halo exchange over ICI + shard_map.

TPU-native replacement for the reference's MPI halo pipeline (reference
src/Lattice.cu.Rt:304-366 and :383-461): where the reference stages 26 margin
buffers through pinned host memory around ``MPI_Isend/Irecv`` and manually
overlaps border/interior kernels, here each device holds one block of the
lattice, halos move with ``lax.ppermute`` over the mesh (ICI neighbors ARE
the lattice neighbors), and XLA's latency-hiding scheduler overlaps the
collective with interior compute.  No host staging exists at all.

Like the reference, which only sends non-empty margins (``NonEmptyMargin``,
src/conf.R:517-563), each exchange ships only the planes whose streaming
vector actually crosses that axis.

Globals go through ``lax.psum``/``pmax`` (reference MPI_Reduce,
src/Lattice.cu.Rt:1093-1106), hoisted outside the iteration loop.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tclb_tpu import telemetry
from tclb_tpu.core.lattice import (LatticeState, SimParams, Streaming,
                                   make_action_step)
from tclb_tpu.core.registry import Model
from tclb_tpu.parallel.mesh import field_spec, flag_spec

_COMP = {"x": 0, "y": 1, "z": 2}


def _validate_mesh(model: Model, mesh: Mesh) -> None:
    expected = ("y", "x") if model.ndim == 2 else ("z", "y", "x")
    if tuple(mesh.axis_names) != expected:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} must be {expected} for a "
            f"{model.ndim}D model (one mesh axis per lattice dim, size 1 for "
            f"unsplit dims; use parallel.mesh.make_mesh)")


def _exchange_axis(block: jnp.ndarray, name: str, axis: int, width: int,
                   n: int, send: Optional[np.ndarray] = None) -> jnp.ndarray:
    """Extend ``block`` with ``width`` halo cells along ``axis`` from the
    torus neighbors on mesh axis ``name``.  ``send`` selects which storage
    planes participate (others get zero halos, which are never read).  On a
    size-1 mesh axis the permute is the identity — the periodic wrap of the
    global domain."""
    src = block if send is None else block[jnp.asarray(send)]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    hi_edge = lax.slice_in_dim(src, src.shape[axis] - width, src.shape[axis],
                               axis=axis)
    lo_edge = lax.slice_in_dim(src, 0, width, axis=axis)
    lo_halo = lax.ppermute(hi_edge, name, fwd)   # from lower neighbor
    hi_halo = lax.ppermute(lo_edge, name, bwd)   # from upper neighbor
    if send is not None:
        shp = list(block.shape)
        shp[axis] = width
        z = jnp.zeros(shp, block.dtype)
        sel = jnp.asarray(send)
        lo_halo = z.at[sel].set(lo_halo)
        hi_halo = z.at[sel].set(hi_halo)
    return jnp.concatenate([lo_halo, block, hi_halo], axis=axis)


def _halo_blocks(block: jnp.ndarray, name: str, axis: int, width: int,
                 n: int) -> tuple:
    """The two halo blocks :func:`_exchange_axis` would put round
    ``block``, ``(lo_halo, hi_halo)``, and no extended copy of it: the
    upper ``width`` cells of the lower torus neighbor on mesh axis
    ``name`` and the lower ``width`` cells of the upper one."""
    size = block.shape[axis]
    hi_edge = lax.slice_in_dim(block, size - width, size, axis=axis)
    lo_edge = lax.slice_in_dim(block, 0, width, axis=axis)
    return (lax.ppermute(hi_edge, name, [(i, (i + 1) % n) for i in range(n)]),
            lax.ppermute(lo_edge, name, [(i, (i - 1) % n) for i in range(n)]))


def _exchange_bytes(shape, axis: int, width: int, n: int, planes: int,
                    itemsize: int) -> int:
    """Bytes one chip sends in one :func:`_exchange_axis` of a block of
    ``shape`` (planes first): ``width`` cells of ``planes`` planes to
    each of its two neighbors, ``2 x width x plane size x planes x
    itemsize``; nothing on a size-1 mesh axis, where no byte leaves the
    chip."""
    if n == 1:
        return 0
    plane = int(np.prod(shape[1:])) // int(shape[axis])
    return 2 * width * plane * planes * itemsize


def _count_halo(exchanges: int, nbytes: int) -> None:
    """The host-side account of one sharded ``iterate`` call: the
    counters, and ``halo_bytes`` on the enclosing span (the Lattice's
    ``iterate.fused`` or ``iterate.globals_step``)."""
    telemetry.counter("halo.exchanges", exchanges)
    telemetry.counter("halo.bytes", nbytes)
    telemetry.annotate(halo_bytes=nbytes)


def halo_pad(block: jnp.ndarray, mesh: Mesh, width: int,
             start_axis: int = 1) -> jnp.ndarray:
    """Extend a local block with halos on every lattice axis (all planes).
    Axes are processed in order, so the second exchange carries corner data
    from the first — the reference's 26-direction margin system collapsed to
    2·ndim collectives."""
    out = block
    for k, name in enumerate(mesh.axis_names):
        out = _exchange_axis(out, name, start_axis + k, width,
                             mesh.shape[name])
    return out


class HaloStreaming(Streaming):
    """Streaming over a device mesh: pull via halo exchange + shifted static
    slices; Field neighbor loads via a halo-padded raw stack."""

    def __init__(self, model: Model, mesh: Mesh,
                 width: Optional[int] = None):
        super().__init__(model)
        _validate_mesh(model, mesh)
        self.mesh = mesh
        self.width = int(width or max(1, model.max_stencil))
        # which storage planes stream across each mesh axis
        self._send: dict[str, Optional[np.ndarray]] = {}
        for name in mesh.axis_names:
            sel = np.nonzero(model.ei[:, _COMP[name]])[0]
            self._send[name] = sel if len(sel) else None
        # does any Field declare a nonzero access stencil?
        self._needs_loader = any(
            lo or hi
            for f in model.fields
            for lo, hi in (f.dx_range, f.dy_range, f.dz_range))

    def pull(self, fields: jnp.ndarray) -> jnp.ndarray:
        w, names = self.width, self.mesh.axis_names
        local = fields.shape[1:]
        padded = fields
        for k, name in enumerate(names):
            send = self._send[name]
            if send is None:
                continue  # nothing streams across this axis
            padded = _exchange_axis(padded, name, 1 + k, w,
                                    self.mesh.shape[name], send)
        out = []
        # track how much each axis was actually padded
        pad = {name: (0 if self._send[name] is None else w) for name in names}
        for i in range(self.model.n_storage):
            e = self.model.ei[i]
            idx = []
            for k, name in enumerate(names):
                d = int(e[_COMP[name]])
                start = pad[name] - d
                idx.append(slice(start, start + local[k]))
            out.append(padded[(i, *idx)])
        return jnp.stack(out)

    def bytes_per_step(self, local_shape, itemsize: int,
                       action: str = "Iteration") -> int:
        """Bytes one chip sends in one step of ``action``, from the
        shapes: each streaming stage's :meth:`pull` (the planes that
        cross each split axis) and, where a Field declares a stencil,
        each stage's :func:`halo_pad` of the raw stack."""
        n_storage = self.model.n_storage

        def sent(planes_on) -> int:
            """One pass of exchanges over the mesh axes, each extending
            the block the next one ships."""
            shape, total = [n_storage, *local_shape], 0
            for k, name in enumerate(self.mesh.axis_names):
                planes = planes_on(name)
                if planes:
                    total += _exchange_bytes(
                        shape, 1 + k, self.width, self.mesh.shape[name],
                        planes, itemsize)
                    shape[1 + k] += 2 * self.width
            return total

        pull = sent(lambda name: 0 if self._send[name] is None
                    else len(self._send[name]))
        pad = sent(lambda name: n_storage) if self._needs_loader else 0
        stages = [self.model.stages[st]
                  for st in self.model.actions[action]]
        return (pull * sum(bool(st.load_densities) for st in stages)
                + pad * len(stages))

    def make_loader(self, raw: jnp.ndarray) -> Callable:
        if not self._needs_loader:
            # no Field declared a stencil: any ctx.load with a nonzero
            # offset would silently wrap at the local shard edge, so fail
            # loudly instead (the declared ranges size the halo)
            def no_load(index: int, dx: int, dy: int, dz: int):
                if dx or dy or dz:
                    raise ValueError(
                        "sharded ctx.load with nonzero offset requires the "
                        "Field to declare its access stencil (add_field "
                        "dx/dy/dz ranges)")
                return raw[index]
            return no_load
        w, names = self.width, self.mesh.axis_names
        local = raw.shape[1:]
        padded = halo_pad(raw, self.mesh, w)

        def load(index: int, dx: int, dy: int, dz: int) -> jnp.ndarray:
            if max(abs(dx), abs(dy), abs(dz)) > w:
                raise ValueError(
                    f"ctx.load offset ({dx},{dy},{dz}) exceeds halo width "
                    f"{w}; declare a wider stencil on the Field")
            d_by_name = {"x": dx, "y": dy, "z": dz}
            idx = []
            for k, name in enumerate(names):
                d = int(d_by_name[name])
                idx.append(slice(w + d, w + d + local[k]))
            return padded[(index, *idx)]

        return load


def _globals_allreduce(model: Model, g: jnp.ndarray, names) -> jnp.ndarray:
    """Cross-device reduction honoring each Global's op (SUM/MAX)."""
    if model.n_globals == 0:
        return g
    is_sum = np.array([gl.op == "SUM" for gl in model.globals_])
    g_sum = lax.psum(g, names)
    g_max = lax.pmax(g, names)
    return jnp.where(jnp.asarray(is_sum), g_sum, g_max)


def band_shards(model: Model, mesh: Mesh, shape) -> Optional[tuple]:
    """``(axis, n, local)`` of a lattice the band kernels can take in
    shards: the mesh axis their band axis is split over (y in 2D, z in
    3D), its size, and one shard's shape; None where the mesh is not the
    model's, splits another axis (the kernels keep the lane plane whole)
    or does not divide the rows."""
    try:
        _validate_mesh(model, mesh)
    except ValueError:
        return None
    if mesh.shape["x"] != 1 or (model.ndim == 3 and mesh.shape["y"] != 1):
        return None
    axis = "y" if model.ndim == 2 else "z"
    n = mesh.shape[axis]
    if shape[0] % n:
        return None
    return axis, n, (shape[0] // n,) + tuple(shape[1:])


def _state_specs(mesh: Mesh) -> LatticeState:
    """How a ``shard_map`` program takes and returns the state: fields
    and flags by the mesh's axes, Globals and iteration replicated."""
    return LatticeState(fields=field_spec(mesh), flags=flag_spec(mesh),
                        globals_=P(), iteration=P())


def _generic_aux(params: SimParams, flags_i32, zones, gz_si, dtype):
    """The aux stack of the generic 2D building block on one shard: the
    flag plane and a plane a zonal setting (those at ``gz_si`` of the
    zone table, by the nodes' ``zones``), in ``dtype``."""
    from tclb_tpu.ops import fusion
    return jnp.stack(
        [flags_i32.astype(dtype)]
        + [fusion.zone_plane(params.zone_table[j].astype(dtype), zones)
           for j in gz_si])


def _zonal_table(params: SimParams, zonal_si, dtype) -> jnp.ndarray:
    """The zone-table rows of the settings at ``zonal_si``, flattened: the
    SMEM operand from which a lean kernel rebuilds its zonal planes."""
    return jnp.concatenate([params.zone_table[j].astype(dtype)
                            for j in zonal_si])


def _streams(model: Model) -> int:
    """What one rep of the Iteration action adds to the iteration
    counter: 1 iff any stage streams — the rule the single-device generic
    engine applies."""
    return int(any(model.stages[st].load_densities
                   for st in model.actions["Iteration"]))


def why_no_sharded_pallas(model: Model, mesh: Mesh, shape, dtype) -> str:
    """Why :func:`make_sharded_pallas_iterate` refuses the case, for the
    ``fused_rejected`` event of dispatch."""
    shards = band_shards(model, mesh, shape)
    if shards is None:
        return (f"mesh: {dict(mesh.shape)} is not the model's, splits an "
                f"axis other than the band axis, or does not divide "
                f"{tuple(shape)}")
    return (f"kernels: no Pallas family takes a shard {shards[2]} of "
            f"{model.name} in {jnp.dtype(dtype).name} with exchanged halos "
            "(3D: neither whole planes nor a y-tiled window fits)")


def make_sharded_pallas_iterate(model: Model, mesh: Mesh, shape,
                                dtype=jnp.float32,
                                present: Optional[set] = None,
                                interpret: Optional[bool] = None,
                                fuse: Optional[int] = None,
                                vmem_budget: Optional[int] = None
                                ) -> Optional[Callable]:
    """Fused Pallas fast path over the device mesh, or None if this
    configuration can't run it.

    The band axis of the kernels (y in 2D, z in 3D) is the sharded axis;
    x (and y in 3D) must be unsplit.  Each kernel call exchanges a halo
    via ``ppermute`` (8 rows in 2D, Mosaic's tile granularity; in 3D the
    K slabs a call of K fused steps reads past either end of the shard)
    and runs the per-shard band kernel — the TPU composition of the
    reference's RunBorder / MPIStream_A / RunInterior / MPIStream_B
    overlap pipeline (src/Lattice.cu.Rt:424-456), with XLA's
    latency-hiding scheduler providing the overlap.  The tuned 2D mode
    (``pallas_d2q9``, two steps a call) and the 3D mode (``pallas_d3q``'s
    fused kernel, whole planes or y-tiled windows, K steps a call;
    ``fuse`` pins K, ``vmem_budget`` is the planner's, for tests) hand
    the kernel the shard as it is and the
    neighbours' two blocks as operands of their own
    (:func:`_halo_blocks`), two calls a loop body: nothing of the
    shard's size is written between two calls.  The 3D mode runs the
    steps ``niter % K`` leaves over through the same kernel at its K = 1
    plan, one slab a side, in the same program; the int32 flags it
    extends by K slabs a side once an ``iterate``.  The generic 2D mode
    still runs its kernel on the extended block
    (:func:`_exchange_axis`'s padded copy), one call a body.

    Like the single-device fast path this is the "NoGlobals"
    specialization: ``globals_`` is zeroed; the Lattice hybrid's trailing
    step supplies them: :func:`make_sharded_pallas_tail` where it takes
    the case (every mesh this engine takes whose shards the generic
    kernel takes with Globals it reduces: the y-split 2D mesh and the
    z-split 3D one), else the sharded XLA step (both psum)."""
    from tclb_tpu.ops import pallas_d2q9, pallas_d3q
    from tclb_tpu.ops.engine import Engine, paired_calls, scan_calls
    shards = band_shards(model, mesh, shape)
    if shards is None:
        return None
    axis, n, local = shards
    itemsize = jnp.dtype(dtype).itemsize

    mode = None
    if model.ndim == 2:
        if local[0] % 8:
            return None
        if pallas_d2q9.supports(model, local, dtype):
            call1, call2, by, by2 = pallas_d2q9.make_pallas_iterate(
                model, local, dtype, interpret=interpret, fuse=2,
                present=present, ext_halo=True)
            mode = "tuned2d"
        else:
            # registry-driven generic kernel as the sharded building
            # block: same 8-row halo contract, per-step aux stack
            from tclb_tpu.ops import pallas_generic
            if not pallas_generic.supports(model, local, dtype):
                return None
            callg, _, byg, gz_names = pallas_generic.make_pallas_iterate(
                model, local, dtype, interpret=interpret, fuse=1,
                present=present, ext_halo=True)
            si = model.setting_index
            gz_si = [si[nm] for nm in gz_names]
            g_adv = _streams(model)
            mode = "generic2d"
        width = 8
    else:
        if not pallas_d3q.supports(model, local, dtype, ext_halo=True):
            return None
        try:
            k3 = pallas_d3q.make_pallas_iterate(
                model, local, dtype, interpret=interpret, present=present,
                ext_halo=True, fuse=fuse,
                **({} if vmem_budget is None
                   else dict(vmem_budget=vmem_budget)))
        except ValueError:
            return None         # no plan at the pinned ``fuse``
        mode = "fused3d"
        width = k3.plan[2]
    zshift = model.zone_shift
    # steps a kernel call of the loop advances
    steps = 2 if mode == "tuned2d" else width if mode == "fused3d" else 1

    def sent(w: int, itemsize: int) -> int:
        """Bytes one chip sends in one exchange of ``w`` cells of one
        plane along the band axis."""
        return _exchange_bytes((1,) + local, 1, w, n, 1, itemsize)

    # per exchange of the fields by a call of the loop and by a call
    # left over (3D: one slab a side); the aux stack, which is exchanged
    # once per call of ``iterate`` (3D: the int32 flags)
    field_bytes = model.n_storage * sent(width, itemsize)
    rest_bytes = model.n_storage * sent(
        1 if mode == "fused3d" else width, itemsize)
    aux_bytes = (sent(width, 4) if mode == "fused3d" else sent(
        width, itemsize) * (3 if mode == "tuned2d" else 1 + len(gz_si)))

    def exch(arr):
        """Prepend/append ``width`` halo rows/slabs from the torus
        neighbors along the sharded axis (identity wrap when n == 1) —
        the shared halo-exchange primitive, axis 1 = the band axis."""
        with jax.named_scope("halo_exchange"):
            return _exchange_axis(arr, axis, 1, width, n)

    state_specs = _state_specs(mesh)

    def split(niter: int) -> tuple:
        """``niter`` steps as the trips of the loop (calls of two fused
        steps in the tuned 2D mode, of K in the 3D mode, of one in the
        generic) and the steps left over, one kernel call each."""
        return (niter, 0) if mode == "generic2d" else divmod(niter, steps)

    @lru_cache(maxsize=None)
    def _program(trips: int, odd: int):
        """The jitted program of a loop of ``trips`` kernel calls and
        the ``odd`` one-step calls after it.  In the tuned 2D mode two
        programs (:func:`iterate`), not one: with the one-step kernel in
        the loop's program the compiler
        keeps one of the loop's two state buffers, or both, out of its
        fast memory at 11 x 1024 x 1024, and the ``kernel2`` that waited
        for its input copies took 308 to 380 us a call on a state it read
        from HBM for 216 (compiled for a described 4 x 1 v5e,
        ``tests/test_mosaic_compile.py``; chip, PR 47; since PR 50 it
        prefetches its band, and the placement was not measured again)."""
        def local_iterate(state: LatticeState, params: SimParams
                          ) -> LatticeState:
            flags_i32 = state.flags.astype(jnp.int32)
            zones = flags_i32 >> zshift
            sett = params.settings.astype(dtype)
            fields = state.fields
            def halos(f, w=width):
                """The neighbours' ``w`` rows or slabs, the kernels'
                operands beside the shard as it is: no padded copy of
                it."""
                with jax.named_scope("halo_exchange"):
                    return _halo_blocks(f, axis, 1, w, n)

            # the generic 2D loop is single, paired=False: its body
            # builds the padded operand anew, which is the copy of the
            # carry a single call a body needs (ROADMAP S9)
            if mode == "generic2d":
                aux_ext = exch(_generic_aux(params, flags_i32, zones,
                                            gz_si, dtype))

                def bodyg(carry, _):
                    f, it = carry
                    out = callg(sett, it[None], exch(f), aux_ext)
                    return (out, it + g_adv), None

                fields, _ = scan_calls(bodyg, (fields, state.iteration),
                                       trips, False)
            elif model.ndim == 2:
                vel, den = pallas_d2q9.zonal_planes(
                    model, params, zones, dtype)
                if trips:
                    aux_ext = exch(jnp.stack(
                        [flags_i32.astype(dtype), vel, den]))

                    def body2(f, _):
                        return call2(sett, f, *halos(f), aux_ext), None

                    # paired: the carry is the kernel's input and its
                    # output at once, and with one call a body XLA copies
                    # it before the call (the copy the padded operand
                    # used to be)
                    fields = scan_calls(body2, fields, trips, True)
                if odd:
                    fields = call1(sett, fields, *halos(fields), flags_i32,
                                   vel, den)
            else:
                ztab = _zonal_table(params, k3.zonal_si, dtype)
                flags_ext = exch(flags_i32[None])[0]

                def body3(call, w):
                    return lambda f, _: (
                        call(sett, ztab, f, *halos(f, w), flags_ext), None)

                # paired, as the one-chip engine's loops are: two calls
                # a body, so that XLA copies no carry before a call
                fields = scan_calls(body3(k3.call, width), fields, trips,
                                    True)
                fields = scan_calls(body3(k3.rest, 1), fields, odd, True)
            return LatticeState(
                fields=fields,
                flags=state.flags,
                globals_=jnp.zeros_like(state.globals_),
                iteration=state.iteration + steps * trips + odd,
            )

        f = jax.shard_map(local_iterate, mesh=mesh,
                          in_specs=(state_specs, P()),
                          out_specs=state_specs, check_vma=False)
        return jax.jit(f, donate_argnums=0)

    def iterate(state, params, niter):
        if params.time_series is not None:
            raise ValueError(
                "pallas iterate does not support Control time series")
        trips, odd = split(int(niter))
        out = state
        if mode == "fused3d":
            out = _program(trips, odd)(out, params)
        else:
            if trips or not odd:
                out = _program(trips, 0)(out, params)
            if odd:
                out = _program(0, odd)(out, params)
        if telemetry.enabled():
            # counted host-side from the shapes: one exchange of the
            # fields per kernel call (a fused pair of steps in the tuned
            # 2D mode, K slabs a side for K fused steps in 3D and one
            # for a step left over) plus the aux stack (3D: the int32
            # flags, K slabs a side) once per call; the wall time is the
            # enclosing span's business
            _count_halo(int(niter), trips * field_bytes
                        + odd * rest_bytes + aux_bytes)
        return out

    def account(niter: int, has_series: bool = False) -> dict:
        """One call's kernel calls, those its two-call loop bodies
        issue, and the halo rows (2D) or slabs (3D) a side the kernel
        takes as operands of their own (0 where the mode pads the shard
        round them); in 3D the steps left over, the shards, and the
        windows the fused kernel cuts ONE shard into."""
        trips, odd = split(niter)
        if mode == "fused3d":
            return dict(kernel_calls=trips + odd,
                        paired_calls=paired_calls(trips, odd),
                        remainder_steps=odd, shards=n, **k3.account,
                        halo_operand_slabs=width)
        tuned = mode == "tuned2d"
        return dict(kernel_calls=trips + odd,
                    paired_calls=paired_calls(trips) if tuned else 0,
                    halo_operand_rows=width if tuned else 0)

    # The generic-kernel building block is capability-probed, not proven:
    # dispatch probes its first call and falls back to the sharded XLA
    # engine on a Mosaic lowering failure.  fuse: steps per kernel call,
    # for the engine tag.  impl: the jitted programs, for the compile tests
    # plan: the 3D mode's windows of one shard, (bz, by, K), for the tag
    return Engine(iterate, account,
                  unproven=mode in ("generic2d", "fused3d"), fuse=steps,
                  plan=k3.plan if mode == "fused3d" else None,
                  impl=dict(program=_program))


def make_sharded_pallas_tail(model: Model, mesh: Mesh, shape,
                             dtype=jnp.float32,
                             present: Optional[set] = None,
                             interpret: Optional[bool] = None):
    """The one step a hybrid engine leaves for the Globals, on a mesh
    split along the kernels' band axis (y in 2D, z in 3D): an
    :class:`Engine` of ``iterate(state, params, 1)``, or None where this
    configuration can't run it (a mesh split in x, or a 3D one in y; 2D
    shards of no multiple of 8 rows; storage other than f32; a model or
    local shape ``pallas_generic`` refuses; Globals its kernel does not
    reduce): the sharded XLA step, :func:`make_sharded_iterate`, runs
    the step then, whatever engine runs the steps before it.

    One ``shard_map`` program: the neighbours' halos are exchanged once,
    each shard runs ONE call of ``pallas_generic``'s one-step kernel with
    in-kernel globals, which sums over the shard's own nodes, and the
    partial sums are reduced across the mesh
    (:func:`_globals_allreduce`): ``globals_`` comes back replicated, as
    the sharded XLA step returns it.  In 2D the band kernel runs on the
    fields and the aux stack padded by their 8 exchanged rows a side
    (:func:`_exchange_axis`).  In 3D the slab kernel (whole planes or
    y-tiled windows, as ``pallas_generic.tile_plan_3d`` cuts the shard)
    takes the shard as it is and the neighbours' ``R1`` slabs (the
    action's reach, 1 for ``d3q27_cumulant``) as operands of their own
    (:func:`_halo_blocks`): no padded copy of a shard's fields, which at
    96 x 384 x 384 would be 1.97 GB more of the peak; only the f32 flag
    plane, the whole aux stack there, comes extended by ``R1`` slabs a
    side.

    The program does not donate the state: it is one kernel call, which
    reads halos of what it writes (``pallas_generic.
    _donating_unless_one_call``, the one-chip tail's rule), so a first
    call that fails leaves the state whole (``Lattice._probe_tail``).
    Nothing has shown that the kernel compiles: ``unproven``."""
    from tclb_tpu.ops import pallas_generic
    from tclb_tpu.ops.engine import Engine
    shards = band_shards(model, mesh, shape)
    if shards is None:
        return None
    axis, n, local = shards
    if ((model.ndim == 2 and local[0] % 8)
            or jnp.dtype(dtype) != jnp.dtype(jnp.float32)
            or not pallas_generic.supports(model, local, dtype,
                                           probe=False)):
        return None
    # cut: how the kernel cuts the shard, 2D its band's rows, 3D its
    # plan (bz, by, 1)
    _, call_g, cut, gz_names = pallas_generic.make_pallas_iterate(
        model, local, dtype, interpret=interpret, fuse=1, present=present,
        ext_halo=True)
    if call_g is None:
        return None
    gz_si = [model.setting_index[nm] for nm in gz_names]
    adv, names = _streams(model), tuple(mesh.axis_names)
    did = dict(kernel_calls=1, paired_calls=0,
               stages_per_step=len(model.actions["Iteration"]))
    if model.ndim == 2:
        width, aux_planes = 8, 1 + len(gz_si)
        did.update(halo_operand_rows=0, bands=local[0] // cut,
                   band_rows=cut, halo_rows=width, aux_planes=aux_planes)

        def operands(state, params, flags_i32) -> tuple:
            """The shard padded round its halo rows, and its aux stack."""
            return (_exchange_axis(state.fields, axis, 1, width, n),
                    _exchange_axis(
                        _generic_aux(params, flags_i32,
                                     flags_i32 >> model.zone_shift, gz_si,
                                     dtype), axis, 1, width, n))
    else:
        windows = pallas_generic.window_account_3d(model, local, cut)
        width, aux_planes = windows["halo_slabs"], 1
        did.update(remainder_steps=0, shards=n, **windows,
                   aux_planes=aux_planes, halo_operand_slabs=width)

        def operands(state, params, flags_i32) -> tuple:
            """The zone table where the kernel is lean (it rebuilds the
            zonal planes from the flags), the shard as it is, the
            neighbours' slabs, the extended flag plane."""
            ztab = [_zonal_table(params, gz_si, dtype)] if gz_si else []
            return (*ztab, state.fields,
                    *_halo_blocks(state.fields, axis, 1, width, n),
                    _exchange_axis(flags_i32.astype(dtype)[None], axis, 1,
                                   width, n))

    halo_bytes = (model.n_storage + aux_planes) * _exchange_bytes(
        (1,) + local, 1, width, n, 1, jnp.dtype(dtype).itemsize)

    def local_step(state: LatticeState, params: SimParams) -> LatticeState:
        flags_i32 = state.flags.astype(jnp.int32)
        with jax.named_scope("halo_exchange"):
            ops = operands(state, params, flags_i32)
        fields, g = call_g(params.settings.astype(dtype),
                           state.iteration[None], *ops)
        return LatticeState(
            fields=fields, flags=state.flags,
            globals_=_globals_allreduce(
                model, g.astype(state.globals_.dtype), names),
            iteration=state.iteration + adv)

    state_specs = _state_specs(mesh)
    program = jax.jit(jax.shard_map(
        local_step, mesh=mesh, in_specs=(state_specs, P()),
        out_specs=state_specs, check_vma=False))

    def iterate(state, params, niter):
        if int(niter) != 1 or params.time_series is not None:
            raise ValueError("the sharded tail is one step that reduces "
                             "the Globals, with no Control time series")
        out = program(state, params)
        if telemetry.enabled():
            _count_halo(1, halo_bytes)
        return out

    def account(niter: int, has_series: bool = False) -> dict:
        """One kernel call a shard and the kernel's windows of ONE
        shard, under the generic engines' names: in 2D the bands of the
        shard padded round its halo rows; in 3D the slab engine's
        windows, the shards, and the slabs a side the kernel takes as
        operands of their own."""
        return dict(did)

    # full_globals: the call returns the last (its one) step's Globals
    return Engine(iterate, account, full_globals=True, unproven=True,
                  fuse=1, impl=dict(program=program))


def make_sharded_iterate(model: Model, mesh: Mesh,
                         action: str = "Iteration",
                         unroll: int = 1,
                         present: Optional[set] = None) -> Callable:
    """``iterate(state, params, niter)`` over the device mesh.

    The whole scan lives inside one ``shard_map`` so per-step halo exchanges
    are collectives inside the compiled loop — the reference's
    per-iteration MPIStream_A/B dance (src/Lattice.cu.Rt:424-456) with the
    host entirely out of the loop.  Like the single-device engine, the
    first niter-1 steps run the NoGlobals specialization; the final step
    reduces and the allreduce happens once after the scan."""
    _validate_mesh(model, mesh)
    streaming = HaloStreaming(model, mesh)
    step_ng = make_action_step(model, action, streaming, present=present,
                               compute_globals=False)
    step = make_action_step(model, action, streaming, present=present,
                            compute_globals=True)
    names = tuple(mesh.axis_names)

    state_specs = _state_specs(mesh)
    # params are fully replicated; a single P() is a valid tree prefix for
    # whatever SimParams contains (incl. Control time series)
    param_specs = P()

    @lru_cache(maxsize=None)
    def _for_niter(niter: int):
        def local_iterate(state: LatticeState, params: SimParams
                          ) -> LatticeState:
            def body(s, _):
                return step_ng(s, params), None
            state, _ = lax.scan(body, state, None, length=max(niter - 1, 0),
                                unroll=unroll)
            if niter > 0:
                state = step(state, params)
            return state.replace(
                globals_=_globals_allreduce(model, state.globals_, names))

        f = jax.shard_map(local_iterate, mesh=mesh,
                          in_specs=(state_specs, param_specs),
                          out_specs=state_specs, check_vma=False)
        return jax.jit(f, donate_argnums=0)

    # how many per-step ppermute exchange rounds the streaming strategy
    # issues (mesh axes the velocity set actually crosses), for the
    # host-side exchange counter
    n_exch = sum(1 for v in streaming._send.values() if v is not None)

    def iterate(state, params, niter):
        if int(niter) <= 0:
            # match the single-device engine: no steps, no allreduce (a
            # psum of the already-reduced globals would scale them by the
            # device count)
            return state
        out = _for_niter(int(niter))(state, params)
        if telemetry.enabled():
            local = [d // mesh.shape[a]
                     for d, a in zip(out.fields.shape[1:], names)]
            _count_halo(int(niter) * n_exch,
                        int(niter) * streaming.bytes_per_step(
                            local, out.fields.dtype.itemsize, action))
        return out

    return iterate
