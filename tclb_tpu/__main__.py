"""Command-line entry point: ``python -m tclb_tpu`` (or the ``tclb``
console script).

Parity target: the reference's per-model binaries
``CLB/<model>/main case.xml [devices]`` (reference src/main.cpp.Rt:220-252)
— one runtime here, the model selected by flag or by the config's
``<CLBConfig model=...>`` attribute, plus catalogue introspection commands
(the reference generates per-model wiki docs instead,
src/Model.md.Rt/src/Models.md.Rt).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from tclb_tpu import telemetry


def _backend_started():
    """Whether the caller had a JAX backend running already (None where
    this JAX does not say: it has no public name for the question)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return None


def run_case(args):
    """What ``tclb run`` does with its parsed arguments; returns the
    finished :class:`~tclb_tpu.control.solver.Solver` (``chip_smoke.py``
    inspects its lattice).  A usage error exits with code 2."""
    import xml.etree.ElementTree as ET

    # honor the config's model attribute when --model is absent
    model_name = args.model
    if model_name is None:
        root = ET.parse(args.case).getroot()
        model_name = root.get("model")
    if model_name is None:
        print("error: no --model flag and no model= attribute on "
              "<CLBConfig>", file=sys.stderr)
        raise SystemExit(2)

    if args.distributed:
        # multi-host: one process per host over DCN, same config
        # everywhere (the reference's mpirun surface,
        # src/main.cpp.Rt:178-183); jax.distributed wires the hosts into
        # one global device set, and the global-view arrays/mesh span it
        from tclb_tpu.parallel.multihost import initialize_distributed
        initialize_distributed(args.distributed)

    import jax
    import jax.numpy as jnp
    with telemetry.import_span("tclb_tpu.control.solver"):
        from tclb_tpu.control.solver import run_config
    with telemetry.import_span("tclb_tpu.models." + model_name):
        from tclb_tpu.models import get_model
        model = get_model(model_name)

    # the backend starts here, under its span, and not at the lattice's
    # first array
    with telemetry.span("startup.devices") as sp:
        if telemetry.enabled():
            sp.add(preloaded=_backend_started())
        devices = jax.devices()
        sp.add(count=len(devices), device_kind=devices[0].device_kind)
        mesh = None
        if args.mesh:
            import numpy as np
            from jax.sharding import Mesh
            axes = tuple(int(v) for v in args.mesh.split("x"))
            names = ("y", "x") if model.ndim == 2 else ("z", "y", "x")
            if len(axes) != len(names):
                print(f"error: --mesh needs {len(names)} factors for a "
                      f"{model.ndim}D model", file=sys.stderr)
                raise SystemExit(2)
            n = int(np.prod(axes))
            mesh = Mesh(np.asarray(devices[:n]).reshape(axes), names)
    dtype = {"f32": jnp.float32, "f64": jnp.float64}[args.precision]
    if dtype is jnp.float64:
        jax.config.update("jax_enable_x64", True)

    monitor = None
    if args.monitor:
        from tclb_tpu.telemetry.http import MonitorServer
        monitor = MonitorServer.from_spec(args.monitor).start()
        print(f"monitor: {monitor.url}/status")

    if args.profile:
        # XLA/TPU trace for TensorBoard (the reference's per-event CUDA
        # timing scaffolding + kernel stats, SURVEY §5 tracing)
        jax.profiler.start_trace(args.profile)
    try:
        solver = run_config(args.case, model, mesh=mesh, dtype=dtype,
                            output=args.output, resume=args.resume)
    finally:
        if args.profile:
            jax.profiler.stop_trace()
            print(f"profile trace written to {args.profile}")
        if monitor is not None:
            monitor.stop()
    return solver


def _cmd_run(args) -> int:
    solver = run_case(args)
    print(f"done: {solver.iter} iterations")
    return 0


def _cmd_models(args) -> int:
    from tclb_tpu.models import get_model, list_models
    for name in list_models():
        if args.verbose:
            m = get_model(name)
            print(f"{name:32s} {m.ndim}D  {m.description}")
        else:
            print(name)
    return 0


def _cmd_describe(args) -> int:
    """Model introspection (the reference's generated per-model wiki page,
    src/Model.md.Rt)."""
    from tclb_tpu.models import get_model
    m = get_model(args.model)
    info = {
        "name": m.name,
        "ndim": m.ndim,
        "description": m.description,
        "densities": list(m.storage_names),
        "settings": [{"name": s.name, "default": s.default,
                      "zonal": s.zonal, "comment": s.comment}
                     for s in m.settings],
        "quantities": sorted(m.quantity_fns),
        "globals": [g.name for g in m.globals_],
        "node_types": sorted(m.node_types),
        "stages": sorted(m.stages),
        "actions": {k: list(v) for k, v in m.actions.items()},
    }
    print(json.dumps(info, indent=2, default=str))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tclb", description="TPU-native lattice-Boltzmann framework")
    sub = p.add_subparsers(dest="cmd", required=True)

    r = sub.add_parser("run", help="run an XML case file")
    r.add_argument("case", help="case.xml config")
    r.add_argument("--model", "-m", help="model name (or model= attr in "
                   "the config)")
    r.add_argument("--output", "-o", default=None, help="output prefix")
    r.add_argument("--mesh", default=None,
                   help="device mesh, e.g. 2x4 (z-y-x major)")
    r.add_argument("--precision", choices=("f32", "f64"), default="f32")
    r.add_argument("--resume", nargs="?", const="latest", default=None,
                   metavar="CKPT",
                   help="resume from a checkpoint before solving: bare "
                   "--resume picks the newest valid checkpoint under the "
                   "config's <SaveCheckpoint> root, or pass an explicit "
                   "checkpoint directory")
    r.add_argument("--profile", default=None, metavar="DIR",
                   help="write a TensorBoard trace of the run to DIR")
    r.add_argument("--monitor", default=None, metavar="[HOST]:PORT",
                   help="serve live /metrics, /status and /trace over "
                   "HTTP for the duration of the run (host defaults to "
                   "127.0.0.1; port 0 picks a free one)")
    r.add_argument("--distributed", default=None, metavar="SPEC",
                   help="multi-host init: 'auto' (TPU pod metadata) or "
                   "coordinator:port,num_processes,process_id")
    r.set_defaults(fn=_cmd_run)

    sw = sub.add_parser("sweep", help="batched parameter sweep over an "
                        "XML base case")
    from tclb_tpu.serve.__main__ import add_sweep_arguments, run_sweep
    add_sweep_arguments(sw)
    sw.set_defaults(fn=run_sweep)

    gw = sub.add_parser("gateway", help="multi-tenant HTTP serving "
                        "gateway (persistent job store + admission "
                        "control + checkpoint-backed resumability)")
    from tclb_tpu.gateway.__main__ import add_gateway_arguments, run_gateway
    add_gateway_arguments(gw)
    gw.set_defaults(fn=run_gateway)

    ls = sub.add_parser("models", help="list the model catalogue")
    ls.add_argument("--verbose", "-v", action="store_true")
    ls.set_defaults(fn=_cmd_models)

    d = sub.add_parser("describe", help="dump a model's registry as JSON")
    d.add_argument("model")
    d.set_defaults(fn=_cmd_describe)

    return p


def main(argv=None) -> int:
    telemetry.boot(time.time())
    try:
        # the subcommands' own modules, which the parser imports
        with telemetry.import_span("tclb_tpu.__main__"):
            parser = build_parser()
        args = parser.parse_args(argv)
        from tclb_tpu.compile_cache import place_compile_cache
        place_compile_cache()
        return args.fn(args)
    finally:
        telemetry.boot_over()


if __name__ == "__main__":
    sys.exit(main())
