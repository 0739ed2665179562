"""Static analyzer tests.

Two halves, mirroring how the reference validates models at codegen time:

* the REAL registry must be clean — every registered model analyzed with
  zero error-severity findings (the CI gate `python -m tclb_tpu.analysis
  --all` asserts the same), and the repo-level hygiene checks must stay
  empty now that the generic resident engine is wired and the
  eligibility caches key on structural fingerprints;
* each checker must actually FIRE — deliberately-broken fixture models
  (wrong weight sum, unpaired velocity set, stencil wider than the halo,
  a stage reading beyond its declaration, a VMEM-overflowing plane
  count) seed exactly the defects the checks exist for.
"""

import json

import numpy as np
import pytest

from tclb_tpu import analysis
from tclb_tpu.analysis import cli, hygiene
from tclb_tpu.analysis.findings import Finding, sort_findings, worst_severity
from tclb_tpu.core.registry import ModelDef
from tclb_tpu.models import get_model, list_models

ALL_MODELS = list_models()


def _error_checks(findings):
    return {f.check for f in findings if f.severity == "error"}


# --------------------------------------------------------------------------- #
# The real registry is clean
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("name", ALL_MODELS)
def test_registered_model_has_no_error_findings(name):
    findings = analysis.analyze_model(name)
    errs = [f for f in findings if f.severity == "error"]
    assert not errs, [f.message for f in errs]


def test_repo_hygiene_clean():
    """No dead engine entry points, no id()-keyed caches — the round-5
    defects this PR fixed must stay fixed."""
    findings = analysis.analyze_repo()
    errs = [f for f in findings if f.severity == "error"]
    assert not errs, [f.message for f in errs]


def test_kernel_safety_ok_for_generic_engine_models():
    m = get_model("d2q9_heat")
    assert analysis.kernel_safety_ok(m)
    # cached on the structural fingerprint: a rebuilt identical model
    # shares the verdict without re-tracing
    assert m.fingerprint in analysis._safety_cache


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #


def test_cli_json_schema(capsys):
    rc = cli.main(["d2q9", "--format", "json", "--shape", "64,128"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(doc) == {"models", "repo", "summary"}
    assert set(doc["models"]) == {"d2q9"}
    assert doc["repo"] == []
    for f in doc["models"]["d2q9"]:
        assert set(f) == {"check", "code", "severity", "model",
                          "message", "where", "details"}
        assert f["code"] == f["check"]           # stable tooling key
        assert f["severity"] in ("error", "warning", "info")
        assert f["model"] == "d2q9"
    s = doc["summary"]
    assert s["models"] == 1
    assert s["errors"] == 0
    assert s["errors"] + s["warnings"] + s["info"] \
        >= len(doc["models"]["d2q9"])


def test_cli_usage_errors(capsys):
    assert cli.main([]) == 2                     # no models, no --all
    assert cli.main(["definitely_not_a_model"]) == 2
    capsys.readouterr()


def test_cli_min_severity_filters_output(capsys):
    rc = cli.main(["d2q9", "--format", "json", "--min-severity", "error"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["models"]["d2q9"] == []           # clean model: all hidden
    assert doc["summary"]["info"] > 0            # ...but still counted


# --------------------------------------------------------------------------- #
# Broken fixtures: every checker fires
# --------------------------------------------------------------------------- #


def _passthrough(groups):
    def run(ctx):
        return ctx.store({g: ctx.group(g) for g in groups})
    return run


def test_invariants_fire_on_wrong_weight_sum():
    d = ModelDef("fx_badweights", ndim=2)
    d.add_densities("f", [(0, 0), (1, 0), (-1, 0), (0, 1), (0, -1)],
                    group="f")
    run = _passthrough(["f"])
    m = d.finalize().bind(run=run, init=run)
    m.declared_weights = {"f": np.array([0.4, 0.2, 0.2, 0.2, 0.2])}
    from tclb_tpu.analysis.invariants import check_invariants
    assert "invariants.weight_sum" in _error_checks(check_invariants(m))
    # ...and through the library API as well
    assert "invariants.weight_sum" in _error_checks(analysis.analyze_model(m))


def test_invariants_fire_on_unpaired_velocity_set():
    d = ModelDef("fx_unpaired", ndim=2)
    d.add_densities("f", [(1, 0), (0, 1)], group="f")
    run = _passthrough(["f"])
    m = d.finalize().bind(run=run, init=run)
    from tclb_tpu.analysis.invariants import check_invariants
    errs = _error_checks(check_invariants(m))
    assert "invariants.net_velocity" in errs
    assert "invariants.opposite_pairing" in errs


def test_footprint_fires_on_stencil_wider_than_halo():
    d = ModelDef("fx_widestencil", ndim=2)
    d.add_density("g", group="g")
    d.add_field("phi", dy=(-12, 12))

    def run(ctx):
        wide = ctx.load("phi", dy=12) + ctx.load("phi", dy=-12)
        return ctx.store({"g": ctx.group("g"), "phi": wide[None]})
    m = d.finalize().bind(run=run, init=_passthrough(["g", "phi"]))
    from tclb_tpu.analysis.footprint import check_footprint
    checks = {f.check for f in check_footprint(m)}
    assert "footprint.halo" in checks            # band engines ineligible
    assert "footprint.adjoint_band" in checks    # 2R > halo
    # declared reads are NOT errors: declaration covers the deep stencil
    assert "footprint.undeclared_read" not in _error_checks(
        check_footprint(m))


def test_footprint_fires_on_undeclared_read():
    d = ModelDef("fx_undeclared", ndim=2)
    d.add_density("g", group="g")
    d.add_field("T", dy=0)                       # declared dy range [0, 0]

    def run(ctx):
        sneaky = ctx.load("T", dy=1)             # ...but reads dy=1
        return ctx.store({"g": ctx.group("g"), "T": sneaky[None]})
    m = d.finalize().bind(run=run, init=_passthrough(["g", "T"]))
    from tclb_tpu.analysis.footprint import (check_footprint,
                                             kernel_safety_errors)
    assert "footprint.undeclared_read" in _error_checks(check_footprint(m))
    assert kernel_safety_errors(m)
    # the engine dispatch consults exactly this verdict: the band kernels
    # would size their windows from the declaration and read stale rows
    assert not analysis.kernel_safety_ok(m)


def test_footprint_fires_on_fusion_halo_overreach():
    """A model whose NAME makes it eligible for the tuned fused z-slab
    kernel but whose declarations reach 2 z-slabs per step: the fused
    engine's K-slab halo grants exactly one reach-slab per fused step,
    so this must surface as an error-severity fusion_halo finding (the
    kernel would silently compute on stale halo slabs)."""
    d = ModelDef("d3q19", ndim=3)       # spoofs the kernel allowlist
    d.add_density("g", group="g")
    d.add_field("phi", dz=(-2, 2))      # 2-slab z-stencil
    run = _passthrough(["g", "phi"])
    m = d.finalize().bind(run=run, init=run)
    from tclb_tpu.analysis.footprint import check_footprint
    assert "footprint.fusion_halo" in _error_checks(check_footprint(m))


def test_footprint_fires_on_3d_adjoint_band_overreach():
    """A 3D model whose fuse-1 chain reach R needs 2*R halo slabs beyond
    what the fused backward (Run_b) slab kernel ever DMAs
    (fusion.ADJ_HALO_MAX per side): for an ``_adj`` model that is an
    error — the model claims adjoint support but every reverse sweep
    silently degrades — and for other names a warning."""
    from tclb_tpu.analysis.footprint import check_footprint
    from tclb_tpu.ops import fusion

    def build(name):
        d = ModelDef(name, ndim=3)
        d.add_density("g", group="g")
        d.add_field("phi", dz=(-5, 5))   # 2*R = 10 > ADJ_HALO_MAX = 8
        run = _passthrough(["g", "phi"])
        return d.finalize().bind(run=run, init=run)

    assert fusion.ADJ_HALO_MAX < 10
    errs = _error_checks(check_footprint(build("fx_wide_adj")))
    assert "footprint.adjoint_band" in errs
    # same geometry without the adjoint claim: capability warning only
    fs = check_footprint(build("fx_wide"))
    bands = [f for f in fs if f.check == "footprint.adjoint_band"]
    assert bands and all(f.severity == "warning" for f in bands)


def test_footprint_3d_adjoint_chunk_on_real_model():
    """The clean side of the band rule: d3q19_adj at its production
    chunk sits exactly at the halo boundary and must report the info
    finding (with the planner's (k, bz) at a concrete shape), never the
    error."""
    from tclb_tpu.analysis.footprint import check_footprint
    m = get_model("d3q19_adj")
    fs = check_footprint(m, shape=(8, 16, 128))
    assert "footprint.adjoint_band" not in _error_checks(fs)
    info = [f for f in fs if f.check == "footprint.adjoint_chunk"]
    assert info and info[0].details["max_chunk"] >= 1
    assert "k" in info[0].details and "bz" in info[0].details


def test_resources_fire_on_vmem_overflow():
    d = ModelDef("fx_vmem", ndim=2)
    for i in range(120):
        d.add_density(f"a[{i}]", group="a")
    run = _passthrough(["a"])
    m = d.finalize().bind(run=run, init=run)
    from tclb_tpu.analysis.resources import check_resources
    checks = {f.check for f in check_resources(m, shape=(512, 8192))}
    assert "resources.band_vmem" in checks       # no band height fits
    assert "resources.adjoint_vmem" in checks    # backward scratch > limit
    # overflow is a capability limit (XLA fallback), not broken physics
    assert not _error_checks(check_resources(m, shape=(512, 8192)))


def test_hygiene_fires_on_id_keyed_cache(tmp_path):
    p = tmp_path / "engine.py"
    p.write_text("CACHE = {}\n"
                 "def supports_x(model):\n"
                 "    CACHE[id(model)] = True\n"
                 "    return True\n")
    fs = hygiene.scan_id_keyed_caches(paths=[str(p)])
    assert [f.check for f in fs] == ["hygiene.id_keyed_cache"]
    assert fs[0].severity == "error"


def test_hygiene_fires_on_unbounded_adjoint(tmp_path):
    p = tmp_path / "naive.py"
    p.write_text(
        "import jax\n"
        "from jax import lax\n"
        "def make_naive_gradient(step, niter):\n"
        "    def loss(theta, state):\n"
        "        def body(c, _):\n"
        "            return step(theta, c), None\n"
        "        out, _ = lax.scan(body, state, None, length=niter)\n"
        "        return out.sum()\n"
        "    return jax.value_and_grad(loss)\n")
    fs = hygiene.scan_unbounded_adjoint(paths=[str(p)])
    assert [f.check for f in fs] == ["hygiene.unbounded_adjoint"]
    assert fs[0].severity == "error"
    assert "make_naive_gradient" in fs[0].message


def test_hygiene_unbounded_adjoint_accepts_budgeted(tmp_path):
    # a levels= budget (nested remat) or a snapshots= budget (revolve)
    # in scope makes the same shape legitimate
    p = tmp_path / "budgeted.py"
    p.write_text(
        "import jax\n"
        "from jax import lax\n"
        "def make_grad(step, niter, levels=2):\n"
        "    def loss(theta, state):\n"
        "        out, _ = lax.scan(lambda c, _: (step(theta, c), None),\n"
        "                          state, None, length=niter)\n"
        "        return out.sum()\n"
        "    return jax.value_and_grad(loss)\n"
        "def make_revolve(step, niter, snapshots):\n"
        "    def loss(theta, state):\n"
        "        out, _ = lax.scan(lambda c, _: (step(theta, c), None),\n"
        "                          state, None, length=niter)\n"
        "        return out.sum()\n"
        "    return jax.vjp(loss)\n")
    assert hygiene.scan_unbounded_adjoint(paths=[str(p)]) == []


def test_hygiene_fires_on_dead_entry_point(tmp_path):
    eng = tmp_path / "ops"
    eng.mkdir()
    (eng / "fake_engine.py").write_text(
        "def supports_foo(model):\n"
        "    return True\n"
        "def make_foo_iterate(model):\n"
        "    assert supports_foo(model)\n"
        "    return model\n"
        "def make_bar_iterate(model):\n"
        "    return model\n")
    user = tmp_path / "user.py"
    user.write_text("from ops import fake_engine\n"
                    "fake_engine.make_bar_iterate(None)\n")
    fs = hygiene.scan_dead_entry_points(engine_dir=str(eng),
                                        sources=[str(user)])
    dead = {f.message.split(" ")[0] for f in fs}
    # the dead builder's internal call must NOT keep its dead eligibility
    # check alive (liveness fixpoint) — both die; the referenced one lives
    assert dead == {"ops.fake_engine.supports_foo",
                    "ops.fake_engine.make_foo_iterate"}


@pytest.mark.parametrize("attr", ["_fast_name", "_tail_name"])
def test_hygiene_fires_on_untraced_dispatch(tmp_path, attr):
    p = tmp_path / "lattice.py"
    p.write_text(
        "from tclb_tpu import telemetry\n"
        "class Lattice:\n"
        "    def _fast_path(self):\n"
        "        self._fast_iter = object()   # no engine_selected\n"
        "    def _iterate_impl(self, n):\n"
        "        try:\n"
        "            self._fast_iter(n)\n"
        "        except Exception:\n"
        f"            self.{attr} = None   # silent demotion\n")
    fs = hygiene.scan_dispatch_telemetry(lattice_path=str(p))
    checks = [f.check for f in fs]
    assert checks == ["hygiene.untraced_dispatch"] * 2
    assert all(f.severity == "error" for f in fs)
    assert any("engine_selected" in f.message for f in fs)
    assert any("engine_fallback" in f.message for f in fs)

    # adding the emissions clears both findings
    p.write_text(
        "from tclb_tpu import telemetry\n"
        "class Lattice:\n"
        "    def _fast_path(self):\n"
        "        self._fast_iter = object()\n"
        "        telemetry.engine_selected('xla')\n"
        "    def _iterate_impl(self, n):\n"
        "        try:\n"
        "            self._fast_iter(n)\n"
        "        except Exception as e:\n"
        f"            self.{attr} = None\n"
        "            telemetry.engine_fallback('pallas', 'xla', repr(e))\n")
    assert hygiene.scan_dispatch_telemetry(lattice_path=str(p)) == []


def test_hygiene_fires_on_unrestorable_handler(tmp_path):
    p = tmp_path / "handlers.py"
    p.write_text(
        "class Handler:\n"
        "    pass\n"
        "class cbLeaky(Handler):\n"
        "    def do_it(self):\n"
        "        self.count = self.count + 1\n"
        "        self.old['x'] = 1.0\n"
        "        self._scratch = 2   # private: not flagged\n"
        "        return 0\n"
        "class cbIndirect(cbLeaky):\n"
        "    def do_it(self):\n"
        "        self.score += 1\n"
        "class cbExempt(Handler):\n"
        "    checkpoint_exempt = True\n"
        "    def do_it(self):\n"
        "        self.count = 1\n"
        "class cbCovered(Handler):\n"
        "    def do_it(self):\n"
        "        self.count = 1\n"
        "    def restorable_state(self):\n"
        "        return {'count': self.count}\n"
        "class NotAHandler:\n"
        "    def do_it(self):\n"
        "        self.count = 1\n")
    fs = hygiene.scan_unrestorable_handlers(paths=[str(p)])
    assert all(f.check == "hygiene.unrestorable_handler" for f in fs)
    assert all(f.severity == "error" for f in fs)
    flagged = {f.message.split(" ")[1].split(".")[0] for f in fs}
    assert flagged == {"cbLeaky", "cbIndirect"}
    leaky = next(f for f in fs if "cbLeaky" in f.message)
    assert "self.count" in leaky.message and "self.old" in leaky.message
    assert "_scratch" not in leaky.message

    # implementing the protocol clears the finding
    p.write_text(
        "class Handler:\n"
        "    pass\n"
        "class cbLeaky(Handler):\n"
        "    def do_it(self):\n"
        "        self.count += 1\n"
        "        return 0\n"
        "    def restorable_state(self):\n"
        "        return {'count': self.count}\n"
        "    def restore_state(self, state):\n"
        "        self.count = state['count']\n")
    assert hygiene.scan_unrestorable_handlers(paths=[str(p)]) == []


def test_hygiene_fires_on_unpinned_device_put(tmp_path):
    """serve/ staging must name its target device: a bare device_put
    commits to jax.devices()[0] and funnels every fleet lane onto one
    device — invisible on single-device test runs, fatal on a pod."""
    p = tmp_path / "staging.py"
    p.write_text(
        "import jax\n"
        "from jax import device_put\n"
        "def stage_bad(x):\n"
        "    return jax.device_put(x)\n"            # flagged: no target
        "def stage_bare_bad(x):\n"
        "    return device_put(x)\n"                # flagged: bare alias
        "def stage_dev(x, dev):\n"
        "    return jax.device_put(x, dev)\n"       # positional target ok
        "def stage_kw(x, dev):\n"
        "    return jax.device_put(x, device=dev)\n"
        "def stage_sharded(x, s):\n"
        "    return jax.device_put(x, sharding=s)\n")
    fs = hygiene.scan_unpinned_device_put(paths=[str(p)])
    assert [f.check for f in fs] == ["hygiene.unpinned_device_put"] * 2
    assert all(f.severity == "error" for f in fs)
    locs = sorted(f.message.split(" ")[0] for f in fs)
    assert locs[0].endswith("staging.py:4"), locs
    assert locs[1].endswith("staging.py:6"), locs

    # the shipped serve/ package itself must be clean (also covered by
    # test_repo_hygiene_clean via check_repo, but assert it directly so
    # a future wiring regression cannot hide the check)
    assert hygiene.scan_unpinned_device_put() == []


def test_hygiene_fires_on_device_work_in_monitor(tmp_path):
    """The HTTP monitor must be structurally jax-free: a handler thread
    that calls into jax (or touches a Lattice) can deadlock against the
    solve loop's dispatch mid-scrape."""
    p = tmp_path / "http.py"
    p.write_text(
        "import jax\n"                              # flagged: import
        "from jax import device_put\n"              # flagged: import fn
        "from tclb_tpu.core.lattice import Lattice\n"  # flagged: Lattice
        "def scrape(x):\n"
        "    jax.block_until_ready(x)\n"       # flagged: jax.attr + call
        "    return device_put(x)\n"                # flagged: call
        "def fine():\n"
        "    return {'ok': True}\n")
    fs = hygiene.scan_device_work_in_monitor(paths=[str(p)])
    assert fs, "expected findings on the poisoned monitor module"
    assert all(f.check == "hygiene.device_work_in_monitor" for f in fs)
    assert all(f.severity == "error" for f in fs)
    joined = " ".join(f.message for f in fs)
    assert "imports jax" in joined
    assert "device_put" in joined
    assert "Lattice" in joined
    assert "block_until_ready" in joined

    # a clean snapshot-reading module passes
    q = tmp_path / "clean.py"
    q.write_text(
        "from tclb_tpu.telemetry import live\n"
        "def scrape():\n"
        "    return live.status_snapshot()\n")
    assert hygiene.scan_device_work_in_monitor(paths=[str(q)]) == []

    # the shipped monitor module itself must be clean
    assert hygiene.scan_device_work_in_monitor() == []


# --------------------------------------------------------------------------- #
# Finding mechanics / fingerprints
# --------------------------------------------------------------------------- #


def test_finding_sorting_and_severity():
    fs = [Finding("c.z", "info", "m", "zz"),
          Finding("a.x", "error", "m", "xx"),
          Finding("b.y", "warning", "m", "yy")]
    assert [f.severity for f in sort_findings(fs)] \
        == ["error", "warning", "info"]
    assert worst_severity(fs) == "error"
    assert worst_severity([]) is None
    with pytest.raises(ValueError):
        Finding("a", "fatal", "m", "bad severity")
    d = fs[1].to_dict()
    assert d["check"] == "a.x" and d["severity"] == "error"


def test_fingerprint_stable_across_rebuilds():
    """Structural fingerprints survive rebuilds (the supports_diff cache
    keys on them — id() would miss rebuilt models and alias recycled
    addresses)."""
    import tclb_tpu.models.wave2d as wave2d
    a, b = wave2d.build(), wave2d.build()
    assert a is not b
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != get_model("d2q9").fingerprint


_BF16_KERNEL_HEADER = (
    "import jax.numpy as jnp\n"
    "STORAGE_DTYPES = (jnp.float32, jnp.bfloat16)\n")


def test_precision_fires_on_unsafe_bf16_accumulation(tmp_path):
    """A kernel in a bf16-storage engine that reduces or accumulates
    raw field loads (no .astype widening) is a silent-precision-loss
    bug — the ladder's contract is narrow storage, f32 arithmetic."""
    from tclb_tpu.analysis.precision import scan_unsafe_accum
    p = tmp_path / "pallas_bad.py"
    p.write_text(_BF16_KERNEL_HEADER +
                 "def kernel(scrf, out_ref):\n"
                 "    work = [scrf[0, k] for k in range(9)]\n"
                 "    rho = jnp.sum(jnp.stack(work), 0)\n"
                 "    acc = work[0]\n"
                 "    acc = acc + work[1]\n"
                 "    out_ref[0] = acc + rho\n")
    fs = scan_unsafe_accum(paths=[str(p)])
    assert [f.check for f in fs] == ["precision.unsafe_accum"] * 2
    assert all(f.severity == "error" for f in fs)


def test_precision_accepts_widened_accumulation(tmp_path):
    from tclb_tpu.analysis.precision import scan_unsafe_accum
    p = tmp_path / "pallas_good.py"
    p.write_text(_BF16_KERNEL_HEADER +
                 "def kernel(scrf, out_ref):\n"
                 "    work = [scrf[0, k].astype(jnp.float32)"
                 " for k in range(9)]\n"
                 "    rho = jnp.sum(jnp.stack(work), 0)\n"
                 "    acc = work[0]\n"
                 "    acc = acc + work[1]\n"
                 "    out_ref[0] = (acc + rho).astype(out_ref.dtype)\n")
    assert scan_unsafe_accum(paths=[str(p)]) == []


def test_precision_skips_f32_only_engines(tmp_path):
    """Engines that never take narrow storage (no bf16 in
    STORAGE_DTYPES) may accumulate in their native dtype freely."""
    from tclb_tpu.analysis.precision import scan_unsafe_accum
    p = tmp_path / "pallas_f32.py"
    p.write_text("import jax.numpy as jnp\n"
                 "def kernel(scrf, out_ref):\n"
                 "    rho = jnp.sum(scrf[0], 0)\n"
                 "    out_ref[0] = rho\n")
    assert scan_unsafe_accum(paths=[str(p)]) == []


def test_unshifted_cast_fires_on_bare_astype_seams(tmp_path):
    """A narrowed-capable kernel casting field planes with a bare
    .astype bypasses the shared DDF-shift helpers: the widen would read
    the stored deviation f_i - w_i as if it were f_i (and the narrow
    would store an unshifted plane into a shifted stack) — silent wrong
    physics the unshifted_cast check makes static."""
    from tclb_tpu.analysis.precision import scan_unshifted_cast
    p = tmp_path / "pallas_bad_cast.py"
    p.write_text(_BF16_KERNEL_HEADER +
                 "def kernel(scrf, out_ref, cdtype, dtype):\n"
                 "    work = [scrf[0, k].astype(cdtype)"
                 " for k in range(9)]\n"
                 "    out_ref[0] = work[0].astype(dtype)\n")
    fs = scan_unshifted_cast(paths=[str(p)])
    assert [f.check for f in fs] == ["precision.unshifted_cast"] * 2
    assert all(f.severity == "error" for f in fs)


def test_unshifted_cast_accepts_helper_seams(tmp_path):
    from tclb_tpu.analysis.precision import scan_unshifted_cast
    p = tmp_path / "pallas_good_cast.py"
    p.write_text(_BF16_KERNEL_HEADER +
                 "from tclb_tpu.core import shift as ddf\n"
                 "def kernel(scrf, out_ref, cdtype, dtype, w):\n"
                 "    work = [ddf.widen_plane(scrf[0, k], cdtype, w)"
                 " for k in range(9)]\n"
                 "    out_ref[0] = ddf.narrow_plane(work[0], dtype, w)\n")
    assert scan_unshifted_cast(paths=[str(p)]) == []


def test_unshifted_cast_skips_f32_only_engines(tmp_path):
    from tclb_tpu.analysis.precision import scan_unshifted_cast
    p = tmp_path / "pallas_f32_cast.py"
    p.write_text("import jax.numpy as jnp\n"
                 "def kernel(scrf, out_ref):\n"
                 "    out_ref[0] = scrf[0].astype(jnp.float32)\n")
    assert scan_unshifted_cast(paths=[str(p)]) == []


def test_unshifted_cast_clean_on_repo():
    """The real engine modules route every field-plane cast through the
    shared helpers (this is the check_repo wiring the CI gate runs)."""
    from tclb_tpu.analysis.precision import scan_unshifted_cast
    assert scan_unshifted_cast() == []


def test_hygiene_fires_on_unpoliced_retry(tmp_path):
    bad = tmp_path / "worker.py"
    bad.write_text(
        "import time\n"
        "def fetch(url):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return download(url)\n"
        "        except OSError:\n"
        "            time.sleep(0.5)\n"
        "    raise RuntimeError\n")
    found = hygiene.scan_unpoliced_retry([str(bad)])
    assert [f.check for f in found] == ["hygiene.unpoliced_retry"]
    assert found[0].severity == "error"
    assert "RetryPolicy" in found[0].message
    # the blessed shape: the same loop driven by RetryPolicy.next_delay
    good = tmp_path / "policed.py"
    good.write_text(
        "import time\n"
        "def fetch(url, retry_policy):\n"
        "    for attempt in range(retry_policy.max_attempts):\n"
        "        try:\n"
        "            return download(url)\n"
        "        except OSError:\n"
        "            delay = retry_policy.next_delay(attempt,\n"
        "                                            deadline=None,\n"
        "                                            key=url)\n"
        "            if delay is None:\n"
        "                raise\n"
        "            time.sleep(delay)\n")
    assert hygiene.scan_unpoliced_retry([str(good)]) == []
    # the shipped serve/ + gateway/ tree is clean, and the repo-wide
    # sweep chains the scan
    assert hygiene.scan_unpoliced_retry() == []
    import inspect
    assert "scan_unpoliced_retry" in inspect.getsource(hygiene.check_repo)

def test_hygiene_fires_on_unsupervised_subprocess(tmp_path):
    """subprocess.Popen / os.fork in the serving stack outside
    serve/pool.py is an orphan factory — no watchdog, no escalation, no
    requeue — and must be flagged; the pool module itself is the one
    sanctioned spawner."""
    bad = tmp_path / "dispatcher.py"
    bad.write_text(
        "import os\n"
        "import subprocess\n"
        "from subprocess import Popen\n"
        "def launch(cmd):\n"
        "    subprocess.Popen(cmd)\n"
        "    subprocess.run(cmd)\n"
        "    Popen(cmd)\n"
        "    if os.fork() == 0:\n"
        "        pass\n")
    found = hygiene.scan_unsupervised_subprocess([str(bad)])
    assert {f.check for f in found} == {"hygiene.unsupervised_subprocess"}
    assert all(f.severity == "error" for f in found)
    assert len(found) >= 4                      # import alias + 4 calls
    assert "WorkerPool" in found[0].message
    # the sanctioned spawner is exempt by location, not content
    pooldir = tmp_path / "serve"
    pooldir.mkdir()
    pool = pooldir / "pool.py"
    pool.write_text("import subprocess\n"
                    "def spawn(cmd):\n"
                    "    return subprocess.Popen(cmd)\n")
    assert hygiene.scan_unsupervised_subprocess([str(pool)]) == []
    # non-spawning subprocess names stay legal
    ok = tmp_path / "types.py"
    ok.write_text("import subprocess\n"
                  "def is_timeout(e):\n"
                  "    return isinstance(e, subprocess.TimeoutExpired)\n")
    assert hygiene.scan_unsupervised_subprocess([str(ok)]) == []
    # the shipped serve/ + gateway/ tree is clean, and the repo-wide
    # sweep chains the scan
    assert hygiene.scan_unsupervised_subprocess() == []
    import inspect
    assert "scan_unsupervised_subprocess" \
        in inspect.getsource(hygiene.check_repo)


# --------------------------------------------------------------------------- #
# Concurrency: lock-discipline checks
# --------------------------------------------------------------------------- #


def _fresh_concurrency():
    from tclb_tpu.analysis import concurrency
    concurrency._analysis_cache.clear()
    return concurrency


def test_concurrency_fires_on_unguarded_shared_state(tmp_path):
    con = _fresh_concurrency()
    p = tmp_path / "svc.py"
    p.write_text(
        "import threading\n"
        "class Svc:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "    def start(self):\n"
        "        t = threading.Thread(target=self._loop)\n"
        "        t.start()\n"
        "    def _loop(self):\n"
        "        while True:\n"
        "            print(self.count)\n"
        "    def bump(self):\n"
        "        self.count += 1\n")
    fs = con.scan_unguarded_shared_state(paths=[str(p)])
    assert [f.check for f in fs] == ["concurrency.unguarded_shared_state"]
    assert fs[0].severity == "error"
    assert "count" in fs[0].message
    assert sorted(fs[0].details["entries"]) == ["api", "thread:_loop"]
    # the same write under the lock is clean
    q = tmp_path / "svc_ok.py"
    q.write_text(p.read_text().replace(
        "    def bump(self):\n        self.count += 1\n",
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.count += 1\n"))
    _fresh_concurrency()
    assert con.scan_unguarded_shared_state(paths=[str(q)]) == []


def test_concurrency_unguarded_waiver_clears_finding(tmp_path):
    con = _fresh_concurrency()
    p = tmp_path / "svc.py"
    p.write_text(
        "import threading\n"
        "class Svc:\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop).start()\n"
        "    def _loop(self):\n"
        "        print(self.flag)\n"
        "    def stop(self):\n"
        "        # concurrency-ok[unguarded]: single boolean latch, one\n"
        "        # writer; worst case the loop sees it a tick late\n"
        "        self.flag = True\n")
    assert con.scan_unguarded_shared_state(paths=[str(p)]) == []
    # a waiver without a justification does not count
    q = tmp_path / "svc_bare.py"
    q.write_text(p.read_text().replace(
        "        # concurrency-ok[unguarded]: single boolean latch, one\n"
        "        # writer; worst case the loop sees it a tick late\n",
        "        # concurrency-ok[unguarded]:\n"))
    _fresh_concurrency()
    fs = con.scan_unguarded_shared_state(paths=[str(q)])
    assert [f.check for f in fs] == ["concurrency.unguarded_shared_state"]


def test_concurrency_fires_on_lock_order_cycle(tmp_path):
    con = _fresh_concurrency()
    p = tmp_path / "deadlock.py"
    p.write_text(
        "import threading\n"
        "class Pair:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def forward(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def backward(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n")
    fs = con.scan_lock_order_cycles(paths=[str(p)])
    assert [f.check for f in fs] == ["concurrency.lock_order_cycle"]
    assert fs[0].severity == "error"
    assert any("_a" in n for n in fs[0].details["cycle"])
    assert any("_b" in n for n in fs[0].details["cycle"])
    # one consistent order is clean
    q = tmp_path / "ordered.py"
    q.write_text(p.read_text().replace(
        "        with self._b:\n            with self._a:\n",
        "        with self._a:\n            with self._b:\n"))
    _fresh_concurrency()
    assert con.scan_lock_order_cycles(paths=[str(q)]) == []


def test_concurrency_lock_order_cycle_through_calls(tmp_path):
    """The inversion hides behind a method call: f holds A and calls g,
    which takes B; h does the reverse.  Only the transitive (may-
    acquire) propagation sees the cycle."""
    con = _fresh_concurrency()
    p = tmp_path / "indirect.py"
    p.write_text(
        "import threading\n"
        "class Pair:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def f(self):\n"
        "        with self._a:\n"
        "            self.take_b()\n"
        "    def take_b(self):\n"
        "        with self._b:\n"
        "            pass\n"
        "    def h(self):\n"
        "        with self._b:\n"
        "            self.take_a()\n"
        "    def take_a(self):\n"
        "        with self._a:\n"
        "            pass\n")
    fs = con.scan_lock_order_cycles(paths=[str(p)])
    assert [f.check for f in fs] == ["concurrency.lock_order_cycle"]


def test_concurrency_fires_on_blocking_under_lock(tmp_path):
    con = _fresh_concurrency()
    p = tmp_path / "slow.py"
    p.write_text(
        "import threading\n"
        "import time\n"
        "import os\n"
        "class Slow:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def nap(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1.0)\n"
        "    def sync(self, fh):\n"
        "        with self._lock:\n"
        "            os.fsync(fh.fileno())\n"
        "    def fine(self):\n"
        "        time.sleep(1.0)\n")
    fs = con.scan_blocking_under_lock(paths=[str(p)])
    assert {f.check for f in fs} == {"concurrency.blocking_under_lock"}
    assert len(fs) == 2                          # nap + sync; fine is clean
    assert all(f.severity == "error" for f in fs)
    assert any("time.sleep" in f.message for f in fs)
    assert any("fsync" in f.message for f in fs)
    # waiver clears the site
    q = tmp_path / "slow_ok.py"
    q.write_text(p.read_text().replace(
        "            time.sleep(1.0)\n    def sync",
        "            # concurrency-ok[blocking]: test fixture says so\n"
        "            time.sleep(1.0)\n    def sync").replace(
        "            os.fsync(fh.fileno())\n",
        "            # concurrency-ok[blocking]: test fixture says so\n"
        "            os.fsync(fh.fileno())\n"))
    _fresh_concurrency()
    assert con.scan_blocking_under_lock(paths=[str(q)]) == []


def test_concurrency_condition_wait_is_not_blocking(tmp_path):
    """Condition.wait releases the lock it waits on — the one
    legitimate 'blocking while holding' pattern (the scheduler's
    _take_batch uses it)."""
    con = _fresh_concurrency()
    p = tmp_path / "cond.py"
    p.write_text(
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._admit = threading.RLock()\n"
        "        self._avail = threading.Condition(self._admit)\n"
        "    def take(self):\n"
        "        with self._avail:\n"
        "            self._avail.wait(timeout=0.1)\n")
    assert con.scan_blocking_under_lock(paths=[str(p)]) == []


def test_concurrency_fires_on_signal_unsafe(tmp_path):
    con = _fresh_concurrency()
    p = tmp_path / "sig.py"
    p.write_text(
        "import signal\n"
        "import threading\n"
        "_lock = threading.Lock()\n"
        "def _on_term(signum, frame):\n"
        "    with _lock:\n"
        "        pass\n"
        "def install():\n"
        "    signal.signal(signal.SIGTERM, _on_term)\n")
    fs = con.scan_signal_unsafe(paths=[str(p)])
    assert [f.check for f in fs] == ["concurrency.signal_unsafe"]
    assert fs[0].severity == "error"
    assert "_lock" in fs[0].message
    # an RLock is reentrant: the interrupted main thread can re-acquire
    q = tmp_path / "sig_ok.py"
    q.write_text(p.read_text().replace("threading.Lock()",
                                       "threading.RLock()"))
    _fresh_concurrency()
    assert con.scan_signal_unsafe(paths=[str(q)]) == []


def test_concurrency_signal_unsafe_through_drain_hook(tmp_path):
    """Drain hooks run inside the SIGTERM handler — a hook that grabs a
    plain Lock one call deep is as unsafe as the handler doing it."""
    con = _fresh_concurrency()
    p = tmp_path / "hook.py"
    p.write_text(
        "import threading\n"
        "from tclb_tpu.telemetry.live import register_drain_hook\n"
        "_state = threading.Lock()\n"
        "def _drain(reason):\n"
        "    _cleanup()\n"
        "def _cleanup():\n"
        "    with _state:\n"
        "        pass\n"
        "def install():\n"
        "    register_drain_hook('fixture', _drain)\n")
    fs = con.scan_signal_unsafe(paths=[str(p)])
    assert [f.check for f in fs] == ["concurrency.signal_unsafe"]
    assert "_state" in fs[0].message


def test_concurrency_shipped_tree_clean_and_wired():
    """The real serving planes carry zero unwaived findings (every
    waiver in-tree has a justification), and check_repo chains the
    concurrency pass into the CI gate."""
    con = _fresh_concurrency()
    fs = con.check_concurrency()
    assert fs == [], [f.message for f in fs]
    import inspect
    assert "check_concurrency" in inspect.getsource(hygiene.check_repo)


def test_concurrency_static_graph_matches_design():
    """The store two-lock split and the scheduler admission path give
    exactly the documented acyclic order edges."""
    con = _fresh_concurrency()
    g = con.lock_order_graph()
    assert "gateway.store.JobStore._lock" in \
        g.get("gateway.store.JobStore._io_lock", set())
    # the reverse edge must never appear: it would close the cycle
    assert "gateway.store.JobStore._io_lock" not in \
        g.get("gateway.store.JobStore._lock", set())
    assert "serve.scheduler.Scheduler._lock" in \
        g.get("serve.scheduler.Scheduler._admit", set())


def test_cli_check_filter_and_codes(capsys):
    rc = cli.main(["--check", "concurrency", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["models"] == {}                   # model analysis skipped
    assert all(f["code"].startswith("concurrency.")
               for f in doc["repo"])
    # family prefix + exact id both parse; unknown names just match
    # nothing (still exit 0 on a clean tree)
    rc = cli.main(["--check",
                   "concurrency.lock_order_cycle,hygiene.id_keyed_cache",
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0


def test_cli_changed_mode_runs(capsys):
    # smoke: --changed must run the repo gate and exit cleanly whatever
    # the work-tree state (the filter can only *hide* findings)
    rc = cli.main(["--changed", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc in (0, 1)
    assert set(doc) == {"models", "repo", "summary"}
