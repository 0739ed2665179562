"""Registry/repo hygiene: dead engine entry points, ``id()``-keyed
caches, unbound stages, and model test/golden inventory.

The round-5 advisor found two instances of the same disease — an engine
entry point (``pallas_generic.supports_resident``/``make_resident_iterate``)
that no dispatch arm ever calls, and an eligibility cache keyed on
``id(model)`` (stale verdicts on recycled addresses, useless re-probes on
rebuilt models).  Both are statically detectable, so this module detects
them for good:

* **dead entry points** — every public ``make_*``/``supports*`` function
  in ``tclb_tpu/ops`` must be reachable: referenced from another module
  (qualified ``module.fn`` or ``from module import fn``) or from a LIVE
  function in its own module.  The liveness fixpoint matters: a dead
  builder calling its own dead eligibility check must not keep either
  alive.
* **id()-keyed caches** — any ``id(...)`` call in package source is
  flagged (the package has no legitimate use; dict keys were the only
  historical one).
"""

from __future__ import annotations

import ast
import os

from tclb_tpu.analysis.findings import Finding
from tclb_tpu.core.registry import Model

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)


def _py_files(root: str) -> list:
    out = []
    for dirpath, _dirnames, filenames in os.walk(root):
        out += [os.path.join(dirpath, f) for f in filenames
                if f.endswith(".py")]
    return sorted(out)


def _default_sources() -> list:
    srcs = _py_files(_PKG_ROOT)
    tests = os.path.join(_REPO_ROOT, "tests")
    if os.path.isdir(tests):
        srcs += _py_files(tests)
    return srcs


def _module_name(path: str, root: str) -> str:
    ap = os.path.abspath(path)
    base = os.path.dirname(os.path.abspath(root))
    if not ap.startswith(base + os.sep):
        # out-of-tree sources (the detector's own tests scan tmp dirs):
        # name relative to the grandparent, so ``<tmp>/ops/eng.py``
        # becomes ``ops.eng`` — matching how its scanned users import it
        base = os.path.dirname(os.path.dirname(ap))
    rel = os.path.relpath(ap, base)
    mod = rel[:-3].replace(os.sep, ".")
    return mod[:-len(".__init__")] if mod.endswith(".__init__") else mod


def _resolve_from(module, level: int, here: str) -> str:
    """Resolve a (possibly relative) ``from ... import`` module path."""
    if level == 0:
        return module or ""
    parts = here.split(".")[:-level]
    return ".".join(parts + ([module] if module else []))


def scan_id_keyed_caches(paths=None) -> list:
    """Flag every call of the builtin ``id`` in the given sources."""
    findings = []
    for path in (paths if paths is not None
                 else _py_files(_PKG_ROOT)):
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except SyntaxError as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "id":
                rel = os.path.relpath(path, _REPO_ROOT)
                findings.append(Finding(
                    "hygiene.id_keyed_cache", "error", "",
                    f"{rel}:{node.lineno} uses id(...) — object-identity "
                    "keys alias recycled addresses and miss structurally "
                    "identical rebuilds; key on Model.fingerprint "
                    "instead", f"{rel}:{node.lineno}"))
    return findings


def _file_refs(tree, modname: str):
    """(qualified_refs, own_module_uses) for one parsed file.

    ``qualified_refs``: set of (module, attr) — ``mod.fn`` attribute
    accesses through import aliases plus direct ``from mod import fn``.
    ``own_module_uses``: {name: set of enclosing top-level function names
    (or "" for module level)} for bare Name loads."""
    aliases: dict = {}
    refs: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
        elif isinstance(node, ast.ImportFrom):
            base = _resolve_from(node.module, node.level, modname)
            for a in node.names:
                refs.add((base, a.name))
                aliases[a.asname or a.name] = (base + "." + a.name
                                               if base else a.name)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases:
            refs.add((aliases[node.value.id], node.attr))

    own: dict = {}

    def collect_names(node, scope: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = scope if scope else child.name
                for dec in child.decorator_list:
                    for n in ast.walk(dec):
                        if isinstance(n, ast.Name):
                            own.setdefault(n.id, set()).add(scope)
                collect_names(child, inner)
            elif isinstance(child, ast.Name) \
                    and isinstance(child.ctx, ast.Load):
                own.setdefault(child.id, set()).add(scope)
                collect_names(child, scope)
            else:
                if isinstance(child, ast.Name):
                    own.setdefault(child.id, set()).add(scope)
                collect_names(child, scope)
    collect_names(tree, "")
    return refs, own


_HORIZON_CALLS = {"scan", "nested_checkpoint_scan", "make_objective_run",
                  "fori_loop", "while_loop"}
_REVERSE_CALLS = {"grad", "value_and_grad", "vjp"}
_POLICY_NAMES = {"levels", "segment", "segments", "revolve_schedule",
                 "schedule", "checkpoint", "remat", "snapshots"}


def _call_name(call: ast.Call):
    fn = call.func
    return (fn.attr if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name) else None)


def _horizon_inside(fnode, defs, _seen=None) -> bool:
    """True if ``fnode`` (a def or lambda) contains a horizon loop,
    following calls to sibling nested defs (one level of resolution is
    enough for the closure-factory idiom used throughout adjoint/)."""
    if _seen is None:
        _seen = set()
    if fnode in _seen:
        return False
    _seen.add(fnode)
    for sub in ast.walk(fnode):
        if isinstance(sub, ast.Call):
            name = _call_name(sub)
            if name in _HORIZON_CALLS:
                return True
            if name in defs and _horizon_inside(defs[name], defs, _seen):
                return True
    return False


def scan_unbounded_adjoint(paths=None) -> list:
    """Flag reverse-mode entry points in ``adjoint/`` that differentiate
    a full-horizon loop with NO checkpoint policy in scope.

    A function that takes ``jax.grad``/``value_and_grad``/``vjp`` of a
    program containing a horizon loop (``lax.scan``/``fori_loop``/
    ``make_objective_run``/...) stores O(T) residuals — at production
    horizons that is an OOM wired into the API, invisible until someone
    raises ``niter``.  Every such entry must show its policy in the same
    function: a ``levels`` remat depth (nested checkpoint scan), a
    ``segment``/spill tier, ``jax.checkpoint``/``remat``, or a revolve
    ``schedule``/``snapshots`` budget.

    A horizon loop that merely COEXISTS with a reverse call is fine —
    the fixed-point adjoint iterates a Neumann series around the VJP of
    one step without ever differentiating through the loop.  The loop
    must sit inside the function handed to the reverse-mode call (the
    differentiated region) to count."""
    if paths is None:
        paths = _py_files(os.path.join(_PKG_ROOT, "adjoint"))
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except SyntaxError as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            has_horizon = has_policy = False
            diffs_horizon = False
            defs = {d.name: d for d in ast.walk(node)
                    if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and d is not node}
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    name = _call_name(sub)
                    if name in _HORIZON_CALLS:
                        has_horizon = True
                    if name in ("checkpoint", "remat"):
                        has_policy = True
                    for kw in sub.keywords:
                        if kw.arg in _POLICY_NAMES:
                            has_policy = True
                if isinstance(sub, ast.Name) and sub.id in _POLICY_NAMES:
                    has_policy = True
                if isinstance(sub, ast.arg) and sub.arg in _POLICY_NAMES:
                    has_policy = True
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Call)
                        and _call_name(sub) in _REVERSE_CALLS
                        and sub.args):
                    continue
                target = sub.args[0]
                if isinstance(target, ast.Lambda):
                    diffs_horizon |= _horizon_inside(target, defs)
                elif isinstance(target, ast.Name) and target.id in defs:
                    diffs_horizon |= _horizon_inside(defs[target.id], defs)
                elif isinstance(target, (ast.Name, ast.Attribute, ast.Call)):
                    # unresolvable callable (imported fn, partial, method):
                    # stay conservative — any loop in scope counts
                    diffs_horizon |= has_horizon
                # tuple/constant first arg: that is a returned vjp function
                # being APPLIED to a cotangent, not a differentiation
            if diffs_horizon and not has_policy:
                rel = os.path.relpath(path, _REPO_ROOT)
                findings.append(Finding(
                    "hygiene.unbounded_adjoint", "error", "",
                    f"{rel}:{node.lineno} `{node.name}` differentiates "
                    "a full-horizon loop with no checkpoint policy "
                    "(no levels=/segment=/snapshots= budget, no "
                    "jax.checkpoint/remat, no revolve schedule) — "
                    "reverse-mode residuals grow O(T) and OOM at "
                    "production horizons", f"{rel}:{node.lineno}"))
    return findings


def scan_dead_entry_points(engine_dir=None, sources=None) -> list:
    """Unreachable engine entry points: public ``make_*``/``supports*``
    functions in ``tclb_tpu/ops`` no live code refers to."""
    engine_dir = engine_dir or os.path.join(_PKG_ROOT, "ops")
    sources = sources if sources is not None else _default_sources()

    entry: dict = {}          # (module, fn) -> lineno
    own_uses: dict = {}       # module -> {name: {enclosing fn or ""}}
    all_refs: set = set()     # qualified (module, fn) refs, everywhere
    parsed: dict = {}
    for path in sorted(set(_py_files(engine_dir)) | set(sources)):
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except SyntaxError:
            continue
        modname = _module_name(path, _PKG_ROOT)
        parsed[modname] = path
        refs, own = _file_refs(tree, modname)
        all_refs |= refs
        if os.path.dirname(os.path.abspath(path)) \
                == os.path.abspath(engine_dir):
            own_uses[modname] = own
            for node in ast.iter_child_nodes(tree):
                if isinstance(node, ast.FunctionDef) \
                        and not node.name.startswith("_") \
                        and (node.name.startswith("make_")
                             or node.name.startswith("supports")):
                    entry[(modname, node.name)] = node.lineno

    # liveness fixpoint: externally referenced entry points are live;
    # an own-module use keeps a function live only if it comes from
    # module level or from a function that is not itself a dead entry
    # point.
    dead = {k for k in entry if k not in all_refs}
    changed = True
    while changed:
        changed = False
        for mod, fn in list(dead):
            users = own_uses.get(mod, {}).get(fn, set())
            live_users = {u for u in users
                          if u == "" or (mod, u) not in dead}
            if live_users:
                dead.discard((mod, fn))
                changed = True

    findings = []
    for mod, fn in sorted(dead):
        rel = os.path.relpath(parsed[mod], _REPO_ROOT)
        findings.append(Finding(
            "hygiene.dead_entry_point", "error", "",
            f"{mod}.{fn} ({rel}:{entry[(mod, fn)]}) is an engine entry "
            "point nothing dispatches to — wire it into the Lattice/"
            "adjoint selection or delete it",
            f"{rel}:{entry[(mod, fn)]}"))
    return findings


def _calls_named(node, name: str) -> bool:
    """True if any call under ``node`` targets ``name`` — bare
    (``engine_selected(...)``) or qualified (``telemetry.engine_selected``)."""
    for n in ast.walk(node):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if isinstance(f, ast.Name) and f.id == name:
            return True
        if isinstance(f, ast.Attribute) and f.attr == name:
            return True
    return False


def _assigns_fast_name(node) -> bool:
    """True if any statement under ``node`` assigns ``self._fast_name``
    or ``self._tail_name`` (the engine of a hybrid's trailing step)."""
    for n in ast.walk(node):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and t.attr in ("_fast_name", "_tail_name") \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    return True
    return False


def scan_dispatch_telemetry(lattice_path=None) -> list:
    """Engine dispatch must be observable: ``_fast_path`` emits
    ``engine_selected`` and every except handler that reassigns
    ``self._fast_name`` or ``self._tail_name`` (i.e. demotes an engine)
    emits ``engine_fallback``.  Without these, a production trace cannot say
    which engine ran — the exact blind spot that once made a
    heat_adj regression untriageable."""
    path = lattice_path or os.path.join(_PKG_ROOT, "core", "lattice.py")
    rel = os.path.relpath(path, _REPO_ROOT)
    try:
        with open(path) as fh:
            tree = ast.parse(fh.read())
    except (OSError, SyntaxError) as e:
        return [Finding("hygiene.unparseable", "error", "",
                        f"cannot parse {path}: {e}", path)]

    findings = []
    fast_path = None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_fast_path":
            fast_path = node
            break
    if fast_path is None:
        findings.append(Finding(
            "hygiene.untraced_dispatch", "error", "",
            f"{rel} has no _fast_path — the dispatch tracing contract "
            "expects one", rel))
    elif not _calls_named(fast_path, "engine_selected"):
        findings.append(Finding(
            "hygiene.untraced_dispatch", "error", "",
            f"{rel}:{fast_path.lineno} _fast_path never emits "
            "engine_selected — traces cannot attribute iterate spans to "
            "an engine", f"{rel}:{fast_path.lineno}"))

    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if _assigns_fast_name(node) \
                and not _calls_named(node, "engine_fallback"):
            findings.append(Finding(
                "hygiene.untraced_dispatch", "error", "",
                f"{rel}:{node.lineno} except handler demotes "
                "self._fast_name without emitting engine_fallback — "
                "silent engine downgrades are invisible in traces",
                f"{rel}:{node.lineno}"))
    return findings


def _public_self_attr_writes(fn_node) -> list:
    """``(attr, lineno)`` for every public ``self.<attr>`` the function
    assigns — plain/augmented assignment targets and subscript stores
    (``self.old[name] = ...``)."""
    out = []
    for n in ast.walk(fn_node):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in targets:
                if isinstance(t, ast.Subscript):
                    t = t.value
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self" \
                        and not t.attr.startswith("_"):
                    out.append((t.attr, n.lineno))
    return out


def scan_unrestorable_handlers(paths=None) -> list:
    """Checkpoint completeness: a Handler subclass whose ``do_it`` mutates
    public ``self`` attributes carries run-state that a full-run
    checkpoint must capture — it must implement ``restorable_state`` in
    its own body (or explicitly opt out with ``checkpoint_exempt =
    True``), otherwise a kill-resume silently resets that state and the
    resumed run diverges from the uninterrupted one."""
    if paths is None:
        paths = _py_files(os.path.join(_PKG_ROOT, "control"))
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)

        classes = {n.name: n for n in ast.walk(tree)
                   if isinstance(n, ast.ClassDef)}

        def is_handler(cls, seen=None) -> bool:
            seen = seen or set()
            if cls.name in seen:
                return False
            seen.add(cls.name)
            for b in cls.bases:
                name = b.id if isinstance(b, ast.Name) else \
                    (b.attr if isinstance(b, ast.Attribute) else None)
                if name == "Handler":
                    return True
                if name in classes and is_handler(classes[name], seen):
                    return True
            return False

        for cls in classes.values():
            if cls.name == "Handler" or not is_handler(cls):
                continue
            body_fns = {n.name for n in cls.body
                        if isinstance(n, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))}
            exempt = any(
                isinstance(n, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "checkpoint_exempt"
                        for t in n.targets)
                and isinstance(n.value, ast.Constant) and n.value.value
                for n in cls.body)
            if "restorable_state" in body_fns or exempt:
                continue
            do_it = next((n for n in cls.body
                          if isinstance(n, ast.FunctionDef)
                          and n.name == "do_it"), None)
            if do_it is None:
                continue
            writes = _public_self_attr_writes(do_it)
            if writes:
                attrs = sorted({a for a, _ln in writes})
                findings.append(Finding(
                    "hygiene.unrestorable_handler", "error", "",
                    f"{rel}:{cls.lineno} {cls.name}.do_it mutates "
                    f"self.{', self.'.join(attrs)} but the class neither "
                    "implements restorable_state() nor sets "
                    "checkpoint_exempt = True — this state is lost on "
                    "checkpoint resume", f"{rel}:{cls.lineno}"))
    return findings


_CTX_TAINT_ATTRS = ("setting", "setting_dt")
_HOST_CASTS = ("float", "int", "bool")


def _is_ctx_setting_call(node) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _CTX_TAINT_ATTRS
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "ctx")


def _assigned_names(target) -> list:
    """Names an assignment target binds.  A subscript store taints only
    the container (``out[i] = tainted`` taints ``out``, never the index
    ``i`` — an index is read, not bound)."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        return [x for e in target.elts for x in _assigned_names(e)]
    if isinstance(target, (ast.Subscript, ast.Starred)):
        return _assigned_names(target.value)
    return []


def scan_ensemble_unsafe(paths=None) -> list:
    """Python-level branching/host-casting on per-case setting values in
    model stage code.

    Under the batched ensemble engine every case carries its *own*
    ``SimParams``, so a setting is a traced per-case value — a
    ``float(...)``/``int(...)``/``bool(...)`` cast, an ``.item()`` pull
    or an ``if``-test on anything derived from ``ctx.setting``/
    ``ctx.setting_dt`` freezes one case's value into the compiled
    program (or fails outright under vmap) and silently breaks the
    bit-parity contract for every other case in the batch.  Casts of
    genuine host constants (``float(E[i, 0])`` on a numpy stencil
    table) are fine and not flagged: taint starts at the ctx setting
    accessors and propagates only through assigned names."""
    if paths is None:
        paths = _py_files(os.path.join(_PKG_ROOT, "models"))
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)
        ctx_fns = [n for n in ast.walk(tree)
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and n.args.args and n.args.args[0].arg == "ctx"]
        seen: set = set()
        for fn in ctx_fns:
            # assignment events in source order.  Taint is replayed as a
            # forward flow: a plain Name assignment from a clean RHS
            # CLEARS the name (models reuse short names like ``c`` for
            # both stencil constants and setting-derived arrays), a
            # subscript store only ever adds taint to the container, and
            # an augmented assignment keeps the old value's taint.
            events: list = []
            for n in ast.walk(fn):
                if not isinstance(n, (ast.Assign, ast.AugAssign,
                                      ast.AnnAssign)):
                    continue
                if n.value is None:
                    continue
                targets = (n.targets if isinstance(n, ast.Assign)
                           else [n.target])
                updates = []
                for t in targets:
                    strong = isinstance(n, (ast.Assign, ast.AnnAssign)) \
                        and isinstance(t, (ast.Name, ast.Tuple, ast.List))
                    for name in _assigned_names(t):
                        updates.append((name, strong))
                if updates:
                    events.append((n.lineno, updates, n.value))
            events.sort(key=lambda e: e[0])

            def expr_tainted(e, tset) -> bool:
                for n in ast.walk(e):
                    if _is_ctx_setting_call(n):
                        return True
                    if isinstance(n, ast.Name) \
                            and isinstance(n.ctx, ast.Load) \
                            and n.id in tset:
                        return True
                return False

            def taint_at(lineno: int) -> set:
                tset: set = set()
                for ln, updates, rhs in events:
                    if ln >= lineno:
                        break
                    hot = expr_tainted(rhs, tset)
                    for name, strong in updates:
                        if hot:
                            tset.add(name)
                        elif strong:
                            tset.discard(name)
                return tset

            def flag(lineno: int, what: str) -> None:
                key = (rel, lineno, what)
                if key in seen:
                    return
                seen.add(key)
                findings.append(Finding(
                    "hygiene.ensemble_unsafe", "error", "",
                    f"{rel}:{lineno} {fn.name}: {what} on a "
                    "ctx.setting-derived value — per-case settings are "
                    "traced under the batched ensemble engine; this "
                    "freezes one case's value into the compiled step "
                    "(keep the computation in jax ops instead)",
                    f"{rel}:{lineno}"))

            def is_none_test(e) -> bool:
                # ``x is None`` / ``x is not None`` are host-structural
                # dispatch, not branching on the setting's value
                return isinstance(e, ast.Compare) \
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in e.ops)

            for n in ast.walk(fn):
                if isinstance(n, ast.Call):
                    f = n.func
                    if isinstance(f, ast.Name) and f.id in _HOST_CASTS \
                            and n.args \
                            and expr_tainted(n.args[0], taint_at(n.lineno)):
                        flag(n.lineno, f"host cast {f.id}(...)")
                    elif isinstance(f, ast.Attribute) and f.attr == "item" \
                            and not n.args \
                            and expr_tainted(f.value, taint_at(n.lineno)):
                        flag(n.lineno, ".item() pull")
                elif isinstance(n, (ast.If, ast.While)) \
                        and not is_none_test(n.test) \
                        and expr_tainted(n.test, taint_at(n.lineno)):
                    flag(n.lineno,
                         f"python {type(n).__name__.lower()}-branch")
                elif isinstance(n, ast.IfExp) \
                        and not is_none_test(n.test) \
                        and expr_tainted(n.test, taint_at(n.lineno)):
                    flag(n.lineno, "python conditional expression")
    return findings


def scan_unpinned_device_put(paths=None) -> list:
    """Device-placement hygiene for the serving fleet: every
    ``device_put`` in ``tclb_tpu/serve`` must name an explicit target —
    a second positional argument or a ``device=``/``sharding=`` keyword.

    A bare ``jax.device_put(x)`` commits to ``jax.devices()[0]``, which
    on a fleet lane silently funnels every lane's staging traffic onto
    device 0 — the exact cross-lane contention the dispatcher exists to
    avoid, and invisible in tests that run on one device."""
    if paths is None:
        paths = _py_files(os.path.join(_PKG_ROOT, "serve"))
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else \
                (f.attr if isinstance(f, ast.Attribute) else None)
            if name != "device_put":
                continue
            pinned = len(node.args) >= 2 or any(
                kw.arg in ("device", "sharding") for kw in node.keywords)
            if not pinned:
                findings.append(Finding(
                    "hygiene.unpinned_device_put", "error", "",
                    f"{rel}:{node.lineno} device_put without an explicit "
                    "device/sharding — in serve/ this commits to "
                    "jax.devices()[0] and funnels every fleet lane's "
                    "staging onto device 0; pass the lane's device "
                    "(or a NamedSharding) explicitly",
                    f"{rel}:{node.lineno}"))
    return findings


_MONITOR_BANNED_ROOTS = ("jax", "jaxlib")
_MONITOR_BANNED_CALLS = ("device_put", "block_until_ready", "device_get")
_MONITOR_BANNED_NAMES = ("Lattice",)


def scan_device_work_in_monitor(paths=None) -> list:
    """The HTTP monitor handler thread must never touch device state: a
    scrape that calls into jax (or walks a Lattice) can deadlock against
    the solve loop's dispatch or, worse, enqueue host-to-device work from
    an arbitrary thread mid-iterate.  The contract is structural —
    ``telemetry/http.py`` reads registry/status snapshots only — so this
    check enforces it by AST: no jax/jaxlib import, no
    ``device_put``/``block_until_ready``/``device_get`` call, and no
    ``Lattice`` reference anywhere in the monitor module."""
    if paths is None:
        paths = [os.path.join(_PKG_ROOT, "telemetry", "http.py")]
    return _scan_device_free_module(
        paths, "hygiene.device_work_in_monitor",
        "the monitor handler thread must only read registry/status "
        "snapshots, never touch jax or device state (scrapes racing the "
        "solve loop can deadlock dispatch); move the work behind a "
        "status provider registered from the owning thread")


def scan_device_work_in_gateway(paths=None) -> list:
    """Same contract, serving front door: the gateway's HTTP handler
    module (``gateway/http.py``) must never import jax or reference a
    Lattice — handler threads validate, write store records, and wait on
    plain events only.  Device work belongs to the
    :class:`GatewayService` worker threads, so a slow or hostile client
    can never fence, allocate on, or deadlock a device."""
    if paths is None:
        paths = [os.path.join(_PKG_ROOT, "gateway", "http.py")]
    return _scan_device_free_module(
        paths, "hygiene.device_work_in_gateway",
        "the gateway handler thread must only validate, enqueue job "
        "records and snapshot plain-python state, never touch jax or "
        "device state (a slow client would be holding a device "
        "hostage); move the work onto the GatewayService worker side")


def _scan_device_free_module(paths, check_name: str, contract: str) -> list:
    """Shared AST enforcement for modules whose threads must stay off
    the device: no jax/jaxlib import, no ``device_put``/
    ``block_until_ready``/``device_get`` call, no ``Lattice``
    reference."""
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)

        def flag(lineno: int, what: str) -> None:
            findings.append(Finding(
                check_name, "error", "",
                f"{rel}:{lineno} {what} — {contract}",
                f"{rel}:{lineno}"))

        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    root = a.name.split(".")[0]
                    if root in _MONITOR_BANNED_ROOTS:
                        flag(node.lineno, f"imports {a.name}")
            elif isinstance(node, ast.ImportFrom):
                root = (node.module or "").split(".")[0]
                if root in _MONITOR_BANNED_ROOTS:
                    flag(node.lineno, f"imports from {node.module}")
                for a in node.names:
                    if a.name in _MONITOR_BANNED_CALLS \
                            or a.name in _MONITOR_BANNED_NAMES:
                        flag(node.lineno, f"imports {a.name}")
            elif isinstance(node, ast.Call):
                f = node.func
                name = f.id if isinstance(f, ast.Name) else \
                    (f.attr if isinstance(f, ast.Attribute) else None)
                if name in _MONITOR_BANNED_CALLS:
                    flag(node.lineno, f"calls {name}(...)")
            elif isinstance(node, ast.Name) \
                    and node.id in _MONITOR_BANNED_NAMES:
                flag(node.lineno, f"references {node.id}")
            elif isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in _MONITOR_BANNED_ROOTS:
                flag(node.lineno,
                     f"uses {node.value.id}.{node.attr}")
    return findings


def scan_unpoliced_retry(paths=None) -> list:
    """Retry discipline for the serving stack: a retry loop in
    ``tclb_tpu/serve`` or ``tclb_tpu/gateway`` — a ``for``/``while``
    that catches exceptions and sleeps a *fixed* amount before going
    around again — must run through :class:`serve.retry.RetryPolicy`.

    Hand-rolled fixed-delay retries are exactly what chaos testing
    punishes: no exponential backoff, no jitter (retry stampedes), and
    no deadline awareness, so a retry ladder can outlive the caller's
    submitted ``timeout_s``.  The structural signature is a loop whose
    body contains an ``except`` handler AND a constant-argument
    ``sleep(...)``, inside a function that never references
    ``RetryPolicy``/``retry_policy``."""
    if paths is None:
        paths = (_py_files(os.path.join(_PKG_ROOT, "serve"))
                 + _py_files(os.path.join(_PKG_ROOT, "gateway"))
                 + _py_files(os.path.join(_PKG_ROOT, "cluster")))
    findings = []
    for path in paths:
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            policed = False
            for n in ast.walk(fn):
                if (isinstance(n, ast.Name) and n.id == "RetryPolicy") \
                        or (isinstance(n, (ast.Attribute, ast.keyword))
                            and (getattr(n, "attr", None) == "retry_policy"
                                 or getattr(n, "arg", None)
                                 == "retry_policy")):
                    policed = True
                    break
            if policed:
                continue
            for loop in ast.walk(fn):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                has_except = any(isinstance(n, ast.ExceptHandler)
                                 for n in ast.walk(loop))
                sleep_line = None
                for n in ast.walk(loop):
                    if isinstance(n, ast.Call):
                        f = n.func
                        name = f.id if isinstance(f, ast.Name) else \
                            (f.attr if isinstance(f, ast.Attribute)
                             else None)
                        if name == "sleep" and n.args \
                                and isinstance(n.args[0], ast.Constant):
                            sleep_line = n.lineno
                            break
                if has_except and sleep_line is not None:
                    findings.append(Finding(
                        "hygiene.unpoliced_retry", "error", "",
                        f"{rel}:{sleep_line} {fn.name}: retry loop with a "
                        "fixed sleep bypasses RetryPolicy — hand-rolled "
                        "backoff has no jitter and no deadline awareness, "
                        "so retries can stampede and outlive the caller's "
                        "timeout_s; compute delays with "
                        "serve.retry.RetryPolicy.next_delay",
                        f"{rel}:{sleep_line}"))
                    break  # one finding per function is enough signal
    return findings


#: subprocess-spawning calls the serving stack may only make inside the
#: supervised pool (attribute name -> how we describe it)
_SPAWN_CALLS = frozenset({"Popen", "run", "call", "check_call",
                          "check_output", "fork", "forkpty", "spawnv",
                          "spawnve", "posix_spawn"})


def scan_unsupervised_subprocess(paths=None) -> list:
    """Process-spawning discipline for the serving stack: the ONLY
    module in ``tclb_tpu/serve`` or ``tclb_tpu/gateway`` allowed to
    start a child process is ``serve/pool.py`` — the supervisor that
    owns heartbeat watchdogs, SIGTERM→SIGKILL escalation, crash-loop
    backoff, and job requeue.

    A ``subprocess.Popen``/``os.fork`` anywhere else is an orphan
    factory: nobody watches its heartbeat, nobody reaps it on hang, and
    a crash loses whatever job it carried.  The structural signature is
    any call to a spawning API (``subprocess.Popen/run/call/check_*``,
    ``os.fork``/``forkpty``/``posix_spawn``) or a ``from subprocess
    import Popen``-style alias, outside the pool module.  The cluster
    plane (``tclb_tpu/cluster``) is held to the same rule: the
    host-agent supervises its local lanes *through* ``WorkerPool``
    rather than spawning children of its own."""
    if paths is None:
        paths = (_py_files(os.path.join(_PKG_ROOT, "serve"))
                 + _py_files(os.path.join(_PKG_ROOT, "gateway"))
                 + _py_files(os.path.join(_PKG_ROOT, "cluster")))
    findings = []
    for path in paths:
        if os.path.basename(path) == "pool.py" \
                and os.path.basename(os.path.dirname(path)) == "serve":
            continue  # the one sanctioned spawner
        try:
            with open(path) as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "hygiene.unparseable", "error", "",
                f"cannot parse {path}: {e}", path))
            continue
        rel = os.path.relpath(path, _REPO_ROOT)

        def flag(lineno: int, what: str) -> None:
            findings.append(Finding(
                "hygiene.unsupervised_subprocess", "error", "",
                f"{rel}:{lineno} {what} outside serve/pool.py — an "
                "unsupervised child has no heartbeat watchdog, no "
                "kill escalation, and no crash-loop backoff, and a "
                "crash silently loses its job; route process spawning "
                "through serve.pool.WorkerPool",
                f"{rel}:{lineno}"))

        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if (node.module or "").split(".")[0] == "subprocess":
                    for a in node.names:
                        if a.name in _SPAWN_CALLS:
                            flag(node.lineno,
                                 f"imports subprocess.{a.name}")
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id in ("subprocess", "os") \
                        and f.attr in _SPAWN_CALLS:
                    flag(node.lineno,
                         f"calls {f.value.id}.{f.attr}(...)")
                elif isinstance(f, ast.Name) and f.id == "Popen":
                    flag(node.lineno, "calls Popen(...)")
    return findings


def check_repo(engine_dir=None, sources=None) -> list:
    from tclb_tpu.analysis.concurrency import check_concurrency
    from tclb_tpu.analysis.precision import (scan_unsafe_accum,
                                             scan_unshifted_cast)
    return (scan_dead_entry_points(engine_dir, sources)
            + scan_id_keyed_caches()
            + scan_unbounded_adjoint()
            + scan_dispatch_telemetry()
            + scan_unrestorable_handlers()
            + scan_ensemble_unsafe()
            + scan_unpinned_device_put()
            + scan_device_work_in_monitor()
            + scan_device_work_in_gateway()
            + scan_unpoliced_retry()
            + scan_unsupervised_subprocess()
            + scan_unsafe_accum()
            + scan_unshifted_cast()
            + check_concurrency())


def check_model_hygiene(model: Model, shape=None) -> list:
    """Per-model hygiene: unbound stages behind registered actions, and
    the test/golden inventory (informational — the generic parametrized
    sweeps cover models no test names explicitly)."""
    findings: list = []
    for action, stages in sorted(model.actions.items()):
        for sname in stages:
            st = model.stages.get(sname)
            if st is None:
                findings.append(Finding(
                    "hygiene.missing_stage", "error", model.name,
                    f"action {action!r} references unregistered stage "
                    f"{sname!r}", f"action:{action}"))
            elif model.stage_fns.get(st.main) is None:
                findings.append(Finding(
                    "hygiene.unbound_stage", "error", model.name,
                    f"action {action!r} stage {sname!r} has no bound "
                    f"function {st.main!r}", f"action:{action}"))

    tests_dir = os.path.join(_REPO_ROOT, "tests")
    named = False
    if os.path.isdir(tests_dir):
        needle_a, needle_b = f'"{model.name}"', f"'{model.name}'"
        for p in _py_files(tests_dir):
            with open(p) as fh:
                src = fh.read()
            if needle_a in src or needle_b in src:
                named = True
                break
    if not named:
        findings.append(Finding(
            "hygiene.no_named_test", "info", model.name,
            "no test references this model by name (the parametrized "
            "all-models sweeps still cover it)"))
    goldens_dir = os.path.join(tests_dir, "goldens")
    has_golden = False
    if os.path.isdir(goldens_dir):
        for f in os.listdir(goldens_dir):
            path = os.path.join(goldens_dir, f)
            if f.endswith(".json") and os.path.isfile(path):
                with open(path) as fh:
                    if model.name in fh.read():
                        has_golden = True
                        break
    if not has_golden:
        findings.append(Finding(
            "hygiene.no_golden", "info", model.name,
            "no golden regression file references this model"))
    return findings
