#!/usr/bin/env python3
"""dccrg-lint — AST-based invariant checker for the dccrg_tpu port.

Every performance property this repo ships rests on hand-maintained
invariants: epoch tables enter kernels as runtime arguments (never
closed over), reductions pin dtypes so x64 promotion can't re-key a
compiled body, ``obs/slo.py`` stays stdlib-only so report tools
file-load without jax, every telemetry series recorded through the
registry is mirrored in the CI gates, and the metrics registry mutates
shared state only under its lock.  This tool enforces those contracts
mechanically, the way the reference dccrg enforces its invariants with
compile-time template machinery.

Stdlib-only by design (ast + json + subprocess): it must run in the
same no-jax contexts it polices.

Rules
-----
DTYPE-PROMOTE      jnp reductions/constructors without an explicit
                   ``dtype=`` in traced code (models/, parallel/,
                   serve/) — the PR 9 uint32→uint64 retrace bug class.
CLOSED-OVER-TABLE  functions handed to jax.jit/vmap/traced_jit whose
                   bodies read device-table bindings (put_table /
                   asarray / device_put products) or ``self.`` state
                   from the enclosing scope instead of taking them as
                   runtime arguments — the PR 5 invariant.  Known
                   boxed/flat offenders live in the baseline, which
                   doubles as the ROADMAP item-4 worklist.
HOST-SYNC          block_until_ready / np.asarray / .item() / float()
                   on device values inside the declared ensemble-step
                   and halo hot paths.
STDLIB-ONLY        module-level non-stdlib imports in declared
                   stdlib-only modules; ``--probe`` additionally
                   file-loads each probe target in a subprocess and
                   asserts sys.modules stays jax-free.
TELEMETRY-DRIFT    recorded counter/gauge/phase/histogram name
                   literals cross-checked against check_telemetry
                   REQUIRED_* and telemetry_diff DEFAULT/GATED sets:
                   gated-but-never-recorded fails always; recorded-
                   but-never-gated fails for phases and histograms
                   (whose gate unions are exhaustive by contract).
LOCK-DISCIPLINE    mutation of a class's shared dict/list/set/deque
                   attributes outside ``with self._lock:`` in any
                   class that owns a threading lock.
ENV-DRIFT          DCCRG_* getenv sites cross-checked against the
                   README env tables: undocumented knobs and dead
                   documented knobs both fail.

Baseline
--------
``tools/lint_baseline.json`` suppresses known findings per site.  A
site key is structural (rule, path, function-qualname detail) — not a
line number — so it survives unrelated edits.  Entries that no longer
match any finding are *stale* and fail the run (the baseline may only
shrink by deleting the entry alongside the fix); ``--update-baseline``
rewrites the file from current findings, preserving reasons.

Exit codes: 0 clean, 1 findings or stale baseline entries, 2 internal
error (unparseable source, missing gate tables).
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import pathlib
import re
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BASELINE_REL = "tools/lint_baseline.json"

# --------------------------------------------------------------- config

#: directories never scanned
SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", "build", "dist",
             ".ipynb_checkpoints", "related"}

#: DTYPE-PROMOTE scope: traced model/infra code where an unpinned
#: reduction can re-key a compiled body under x64
TRACED_PREFIXES = ("dccrg_tpu/models/", "dccrg_tpu/parallel/",
                   "dccrg_tpu/serve/")

#: jnp calls that promote to a config-dependent dtype unless pinned
DTYPE_SENSITIVE = {"sum", "prod", "cumsum", "cumprod", "arange"}

#: declared stdlib-only modules (AST import check).  tools/*.py are
#: stdlib-only by contract — report/diff tools must file-load without
#: jax.
STDLIB_ONLY_EXTRA = ("dccrg_tpu/obs/slo.py", "dccrg_tpu/obs/flightrec.py",
                     "dccrg_tpu/obs/registry.py", "dccrg_tpu/obs/live.py",
                     "dccrg_tpu/obs/alerts.py")

#: subprocess import-probe targets: file-load must leave sys.modules
#: jax-free (flightrec/registry are package-relative, probed via slo's
#: loader contract instead — see tests/test_lint.py)
PROBE_TARGETS = ("dccrg_tpu/obs/slo.py", "dccrg_tpu/obs/live.py",
                 "dccrg_tpu/obs/alerts.py", "tools/slo_report.py",
                 "tools/fleet_top.py", "tools/telemetry_diff.py",
                 "tools/dccrg_lint.py")

#: HOST-SYNC hot paths: per file, the function qualnames that sit on
#: the steady-state dispatch path.  The check is lexical (this body
#: only); oracle/verify helpers are deliberately absent — their host
#: syncs are the point.
HOT_FUNCTIONS = {
    "dccrg_tpu/serve/ensemble.py": {
        "Cohort.step", "Scheduler.step_once", "Scheduler.run",
    },
    "dccrg_tpu/parallel/halo.py": {
        "HaloExchange.__call__", "HaloExchange._dispatch",
        "HaloExchange.start", "HaloExchange._start_dispatch",
        "HaloExchange.finish", "HaloExchange._finish_dispatch",
    },
}

#: calls that force a device→host sync
HOST_SYNC_TAILS = {"block_until_ready", "device_get", "item"}
HOST_SYNC_NP = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}

#: registry methods that record a named series, by kind
RECORD_KINDS = {
    "inc": "counter", "inc_many": "counter", "inc_batch": "counter",
    "gauge": "gauge", "observe": "histogram",
    "phase": "phase", "phase_add": "phase",
}

#: gate tables parsed out of the CI tools (name -> kind)
CHECK_GATES = {
    "REQUIRED_PHASES": "phase",
    "REQUIRED_NONZERO_COUNTERS": "counter",
    "REQUIRED_HISTOGRAMS": "histogram",
}
DIFF_GATES = {
    "DEFAULT_PHASES": "phase",
    "GATED_COUNTERS": "counter",
    "DEFAULT_ALLOW": "phase",
    "GATED_GAUGES_MIN": "gauge",
    "GATED_GAUGES_MAX": "gauge",
    "GATED_QUANTILES": "histogram",   # tuples of (name, q)
}

#: metric-name grammar: dotted lowercase ("halo.bytes_moved")
METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

#: mutating methods on dict/list/set/deque
MUTATORS = {"append", "appendleft", "add", "clear", "pop", "popitem",
            "popleft", "update", "setdefault", "extend", "remove",
            "insert", "discard"}

#: calls that materialize a device table; closing over their products
#: inside a jitted body bakes content into the trace
TABLE_CALL_TAILS = {"put_table", "asarray", "device_put"}

ENV_PREFIX = "DCCRG_"


# ------------------------------------------------------------ framework

@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative posix path
    line: int
    site: str          # structural site id (stable across edits)
    message: str

    @property
    def key(self):
        return (self.rule, self.path, self.site)

    def to_json(self):
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "site": self.site, "message": self.message}


class Mod:
    """One parsed source file with parent links and qualname map."""

    def __init__(self, root: pathlib.Path, path: pathlib.Path):
        self.rel = path.relative_to(root).as_posix()
        self.src = path.read_text()
        self.tree = ast.parse(self.src, filename=str(path))
        self.parent = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
        self.qualname = {}
        self._name_scopes(self.tree, ())

    def _name_scopes(self, node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                sub = stack + (child.name,)
                self.qualname[child] = ".".join(sub)
                self._name_scopes(child, sub)
            else:
                self._name_scopes(child, stack)

    def ancestors(self, node):
        while node in self.parent:
            node = self.parent[node]
            yield node

    def enclosing_qualname(self, node) -> str:
        for anc in self.ancestors(node):
            if anc in self.qualname:
                return self.qualname[anc]
        return "<module>"


def dotted(node) -> str | None:
    """'jax.numpy.sum' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Ctx:
    def __init__(self, root: pathlib.Path):
        self.root = root
        self.mods: dict[str, Mod] = {}
        self.errors: list[str] = []
        for path in sorted(root.rglob("*.py")):
            rel = path.relative_to(root)
            if any(part in SKIP_DIRS for part in rel.parts):
                continue
            try:
                self.mods[rel.as_posix()] = Mod(root, path)
            except (SyntaxError, UnicodeDecodeError) as e:
                self.errors.append(f"{rel.as_posix()}: unparseable: {e}")

    def under(self, *prefixes):
        for rel, mod in sorted(self.mods.items()):
            if any(rel.startswith(p) for p in prefixes):
                yield rel, mod


class Rule:
    name = "?"
    blurb = "?"

    def run(self, ctx: Ctx):
        raise NotImplementedError


# ------------------------------------------------------- DTYPE-PROMOTE

class DtypePromote(Rule):
    name = "dtype-promote"
    blurb = ("jnp reduction/constructor without dtype= in traced code "
             "(x64 promotion re-keys the compiled body — PR 9 bug class)")

    def run(self, ctx):
        for rel, mod in ctx.under(*TRACED_PREFIXES):
            counts = {}
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func)
                if d is None:
                    continue
                head, _, tail = d.rpartition(".")
                if tail not in DTYPE_SENSITIVE:
                    continue
                if head not in ("jnp", "jax.numpy"):
                    continue
                if any(kw.arg == "dtype" for kw in node.keywords):
                    continue
                qn = mod.enclosing_qualname(node)
                ordinal = counts.get((qn, tail), 0)
                counts[(qn, tail)] = ordinal + 1
                yield Finding(
                    self.name, rel, node.lineno,
                    f"{qn}:{tail}#{ordinal}",
                    f"{d}(...) without dtype= — under x64 this promotes "
                    f"and re-keys every consumer's trace; pin it like "
                    f"game_of_life.live_neighbor_count (dtype=jnp.uint32)",
                )


# -------------------------------------------------- CLOSED-OVER-TABLE

class ClosedOverTable(Rule):
    name = "closed-over-table"
    blurb = ("jitted function closes over device-table bindings or reads "
             "self state instead of taking them as runtime arguments "
             "(PR 5 invariant; baseline = ROADMAP item-4 worklist)")

    JIT_NAMES = {"jax.jit", "jax.vmap", "jit", "vmap", "traced_jit",
                 "exec_cache.traced_jit"}
    PARTIALS = {"partial", "functools.partial"}

    def run(self, ctx):
        for rel, mod in ctx.under("dccrg_tpu/"):
            entries = self._jit_entries(mod)
            for fn in entries:
                qn = mod.qualname.get(fn, fn.name)
                yield from self._check_entry(mod, rel, fn, qn)

    # ---- entry discovery

    def _jit_entries(self, mod):
        entries = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    if self._is_jit_expr(dec):
                        entries.append(node)
                        break
            elif isinstance(node, ast.Call):
                d = dotted(node.func)
                if d in self.JIT_NAMES:
                    for arg in node.args:
                        if isinstance(arg, ast.Name):
                            fd = self._resolve_local_def(mod, node, arg.id)
                            if fd is not None:
                                entries.append(fd)
        seen, out = set(), []
        for fn in entries:
            if id(fn) not in seen:
                seen.add(id(fn))
                out.append(fn)
        return out

    def _resolve_local_def(self, mod, call, name):
        """The FunctionDef `name` refers to at `call`: nearest enclosing
        scope with a directly-nested def of that name (lexical scoping —
        a module-wide name match would conflate every `step`)."""
        scopes = [a for a in mod.ancestors(call)
                  if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Module))]
        for scope in scopes:
            hit = None

            def walk(node):
                nonlocal hit
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        if child.name == name and hit is None:
                            hit = child
                        continue
                    if isinstance(child, ast.Lambda):
                        continue
                    walk(child)

            walk(scope)
            if hit is not None:
                return hit
        return None

    def _is_jit_expr(self, dec):
        d = dotted(dec)
        if d in self.JIT_NAMES:
            return True
        if isinstance(dec, ast.Call):
            fd = dotted(dec.func)
            if fd in self.JIT_NAMES:
                return True
            if fd in self.PARTIALS and dec.args:
                return dotted(dec.args[0]) in self.JIT_NAMES
        return False

    # ---- per-entry closure analysis

    def _check_entry(self, mod, rel, fn, qn):
        bound = self._bound_names(fn)
        free_reads = {}
        self_reads = {}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Name) and
                    isinstance(node.ctx, ast.Load) and
                    node.id not in bound):
                free_reads.setdefault(node.id, node)
            if (isinstance(node, ast.Attribute) and
                    isinstance(node.ctx, ast.Load) and
                    isinstance(node.value, ast.Name) and
                    node.value.id == "self"):
                self_reads.setdefault(node.attr, node)

        scopes = [a for a in mod.ancestors(fn)
                  if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))]
        lam_tables = self._materializing_lambdas(scopes)

        for name, node in sorted(free_reads.items()):
            binding = self._nearest_binding(scopes, name)
            if binding is None:
                continue
            if self._materializes(binding, lam_tables):
                yield Finding(
                    self.name, rel, fn.lineno, f"{qn}:{name}",
                    f"jitted `{qn}` closes over `{name}` (a put_table/"
                    f"asarray-materialized device table) — content is "
                    f"baked into the trace, so every instance compiles "
                    f"its own body; pass it as a runtime argument",
                )
        table_attrs = self._materialized_self_attrs(mod, fn)
        for attr, node in sorted(self_reads.items()):
            if attr not in table_attrs:
                continue
            yield Finding(
                self.name, rel, node.lineno, f"{qn}:self.{attr}",
                f"jitted `{qn}` reads `self.{attr}` (a device table "
                f"materialized in __init__) — instance state inside a "
                f"traced body re-keys per object; take it as a runtime "
                f"argument",
            )

    def _materialized_self_attrs(self, mod, fn):
        """self attributes bound to put_table/asarray/device_put
        products anywhere in the enclosing class — the array-valued
        instance state a traced body must not read."""
        cls = next((a for a in mod.ancestors(fn)
                    if isinstance(a, ast.ClassDef)), None)
        if cls is None:
            return frozenset()
        out = set()
        for node in ast.walk(cls):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            value = node.value
            if value is None:
                continue
            for t in targets:
                if (isinstance(t, ast.Attribute) and
                        isinstance(t.value, ast.Name) and
                        t.value.id == "self" and
                        self._has_table_call(value)):
                    out.add(t.attr)
        return frozenset(out)

    def _bound_names(self, fn):
        bound = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(node.name)
                a = node.args
                for arg in (a.posonlyargs + a.args + a.kwonlyargs):
                    bound.add(arg.arg)
                if a.vararg:
                    bound.add(a.vararg.arg)
                if a.kwarg:
                    bound.add(a.kwarg.arg)
            elif isinstance(node, ast.Lambda):
                a = node.args
                for arg in (a.posonlyargs + a.args + a.kwonlyargs):
                    bound.add(arg.arg)
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                bound.add(node.id)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for alias in node.names:
                    bound.add((alias.asname or alias.name).split(".")[0])
            elif isinstance(node, ast.comprehension):
                for t in ast.walk(node.target):
                    if isinstance(t, ast.Name):
                        bound.add(t.id)
        return bound

    def _scope_bindings(self, scope):
        """name -> value expr assigned directly in `scope` (not inside
        nested function bodies)."""
        out = {}

        def walk(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(child, ast.Assign):
                    for t in child.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                out.setdefault(n.id, child.value)
                walk(child)

        walk(scope)
        return out

    def _nearest_binding(self, scopes, name):
        for scope in scopes:
            b = self._scope_bindings(scope)
            if name in b:
                return b[name]
        return None

    def _materializing_lambdas(self, scopes):
        names = set()
        for scope in scopes:
            for n, v in self._scope_bindings(scope).items():
                if isinstance(v, ast.Lambda) and self._has_table_call(v):
                    names.add(n)
        return names

    def _has_table_call(self, expr, lam_tables=frozenset()):
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is None:
                    continue
                tail = d.rpartition(".")[2]
                if tail in TABLE_CALL_TAILS or d in lam_tables:
                    return True
        return False

    def _materializes(self, binding, lam_tables):
        return self._has_table_call(binding, lam_tables)


# ------------------------------------------------------------ HOST-SYNC

class HostSync(Rule):
    name = "host-sync"
    blurb = ("device→host sync (block_until_ready/np.asarray/.item()/"
             "float()) inside a declared ensemble/halo hot path")

    def run(self, ctx):
        for rel, wanted in HOT_FUNCTIONS.items():
            mod = ctx.mods.get(rel)
            if mod is None:
                ctx.errors.append(f"host-sync: hot-path file missing: {rel}")
                continue
            found = set()
            for node, qn in mod.qualname.items():
                if qn in wanted and isinstance(node, ast.FunctionDef):
                    found.add(qn)
                    yield from self._scan(mod, rel, node, qn)
            for missing in sorted(wanted - found):
                ctx.errors.append(
                    f"host-sync: declared hot function {rel}:{missing} "
                    f"not found — update HOT_FUNCTIONS")

    def _scan(self, mod, rel, fn, qn):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            bad = None
            if d in HOST_SYNC_NP:
                bad = d
            elif d == "float" and node.args and not isinstance(
                    node.args[0], ast.Constant):
                bad = "float"
            elif (isinstance(node.func, ast.Attribute) and
                  node.func.attr in HOST_SYNC_TAILS):
                bad = node.func.attr
            if bad:
                yield Finding(
                    self.name, rel, node.lineno, f"{qn}:{bad}",
                    f"`{bad}` in hot path `{qn}` blocks on the device — "
                    f"move it off the steady-state dispatch path (the "
                    f"verify oracles are the sanctioned sync sites)",
                )


# ---------------------------------------------------------- STDLIB-ONLY

class StdlibOnly(Rule):
    name = "stdlib-only"
    blurb = ("module-level non-stdlib import in a declared stdlib-only "
             "module (report tools must file-load without jax)")

    def declared(self, ctx):
        out = list(STDLIB_ONLY_EXTRA)
        for rel in ctx.mods:
            if rel.startswith("tools/") and "/" not in rel[len("tools/"):]:
                out.append(rel)
        return sorted(set(r for r in out if r in ctx.mods))

    def run(self, ctx):
        declared = set(self.declared(ctx))
        stdlib = set(sys.stdlib_module_names) | {"__future__"}
        for rel in sorted(declared):
            mod = ctx.mods[rel]
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        root = alias.name.split(".")[0]
                        if root not in stdlib and not self._nested(mod, node):
                            yield self._finding(rel, node, root, mod)
                elif isinstance(node, ast.ImportFrom):
                    if node.level:
                        target = self._resolve_relative(rel, node)
                        if target not in declared and not self._nested(
                                mod, node):
                            yield Finding(
                                self.name, rel, node.lineno,
                                f"from:{'.' * node.level}{node.module or ''}",
                                f"relative import of `{node.module}` — "
                                f"target is not itself declared "
                                f"stdlib-only",
                            )
                        continue
                    root = (node.module or "").split(".")[0]
                    if root and root not in stdlib and not self._nested(
                            mod, node):
                        yield self._finding(rel, node, root, mod)

    def _nested(self, mod, node):
        """imports inside functions (lazy imports) are the sanctioned
        escape hatch — only module-level imports break file-load."""
        return any(isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))
                   for a in mod.ancestors(node))

    def _resolve_relative(self, rel, node):
        base = pathlib.PurePosixPath(rel).parent
        for _ in range(node.level - 1):
            base = base.parent
        mod_path = (node.module or "").replace(".", "/")
        return (base / f"{mod_path}.py").as_posix()

    def _finding(self, rel, node, root, mod):
        return Finding(
            self.name, rel, node.lineno, f"import:{root}",
            f"module-level `import {root}` in stdlib-only module — "
            f"move it inside the function that needs it (see "
            f"telemetry_diff._slo() for the file-load pattern)",
        )

    # ---- subprocess probe

    @staticmethod
    def probe(root: pathlib.Path, rel: str) -> str | None:
        """File-load `rel` in a clean subprocess; return an error
        string if jax lands in sys.modules (or the load fails)."""
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('probe', {str(root / rel)!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['probe'] = m\n"
            "spec.loader.exec_module(m)\n"
            "bad = sorted(k for k in sys.modules if k == 'jax' or "
            "k.startswith('jax.') or k.startswith('jaxlib'))\n"
            "assert not bad, f'jax leaked into sys.modules: {bad}'\n"
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            return (r.stderr.strip().splitlines() or ["load failed"])[-1]
        return None


# ------------------------------------------------------ TELEMETRY-DRIFT

class TelemetryDrift(Rule):
    name = "telemetry-drift"
    blurb = ("recorded telemetry series vs CI gate sets: gated-but-"
             "never-recorded, and phases/histograms recorded-but-"
             "never-gated")

    def run(self, ctx):
        recorded, partial, weak = self._recorded(ctx)
        gates = {}
        ok = True
        for rel, table in (("tools/check_telemetry.py", CHECK_GATES),
                           ("tools/telemetry_diff.py", DIFF_GATES)):
            mod = ctx.mods.get(rel)
            if mod is None:
                ctx.errors.append(f"telemetry-drift: missing {rel}")
                ok = False
                continue
            got = self._gate_tables(mod, table)
            for var in table:
                if var not in got:
                    ctx.errors.append(
                        f"telemetry-drift: {rel} has no literal tuple "
                        f"assignment `{var}`")
                    ok = False
            for var, (kind, names) in got.items():
                for n in names:
                    gates.setdefault((kind, n), []).append(f"{rel}:{var}")
        if not ok:
            return

        # (a) gated but never recorded
        for (kind, n), where in sorted(gates.items()):
            strong = recorded.get(kind, set())
            if n in strong or n in weak:
                continue
            if any(n.startswith(p) for p in partial.get(kind, set()) if p):
                continue
            yield Finding(
                self.name, where[0].split(":")[0], 1,
                f"gate:{kind}:{n}",
                f"{kind} `{n}` is gated in {', '.join(where)} but never "
                f"recorded through the registry — dead gate or renamed "
                f"series",
            )

        # (b) recorded but never gated — phases and histograms only:
        # their gate unions are exhaustive by contract; counters/gauges
        # gates are deliberately selective witnesses.
        phase_union = {n for (k, n) in gates if k == "phase"}
        hist_union = {n for (k, n) in gates if k == "histogram"}
        for kind, union in (("phase", phase_union),
                            ("histogram", hist_union)):
            for n, (rel, line) in sorted(recorded.get(
                    kind + "_sites", {}).items()):
                if n in union:
                    continue
                yield Finding(
                    self.name, rel, line, f"recorded:{kind}:{n}",
                    f"{kind} `{n}` is recorded here but appears in no "
                    f"check_telemetry/telemetry_diff gate set — add it "
                    f"to the gates or drop the series",
                )

    def _recorded(self, ctx):
        recorded = {"counter": set(), "gauge": set(), "histogram": set(),
                    "phase": set(), "phase_sites": {},
                    "histogram_sites": {}}
        partial = {"counter": set(), "gauge": set(), "histogram": set(),
                   "phase": set()}
        weak = set()
        for rel, mod in ctx.under("dccrg_tpu/"):
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Constant) and
                        isinstance(node.value, str) and
                        METRIC_NAME_RE.match(node.value)):
                    weak.add(node.value)
                if not isinstance(node, ast.Call):
                    continue
                if not isinstance(node.func, ast.Attribute):
                    continue
                kind = RECORD_KINDS.get(node.func.attr)
                if kind is None or not node.args:
                    continue
                first = node.args[0]
                if (isinstance(first, ast.Constant) and
                        isinstance(first.value, str)):
                    recorded[kind].add(first.value)
                    sites = recorded.get(kind + "_sites")
                    if sites is not None and first.value not in sites:
                        sites[first.value] = (rel, node.lineno)
                elif isinstance(first, ast.JoinedStr):
                    head = first.values[0] if first.values else None
                    if (isinstance(head, ast.Constant) and
                            isinstance(head.value, str)):
                        partial[kind].add(head.value)
        return recorded, partial, weak

    def _gate_tables(self, mod, table):
        out = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id in table:
                    names = []
                    for el in ast.walk(node.value):
                        if (isinstance(el, ast.Constant) and
                                isinstance(el.value, str)):
                            names.append(el.value)
                    out[t.id] = (table[t.id], names)
        return out


# ------------------------------------------------------ LOCK-DISCIPLINE

class LockDiscipline(Rule):
    name = "lock-discipline"
    blurb = ("mutation of lock-guarded shared dict/list/set/deque "
             "attributes outside `with self._lock:`")

    CONTAINER_CALLS = {"dict", "list", "set", "deque",
                       "collections.deque", "collections.defaultdict",
                       "defaultdict", "OrderedDict",
                       "collections.OrderedDict"}
    LOCK_CALLS = {"threading.Lock", "threading.RLock", "Lock", "RLock"}

    def run(self, ctx):
        for rel, mod in ctx.under("dccrg_tpu/"):
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    yield from self._check_class(mod, rel, node)

    def _check_class(self, mod, rel, cls):
        init = next((n for n in cls.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "__init__"), None)
        if init is None:
            return
        lock_attrs, guarded = set(), set()
        for node in ast.walk(init):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                t = node.target
            else:
                continue
            if not (isinstance(t, ast.Attribute) and
                    isinstance(t.value, ast.Name) and t.value.id == "self"):
                continue
            v = node.value
            if isinstance(v, ast.Call) and dotted(v.func) in self.LOCK_CALLS:
                lock_attrs.add(t.attr)
            elif isinstance(v, (ast.Dict, ast.List, ast.Set)):
                guarded.add(t.attr)
            elif (isinstance(v, ast.Call) and
                  dotted(v.func) in self.CONTAINER_CALLS):
                guarded.add(t.attr)
        if not lock_attrs or not guarded:
            return
        qn_cls = mod.qualname[cls]
        for meth in cls.body:
            if (not isinstance(meth, ast.FunctionDef) or
                    meth.name == "__init__"):
                continue
            for node in ast.walk(meth):
                attr = self._mutation(node, guarded)
                if attr and not self._under_lock(mod, node, lock_attrs,
                                                 meth):
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"{qn_cls}.{meth.name}:{attr}",
                        f"`{qn_cls}.{meth.name}` mutates shared "
                        f"`self.{attr}` outside `with self._lock:` — "
                        f"concurrent recorders race (see the registry "
                        f"thread-stress test)",
                    )

    def _self_attr(self, node):
        if (isinstance(node, ast.Attribute) and
                isinstance(node.value, ast.Name) and
                node.value.id == "self"):
            return node.attr
        return None

    def _mutation(self, node, guarded):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = (node.targets if isinstance(node, (ast.Assign,
                                                         ast.Delete))
                       else [node.target])
            for t in targets:
                if isinstance(t, ast.Subscript):
                    a = self._self_attr(t.value)
                    if a in guarded:
                        return a
                a = self._self_attr(t)
                if a in guarded:
                    return a
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Attribute):
            if node.func.attr in MUTATORS:
                base = node.func.value
                if isinstance(base, ast.Subscript):
                    base = base.value
                a = self._self_attr(base)
                if a in guarded:
                    return a
        return None

    def _under_lock(self, mod, node, lock_attrs, stop):
        for anc in mod.ancestors(node):
            if isinstance(anc, ast.With):
                for item in anc.items:
                    expr = item.context_expr
                    a = self._self_attr(expr)
                    if a is None and isinstance(expr, ast.Call):
                        a = self._self_attr(expr.func)
                    if a in lock_attrs:
                        return True
            if anc is stop:
                return False
        return False


# ------------------------------------------------------------ ENV-DRIFT

class EnvDrift(Rule):
    name = "env-drift"
    blurb = ("DCCRG_* getenv sites vs README env tables: undocumented "
             "knobs and dead documented knobs")

    GETENV = {"os.environ.get", "os.getenv", "environ.get",
              "os.environ.setdefault", "environ.setdefault"}

    def run(self, ctx):
        read_sites = {}
        referenced = set()
        for rel, mod in ctx.under("dccrg_tpu/", "tools/", "examples/"):
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Constant) and
                        isinstance(node.value, str) and
                        node.value.startswith(ENV_PREFIX)):
                    referenced.add(node.value)
                name = self._getenv_key(node)
                if name:
                    read_sites.setdefault(name, (rel, node.lineno))

        readme = ctx.root / "README.md"
        if not readme.exists():
            ctx.errors.append("env-drift: README.md not found")
            return
        documented = set(re.findall(r"\bDCCRG_[A-Z0-9_]+\b",
                                    readme.read_text()))

        for name, (rel, line) in sorted(read_sites.items()):
            if name not in documented:
                yield Finding(
                    self.name, rel, line, f"undocumented:{name}",
                    f"env knob `{name}` is read here but has no README "
                    f"row — document it (or run --fix-docs for a "
                    f"paste-ready row)",
                )
        for name in sorted(documented - referenced):
            yield Finding(
                self.name, "README.md", 1, f"dead:{name}",
                f"env knob `{name}` is documented in README but no "
                f"longer referenced anywhere in code — delete the row",
            )

    def _getenv_key(self, node):
        if not (isinstance(node, ast.Call) and node.args):
            # os.environ["DCCRG_X"] loads
            if (isinstance(node, ast.Subscript) and
                    isinstance(node.ctx, ast.Load) and
                    dotted(node.value) in ("os.environ", "environ") and
                    isinstance(node.slice, ast.Constant) and
                    isinstance(node.slice.value, str) and
                    node.slice.value.startswith(ENV_PREFIX)):
                return node.slice.value
            return None
        if dotted(node.func) not in self.GETENV:
            return None
        first = node.args[0]
        if (isinstance(first, ast.Constant) and
                isinstance(first.value, str) and
                first.value.startswith(ENV_PREFIX)):
            return first.value
        return None

    @staticmethod
    def fix_docs(findings):
        rows = []
        for f in findings:
            if f.rule != "env-drift" or not f.site.startswith(
                    "undocumented:"):
                continue
            name = f.site.split(":", 1)[1]
            rows.append(f"| `{name}` | (unset) | TODO: describe — read "
                        f"at {f.path}:{f.line} |")
        return rows


# ------------------------------------------------------------- baseline

def load_baseline(path: pathlib.Path):
    if not path.exists():
        return []
    data = json.loads(path.read_text())
    return data.get("entries", [])


def apply_baseline(findings, entries):
    by_key = {(e["rule"], e["path"], e["site"]): e for e in entries}
    active, suppressed, matched = [], [], set()
    for f in findings:
        if f.key in by_key:
            suppressed.append(f)
            matched.add(f.key)
        else:
            active.append(f)
    stale = [e for e in entries
             if (e["rule"], e["path"], e["site"]) not in matched]
    return active, suppressed, stale


def write_baseline(path, findings, old_entries, carried=()):
    reasons = {(e["rule"], e["path"], e["site"]): e.get("reason", "")
               for e in old_entries}
    entries = [
        {"rule": f.rule, "path": f.path, "site": f.site,
         "reason": reasons.get(f.key, "unreviewed — justify or fix")}
        for f in sorted(findings, key=lambda f: f.key)
    ] + list(carried)
    entries.sort(key=lambda e: (e["rule"], e["path"], e["site"]))
    path.write_text(json.dumps({"entries": entries}, indent=2) + "\n")
    return entries


# ------------------------------------------------------------------ cli

RULES = (DtypePromote, ClosedOverTable, HostSync, StdlibOnly,
         TelemetryDrift, LockDiscipline, EnvDrift)


def run_lint(root: pathlib.Path, rules=None, baseline_entries=None):
    """Programmatic entry: returns (active, suppressed, stale, errors)."""
    ctx = Ctx(root)
    ran = tuple(rules or RULES)
    findings = []
    for cls in ran:
        findings.extend(cls().run(ctx))
    entries = baseline_entries
    if entries is None:
        entries = load_baseline(root / BASELINE_REL)
    # staleness is only decidable for rules that ran: a --rule subset
    # must not declare the other rules' baseline entries fixed
    ran_names = {cls.name for cls in ran}
    entries = [e for e in entries if e["rule"] in ran_names]
    active, suppressed, stale = apply_baseline(findings, entries)
    return active, suppressed, stale, ctx.errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dccrg_lint",
        description="AST invariant checker for the dccrg_tpu port")
    ap.add_argument("--root", default=str(REPO_ROOT),
                    help="repo root to scan (default: this checkout)")
    ap.add_argument("--rule", action="append", default=None,
                    help="run only this rule (repeatable)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    ap.add_argument("--fix-docs", action="store_true",
                    help="print paste-ready README rows for "
                         "undocumented env knobs")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(preserves reasons for surviving entries)")
    ap.add_argument("--probe", action="store_true",
                    help="also run the subprocess stdlib-only import "
                         "probe (slower)")
    args = ap.parse_args(argv)

    root = pathlib.Path(args.root).resolve()
    rules = RULES
    if args.rule:
        by_name = {c.name: c for c in RULES}
        unknown = [r for r in args.rule if r not in by_name]
        if unknown:
            print(f"unknown rule(s): {', '.join(unknown)} "
                  f"(have: {', '.join(by_name)})", file=sys.stderr)
            return 2
        rules = tuple(by_name[r] for r in args.rule)

    active, suppressed, stale, errors = run_lint(root, rules)

    probe_failures = []
    if args.probe:
        for rel in PROBE_TARGETS:
            if not (root / rel).exists():
                continue
            err = StdlibOnly.probe(root, rel)
            if err:
                probe_failures.append({"path": rel, "error": err})

    if args.update_baseline:
        path = root / BASELINE_REL
        old = load_baseline(path)
        # a --rule subset only rewrites its own rules' entries; the
        # rest of the baseline is carried over untouched
        ran_names = {c.name for c in rules}
        carried = [e for e in old if e["rule"] not in ran_names]
        entries = write_baseline(path, active + suppressed, old,
                                 carried=carried)
        print(f"baseline rewritten: {len(entries)} entries")
        return 0

    rc = 1 if (active or stale or errors or probe_failures) else 0

    if args.fix_docs:
        rows = EnvDrift.fix_docs(active)
        if rows:
            print("# paste into the README env table:")
            for r in rows:
                print(r)
        else:
            print("# no undocumented env knobs")

    if args.as_json:
        print(json.dumps({
            "findings": [f.to_json() for f in active],
            "suppressed": len(suppressed),
            "stale_baseline": stale,
            "probe_failures": probe_failures,
            "errors": errors,
            "rc": rc,
        }, indent=2))
        return rc

    for f in active:
        print(f"{f.path}:{f.line}: [{f.rule}] {f.message}")
    for e in stale:
        print(f"{BASELINE_REL}: stale baseline entry "
              f"{e['rule']}:{e['path']}:{e['site']} — the finding is "
              f"gone; delete the entry")
    for p in probe_failures:
        print(f"{p['path']}: [stdlib-only probe] {p['error']}")
    for e in errors:
        print(f"[lint-error] {e}")
    if rc == 0:
        n = len(suppressed)
        print(f"dccrg-lint: clean ({len(rules)} rules, "
              f"{n} baseline-suppressed)")
    else:
        print(f"dccrg-lint: {len(active)} finding(s), {len(stale)} "
              f"stale baseline, {len(errors)} error(s)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
