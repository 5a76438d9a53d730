"""The rule catalogue — this codebase's implicit contracts, as checks.

Each rule encodes a convention earlier PRs established but nothing
enforced (see the module docstrings it references):

- ``host-sync-hot-path``   — no ``.item()`` / ``np.asarray`` / traced
  ``float()``/``int()`` inside jitted / ``costed_jit`` functions, and no
  per-window forced fetches inside streamed window loops (the sync-free
  growth contract of PR 3; ``train.host_syncs`` exists to count the few
  sanctioned ones);
- ``recompile-hazard``     — named hot-path executables in ``train/``,
  ``serve/`` and ``pipeline/`` route through ``obs.costed_jit`` so the
  recompile sentinel sees them (PR 8), and executable names are never
  interpolated f-strings (per-name dedup would count every distinct
  name once and the sentinel goes blind);
- ``knob-registry``        — every ``-Dshifu.*`` / ``SHIFU_*`` literal
  read or mentioned anywhere resolves against ``config/knobs.py``;
- ``atomic-write``         — artifact writes are tmp+``os.replace``
  atomic via ``ioutil`` (PR 4), never a raw ``open(path, "w")``;
- ``telemetry-guard``      — instrument *factory* lookups stay out of
  hot loops (hoist the handle; the zero-cost-when-disabled contract of
  PR 1/7 is only zero-cost when the name lookup isn't per-iteration);
- ``metric-manifest`` / ``span-manifest`` / ``fault-site`` — the
  grep-based manifest lints that lived in ``tests/test_obs_plane.py``,
  now first-class AST rules (names resolve against ``obs/manifest.py``
  and ``faults.SITES``).
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import (FileContext, LintEngine, Rule, call_name,
                     fstring_head, qualname, str_const)

__all__ = ["ALL_RULES", "make_rules"]

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_light(rel: str, alias: str):
    """Import a dependency-free module by file path, dodging package
    ``__init__`` chains (``shifu_tpu.obs`` pulls jax; the linter must
    stay import-light so a full-tree run clears the <5 s guard cold)."""
    name = f"_shifu_lint_{alias}"
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(_PKG_DIR, rel)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _knobs():
    return _load_light(os.path.join("config", "knobs.py"), "knobs")


def _obs_manifest():
    return _load_light(os.path.join("obs", "manifest.py"), "obs_manifest")


def _fault_sites() -> Dict[Tuple[str, str], str]:
    return _load_light("faults.py", "faults").SITES


# --------------------------------------------------------------- helpers
_JIT_NAMES = ("jax.jit", "jit")


def _is_jit_decorator(dec: ast.AST) -> bool:
    if qualname(dec) in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fq = call_name(dec)
        if fq in _JIT_NAMES or fq.endswith("costed_jit"):
            return True
        if fq in ("partial", "functools.partial") and dec.args:
            aq = qualname(dec.args[0])
            if aq in _JIT_NAMES or aq.endswith("costed_jit"):
                return True
    return False


def _static_argnames(fn: ast.AST) -> Set[str]:
    """Names bound statically by the jit decorator — ``float()``/
    ``int()`` over these is host math, not a device sync."""
    out: Set[str] = set()
    for dec in getattr(fn, "decorator_list", ()):
        if not isinstance(dec, ast.Call):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                for el in ast.walk(kw.value):
                    s = str_const(el)
                    if s:
                        out.add(s)
    return out


def _param_names(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = [p.arg for p in
             (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def _enclosing(parents: Sequence[ast.AST], *types) -> Optional[ast.AST]:
    for node in reversed(parents):
        if isinstance(node, types):
            return node
    return None


def _enclosing_jit_fn(parents: Sequence[ast.AST]) -> Optional[ast.AST]:
    for node in reversed(parents):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_decorator(d) for d in node.decorator_list):
                return node
    return None


_WINDOW_ITERS = (".prepared(", ".windows(", ".tail_items(")


def _enclosing_window_loop(parents: Sequence[ast.AST],
                           ctx: FileContext) -> Optional[ast.For]:
    """Nearest enclosing ``for`` whose iterable is a streamed window
    source (``stream.prepared(...)`` / ``.windows(...)`` /
    ``cache.tail_items(...)``) — the per-window hot loop."""
    for node in reversed(parents):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None
        if isinstance(node, ast.For):
            it = ctx.src(node.iter)
            if any(w in it for w in _WINDOW_ITERS):
                return node
    return None


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# ---------------------------------------------------------------- rule 1
class HostSyncRule(Rule):
    name = "host-sync-hot-path"
    doc = ("no .item()/.tolist()/np.asarray()/jax.device_get() and no "
           "float()/int() over traced parameters inside jitted/"
           "costed_jit functions; no forced per-window fetches inside "
           "streamed window loops")
    interests = (ast.Call,)

    _NP_SYNCS = ("np.asarray", "np.array", "np.asanyarray",
                 "numpy.asarray", "numpy.array", "jax.device_get")

    def visit(self, node: ast.Call, parents, ctx) -> None:
        func = node.func
        is_item = (isinstance(func, ast.Attribute)
                   and func.attr in ("item", "tolist") and not node.args)
        fq = call_name(node)
        jit_fn = _enclosing_jit_fn(parents)
        if jit_fn is not None:
            if is_item:
                self.report(ctx, node,
                            f".{func.attr}() inside the jitted function "
                            f"'{jit_fn.name}' forces a device->host sync "
                            "(or breaks tracing) — return the value and "
                            "fetch outside the executable")
                return
            if fq in self._NP_SYNCS:
                self.report(ctx, node,
                            f"{fq}() inside the jitted function "
                            f"'{jit_fn.name}' materializes a traced value "
                            "on host — use jnp inside the trace")
                return
            if fq in ("float", "int") and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant):
                traced = (_param_names(jit_fn) - _static_argnames(jit_fn))
                if _names_in(node.args[0]) & traced:
                    self.report(ctx, node,
                                f"{fq}() over a traced parameter of "
                                f"'{jit_fn.name}' forces a host sync — "
                                "keep it in-graph or mark the argument "
                                "static")
                return
        if not (is_item or fq == "jax.device_get"):
            return
        loop = _enclosing_window_loop(parents, ctx)
        if loop is not None:
            what = f".{func.attr}()" if is_item else f"{fq}()"
            self.report(ctx, node,
                        f"{what} inside a streamed window loop syncs the "
                        "device every window — accumulate on device and "
                        "fetch once after the sweep (train.host_syncs "
                        "counts the sanctioned packed fetches)")


# ---------------------------------------------------------------- rule 2
class RecompileHazardRule(Rule):
    name = "recompile-hazard"
    doc = ("hot-path layers (train/, serve/, pipeline/) route named "
           "executables through obs.costed_jit so the recompile "
           "sentinel sees them; executable names are never interpolated "
           "f-strings (per-name dedup would go blind)")
    interests = (ast.Call, ast.FunctionDef, ast.AsyncFunctionDef)

    _HOT_LAYERS = ("train", "serve", "pipeline")

    def _hot(self, ctx: FileContext) -> bool:
        parts = ctx.rel_path.split("/")
        return any(p in self._HOT_LAYERS for p in parts[:-1])

    def visit(self, node, parents, ctx) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if not self._hot(ctx):
                return
            for dec in node.decorator_list:
                if self._is_bare_jit(dec):
                    self.report(
                        ctx, dec,
                        f"bare jax.jit decorating '{node.name}' in a "
                        "hot-path layer — route through obs.costed_jit("
                        "name, ...) so the recompile sentinel and the "
                        "cost plane see this executable",
                        line=dec.lineno)
            return
        fq = call_name(node)
        if fq.endswith("costed_jit") or fq.endswith("record_executable"):
            if node.args and isinstance(node.args[0], ast.JoinedStr) \
                    and any(isinstance(v, ast.FormattedValue)
                            for v in node.args[0].values):
                self.report(ctx, node,
                            f"f-string executable name passed to {fq} — "
                            "every distinct interpolation mints a new "
                            "name, so the sentinel's per-name recompile "
                            "dedup never fires; use a fixed name (or a "
                            "bounded, shape-keyed family registered "
                            "per-bucket like serve does)")
            return
        if fq in _JIT_NAMES and self._hot(ctx):
            self.report(ctx, node,
                        "bare jax.jit() call in a hot-path layer — wrap "
                        "with obs.costed_jit(name, fn, ...) so the "
                        "recompile sentinel and cost attribution see "
                        "the executable")

    @staticmethod
    def _is_bare_jit(dec: ast.AST) -> bool:
        if qualname(dec) in _JIT_NAMES:
            return True
        if isinstance(dec, ast.Call):
            fq = call_name(dec)
            if fq in _JIT_NAMES:
                return True
            if fq in ("partial", "functools.partial") and dec.args \
                    and qualname(dec.args[0]) in _JIT_NAMES:
                return True
        return False


# ---------------------------------------------------------------- rule 3
_PROP_READS = ("get_property", "get_int", "get_float", "get_bool",
               "set_property")
_KNOB_MENTION_RE = re.compile(
    # the lookbehinds keep reference Java packages (ml.shifu.shifu.*)
    # and prefixed env names out of the mention scan
    r"-D(shifu\.[A-Za-z0-9_.]+)"
    r"|(?<![\w.])(SHIFU_[A-Z0-9][A-Z0-9_]*)"
    r"|(?<![\w.])(shifu\.[A-Za-z][A-Za-z0-9_.]*)")


class KnobRegistryRule(Rule):
    name = "knob-registry"
    doc = ("every -Dshifu.* / SHIFU_* literal read or mentioned in "
           "shifu_tpu/ must be declared in config/knobs.py (and every "
           "declared knob must appear in the README table and be "
           "referenced somewhere)")
    interests = (ast.Call, ast.Subscript, ast.Constant)

    _SKIP_FILES = ("config/knobs.py",)

    def __init__(self) -> None:
        super().__init__()
        self.knobs = _knobs()
        self.referenced: Set[str] = set()   # normalized declared names hit

    def _skip(self, ctx: FileContext) -> bool:
        return any(ctx.rel_path.endswith(s) for s in self._SKIP_FILES)

    def _note(self, token: str) -> None:
        k = self.knobs
        if token in k.KNOBS:
            self.referenced.add(token)
        else:
            tl = token.lower()
            for n in k.KNOBS:
                if n.lower() == tl or n.lower().startswith(tl):
                    self.referenced.add(n)

    def _check_read(self, token: str, node, ctx,
                    where: str) -> None:
        if not (token.startswith("shifu.") or token.startswith("SHIFU_")):
            return
        if self.knobs.is_declared(token):
            self._note(token)
            return
        self.report(ctx, node,
                    f"knob {token!r} read via {where} is not declared in "
                    "config/knobs.py — add a Knob(name, kind, type, "
                    "default, doc) entry (and the README table row)")

    def visit(self, node, parents, ctx) -> None:
        if self._skip(ctx):
            return
        if isinstance(node, ast.Call):
            fq = call_name(node)
            leaf = fq.rsplit(".", 1)[-1]
            if leaf in _PROP_READS and node.args:
                s = str_const(node.args[0])
                if s is not None:
                    self._check_read(s, node, ctx, f"{leaf}()")
                return
            if fq in ("os.getenv", "os.environ.get",
                      "environ.get") and node.args:
                s = str_const(node.args[0])
                if s is not None:
                    self._check_read(s, node, ctx, fq)
                return
            return
        if isinstance(node, ast.Subscript):
            if qualname(node.value) in ("os.environ", "environ"):
                s = str_const(node.slice)
                if s is not None:
                    self._check_read(s, node, ctx, "os.environ[]")
            return
        # mentions in docstrings / help text / messages (f-string parts
        # arrive here too — JoinedStr children are Constant nodes)
        text = str_const(node)
        if text is None:
            return
        if self._in_read_call(node, parents):
            return                       # already judged by the read branch
        for m in _KNOB_MENTION_RE.finditer(text):
            token = (m.group(1) or m.group(2) or m.group(3)).rstrip(".")
            if token in ("shifu", "SHIFU"):
                continue
            if self.knobs.is_declared(token) \
                    or self.knobs.is_declared_prefix(token):
                self._note(token)
                continue
            self.report(ctx, node,
                        f"mention of undeclared knob {token!r} — "
                        "declare it in config/knobs.py or fix the "
                        "doc (dead knobs rot)")

    @staticmethod
    def _in_read_call(node: ast.AST, parents) -> bool:
        """Is this literal the key argument of a read call / env
        subscript the read branch already checked?"""
        if not parents:
            return False
        parent = parents[-1]
        if isinstance(parent, ast.Call):
            fq = call_name(parent)
            leaf = fq.rsplit(".", 1)[-1]
            if (leaf in _PROP_READS
                    or fq in ("os.getenv", "os.environ.get",
                              "environ.get")) \
                    and parent.args and parent.args[0] is node:
                return True
        if isinstance(parent, ast.Subscript) \
                and qualname(parent.value) in ("os.environ", "environ"):
            return True
        return False

    def finish(self, engine: LintEngine) -> None:
        knobs_rel = "shifu_tpu/config/knobs.py"
        readme = os.path.join(engine.root, "README.md")
        readme_text = ""
        if os.path.isfile(readme):
            with open(readme, encoding="utf-8") as f:
                readme_text = f.read()
        for name, knob in sorted(self.knobs.KNOBS.items()):
            if readme_text and name not in readme_text:
                self.report_project(
                    knobs_rel,
                    f"declared knob {name!r} missing from the README "
                    "knob table — regenerate with "
                    "knobs.knob_table_markdown()")
            if name not in self.referenced:
                self.report_project(
                    knobs_rel,
                    f"declared knob {name!r} is never read or mentioned "
                    "in shifu_tpu/ — remove the dead declaration (or "
                    "wire the knob)")


# ---------------------------------------------------------------- rule 4
class AtomicWriteRule(Rule):
    name = "atomic-write"
    doc = ("artifact writes are atomic (ioutil tmp+os.replace) — a raw "
           "open(path, 'w')/np.save*(path) can leave a torn, committed-"
           "looking file for a resumed run to trust; json.dump/.write "
           "targets are caught at their open() site")
    interests = (ast.Call,)

    _NP_WRITERS = ("np.save", "np.savez", "np.savez_compressed",
                   "numpy.save", "numpy.savez", "numpy.savez_compressed")

    def _exempt_scope(self, parents, ctx) -> bool:
        """tmp-file discipline is the atomic pattern itself: a write
        whose enclosing function — or enclosing class, for write-
        through protocols like the spill cache (open .part in append(),
        os.replace in finish()) — calls os.replace() is exempt."""
        scope = _enclosing(parents, ast.FunctionDef, ast.AsyncFunctionDef)
        if scope is not None and self._calls_replace(scope):
            return True
        cls = _enclosing(parents, ast.ClassDef)
        if cls is not None and self._calls_replace(cls):
            return True
        if scope is None and cls is None and parents:
            return self._calls_replace(parents[0])
        return False

    @staticmethod
    def _calls_replace(scope: ast.AST) -> bool:
        for n in ast.walk(scope):
            if isinstance(n, ast.Call) and call_name(n) == "os.replace":
                return True
        return False

    @staticmethod
    def _buf_names(parents) -> Set[str]:
        scope = _enclosing(parents, ast.FunctionDef, ast.AsyncFunctionDef)
        if scope is None:
            return set()
        out: Set[str] = set()
        for n in ast.walk(scope):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                if call_name(n.value).rsplit(".", 1)[-1] == "BytesIO":
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            out.add(t.id)
        return out

    def visit(self, node: ast.Call, parents, ctx) -> None:
        if ctx.rel_path.endswith("ioutil.py"):
            return
        fq = call_name(node)
        if fq == "open" and node.args:
            mode = None
            if len(node.args) >= 2:
                mode = str_const(node.args[1])
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = str_const(kw.value)
            if not mode or not any(c in mode for c in "wax"):
                return                  # read modes (incl. r+b) pass
            path_src = ctx.src(node.args[0])
            if "tmp" in path_src.lower():
                return
            if self._exempt_scope(parents, ctx):
                return
            self.report(ctx, node,
                        f"raw open({path_src or '...'}, {mode!r}) — a "
                        "crash mid-write leaves a torn file; use "
                        "ioutil.atomic_write_text/json/bytes (or write "
                        "a .tmp and os.replace)")
            return
        if fq in self._NP_WRITERS and node.args:
            target = node.args[0]
            tsrc = ctx.src(target)
            if "tmp" in tsrc.lower() or "buf" in tsrc.lower():
                return
            if isinstance(target, ast.Name) \
                    and target.id in self._buf_names(parents):
                return
            if self._exempt_scope(parents, ctx):
                return
            self.report(ctx, node,
                        f"{fq}({tsrc or '...'}) writes the final path "
                        "directly — np.save* mid-crash leaves a torn "
                        "zip; use ioutil.atomic_savez (or a BytesIO + "
                        "atomic_write_bytes)")


# ---------------------------------------------------------------- rule 5
class TelemetryGuardRule(Rule):
    name = "telemetry-guard"
    doc = ("obs.counter/gauge/histogram factory lookups stay out of "
           "loops — hoist the instrument handle before the loop, or "
           "guard the block with obs.enabled() / a hoisted obs_on "
           "bool; the name lookup takes the registry lock per "
           "iteration even when telemetry is off")
    interests = (ast.Call,)

    _FACTORIES = ("counter", "gauge", "histogram")
    _BASES = ("obs", "registry", "_registry")
    _GUARDS = ("enabled(", "obs_on", "telemetry_on")

    def visit(self, node: ast.Call, parents, ctx) -> None:
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in self._FACTORIES
                and qualname(func.value) in self._BASES):
            return
        in_loop = False
        for p in reversed(parents):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if isinstance(p, (ast.For, ast.While)):
                in_loop = True
                break
        if not in_loop:
            return
        for p in reversed(parents):
            if isinstance(p, ast.If) \
                    and any(g in ctx.src(p.test) for g in self._GUARDS):
                return
        name = str_const(node.args[0]) if node.args else None
        self.report(ctx, node,
                    f"instrument factory {qualname(func.value)}."
                    f"{func.attr}({name!r}) inside a loop — hoist the "
                    "handle out of the loop or guard with obs.enabled() "
                    "(the per-iteration name lookup defeats the "
                    "zero-cost-when-disabled contract)")


# ------------------------------------------------------------ rules 6-8
class MetricManifestRule(Rule):
    name = "metric-manifest"
    doc = ("every obs.counter/gauge/histogram name literal resolves "
           "against obs/manifest.py with the declared instrument type; "
           "f-string families must start with a declared prefix (a "
           "typo'd name silently mints a NEW metric)")
    interests = (ast.Call,)

    _FACTORIES = ("counter", "gauge", "histogram")
    _BASES = ("obs", "registry", "_registry")

    def __init__(self) -> None:
        super().__init__()
        self.manifest = _obs_manifest()

    def visit(self, node: ast.Call, parents, ctx) -> None:
        if ctx.rel_path.endswith("obs/manifest.py"):
            return
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in self._FACTORIES
                and qualname(func.value) in self._BASES):
            return
        if not node.args:
            return
        kind = func.attr
        arg = node.args[0]
        head = fstring_head(arg)
        if head is not None and isinstance(arg, ast.JoinedStr) \
                and any(isinstance(v, ast.FormattedValue)
                        for v in arg.values):
            if not any(head.startswith(p)
                       for p in self.manifest.PREFIXES):
                self.report(ctx, node,
                            f"f-string {kind} name {head + '...'!r} has "
                            "no declared prefix in obs.manifest.PREFIXES")
            return
        name = str_const(arg) if head is None else head
        if name is None:
            return
        if not self.manifest.is_declared(name):
            self.report(ctx, node,
                        f"{kind} {name!r} not declared in "
                        "obs.manifest.MANIFEST — a typo here would "
                        "silently mint a new metric")
        elif name in self.manifest.MANIFEST \
                and self.manifest.MANIFEST[name][0] != kind:
            self.report(ctx, node,
                        f"{name!r} used as {kind} but declared "
                        f"{self.manifest.MANIFEST[name][0]} in "
                        "obs.manifest.MANIFEST")


class SpanManifestRule(Rule):
    name = "span-manifest"
    doc = ("every obs.span()/record_span() name literal resolves "
           "against obs.manifest.SPANS (the timeline tracks / report "
           "sections join on these; a typo'd span silently vanishes "
           "from every report)")
    interests = (ast.Call,)

    _BASES = ("obs", "tracer")

    def visit(self, node: ast.Call, parents, ctx) -> None:
        if ctx.rel_path.endswith("obs/manifest.py"):
            return
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in ("span", "record_span")
                and qualname(func.value) in self._BASES):
            return
        if not node.args:
            return
        arg = node.args[0]
        if isinstance(arg, ast.JoinedStr):
            head = fstring_head(arg) or ""
            self.report(ctx, node,
                        f"f-string span name {head + '...'!r}: span "
                        "names are literals declared in "
                        "obs.manifest.SPANS")
            return
        name = str_const(arg)
        if name is None:
            return                      # step-root spans named by variable
        if not _obs_manifest().is_declared_span(name):
            self.report(ctx, node,
                        f"span {name!r} not declared in "
                        "obs.manifest.SPANS")


class FaultSiteRule(Rule):
    name = "fault-site"
    doc = ("every faults.fire(site, point, ...) literal pair resolves "
           "against faults.SITES — an undeclared site can't be armed "
           "from the documented spec grammar and would silently never "
           "fire")
    interests = (ast.Call,)

    def visit(self, node: ast.Call, parents, ctx) -> None:
        fq = call_name(node)
        if not (fq == "fire" or fq.endswith(".fire")):
            return
        if fq not in ("fire", "faults.fire") and \
                not fq.endswith("faults.fire"):
            return
        if len(node.args) < 2:
            return
        site, point = str_const(node.args[0]), str_const(node.args[1])
        if site is None or point is None:
            return
        if (site, point) not in self._sites():
            self.report(ctx, node,
                        f"fault site ({site!r}, {point!r}) not declared "
                        "in faults.SITES — declare the boundary (and "
                        "its spec-grammar line) so it can be armed")

    @staticmethod
    def _sites() -> Dict[Tuple[str, str], str]:
        return _fault_sites()


ALL_RULES = (HostSyncRule, RecompileHazardRule, KnobRegistryRule,
             AtomicWriteRule, TelemetryGuardRule, MetricManifestRule,
             SpanManifestRule, FaultSiteRule)


def make_rules(names: Optional[Sequence[str]] = None) -> List[Rule]:
    """Instantiate the catalogue (or the named subset, lint-CLI
    ``--rules``)."""
    if names is None:
        return [cls() for cls in ALL_RULES]
    by_name = {cls.name: cls for cls in ALL_RULES}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        known = ", ".join(sorted(by_name))
        raise ValueError(f"unknown rule(s) {unknown} — known: {known}")
    return [by_name[n]() for n in names]
