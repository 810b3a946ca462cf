"""psrlint's rule catalog — one rule per bug class this repo has
already paid to fix by hand.  Each docstring cites the PR that fixed
the class; the rule exists so the NEXT PR cannot reintroduce it.

Scopes are deliberate: a rule runs only where its invariant holds
(PL002 outside the lease registry, PL006 inside ``io/``, PL009 in the
resilience-adjacent modules), so a clean run means the invariant holds
where it matters, not that the rule was too timid to fire.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from pypulsar_tpu.analysis.engine import (
    FileContext, Finding, ProjectContext, ProjectRule, Rule,
)

__all__ = ["ALL_RULES", "all_rules"]


# ---------------------------------------------------------------------------
# shared helpers

def _is_test(ctx: FileContext) -> bool:
    return (ctx.relpath.startswith("tests/")
            or ctx.relpath.rsplit("/", 1)[-1].startswith("test_"))


def _in_package(ctx: FileContext) -> bool:
    return ctx.relpath.startswith("pypulsar_tpu/")


def _call_name(node: ast.Call) -> str:
    """Dotted-ish name of a call target: 'os.environ.get', 'range'."""
    parts: List[str] = []
    cur = node.func
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return ".".join(reversed(parts))


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---------------------------------------------------------------------------
# PL001 — py2 truediv feeding an index/size context

class TruedivIndexRule(Rule):
    """``x[a / b]`` / ``range(a / b)``: the reference's py2 heritage
    defect (PAPER.md; last hand-audit in PR 8's division sweep).  In
    py3 ``/`` is float division, so an index/size built from it either
    crashes or — worse, via downstream ``int()`` — silently truncates
    differently than the py2 original.  Use ``//``.

    Contexts covered: subscript indices/slice bounds and direct
    ``range(...)`` arguments.  Climbing stops at any other call
    boundary (``a[int(x / y)]`` is an explicit, visible coercion)."""

    code = "PL001"
    name = "py2-truediv-index"
    summary = "true division feeding an index/size context; use //"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        for node in ctx.walk():
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Div)):
                continue
            cur = node
            while True:
                parent_entry = parents.get(cur)
                if parent_entry is None:
                    break
                parent, field = parent_entry
                if isinstance(parent, ast.Call):
                    if (isinstance(parent.func, ast.Name)
                            and parent.func.id == "range"
                            and field == "args"):
                        yield self.finding(
                            ctx, node,
                            "true division result used as a range() "
                            "bound; use // (py2-heritage defect)")
                    break
                if isinstance(parent, ast.Subscript) and field == "slice":
                    yield self.finding(
                        ctx, node,
                        "true division result used as a subscript "
                        "index; use // (py2-heritage defect)")
                    break
                if isinstance(parent, ast.stmt):
                    break
                cur = parent


# ---------------------------------------------------------------------------
# PL002 — bare jax.devices() outside the lease registry

class BareJaxDevicesRule(Rule):
    """``jax.devices()`` anywhere but ``parallel/mesh.py`` bypasses the
    gang-lease registry PR 6 introduced: a stage running under a lease
    that probes raw device 0 can address a chip another gang owns.
    Resolve through ``parallel.mesh.lease_devices()`` (lease first,
    then default_device, then local devices)."""

    code = "PL002"
    name = "bare-jax-devices"
    summary = "bare jax.devices() outside parallel/mesh.py"

    _EXEMPT = "pypulsar_tpu/parallel/mesh.py"

    def applies_to(self, ctx: FileContext) -> bool:
        if ctx.relpath == self._EXEMPT or _is_test(ctx):
            return False
        return (_in_package(ctx) or ctx.relpath.startswith("tools/")
                or ctx.relpath == "bench.py")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ctx.walk():
            if (isinstance(node, ast.Call)
                    and _call_name(node) == "jax.devices"):
                yield self.finding(
                    ctx, node,
                    "bare jax.devices() bypasses the gang-lease "
                    "registry; use parallel.mesh.lease_devices() "
                    "(PR 6 invariant)")


# ---------------------------------------------------------------------------
# PL003 — non-atomic artifact write

_ARTIFACT_EXTS = (
    ".dat", ".inf", ".cand", ".cands", ".txtcand", ".pfd", ".fil",
    ".fits", ".sub", ".events", ".pulses", ".mask", ".json", ".jsonl",
)
_TMP_MARK = re.compile(r"\.tmp|tmp$|^tmp", re.IGNORECASE)
_OUT_NAME = re.compile(r"^(out|dest|dst)[a-z_]*$")


class NonAtomicWriteRule(Rule):
    """A resumable pipeline's artifacts are validated by size/sha256
    (PR 3): an ``open(path, 'w'/'wb')`` straight onto an artifact path
    leaves a torn file behind a kill that later validation may accept.
    Write ``path + '.tmp'`` and ``os.replace`` it, or use
    ``resilience.journal.atomic_write_bytes/_text``.

    Heuristic scope — flags a write-mode ``open`` whose path expression
    names an artifact extension or an out-ish variable, unless the path
    carries a tmp marker or the enclosing function calls
    ``os.replace`` (the tmp+rename idiom in place)."""

    code = "PL003"
    name = "non-atomic-artifact-write"
    summary = "write-mode open() on an artifact path without tmp+os.replace"

    def applies_to(self, ctx: FileContext) -> bool:
        return _in_package(ctx) and not _is_test(ctx)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        replace_scopes = self._os_replace_scopes(ctx)
        for node in ctx.walk():
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "open" and node.args):
                continue
            mode = self._write_mode(node)
            if mode is None:
                continue
            path_expr = node.args[0]
            if not self._artifactish(path_expr):
                continue
            if self._tmp_marked(path_expr):
                continue
            if self._enclosing_function(node, parents) in replace_scopes:
                continue
            yield self.finding(
                ctx, node,
                f"open(..., {mode!r}) writes an artifact path in place; "
                "write a '.tmp' sibling and os.replace() it (or use "
                "resilience.journal.atomic_write_*) so a kill cannot "
                "leave a torn artifact (PR 3 invariant)")

    @staticmethod
    def _write_mode(node: ast.Call) -> Optional[str]:
        mode_node = None
        if len(node.args) >= 2:
            mode_node = node.args[1]
        for kw in node.keywords:
            if kw.arg == "mode":
                mode_node = kw.value
        mode = _const_str(mode_node)
        if mode and any(c in mode for c in "wax"):
            return mode
        return None

    @staticmethod
    def _artifactish(expr) -> bool:
        for sub in ast.walk(expr):
            s = _const_str(sub)
            if s and any(s.endswith(ext) or ext + "." in s
                         for ext in _ARTIFACT_EXTS):
                return True
            if isinstance(sub, ast.Name) and _OUT_NAME.match(sub.id):
                return True
        return False

    @staticmethod
    def _tmp_marked(expr) -> bool:
        for sub in ast.walk(expr):
            s = _const_str(sub)
            if s and _TMP_MARK.search(s):
                return True
            if isinstance(sub, ast.Name) and "tmp" in sub.id.lower():
                return True
        return False

    @staticmethod
    def _enclosing_function(node, parents):
        cur = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            entry = parents.get(cur)
            cur = entry[0] if entry else None
        return None

    def _os_replace_scopes(self, ctx: FileContext) -> Set[ast.AST]:
        scopes: Set[ast.AST] = set()
        parents = ctx.parents
        for node in ctx.walk():
            if (isinstance(node, ast.Call)
                    and _call_name(node) in ("os.replace", "os.rename")):
                fn = self._enclosing_function(node, parents)
                if fn is not None:
                    scopes.add(fn)
        return scopes


# ---------------------------------------------------------------------------
# PL004 — env-knob registry drift (code vs README "Runtime knobs")

_KNOB_RE = re.compile(r"PYPULSAR_TPU_[A-Z0-9_]+")


class KnobRegistryDriftRule(ProjectRule):
    """Every ``PYPULSAR_TPU_*`` env knob the code reads must have a row
    in the README "Runtime knobs" table, and every row must name a knob
    the code still reads (PR 7 added the table; PR 8's knobs drifted —
    an operator cannot tune what the registry does not list)."""

    code = "PL004"
    name = "knob-registry-drift"
    summary = "env knob missing from the README table (or vice versa)"

    _ENV_CALLS = ("os.environ.get", "environ.get", "os.getenv", "getenv")

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        accesses: Dict[str, Tuple[str, int, int]] = {}
        for ctx in project.contexts:
            if _is_test(ctx):
                continue
            if not (_in_package(ctx) or ctx.relpath.startswith("tools/")
                    or ctx.relpath == "bench.py"):
                continue
            for name, node in self._env_reads(ctx):
                accesses.setdefault(
                    name, (ctx.relpath, node.lineno, node.col_offset + 1))

        if project.readme_text is None:
            return
        documented: Dict[str, int] = {}
        in_section = False
        for i, line in enumerate(project.readme_text.splitlines(), 1):
            if line.startswith("## "):
                in_section = line.strip().lower() == "## runtime knobs"
                continue
            if in_section and line.lstrip().startswith("|"):
                for m in _KNOB_RE.finditer(line):
                    documented.setdefault(m.group(0), i)

        for name in sorted(set(accesses) - set(documented)):
            path, line, col = accesses[name]
            yield Finding(
                self.code, path, line, col,
                f"env knob {name} is read here but has no row in the "
                f"README 'Runtime knobs' table (registry drift, PR 7/8)")
        for name in sorted(set(documented) - set(accesses)):
            yield Finding(
                self.code, project.readme_rel or "README.md",
                documented[name], 1,
                f"README 'Runtime knobs' documents {name} but no code "
                f"reads it (stale row, registry drift)")

    def _env_reads(self, ctx: FileContext):
        for node in ctx.walk():
            if isinstance(node, ast.Call):
                cn = _call_name(node)
                # os.environ/getenv plus the repo's typo-tolerant
                # env_float/env_int helpers (resilience.health)
                if ((cn in self._ENV_CALLS
                     or cn.split(".")[-1].startswith("env_"))
                        and node.args):
                    s = _const_str(node.args[0])
                    if s and s.startswith("PYPULSAR_TPU_"):
                        yield s, node
            elif isinstance(node, ast.Subscript):
                if (_attr_chain(node.value) in ("os.environ", "environ")):
                    s = _const_str(node.slice)
                    if s and s.startswith("PYPULSAR_TPU_"):
                        yield s, node
            elif isinstance(node, ast.Assign):
                # ENV_FAULTS = "PYPULSAR_TPU_FAULTS" constant bindings:
                # the binding site IS the knob's in-code registration
                # (the read goes through the constant).  Only the ENV_*
                # naming convention counts, and the value must be
                # EXACTLY one knob token — a doc/message string or a
                # stray constant that merely mentions a knob must not
                # mask real drift
                for tgt in node.targets:
                    if (isinstance(tgt, ast.Name)
                            and tgt.id.startswith("ENV_")):
                        s = _const_str(node.value)
                        if s and _KNOB_RE.fullmatch(s):
                            yield s, node


def _attr_chain(node) -> str:
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return ""


# ---------------------------------------------------------------------------
# PL005 — fault-point literal in tests/bench with no defining trip site

_FAULT_KINDS = {"oom", "io", "kill", "exit", "hang", "device",
                "nanburst", "dropblock", "dcjump", "bitflip", "truncate"}
_POINT_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*$")


class DeadFaultPointRule(ProjectRule):
    """A fault spec in a test/bench naming a point no ``trip``/
    ``trip_data`` call site defines arms a fault that never fires: the
    test silently stops covering its failure path (the cousin of PR 7's
    ``configure()`` chaos-wipe bug).  A point counts as defined by a
    production literal, a production f-string prefix/suffix (dynamic
    stage points), a ``*POINT*`` string constant, or a trip call in the
    referencing test file itself (machinery self-tests)."""

    code = "PL005"
    name = "dead-fault-point"
    summary = "fault-point literal with no defining trip()/trip_data() site"

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        exact: Set[str] = set()
        prefixes: Set[str] = set()
        suffixes: Set[str] = set()
        per_file_exact: Dict[str, Set[str]] = {}
        per_file_prefix: Dict[str, Set[str]] = {}

        for ctx in project.contexts:
            fe, fp, fs = self._defined_points(ctx)
            if _in_package(ctx) and not _is_test(ctx):
                exact |= fe
                prefixes |= fp
                suffixes |= fs
            per_file_exact[ctx.relpath] = fe
            per_file_prefix[ctx.relpath] = fp

        for ctx in project.contexts:
            if not (_is_test(ctx) or ctx.relpath == "bench.py"):
                continue
            for point, node in self._referenced_points(ctx):
                if point in exact or point in per_file_exact[ctx.relpath]:
                    continue
                if any(point.startswith(p) for p in
                       prefixes | per_file_prefix[ctx.relpath] if p):
                    continue
                if any(point.endswith(s) for s in suffixes if s):
                    continue
                yield self.finding(
                    ctx, node,
                    f"fault point '{point}' is armed/inspected here but "
                    f"no trip()/trip_data() call site defines it — the "
                    f"fault can never fire (dead chaos coverage)")

    # -- definitions --------------------------------------------------
    def _defined_points(self, ctx: FileContext
                        ) -> Tuple[Set[str], Set[str], Set[str]]:
        exact: Set[str] = set()
        prefixes: Set[str] = set()
        suffixes: Set[str] = set()
        for node in ctx.walk():
            if isinstance(node, ast.Call):
                cn = _call_name(node)
                if cn.split(".")[-1] in ("trip", "trip_data") and node.args:
                    arg = node.args[0]
                    s = _const_str(arg)
                    if s is not None:
                        exact.add(s)
                    elif isinstance(arg, ast.JoinedStr) and arg.values:
                        first, last = arg.values[0], arg.values[-1]
                        fs = _const_str(first)
                        ls = _const_str(last)
                        if fs:
                            prefixes.add(fs)
                        elif ls:
                            suffixes.add(ls)
            elif isinstance(node, ast.Assign):
                # FAULT_POINT = "data.block" style registered constants,
                # plus FAULT_POINTS = ("a.b", "c.d") tuple/list registries
                # (round 24: the broker publishes its points as a tuple)
                for tgt in node.targets:
                    if not (isinstance(tgt, ast.Name)
                            and "POINT" in tgt.id):
                        continue
                    s = _const_str(node.value)
                    if s:
                        exact.add(s)
                    elif isinstance(node.value, (ast.Tuple, ast.List)):
                        for elt in node.value.elts:
                            es = _const_str(elt)
                            if es:
                                exact.add(es)
        return exact, prefixes, suffixes

    # -- references ---------------------------------------------------
    def _referenced_points(self, ctx: FileContext):
        seen: Set[Tuple[str, int]] = set()
        for node in ctx.walk():
            if isinstance(node, ast.Call):
                cn = _call_name(node)
                if cn.split(".")[-1] == "hits" and node.args:
                    s = _const_str(node.args[0])
                    if s and _POINT_RE.match(s):
                        key = (s, node.lineno)
                        if key not in seen:
                            seen.add(key)
                            yield s, node
            s = _const_str(node)
            if s is None:
                continue
            for part in s.split(","):
                fields = part.strip().split(":")
                if len(fields) < 2 or fields[0] not in _FAULT_KINDS:
                    continue
                if len(fields) >= 3 and not fields[2].isdigit():
                    continue
                point = fields[1]
                if not _POINT_RE.match(point):
                    continue
                key = (point, node.lineno)
                if key not in seen:
                    seen.add(key)
                    yield point, node


# ---------------------------------------------------------------------------
# PL006 — raw header reads in io/ bypassing read_exact

class RawHeaderReadRule(Rule):
    """``struct.unpack(fmt, f.read(n))`` trusts a short read: at EOF
    ``read`` returns ``b''`` and unpack raises a bare struct.error with
    no path/offset — the exact failure shape PR 8's DataFormatError
    error hierarchy (``io/errors.py``) exists to locate.  Use
    ``read_exact(f, n, path, what)``.  Same for ``.read(n).decode()``
    header chains."""

    code = "PL006"
    name = "raw-header-read"
    summary = "struct.unpack / .read().decode() bypassing read_exact"

    def applies_to(self, ctx: FileContext) -> bool:
        return (ctx.relpath.startswith("pypulsar_tpu/io/")
                and ctx.relpath != "pypulsar_tpu/io/errors.py")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            cn = _call_name(node)
            if cn.split(".")[-1] in ("unpack", "unpack_from") \
                    and cn.split(".")[0] == "struct":
                if any(self._is_read_call(sub)
                       for a in node.args for sub in ast.walk(a)):
                    yield self.finding(
                        ctx, node,
                        "struct.unpack over a raw .read(): a short read "
                        "at EOF raises an unlocated struct.error — use "
                        "io.errors.read_exact (PR 8 error hierarchy)")
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "decode"
                    and self._is_read_call(node.func.value)):
                yield self.finding(
                    ctx, node,
                    ".read(n).decode() header chain trusts a short "
                    "read — use io.errors.read_exact (PR 8 error hierarchy)")

    @staticmethod
    def _is_read_call(node) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "read"
                and bool(node.args))


# ---------------------------------------------------------------------------
# PL007 — mutable default argument

class MutableDefaultRule(Rule):
    """A ``def f(x, acc=[])`` default is created once and shared across
    calls — in a fleet runtime that means cross-observation state
    bleed.  Default to ``None`` and materialize inside."""

    code = "PL007"
    name = "mutable-default-argument"
    summary = "mutable default argument ([], {}, set(), ...)"

    _MUTABLE_CALLS = {"list", "dict", "set", "bytearray", "defaultdict",
                      "OrderedDict", "Counter", "deque"}

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ctx.walk():
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if self._mutable(d):
                    name = getattr(node, "name", "<lambda>")
                    yield self.finding(
                        ctx, d,
                        f"mutable default argument in {name}(); the "
                        f"object is shared across calls — default to "
                        f"None and materialize inside")

    def _mutable(self, node) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                             ast.DictComp, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            return _call_name(node).split(".")[-1] in self._MUTABLE_CALLS
        return False


# ---------------------------------------------------------------------------
# PL008 — telemetry span opened outside a with/finally discipline

class SpanLeakRule(Rule):
    """``telemetry.span()`` is a context manager; calling it without
    entering it records nothing (and an enter without a guaranteed exit
    corrupts span nesting for the whole thread — PR 1's discipline).
    Compliant shapes: ``with span(...)``, ``stack.enter_context(
    span(...))``, or returning the manager to the caller."""

    code = "PL008"
    name = "span-not-context-managed"
    summary = "telemetry span opened without with/enter_context"

    def applies_to(self, ctx: FileContext) -> bool:
        return not _is_test(ctx) and (
            _in_package(ctx) or ctx.relpath.startswith("tools/")
            or ctx.relpath == "bench.py")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        for node in ctx.walk():
            if not (isinstance(node, ast.Call) and self._is_span(node)):
                continue
            entry = parents.get(node)
            parent = entry[0] if entry else None
            if isinstance(parent, ast.withitem):
                continue
            if isinstance(parent, ast.Return):
                continue
            if (isinstance(parent, ast.Call)
                    and isinstance(parent.func, ast.Attribute)
                    and parent.func.attr == "enter_context"):
                continue
            yield self.finding(
                ctx, node,
                "telemetry span created outside a with/enter_context — "
                "it either never records or can leak its nesting level "
                "on an exception (PR 1 discipline)")

    @staticmethod
    def _is_span(node: ast.Call) -> bool:
        f = node.func
        if isinstance(f, ast.Name):
            return f.id == "span"
        if isinstance(f, ast.Attribute) and f.attr == "span":
            return (isinstance(f.value, ast.Name)
                    and f.value.id in ("telemetry", "_telemetry", "obs"))
        return False


# ---------------------------------------------------------------------------
# PL009 — except Exception swallowing must_propagate faults

class SwallowedFaultRule(Rule):
    """In the resilience-adjacent modules an ``except Exception`` that
    degrades silently can swallow a watchdog interrupt, a chip-indicting
    fault, or an injected fault — hiding a device strike and defeating
    the retry->quarantine path (PR 7's no_degrade contract).  Compliant
    handlers re-raise, gate on ``health.no_degrade``/``must_propagate``,
    propagate the exception as a value, or carry a reasoned trailing
    comment (the ``# noqa: BLE001 - why`` idiom) explaining why broad
    capture is safe HERE."""

    code = "PL009"
    name = "swallowed-propagating-fault"
    summary = "except Exception without no_degrade gate / reason"

    _SCOPES = ("pypulsar_tpu/parallel/", "pypulsar_tpu/survey/",
               "pypulsar_tpu/resilience/")
    # the reason marker is a space-delimited dash ("# noqa: BLE001 - why"
    # / "# — why"): a hyphenATED word ("# best-effort") must not count
    # as a reason, or the rule goes vacuous
    _REASON_RE = re.compile(r"#.*(?:\s|^)[-—]\s+\S")

    def applies_to(self, ctx: FileContext) -> bool:
        return any(ctx.relpath.startswith(s) for s in self._SCOPES)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ctx.walk():
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._catches_exception(node.type):
                continue
            if self._compliant(node, ctx):
                continue
            yield self.finding(
                ctx, node,
                "except Exception here can swallow must_propagate "
                "faults (watchdog interrupts, chip strikes, injected "
                "faults); gate with health.no_degrade(e)/re-raise, or "
                "justify with a reasoned trailing comment (PR 7 "
                "no_degrade contract)")

    @staticmethod
    def _catches_exception(type_node) -> bool:
        def _is_exc(n):
            return ((isinstance(n, ast.Name) and n.id == "Exception")
                    or (isinstance(n, ast.Attribute)
                        and n.attr == "Exception"))
        if _is_exc(type_node):
            return True
        if isinstance(type_node, ast.Tuple):
            return any(_is_exc(e) for e in type_node.elts)
        return False

    def _compliant(self, handler: ast.ExceptHandler,
                   ctx: FileContext) -> bool:
        if self._REASON_RE.search(ctx.line_text(handler.lineno)):
            return True
        bound = handler.name
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                if _call_name(node).split(".")[-1] in (
                        "no_degrade", "must_propagate"):
                    return True
            if (bound and isinstance(node, ast.Name)
                    and node.id == bound
                    and isinstance(node.ctx, ast.Load)):
                return True  # exception propagated as a value
        return False


# ---------------------------------------------------------------------------
# PL011 — raw PYPULSAR_TPU_* env read outside the knob registry

class RawKnobReadRule(Rule):
    """Round 17 made ``tune/knobs.py`` the single read path for every
    ``PYPULSAR_TPU_*`` tunable (``trial > env > tuned cache > default``
    precedence). A raw ``os.environ.get``/``getenv``/``environ[...]``
    read anywhere else silently bypasses the auto-tuning cache AND the
    typo-tolerance contract — the knob looks tunable but the tuner can
    never move it. Route through ``knobs.env_int/env_float/env_str``.

    Flags the constant-indirection idiom too (``os.environ.get(ENV_X)``
    with an ``ENV_``-named constant). Env *writes* (``os.environ[k] =
    v`` in bench/tests arming subprocess knobs) are fine — only Load
    context is a read. Suppressions are reserved for bootstrap probes
    where the registry genuinely cannot be imported."""

    code = "PL011"
    name = "raw-knob-read"
    summary = "raw PYPULSAR_TPU_* env read outside tune/knobs.py"

    _EXEMPT = "pypulsar_tpu/tune/knobs.py"
    _ENV_CALLS = ("os.environ.get", "environ.get", "os.getenv", "getenv")

    def applies_to(self, ctx: FileContext) -> bool:
        if ctx.relpath == self._EXEMPT or _is_test(ctx):
            return False
        return (_in_package(ctx) or ctx.relpath.startswith("tools/")
                or ctx.relpath == "bench.py")

    def _knob_name(self, node) -> Optional[str]:
        s = _const_str(node)
        if s is not None:
            return s if s.startswith("PYPULSAR_TPU_") else None
        if isinstance(node, ast.Name) and node.id.startswith("ENV_"):
            return node.id
        return None

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ctx.walk():
            if (isinstance(node, ast.Call)
                    and _call_name(node) in self._ENV_CALLS
                    and node.args):
                name = self._knob_name(node.args[0])
                if name:
                    yield self.finding(
                        ctx, node,
                        f"raw env read of {name} bypasses the knob "
                        f"registry (env > tuned cache > default); use "
                        f"tune.knobs.env_int/env_float/env_str")
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and _attr_chain(node.value) in ("os.environ",
                                                    "environ")):
                name = self._knob_name(node.slice)
                if name:
                    yield self.finding(
                        ctx, node,
                        f"raw os.environ[{name!r}] read bypasses the "
                        f"knob registry; use tune.knobs accessors")


# ---------------------------------------------------------------------------
# psrrace static rules (PL012-PL016, round 19): the concurrency bug
# classes the threaded fleet runtime (PRs 5-13) paid for by hand — lock
# ordering, blocking under a lock, leak-prone acquires, unguarded
# condition waits, orphanable threads. The runtime half lives in
# resilience/locks.py (lockdep); these rules lock the SOURCE shapes in.

_LOCKISH_RE = re.compile(r"(?:^|_)(?:lock|locks|mutex|cv|cond)$", re.I)
_CONDISH_RE = re.compile(r"(?:^|_)(?:cv|cond|condition)$", re.I)


def _enclosing_fn(node, parents):
    cur = node
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return cur
        entry = parents.get(cur)
        cur = entry[0] if entry else None
    return None


def _enclosing_class_name(node, parents) -> Optional[str]:
    cur = node
    while cur is not None:
        if isinstance(cur, ast.ClassDef):
            return cur.name
        entry = parents.get(cur)
        cur = entry[0] if entry else None
    return None


def _lockish_name(expr) -> Optional[str]:
    """The final name segment of a lock-looking expression (``self._cv``
    -> ``_cv``), or None when the expression does not look like a lock.
    Name-convention based BY DESIGN: this repo's locks are uniformly
    ``*_lock`` / ``*_cv`` (and the tracked wrappers keep that idiom), so
    a miss means a naming drift worth fixing anyway."""
    if isinstance(expr, ast.Name):
        return expr.id if _LOCKISH_RE.search(expr.id) else None
    if isinstance(expr, ast.Attribute):
        return expr.attr if _LOCKISH_RE.search(expr.attr) else None
    return None


def _lock_key(ctx: FileContext, node, expr) -> Optional[str]:
    """Graph node identity for a lock expression: ``<Class>.<attr>`` for
    ``self._lock``-style attributes (the class IS the lock's home, so
    the same class merges across files), the receiver chain verbatim for
    other attributes (``sched._lock`` from any file is one node —
    variable naming is the convention-based join key, same philosophy
    as the lockish-name heuristic itself), and ``<module-stem>.<name>``
    for module-global lock names (two modules' private globals must NOT
    merge on a shared spelling)."""
    tail = _lockish_name(expr)
    if tail is None:
        return None
    if isinstance(expr, ast.Attribute):
        chain = _attr_chain(expr)
        root = chain.split(".", 1)[0]
        if root in ("self", "cls"):
            cls = _enclosing_class_name(node, ctx.parents)
            if cls:
                return f"{cls}.{tail}"
        return chain
    stem = ctx.relpath.rsplit("/", 1)[-1].removesuffix(".py")
    return f"{stem}.{tail}"


def _concurrency_scope(ctx: FileContext) -> bool:
    return not _is_test(ctx) and (
        _in_package(ctx) or ctx.relpath.startswith("tools/")
        or ctx.relpath == "bench.py")


# ---------------------------------------------------------------------------
# PL012 — cross-file lock-order inversion


class LockOrderInversionRule(ProjectRule):
    """Build the lock acquisition-order graph from lexically nested
    ``with <lock>`` scopes over the WHOLE project (edges merge across
    files via class-qualified lock keys) and flag every cycle — the
    static twin of ``resilience.locks``' runtime lockdep, catching the
    AB/BA deadlocks PR 7 and PR 13 each had to fix in review before any
    thread runs. Also flags a lexically nested re-``with`` of the same
    non-reentrant lock (instant self-deadlock). Lexical analysis only:
    a cross-function nesting is runtime lockdep's job."""

    code = "PL012"
    name = "lock-order-inversion"
    summary = "nested with-lock scopes form an ordering cycle"

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        graph: Dict[str, Set[str]] = {}
        sites: Dict[Tuple[str, str], Tuple[FileContext, ast.AST]] = {}
        self_deadlocks: List[Tuple[FileContext, ast.AST, str]] = []
        for ctx in project.contexts:
            if not _concurrency_scope(ctx) or ctx.tree is None:
                continue
            parents = ctx.parents
            for node in ctx.walk():
                if not isinstance(node, ast.With):
                    continue
                inner = self._with_keys(ctx, node)
                if not inner:
                    continue
                outer = self._outer_keys(ctx, node, parents)
                # multiple lockish items in ONE with are ordered too
                for i in range(len(inner)):
                    for j in range(i + 1, len(inner)):
                        graph.setdefault(inner[i], set()).add(inner[j])
                        sites.setdefault((inner[i], inner[j]),
                                         (ctx, node))
                for ok in outer:
                    for ik in inner:
                        if ok == ik:
                            if "rlock" not in ik.lower():
                                self_deadlocks.append((ctx, node, ik))
                            continue
                        graph.setdefault(ok, set()).add(ik)
                        sites.setdefault((ok, ik), (ctx, node))

        for ctx, node, key in self_deadlocks:
            yield self.finding(
                ctx, node,
                f"nested 'with' re-acquisition of the non-reentrant "
                f"lock {key!r}: a plain Lock self-deadlocks here — use "
                f"an RLock or restructure (runtime twin: "
                f"resilience.locks lockdep)")

        reported: Set[frozenset] = set()
        for a, b in sorted(sites):
            back = self._path(graph, b, a)
            if back is None:
                continue
            cycle = [a] + back  # a -> b -> ... -> a
            key = frozenset(cycle)
            if key in reported:
                continue
            reported.add(key)
            ctx, node = sites[(a, b)]
            others = ", ".join(
                f"{c2.relpath}:{n2.lineno}"
                for (x, y), (c2, n2) in sorted(sites.items())
                if x in key and y in key and (x, y) != (a, b))
            yield self.finding(
                ctx, node,
                f"lock-order inversion: acquisition cycle "
                f"{' -> '.join(cycle)} (other edge sites: "
                f"{others or 'same statement'}); pick ONE order and "
                f"document it in the ARCHITECTURE lock hierarchy")

    def _with_keys(self, ctx: FileContext, node: ast.With) -> List[str]:
        out = []
        for item in node.items:
            key = _lock_key(ctx, node, item.context_expr)
            if key is not None:
                out.append(key)
        return out

    def _outer_keys(self, ctx, node, parents) -> List[str]:
        out: List[str] = []
        cur = node
        while True:
            entry = parents.get(cur)
            if entry is None:
                break
            parent, field = entry
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                break  # a closure body runs later, outside the with
            if isinstance(parent, ast.With) and field == "body":
                out.extend(self._with_keys(ctx, parent))
            cur = parent
        return out

    @staticmethod
    def _path(graph: Dict[str, Set[str]], src: str,
              dst: str) -> Optional[List[str]]:
        if src == dst:
            return [src]
        seen = {src}
        frontier = [[src]]
        while frontier:
            nxt = []
            for path in frontier:
                for peer in sorted(graph.get(path[-1], ())):
                    if peer == dst:
                        return path + [dst]
                    if peer not in seen:
                        seen.add(peer)
                        nxt.append(path + [peer])
            frontier = nxt
        return None


# ---------------------------------------------------------------------------
# PL013 — blocking call while holding a lock


class BlockingWhileLockedRule(Rule):
    """A sleep / file-open / subprocess / jax dispatch / ``.result()`` /
    thread-join inside a ``with <lock>`` body serializes every peer of
    that lock behind wall-clock time the lock was never meant to cover —
    the shape behind PR 7's first watchdog deadline bugs (and the reason
    the scheduler's retry backoff runs on a timer thread, not under the
    lease). Move the blocking work outside the critical section; a
    deliberate exception carries a suppression with its reason."""

    code = "PL013"
    name = "blocking-while-locked"
    summary = "blocking call (sleep/IO/subprocess/jax/.result) under a lock"

    _BLOCKING_DOTTED = {
        "time.sleep", "os.replace", "os.rename", "os.fsync",
        "os.remove", "os.unlink", "shutil.rmtree", "shutil.copy",
        "shutil.copyfile", "shutil.disk_usage",
    }
    _BLOCKING_ATTRS = {"result", "block_until_ready", "device_put"}

    def applies_to(self, ctx: FileContext) -> bool:
        return _concurrency_scope(ctx)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        seen: Set[Tuple[int, int]] = set()  # nested lock withs: report once
        for node in ctx.walk():
            if not isinstance(node, ast.With):
                continue
            if not any(_lockish_name(item.context_expr)
                       for item in node.items):
                continue
            fn = _enclosing_fn(node, parents)
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    key = (sub.lineno, sub.col_offset)
                    if key in seen:
                        continue
                    if _enclosing_fn(sub, parents) is not fn:
                        continue  # closure body: runs later, unlocked
                    why = self._blocking(sub)
                    if why:
                        seen.add(key)
                        yield self.finding(
                            ctx, sub,
                            f"{why} inside a 'with <lock>' block: every "
                            f"peer of this lock now waits on wall-clock "
                            f"work the lock was not meant to cover — "
                            f"move it outside the critical section "
                            f"(scheduler precedent: retry backoff runs "
                            f"on a timer, never under the lease)")

    def _blocking(self, call: ast.Call) -> Optional[str]:
        cn = _call_name(call)
        if isinstance(call.func, ast.Name) and call.func.id == "open":
            return "file IO (open)"
        if cn == "sleep" or cn in self._BLOCKING_DOTTED:
            return f"blocking call {cn}()"
        if cn.startswith("subprocess."):
            return f"subprocess call {cn}()"
        root = cn.split(".", 1)[0]
        if root in ("jax", "jnp"):
            return f"jax dispatch {cn}()"
        if isinstance(call.func, ast.Attribute):
            attr = call.func.attr
            if attr in ("result", "block_until_ready") and not call.args:
                return f".{attr}() (blocks on async work)"
            if attr == "join" and self._threadish_join(call):
                return ".join() (blocks on another thread)"
        return None

    @staticmethod
    def _threadish_join(call: ast.Call) -> bool:
        """``t.join()`` / ``t.join(5)`` / ``t.join(timeout=...)`` —
        but never ``sep.join(parts)`` (one non-numeric positional)."""
        if any(kw.arg == "timeout" for kw in call.keywords):
            return True
        if not call.args and not call.keywords:
            return True
        if len(call.args) == 1 and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, (int, float)):
            return True
        return False


# ---------------------------------------------------------------------------
# PL014 — bare .acquire() without try/finally release


class BareAcquireRule(Rule):
    """``lock.acquire()`` with no ``try/finally: lock.release()`` leaks
    the lock on ANY exception between acquire and release — including
    the watchdog's async interrupts, which land at an arbitrary bytecode
    boundary. Use ``with lock:`` (preferred — the tracked wrappers make
    it lockdep-visible too), or acquire immediately before a
    ``try/finally`` that releases."""

    code = "PL014"
    name = "bare-acquire"
    summary = ".acquire() without a try/finally release"

    def applies_to(self, ctx: FileContext) -> bool:
        return _concurrency_scope(ctx)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        for node in ctx.walk():
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "acquire"):
                continue
            if _lockish_name(node.func.value) is None:
                continue
            chain = _attr_chain(node.func.value)
            if self._guarded(node, chain, parents):
                continue
            yield self.finding(
                ctx, node,
                f"bare {chain}.acquire() with no try/finally release: "
                f"any exception (including a watchdog async interrupt) "
                f"between acquire and release strands the lock — use "
                f"'with {chain}:' or acquire directly before a "
                f"try/finally that releases")

    def _guarded(self, node, chain: str, parents) -> bool:
        # (a) inside a Try whose finalbody releases the same lock
        cur = node
        while True:
            entry = parents.get(cur)
            if entry is None:
                break
            parent, field = entry
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                break
            if isinstance(parent, ast.Try) and field == "body" \
                    and self._releases(parent.finalbody, chain):
                return True
            cur = parent
        # (b) the acquire's statement is immediately followed by such a
        # Try (the classic acquire-then-guard idiom)
        stmt = node
        while stmt is not None and not isinstance(stmt, ast.stmt):
            entry = parents.get(stmt)
            stmt = entry[0] if entry else None
        if stmt is None:
            return False
        entry = parents.get(stmt)
        if entry is None:
            return False
        parent, field = entry
        body = getattr(parent, field, None)
        if not isinstance(body, list) or stmt not in body:
            return False
        idx = body.index(stmt)
        if idx + 1 < len(body):
            nxt = body[idx + 1]
            if isinstance(nxt, ast.Try) \
                    and self._releases(nxt.finalbody, chain):
                return True
        return False

    @staticmethod
    def _releases(stmts, chain: str) -> bool:
        for stmt in stmts:
            for sub in ast.walk(stmt):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "release"
                        and _attr_chain(sub.func.value) == chain):
                    return True
        return False


# ---------------------------------------------------------------------------
# PL015 — Condition.wait outside a predicate while loop


class ConditionWaitPredicateRule(Rule):
    """``cv.wait()`` not inside a ``while`` loop: condition variables
    have spurious wakeups and lost-wakeup races by contract — a bare
    ``if``/straight-line wait resumes with the predicate still false
    (the lost-completion shape PR 13 fixed in review). Re-test the
    predicate in a loop (``while not pred: cv.wait()``), or use
    ``cv.wait_for(pred)``."""

    code = "PL015"
    name = "condition-wait-no-predicate-loop"
    summary = "Condition.wait outside a predicate while loop"

    def applies_to(self, ctx: FileContext) -> bool:
        return _concurrency_scope(ctx)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        for node in ctx.walk():
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "wait"):
                continue
            recv = node.func.value
            tail = None
            if isinstance(recv, ast.Name):
                tail = recv.id
            elif isinstance(recv, ast.Attribute):
                tail = recv.attr
            if tail is None or not _CONDISH_RE.search(tail):
                continue
            if self._in_while(node, parents):
                continue
            yield self.finding(
                ctx, node,
                f"{_attr_chain(recv)}.wait() outside a predicate while "
                f"loop: spurious wakeups and notify races resume with "
                f"the predicate still false — 'while not <pred>: "
                f"{tail}.wait()' or wait_for(<pred>)")

    @staticmethod
    def _in_while(node, parents) -> bool:
        cur = node
        while True:
            entry = parents.get(cur)
            if entry is None:
                return False
            parent, _ = entry
            if isinstance(parent, ast.While):
                return True
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                return False
            cur = parent


# ---------------------------------------------------------------------------
# PL016 — threads without daemon-or-join discipline


class ThreadDisciplineRule(Rule):
    """A ``threading.Thread``/``Timer`` that is neither ``daemon=True``
    nor joined in its creating function outlives the fleet that spawned
    it: a non-daemon orphan blocks interpreter exit (the survey CLI
    hangs after the run 'finished'), and an unjoined worker races
    teardown for shared state. Every thread in this runtime declares its
    lifetime: daemon (watchdog, heartbeat renewers, prefetch producers,
    retry timers) or joined (lane workers, claim loop)."""

    code = "PL016"
    name = "thread-without-daemon-or-join"
    summary = "threading.Thread/Timer with neither daemon=True nor a join"

    _CTORS = {"threading.Thread", "Thread", "threading.Timer", "Timer"}

    def applies_to(self, ctx: FileContext) -> bool:
        return _concurrency_scope(ctx)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        parents = ctx.parents
        for node in ctx.walk():
            if not (isinstance(node, ast.Call)
                    and _call_name(node) in self._CTORS):
                continue
            if any(kw.arg == "daemon" and isinstance(kw.value, ast.Constant)
                   and kw.value.value is True for kw in node.keywords):
                continue
            fn = _enclosing_fn(node, parents)
            scope = fn if fn is not None else None
            if scope is not None and self._disciplined(scope):
                continue
            yield self.finding(
                ctx, node,
                f"{_call_name(node)}(...) with neither daemon=True nor "
                f"a join in the creating function: a non-daemon orphan "
                f"blocks interpreter exit and races teardown — declare "
                f"the thread's lifetime (daemon=True, t.daemon = True, "
                f"or join it)")

    @staticmethod
    def _disciplined(fn) -> bool:
        for sub in ast.walk(fn):
            # <var>.daemon = True
            if isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    if (isinstance(tgt, ast.Attribute)
                            and tgt.attr == "daemon"
                            and isinstance(sub.value, ast.Constant)
                            and sub.value.value is True):
                        return True
            # a thread-shaped .join() anywhere in the function
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "join"
                    and BlockingWhileLockedRule._threadish_join(sub)):
                return True
        return False


# ---------------------------------------------------------------------------
# PL017 — telemetry name drift between emitters and consumers


class TelemetryNameDriftRule(ProjectRule):
    """Telemetry names are a cross-file contract with no compiler: the
    tree emits ``telemetry.event("survey.slo_burn", ...)`` and tlmsum /
    bench / the tests consume the same dotted literal.  Rename one side
    and the other silently reads zeros — the observability flavor of
    PL004's knob drift (round 21).  Two directions, scoped to the
    dotted ``survey.`` / ``tune.`` families:

    - a consumer literal (``pypulsar_tpu/obs/summarize.py``,
      ``bench.py``, ``tests/``) nothing in the production tree emits is
      drift — the consumer reads a channel that never carries data;
    - a production ``event()`` literal no consumer references is drift
      the other way — a verdict nobody renders or asserts.  (Counters,
      gauges and spans render generically in tlmsum, so only the
      event channel — the verdict channel — needs a named consumer.)

    Emission counts via a literal first argument to ``counter`` /
    ``event`` / ``gauge`` / ``span`` / ``record_span``, an f-string
    family prefix (dynamic stage names), or a production string
    assignment that flows into an emit call (the watchdog's
    ``name = "survey.deadline_exceeded"`` shape).  Fault-point
    literals (PL005's domain) are excluded in both directions."""

    code = "PL017"
    name = "telemetry-name-drift"
    summary = "telemetry name referenced on one side of the emit/consume contract only"

    _FAMILIES = ("survey.", "tune.")
    _EMIT_FNS = ("counter", "event", "gauge", "span", "record_span")
    _FAULT_FNS = ("trip", "trip_data", "hits", "configure",
                  "parse_chaos_spec")
    _NAME_RE = re.compile(
        r"^(?:survey|tune)\.[A-Za-z0-9_.]*[A-Za-z0-9_]$")
    # dotted names that are files, not telemetry channels
    _EXT = (".json", ".jsonl", ".npz", ".npy", ".out", ".txt", ".fil",
            ".dat", ".csv", ".md")

    @classmethod
    def _is_name(cls, s: str) -> bool:
        return bool(cls._NAME_RE.match(s)) \
            and not s.endswith(cls._EXT)

    @staticmethod
    def _is_consumer(ctx: FileContext) -> bool:
        if ctx.relpath.rsplit("/", 1)[-1] == "test_psrlint.py":
            # the linter's own tests assert on fixture names that are
            # drift BY DESIGN — they are specimens, not consumers
            return False
        return (_is_test(ctx) or ctx.relpath == "bench.py"
                or ctx.relpath == "pypulsar_tpu/obs/summarize.py")

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        emitted: Set[str] = set()
        emit_prefixes: Set[str] = set()
        event_sites: List[Tuple[FileContext, ast.AST, str]] = []
        fault_exact: Set[str] = set()
        fault_prefixes: Set[str] = set()
        consumed: Dict[str, List[Tuple[FileContext, ast.AST]]] = {}

        for ctx in project.contexts:
            is_prod = _in_package(ctx) and not _is_test(ctx)
            for node in ctx.walk():
                if isinstance(node, ast.Call):
                    fn = _call_name(node).split(".")[-1]
                    if fn in self._EMIT_FNS and node.args and is_prod:
                        arg = node.args[0]
                        s = _const_str(arg)
                        if s is not None and self._is_name(s):
                            emitted.add(s)
                            if fn == "event":
                                event_sites.append((ctx, node, s))
                        elif isinstance(arg, ast.JoinedStr) and arg.values:
                            fs = _const_str(arg.values[0])
                            if fs and fs.startswith(self._FAMILIES):
                                emit_prefixes.add(fs)
                    elif fn in self._FAULT_FNS and node.args:
                        arg = node.args[0]
                        s = _const_str(arg)
                        if s is not None:
                            fault_exact.add(s)
                        elif isinstance(arg, ast.JoinedStr) and arg.values:
                            fs = _const_str(arg.values[0])
                            if fs:
                                fault_prefixes.add(fs)
                elif isinstance(node, ast.Assign) and is_prod:
                    # the variable-flow shape: name = "survey.x" feeding
                    # a later emit call in the same production file
                    s = _const_str(node.value)
                    if s is not None and self._is_name(s):
                        emitted.add(s)
                if self._is_consumer(ctx):
                    s = _const_str(node)
                    if s is not None and self._is_name(s):
                        consumed.setdefault(s, []).append((ctx, node))

        def _is_fault_point(s: str) -> bool:
            return (s in fault_exact
                    or any(s.startswith(p) for p in fault_prefixes if p))

        # direction 1: consumer literal nothing emits
        seen: Set[Tuple[str, str]] = set()
        for s, sites in sorted(consumed.items()):
            if s in emitted or _is_fault_point(s):
                continue
            if any(s.startswith(p) for p in emit_prefixes):
                continue
            for ctx, node in sites:
                key = (ctx.relpath, s)
                if key in seen:
                    continue
                seen.add(key)
                yield self.finding(
                    ctx, node,
                    f"telemetry name '{s}' is consumed here but nothing "
                    f"in the tree emits it — the consumer reads a "
                    f"channel that never carries data (rename drift?)")

        # direction 2: production event nobody consumes
        seen2: Set[str] = set()
        for ctx, node, s in event_sites:
            if s in consumed or _is_fault_point(s) or s in seen2:
                continue
            seen2.add(s)
            yield self.finding(
                ctx, node,
                f"telemetry event '{s}' is emitted here but no consumer "
                f"(tlmsum, bench.py, tests/) references it — a verdict "
                f"nobody renders or asserts (rename drift?)")


# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# PL018 — raw jax.jit outside the compilation plane


class RawJitRule(Rule):
    """Raw ``jax.jit`` bypasses the compilation plane (round 22): a
    directly-jitted kernel gets no AOT executable registry entry, no
    compile telemetry (``compile.cache_miss`` stays blind to it) and no
    warm-pool precompile — exactly the critical-path trace+compile
    stall the plane exists to remove.  Every jit in the tree goes
    through :func:`pypulsar_tpu.compile.plane_jit` except the plane
    itself and the ``ops/`` leaf-kernel modules registered in
    :data:`pypulsar_tpu.compile.registry.OPS_LEAF_ALLOWLIST` (their
    call sites are reached through plane-wrapped stage runners one
    layer up, so re-wrapping them would double-count the same
    compiles).

    Any ``jax.jit`` attribute reference counts — ``@jax.jit``
    decorators (bare or parameterized), direct ``jax.jit(fn)`` calls,
    and indirections like ``functools.partial(jax.jit, ...)``.  Other
    modules' ``.jit`` attributes (``self.jit``, ``nn.jit``) and the
    word in strings/comments stay silent.  Tests are exempt."""

    code = "PL018"
    name = "raw-jax-jit"
    summary = "raw jax.jit outside the compilation plane; use compile.plane_jit"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _in_package(ctx) or _is_test(ctx):
            return
        if ctx.relpath.startswith("pypulsar_tpu/compile/"):
            return
        from pypulsar_tpu.compile.registry import OPS_LEAF_ALLOWLIST

        if ctx.relpath in OPS_LEAF_ALLOWLIST:
            return
        for node in ctx.walk():
            if (isinstance(node, ast.Attribute) and node.attr == "jit"
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "jax"):
                yield self.finding(
                    ctx, node,
                    "raw jax.jit bypasses the compilation plane (no AOT "
                    "registry entry, no compile telemetry, no warm-pool "
                    "precompile); use pypulsar_tpu.compile.plane_jit")


ALL_RULES: Tuple[type, ...] = (
    TruedivIndexRule, BareJaxDevicesRule, NonAtomicWriteRule,
    KnobRegistryDriftRule, DeadFaultPointRule, RawHeaderReadRule,
    MutableDefaultRule, SpanLeakRule, SwallowedFaultRule,
    RawKnobReadRule, LockOrderInversionRule, BlockingWhileLockedRule,
    BareAcquireRule, ConditionWaitPredicateRule, ThreadDisciplineRule,
    TelemetryNameDriftRule, RawJitRule,
)


def all_rules() -> List[Rule]:
    """Fresh instances of the full catalog, code order."""
    return [cls() for cls in ALL_RULES]
