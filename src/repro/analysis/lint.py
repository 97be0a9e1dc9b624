"""repro-lint: AST-based JAX-hygiene linter for this repository.

Rules (each finding carries file:line:col, a rule id and a fix hint):

* **RPR001** — deprecated pre-engine entry points (``fleet_fit``,
  ``sharded_fleet_fit``, ``federated_fit``, ``fit_on_mesh``) called
  anywhere outside their deprecation shims.  New code goes through
  ``DAEFEngine`` / ``ExecutionPlan``.
* **RPR002** — ``os.environ`` / ``os.getenv`` read inside a jit-traced
  body (the value is baked into one trace and the jit cache goes stale
  when the env flips), or at import time of a library module (the
  process can never flip it again).  Read it in a plain function called
  before any trace and pass the value on — the ``compile_cache.enable()``
  idiom.
* **RPR003** — host ``np.*`` call applied to a value that flows from a
  jit-traced function's parameters: a tracer leak (``TracerArrayConversionError``
  at best, a silent device sync at worst).  Use ``jnp.*`` inside traced code.
* **RPR004** — Python ``if``/``while`` on a tracer-valued expression
  inside a jit-traced function (``TracerBoolConversionError`` under
  jit).  Branch with ``lax.cond``/``jnp.where``, or mark the argument
  static.  Static attributes (``.shape``/``.ndim``/``.dtype``/``.size``,
  ``len()``, ``isinstance()``) are recognised and allowed.
* **RPR005** — blanket ``warnings.filterwarnings("ignore")`` /
  ``warnings.simplefilter("ignore")`` without a ``message=``/
  ``category=``/``module=`` filter: swallows every future warning in the
  process, including the retrace/donation diagnostics this package
  exists to surface.
* **RPR006** — ``time.time()``/``time.perf_counter()`` or the stdlib
  ``random`` module in library code (``src/repro`` outside ``launch/``):
  library results must be deterministic and trace-safe; wall-clock and
  host RNG belong in drivers and benchmarks.
* **RPR007** — ``jax.random.PRNGKey(<literal>)`` or the stdlib
  ``random`` module in privacy code (``src/repro/privacy/``): a
  hard-coded key makes every DP noise draw predictable (and reused
  across releases — a catastrophic privacy failure, not a flaky test),
  and unseeded host RNG is unauditable.  Release keys must be derived
  per (site, round) from the config seed (``fold_in`` — the
  ``FederationSession._dp_key`` idiom) and passed IN.
* **RPR008** — hard-coded ``interpret=True`` in library code
  (``src/repro`` outside ``kernels/*/ref.py``): pins every caller to the
  Pallas interpreter, silently discarding accelerator compilation.
  Backend selection belongs to the resolver chain
  (``rolann_stats.ops._resolve_interpret``: explicit arg, else
  interpret iff the backend is the CPU); reference oracles under ``kernels/*/ref.py`` are exempt.

Escapes: append ``# repro-lint: disable=RPR001`` (comma-separate several
ids) to a line to suppress findings on it, or grandfather existing
findings in a baseline file of ``path RULE count`` lines (see
``--write-baseline``).  A file whose first lines contain
``# repro-lint: library`` opts into the library-scoped rules regardless
of its path; ``# repro-lint: privacy`` does the same for the
privacy-scoped rule.

CLI::

    python -m repro.analysis.lint [--baseline FILE] [--write-baseline FILE] paths...

(also reachable as ``python -m repro.analysis paths...``).  Directories
are walked recursively for ``*.py``, skipping ``lint_fixtures``/hidden
dirs; explicitly named files are always linted.  Exit code 1 iff
findings remain after disables and baseline subtraction.
"""
from __future__ import annotations

import argparse
import ast
import re
import sys
from collections import Counter
from dataclasses import dataclass
from pathlib import Path

DEPRECATED_ENTRY_POINTS = {
    "fleet_fit": "DAEFEngine(config, ExecutionPlan(mode='vmap', tenants=k)).fit",
    "sharded_fleet_fit": "DAEFEngine(config, ExecutionPlan(mode='mesh', tenants=k)).fit",
    "federated_fit": "DAEFEngine(config, plan).session().round(parts)",
    "fit_on_mesh": "DAEFEngine(config, ExecutionPlan(mode='mesh', mesh_axes=...)).fit",
}

#: Attributes that are static (python-level) even on a tracer — reading
#: them never leaks a traced value into host control flow.
STATIC_TRACER_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding", "weak_type"}
#: Builtins whose result on a tracer is static.
STATIC_CALLS = {"len", "isinstance", "type", "id", "repr", "str", "hash"}

DISABLE_RE = re.compile(r"#\s*repro-lint:\s*disable=([A-Z0-9,\s]+)")
LIBRARY_MARK_RE = re.compile(r"#\s*repro-lint:\s*library\b")
PRIVACY_MARK_RE = re.compile(r"#\s*repro-lint:\s*privacy\b")

RULES = {
    "RPR001": "deprecated pre-engine entry point",
    "RPR002": "env read at import/trace time",
    "RPR003": "host np.* on a traced value",
    "RPR004": "python control flow on a traced value",
    "RPR005": "blanket warnings filter",
    "RPR006": "wall-clock/stdlib random in library code",
    "RPR007": "fixed PRNG key / host randomness in privacy code",
    "RPR008": "hard-coded interpret=True in library code",
}


@dataclass(frozen=True)
class Finding:
    """One lint finding: location, rule id, message and a fix hint."""

    path: str
    line: int
    col: int
    rule: str
    message: str
    hint: str

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message} (hint: {self.hint})")


# ---------------------------------------------------------------------------
# Helpers: name resolution on the AST
# ---------------------------------------------------------------------------

def _dotted(node: ast.expr) -> str | None:
    """'a.b.c' for nested Attribute/Name chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> str | None:
    return _dotted(call.func)


def _const_str_items(node: ast.expr | None) -> list[str]:
    """String constants from a str / tuple / list literal."""
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _const_int_items(node: ast.expr | None) -> list[int]:
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


class _Imports(ast.NodeVisitor):
    """Track what local names the interesting modules are bound to."""

    def __init__(self) -> None:
        self.numpy: set[str] = set()        # `import numpy as np` -> {"np"}
        self.stdlib_random = False          # `import random`
        self.stdlib_time: set[str] = set()  # names bound to stdlib time
        self.jit_names: set[str] = set()    # names that mean jax.jit
        self.partial_names: set[str] = set()  # names that mean functools.partial

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            bound = alias.asname or alias.name.split(".")[0]
            if alias.name == "numpy" or alias.name.startswith("numpy."):
                self.numpy.add(bound)
            if alias.name == "random":
                self.stdlib_random = True
            if alias.name == "time":
                self.stdlib_time.add(bound)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "jax":
            for alias in node.names:
                if alias.name == "jit":
                    self.jit_names.add(alias.asname or "jit")
        if node.module == "functools":
            for alias in node.names:
                if alias.name == "partial":
                    self.partial_names.add(alias.asname or "partial")


def _is_jax_jit(node: ast.expr, imports: _Imports) -> bool:
    name = _dotted(node)
    return name in ({"jax.jit"} | imports.jit_names)


def _jit_decorator_info(dec: ast.expr, imports: _Imports):
    """(is_jit, static_argnames, static_argnums) for one decorator node.

    Recognises ``@jax.jit``, ``@jit``, ``@jax.jit(...)``, and
    ``@partial(jax.jit, ...)`` / ``@functools.partial(jax.jit, ...)``.
    """
    if _is_jax_jit(dec, imports):
        return True, [], []
    if isinstance(dec, ast.Call):
        callee = _dotted(dec.func)
        is_partial = callee in (
            {"functools.partial"} | imports.partial_names
        ) and dec.args and _is_jax_jit(dec.args[0], imports)
        if is_partial or _is_jax_jit(dec.func, imports):
            names = [kw.value for kw in dec.keywords
                     if kw.arg == "static_argnames"]
            nums = [kw.value for kw in dec.keywords
                    if kw.arg == "static_argnums"]
            return (True,
                    _const_str_items(names[0] if names else None),
                    _const_int_items(nums[0] if nums else None))
    return False, [], []


def _param_names(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)] + (
        [a.vararg.arg] if a.vararg else []
    ) + ([a.kwarg.arg] if a.kwarg else [])


# ---------------------------------------------------------------------------
# Taint: which names (can) hold traced values inside a jitted body
# ---------------------------------------------------------------------------

class _TaintWalker:
    """Forward-propagates "derived from a traced parameter" through the
    straight-line assignments of a jitted function body.  Two passes so
    names assigned late but used early in loops still taint."""

    def __init__(self, tainted: set[str]):
        self.tainted = set(tainted)

    def references_tainted(self, node: ast.expr) -> bool:
        """Does ``node`` read a tainted name *as a traced value*?

        Subtrees that produce static values are skipped: static
        attributes (``x.shape`` ...), ``len(x)``/``isinstance(x, ...)``,
        and string-y contexts (f-string conversions stay flagged — they
        force the value to host anyway, but that is RPR003's business
        only when np is involved).
        """
        return self._walk(node)

    def _walk(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in STATIC_TRACER_ATTRS:
            return False
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            if callee in STATIC_CALLS:
                return False
        if isinstance(node, ast.Name):
            return isinstance(node.ctx, ast.Load) and node.id in self.tainted
        return any(self._walk(child) for child in ast.iter_child_nodes(node))

    def _taint_target(self, target: ast.expr) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                self.tainted.add(n.id)

    def propagate(self, body: list[ast.stmt]) -> None:
        for _ in range(2):
            for stmt in body:
                for node in ast.walk(stmt):
                    if isinstance(node, ast.Assign):
                        if self._walk(node.value):
                            for t in node.targets:
                                self._taint_target(t)
                    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                        if node.value is not None and self._walk(node.value):
                            self._taint_target(node.target)
                    elif isinstance(node, ast.For):
                        if self._walk(node.iter):
                            self._taint_target(node.target)
                    elif isinstance(node, ast.withitem):
                        if node.optional_vars is not None and \
                                self._walk(node.context_expr):
                            self._taint_target(node.optional_vars)
                    elif isinstance(node, (ast.FunctionDef,
                                           ast.AsyncFunctionDef, ast.Lambda)):
                        # A def nested in a jitted body (scan/cond bodies,
                        # vmapped closures) receives traced values too.
                        if isinstance(node, ast.Lambda):
                            a = node.args
                            for p in (*a.posonlyargs, *a.args, *a.kwonlyargs):
                                self.tainted.add(p.arg)
                        else:
                            self.tainted.update(_param_names(node))


# ---------------------------------------------------------------------------
# The per-file checker
# ---------------------------------------------------------------------------

class _Checker(ast.NodeVisitor):
    def __init__(self, path: str, source: str, *, library: bool,
                 privacy: bool = False, kernel_ref: bool = False):
        self.path = path
        self.library = library
        self.privacy = privacy
        self.kernel_ref = kernel_ref
        self.findings: list[Finding] = []
        self.imports = _Imports()
        self._fn_stack: list[ast.FunctionDef | ast.AsyncFunctionDef] = []
        self._jit_stack: list[_TaintWalker] = []
        self._disables: dict[int, set[str]] = {}
        for i, line in enumerate(source.splitlines(), start=1):
            m = DISABLE_RE.search(line)
            if m:
                self._disables[i] = {
                    r.strip() for r in m.group(1).split(",") if r.strip()
                }

    # -- plumbing ----------------------------------------------------------

    def add(self, node: ast.AST, rule: str, message: str, hint: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in self._disables.get(line, ()):
            return
        self.findings.append(Finding(
            path=self.path, line=line, col=getattr(node, "col_offset", 0) + 1,
            rule=rule, message=message, hint=hint,
        ))

    @property
    def _taint(self) -> _TaintWalker | None:
        return self._jit_stack[-1] if self._jit_stack else None

    def _at_module_level(self) -> bool:
        return not self._fn_stack

    # -- imports -----------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        self.imports.visit_Import(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        self.imports.visit_ImportFrom(node)

    # -- function scoping / jit detection ----------------------------------

    def _visit_fn(self, node: ast.FunctionDef | ast.AsyncFunctionDef) -> None:
        is_jit, static_names, static_nums = False, [], []
        for dec in node.decorator_list:
            is_jit, static_names, static_nums = _jit_decorator_info(
                dec, self.imports
            )
            if is_jit:
                break
        self._fn_stack.append(node)
        if is_jit:
            params = _param_names(node)
            static = set(static_names)
            static.update(params[i] for i in static_nums if i < len(params))
            tainted = {p for p in params if p not in static and p != "self"}
            walker = _TaintWalker(tainted)
            walker.propagate(node.body)
            self._jit_stack.append(walker)
        self.generic_visit(node)
        if is_jit:
            self._jit_stack.pop()
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    # -- RPR002: env reads -------------------------------------------------

    def _check_env_read(self, node: ast.AST, what: str) -> None:
        if self._jit_stack:
            self.add(
                node, "RPR002",
                f"{what} inside a jit-traced body: the value is baked into "
                "this trace and the cache goes stale when the env flips",
                "read it before trace time and pass the value in (the "
                "compile_cache.enable() idiom)",
            )
        elif self.library and self._at_module_level():
            self.add(
                node, "RPR002",
                f"{what} at import time of a library module: the process "
                "can never flip it again (tests/serving cannot override "
                "per-call)",
                "move the read into the function that consumes it, "
                "resolved at call time",
            )

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if _dotted(node) == "os.environ":
            self._check_env_read(node, "os.environ read")
        self.generic_visit(node)

    # -- calls: RPR001 / RPR002(getenv) / RPR003 / RPR005 / RPR006 ---------

    def visit_Call(self, node: ast.Call) -> None:
        callee = _dotted(node.func) or ""
        leaf = callee.rsplit(".", 1)[-1]

        if leaf in DEPRECATED_ENTRY_POINTS:
            self.add(
                node, "RPR001",
                f"deprecated pre-engine entry point {leaf}() — placement is "
                "an ExecutionPlan field, not a module choice",
                f"use {DEPRECATED_ENTRY_POINTS[leaf]}",
            )

        if callee == "os.getenv":
            self._check_env_read(node, "os.getenv()")

        if self._taint is not None:
            root = callee.split(".", 1)[0]
            if root in self.imports.numpy and callee != root:
                if any(self._taint.references_tainted(a) for a in node.args) \
                        or any(self._taint.references_tainted(kw.value)
                               for kw in node.keywords):
                    self.add(
                        node, "RPR003",
                        f"host {callee}() applied to a value derived from a "
                        "jit parameter: tracer leak / hidden device sync",
                        "use the jnp equivalent inside traced code, or hoist "
                        "the host step out of the jitted function",
                    )

        if callee in ("warnings.filterwarnings", "warnings.simplefilter"):
            action = node.args[0].value if node.args and isinstance(
                node.args[0], ast.Constant) else next(
                (kw.value.value for kw in node.keywords
                 if kw.arg == "action" and isinstance(kw.value, ast.Constant)),
                None,
            )
            narrowing = {kw.arg for kw in node.keywords} & \
                {"message", "category", "module"}
            if callee == "warnings.simplefilter" and len(node.args) > 1:
                narrowing.add("category")
            if action == "ignore" and not narrowing:
                self.add(
                    node, "RPR005",
                    "blanket warnings ignore without a message/category/"
                    "module filter swallows every future diagnostic in the "
                    "process",
                    "narrow with message=... / category=..., or probe the "
                    "fact once instead (repro.analysis.donation)",
                )

        if self.library:
            if callee in ("time.time", "time.perf_counter", "time.monotonic") \
                    and callee.split(".", 1)[0] in self.imports.stdlib_time:
                self.add(
                    node, "RPR006",
                    f"{callee}() in library code: wall-clock makes library "
                    "results nondeterministic and is a host sync under jit",
                    "time in drivers/benchmarks; pass timestamps in as data",
                )
            if self.imports.stdlib_random and callee.startswith("random."):
                self.add(
                    node, "RPR006",
                    f"stdlib {callee}() in library code: unseeded host RNG "
                    "breaks reproducibility",
                    "use jax.random with an explicit key (or numpy "
                    "default_rng in host-side test/driver code)",
                )

        if self.library and not self.kernel_ref:
            for kw in node.keywords:
                if kw.arg == "interpret" and isinstance(
                    kw.value, ast.Constant
                ) and kw.value.value is True:
                    self.add(
                        kw.value, "RPR008",
                        "hard-coded interpret=True in library code pins this "
                        "call to the Pallas interpreter — accelerator "
                        "compilation is silently discarded for every caller",
                        "pass interpret through (None resolves via "
                        "rolann_stats.ops._resolve_interpret: explicit arg, "
                        "else interpret iff the backend is the CPU); only "
                        "kernels/*/ref.py oracles may pin it",
                    )

        if self.privacy:
            if leaf == "PRNGKey" and node.args and isinstance(
                node.args[0], ast.Constant
            ):
                self.add(
                    node, "RPR007",
                    f"hard-coded {callee}({node.args[0].value!r}) in privacy "
                    "code: a fixed key makes every DP noise draw predictable "
                    "and REUSED across releases",
                    "derive the release key per (site, round) from the "
                    "config seed via fold_in and pass it in "
                    "(FederationSession._dp_key)",
                )
            if self.imports.stdlib_random and callee.startswith("random."):
                self.add(
                    node, "RPR007",
                    f"stdlib {callee}() in privacy code: host RNG is "
                    "unauditable — noise calibration cannot be verified or "
                    "reproduced",
                    "draw noise from jax.random with a keyed, per-release "
                    "key (or a hash-seeded numpy Generator for secagg masks)",
                )
        self.generic_visit(node)

    # -- RPR004: control flow on tracers -----------------------------------

    def _check_branch(self, node: ast.If | ast.While, kind: str) -> None:
        if self._taint is not None and \
                self._taint.references_tainted(node.test):
            self.add(
                node, "RPR004",
                f"python `{kind}` on a tracer-valued expression inside a "
                "jit-traced function (TracerBoolConversionError under jit)",
                "use lax.cond/jnp.where, or mark the driving argument "
                "static if it is configuration",
            )
        self.generic_visit(node)

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node, "if")

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node, "while")


# ---------------------------------------------------------------------------
# File / path drivers
# ---------------------------------------------------------------------------

def _is_library_path(path: Path) -> bool:
    parts = path.resolve().parts
    if "repro" in parts and "src" in parts:
        sub = parts[parts.index("repro") + 1:]
        return bool(sub) and sub[0] != "launch"
    return False


def _is_privacy_path(path: Path) -> bool:
    parts = path.resolve().parts
    if "repro" in parts and "src" in parts:
        sub = parts[parts.index("repro") + 1:]
        return bool(sub) and sub[0] == "privacy"
    return False


def _is_kernel_ref_path(path: Path) -> bool:
    """``src/repro/kernels/<kernel>/ref.py`` — the pure-jnp oracles, the one
    place a pinned ``interpret=True`` is legitimate (RPR008 exemption)."""
    parts = path.resolve().parts
    if "repro" in parts and "src" in parts:
        sub = parts[parts.index("repro") + 1:]
        return len(sub) >= 2 and sub[0] == "kernels" and sub[-1] == "ref.py"
    return False


def check_source(source: str, path: str = "<string>",
                 *, library: bool | None = None,
                 privacy: bool | None = None) -> list[Finding]:
    """Lint one source string; ``library``/``privacy`` force the scoped
    rules on or off (default: from the path / the ``# repro-lint:
    library`` / ``# repro-lint: privacy`` marks)."""
    head = "\n".join(source.splitlines()[:10])
    if library is None:
        library = bool(LIBRARY_MARK_RE.search(head)) or \
            _is_library_path(Path(path))
    if privacy is None:
        privacy = bool(PRIVACY_MARK_RE.search(head)) or \
            _is_privacy_path(Path(path))
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path=path, line=e.lineno or 0, col=e.offset or 0,
                        rule="RPR000", message=f"syntax error: {e.msg}",
                        hint="fix the file before linting")]
    checker = _Checker(path, source, library=library, privacy=privacy,
                       kernel_ref=_is_kernel_ref_path(Path(path)))
    checker.visit(tree)
    return sorted(checker.findings, key=lambda f: (f.line, f.col, f.rule))


def check_path(path: str | Path, *, library: bool | None = None,
               privacy: bool | None = None) -> list[Finding]:
    """Lint one file on disk."""
    p = Path(path)
    return check_source(p.read_text(), str(p), library=library,
                        privacy=privacy)


SKIP_DIRS = {"lint_fixtures", "__pycache__", ".git", ".ruff_cache",
             "node_modules", ".venv"}


def collect_files(paths: list[str]) -> list[Path]:
    """Expand the CLI path arguments: directories are walked for ``*.py``
    (skipping fixture/hidden dirs); explicit files are always included."""
    out: list[Path] = []
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not SKIP_DIRS.intersection(f.parts):
                    out.append(f)
        else:
            out.append(p)
    return out


# ---------------------------------------------------------------------------
# Baseline: grandfathered findings as `path RULE count` lines
# ---------------------------------------------------------------------------

def load_baseline(path: Path) -> Counter:
    """Baseline counts keyed ``(path, rule)``.  Count-based (not
    line-based) so unrelated edits to a grandfathered file don't churn
    the baseline; *new* findings of a baselined rule still fail because
    they exceed the recorded count."""
    counts: Counter = Counter()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        try:
            file_part, rule, count = line.split()
            counts[(file_part, rule)] += int(count)
        except ValueError as e:
            raise SystemExit(
                f"{path}:{i}: bad baseline line {line!r} "
                "(want: <path> <RULE> <count>)"
            ) from e
    return counts


def apply_baseline(findings: list[Finding], baseline: Counter,
                   root: Path | None = None
                   ) -> tuple[list[Finding], Counter]:
    """(kept findings, stale entries).  Earliest findings are the ones
    grandfathered; stale = baselined counts no longer reached.

    Baseline keys are repo-relative; ``root`` (normally the baseline
    file's directory) lets absolute finding paths match them.
    """
    remaining = Counter(baseline)
    kept = []
    for f in findings:
        p = Path(f.path)
        if root is not None and p.is_absolute():
            try:
                p = p.resolve().relative_to(root)
            except ValueError:
                pass
        key = (p.as_posix(), f.rule)
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
        else:
            kept.append(f)
    stale = Counter({k: v for k, v in remaining.items() if v > 0})
    return kept, stale


def write_baseline(findings: list[Finding], path: Path) -> None:
    counts: Counter = Counter(
        (str(Path(f.path).as_posix()), f.rule) for f in findings
    )
    lines = [
        "# repro-lint baseline: grandfathered findings as `path RULE count`.",
        "# Regenerate with: python -m repro.analysis --write-baseline "
        f"{path.name} <paths>",
    ]
    lines += [f"{p} {rule} {n}" for (p, rule), n in sorted(counts.items())]
    path.write_text("\n".join(lines) + "\n")


DEFAULT_BASELINE = "repro-lint.baseline"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repro-lint: repo-specific JAX-hygiene static analysis",
    )
    parser.add_argument("paths", nargs="+", help="files or directories")
    parser.add_argument("--baseline", default=None,
                        help=f"baseline file (default: ./{DEFAULT_BASELINE} "
                             "when present)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore any baseline file")
    parser.add_argument("--write-baseline", metavar="FILE",
                        help="write current findings as the new baseline "
                             "and exit 0")
    args = parser.parse_args(argv)

    findings: list[Finding] = []
    files = collect_files(args.paths)
    for f in files:
        findings.extend(check_path(f))

    if args.write_baseline:
        write_baseline(findings, Path(args.write_baseline))
        print(f"wrote {len(findings)} finding(s) to {args.write_baseline}")
        return 0

    stale: Counter = Counter()
    if not args.no_baseline:
        baseline_path = Path(args.baseline) if args.baseline else \
            Path(DEFAULT_BASELINE)
        if args.baseline and not baseline_path.exists():
            raise SystemExit(f"baseline file not found: {baseline_path}")
        if baseline_path.exists():
            findings, stale = apply_baseline(
                findings, load_baseline(baseline_path),
                root=baseline_path.resolve().parent,
            )

    for f in findings:
        print(f.format())
    for (p, rule), n in sorted(stale.items()):
        print(f"note: stale baseline entry {p} {rule} x{n} "
              "(finding fixed? shrink the baseline)")
    n_files = len(files)
    if findings:
        print(f"\nrepro-lint: {len(findings)} finding(s) in {n_files} files")
        return 1
    print(f"repro-lint: clean ({n_files} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
