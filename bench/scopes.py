"""The fit's stages, for the per-layer readers ``metrics/fit_*``.

Host: the program's span store (``repro.obs.snapshot()``) holds, per span
path, a count and seconds, and the same again for the spans during which
JAX traced or compiled.  ``span_ms`` is a span's mean over the spans that
did not; ``compiled_s`` the seconds of those that did.

Device: the fit program carries named scopes (``encoder``, ``layer<i>``,
``stats``, ``solve``, ``forward``, ``errors``) in its ops' ``op_name``
metadata.  ``device_split`` lowers the program the cell's engine runs
(``DAEFEngine.lower_fit``, the engine built as ``generators/fit.py``
builds it), compiles it, maps each instruction of the compiled HLO text to
its ``op_name`` (a fusion takes its root's) and sums the trace's device
seconds per operation (``trace_reduce``'s ``op_s``, keyed
``<module>:<instruction>`` with `` <target>`` after a custom call) by stage.
An op of another program (eager key derivation, transfers) is left out of
the stages and reported apart.

A program without spans or scopes (older than them) gives None, and no
reader raises for it.
"""
from __future__ import annotations

import re
import sys

import program

STAGES = ("encoder", "stats", "solve")  # in this order; the rest is forward
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")

_SPLITS: dict[int, dict | None] = {}


def _rows(name: str) -> list[dict] | None:
    """The span table's rows whose span is ``name`` (at any depth)."""
    try:
        from repro import obs
    except ImportError:
        return None
    rows = [row for path, row in obs.snapshot().items()
            if path.rsplit("/", 1)[-1] == name]
    return rows or None


def span_ms(name: str) -> float | None:
    """Mean host milliseconds of span ``name``, over the spans during which
    JAX neither traced nor compiled."""
    rows = _rows(name)
    if rows is None:
        return None
    count = sum(r["count"] - r["compiled_count"] for r in rows)
    seconds = sum(r["seconds"] - r["compiled_seconds"] for r in rows)
    return 1e3 * seconds / count if count else None


def compiled_s(name: str) -> float | None:
    """Seconds of the ``name`` spans during which JAX traced or compiled."""
    rows = _rows(name)
    if rows is None:
        return None
    return sum(r["compiled_seconds"] for r in rows)


def stage(op_name: str) -> str:
    """The stage an ``op_name`` belongs to: ``encoder``, else ``stats``,
    else ``solve``, else ``forward`` / ``errors`` by its scope, else
    ``unscoped``.  Transformation wrappers (``vmap(encoder)``) are looked
    through."""
    parts = set()
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        parts.add(part)
    for name in (*STAGES, "forward", "errors"):
        if name in parts:
            return name
    return "unscoped"


def parse(hlo_text: str) -> tuple[str | None, dict[str, str]]:
    """(module name, instruction -> ``op_name``) of a compiled HLO module's
    text; a fusion takes the ``op_name`` of its computation's root, and its
    own where the root has none."""
    module, comp = None, None
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    for line in hlo_text.splitlines():
        if module is None and (m := _MODULE.match(line)):
            module = m.group(1)
            continue
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        instr = m.group(1)
        if (op := _OP_NAME.search(line)) is not None:
            own[instr] = op.group(1)
        if " fusion(" in line and (c := _CALLS.search(line)) is not None:
            calls[instr] = c.group(1)
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = instr

    def resolve(instr: str, depth: int = 0) -> str | None:
        comp = calls.get(instr)
        root = roots.get(comp) if comp is not None else None
        if root is not None and depth < 8:
            got = resolve(root, depth + 1)
            if got is not None:
                return got
        return own.get(instr)

    names = {}
    for instr in own.keys() | calls.keys():
        op = resolve(instr)
        if op is not None:
            names[instr] = op
    return module, names


def split(op_s: dict, module: str, names: dict[str, str]) -> dict:
    """Device seconds of ``op_s`` by stage of the program ``module``; ops of
    other programs under ``other``, the program's ops missing from
    ``names`` under ``unmapped``."""
    out = dict.fromkeys((*STAGES, "forward", "errors", "unscoped", "unmapped",
                         "other"), 0.0)
    for key, seconds in op_s.items():
        mod, sep, op = key.partition(":")
        if not sep or mod != module:
            out["other"] += seconds
            continue
        op_name = names.get(op.split(" ", 1)[0])
        out["unmapped" if op_name is None else stage(op_name)] += seconds
    return out


def _compiled_text(run) -> str | None:
    """HLO text of the fit program the cell's engine runs, or None where the
    program cannot lower it."""
    import jax

    from repro.engine import DAEFEngine, ExecutionPlan

    if not hasattr(DAEFEngine, "lower_fit"):
        return None
    cfg, rec = run["config"], run["record"]
    k = int(cfg.get("tenants", 1))
    n = rec["samples_per_fit"] // k
    m0 = cfg["layer_sizes"][0]
    if k == 1:
        engine = DAEFEngine(program.daef_config(cfg))
        x, kw = jax.ShapeDtypeStruct((m0, n), "float32"), {}
    else:
        engine = DAEFEngine(program.daef_config(cfg),
                            ExecutionPlan(mode="vmap", tenants=k))
        x = jax.ShapeDtypeStruct((k, m0, n), "float32")
        kw = {"seeds": program.tenant_seeds(cfg)}
    text = engine.lower_fit(x, **kw).compile().as_text()
    if _scoped(text):
        return text
    # The persistent compile cache keys a program without its metadata, so
    # it may hand back an executable compiled before the scopes existed:
    # compile this one program again without it (and without JAX's
    # in-memory caches, which keep that executable too).
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        return engine.lower_fit(x, **kw).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _scoped(text: str) -> bool:
    return any(stage(op) != "unscoped" for op in _OP_NAME.findall(text))


def device_split(run) -> dict | None:
    """Device seconds of the traced window by stage of the fit program
    (``split``), or None without a device trace or without scopes."""
    t = run["trace"]
    if not t or not t.get("op_s"):
        return None
    key = id(t)
    if key not in _SPLITS:
        text = _compiled_text(run)
        module, names = parse(text) if text is not None else (None, {})
        got = None
        if module is not None and any(stage(op) != "unscoped" for op in names.values()):
            got = split(t["op_s"], module, names)
            print(f"scopes: device seconds of the window by stage of {module}: "
                  f"{got}", file=sys.stderr)
        _SPLITS[key] = got
    return _SPLITS[key]


def device_ms(run, *stages: str) -> float | None:
    """Device milliseconds per fit of the fit program's ops in ``stages``."""
    got = device_split(run)
    fits = run["record"].get("fits")
    if got is None or not fits:
        return None
    return 1e3 * sum(got[s] for s in stages) / fits
