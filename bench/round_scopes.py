"""The federated round's programs, for the per-layer readers of the
``fed_round`` traffic (``metrics/round_*``, ``metrics/merge_*``).

A round runs two programs of its own on every device: the per-shard fit
program (``DAEFEngine.lower_fit``) and the tree program
(``DAEFEngine.lower_reduce``, named scopes ``merge_local``,
``merge_exchange``, ``merge_cross`` and ``merge_solve``), then
``_every_nth``, which keeps one merged model.  ``programs`` lowers and
compiles the first two as the cell's engine runs them (the record's
``engine``) and reads, from each compiled text,
its module name, each instruction's ``op_name`` (``scopes.parse``) and the
instructions that hold others: a ``while``, ``conditional`` or ``call``,
whose trace event spans the events of its body.  ``device_ms`` sums the
trace's device seconds (``trace_reduce``'s ``op_s``) of a program's leaf
operations only, so nothing counts twice.

A program without ``lower_reduce`` (older than the merge scopes) gives
None, and no reader raises for it.
"""
from __future__ import annotations

import re
import sys

import numpy as np

import scopes

MERGE_SCOPES = ("merge_local", "merge_exchange", "merge_cross", "merge_solve")
DEDUP_MODULE = "jit__every_nth"
_HOLDER = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*?\s(?:while|conditional|call)\(")

_PROGRAMS: dict[int, dict | None] = {}


def holders(hlo_text: str) -> set[str]:
    """Instructions of a compiled HLO text that hold other instructions
    (``while``, ``conditional``, ``call``)."""
    return {m.group(1) for line in hlo_text.splitlines()
            if (m := _HOLDER.match(line)) is not None}


def merge_scope(op_name: str) -> str:
    """The merge scope an ``op_name`` belongs to, or ``unscoped``;
    transformation wrappers (``vmap(merge_solve)``) are looked through."""
    parts = set()
    for part in op_name.split("/"):
        while (m := scopes._WRAPPED.match(part)) is not None:
            part = m.group(1)
        parts.add(part)
    return next((s for s in MERGE_SCOPES if s in parts), "unscoped")


def _merge_text(engine, fleet_shape, group: int) -> str:
    text = engine.lower_reduce(fleet_shape, group).compile().as_text()
    if any(merge_scope(op) != "unscoped" for op in scopes._OP_NAME.findall(text)):
        return text
    # The persistent compile cache keys a program without its metadata
    # (see scopes._compiled_text): compile this one again without it.
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        return engine.lower_reduce(fleet_shape, group).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _program(text: str) -> dict:
    module, names = scopes.parse(text)
    return {"module": module, "names": names, "holders": holders(text)}


def programs(run) -> dict | None:
    """``{"fit": ..., "merge": ...}``, each the program's ``module``,
    ``names`` (instruction -> ``op_name``) and ``holders``; None without a
    device trace or without ``DAEFEngine.lower_reduce``."""
    import jax

    from repro.engine import DAEFEngine

    t, rec = run["trace"], run["record"]
    if not t or not t.get("op_s") or not hasattr(DAEFEngine, "lower_reduce"):
        return None
    key = id(t)
    if key not in _PROGRAMS:
        engine = rec["engine"]
        shape = rec["fleet_shape"]
        k, m0 = shape.seeds.shape[0], run["config"]["layer_sizes"][0]
        n = rec["samples_per_round"] // k
        seeds = np.full(k, run["config"]["federation"]["seed"], np.int32)
        fit = engine.lower_fit(jax.ShapeDtypeStruct((k, m0, n), "float32"),
                               seeds=seeds).compile().as_text()
        got = {"fit": _program(fit),
               "merge": _program(_merge_text(engine, shape, rec["group_size"]))}
        print(f"round_scopes: fit program {got['fit']['module']}, tree program "
              f"{got['merge']['module']}", file=sys.stderr)
        _PROGRAMS[key] = got
    return _PROGRAMS[key]


def leaf_ops(run, prog: dict):
    """(instruction, ``op_name`` or None, device seconds) of each leaf
    operation of ``prog`` in the trace."""
    for key, seconds in run["trace"]["op_s"].items():
        mod, sep, op = key.partition(":")
        if not sep or mod != prog["module"]:
            continue
        instr = op.split(" ", 1)[0]
        if instr not in prog["holders"]:
            yield instr, prog["names"].get(instr), seconds


def per_round_ms(run, seconds: float) -> float | None:
    rounds = run["record"].get("rounds")
    return 1e3 * seconds / rounds if rounds else None


def fit_ms(run) -> float | None:
    """Device milliseconds per round of the per-shard fit program's leaf
    operations, mean over the devices."""
    progs = programs(run)
    if progs is None:
        return None
    return per_round_ms(run, sum(s for _, _, s in leaf_ops(run, progs["fit"])))


def merge_ms(run, *only: str) -> float | None:
    """Device milliseconds per round of the tree program's leaf operations
    (in the merge scopes ``only``, if given) and, without ``only``, of
    ``_every_nth``'s."""
    progs = programs(run)
    if progs is None:
        return None
    total = sum(s for _, op, s in leaf_ops(run, progs["merge"])
                if not only or merge_scope(op or "") in only)
    if not only:
        total += sum(s for key, s in run["trace"]["op_s"].items()
                     if key.partition(":")[0] == DEDUP_MODULE)
    return per_round_ms(run, total)
