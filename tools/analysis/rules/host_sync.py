"""RL002 — host synchronisation inside hot (jitted/scanned) functions.

``int()``/``float()``/``bool()``/``.item()``/``np.asarray`` on a device
value blocks until the device catches up.  Inside a jitted function it
is worse: under trace it either fails (ConcretizationTypeError) or — for
code that only *sometimes* traces, like a per-step loop's eager
path — silently serialises every step.  PR 3's
``RandomState(int(state.round))`` cost a full device sync per round
before it was caught by a profile, not by review.

Hot functions are found structurally: anything passed to ``jax.jit`` /
``jax.vmap`` / ``jax.grad`` / ``jax.value_and_grad`` / ``jax.pmap`` or
the repo's ``scan_phase`` / ``sharded_scan_phase`` builders (directly,
by name, through ``self.attr = fn`` indirection, or via a jit
decorator), plus everything they call in the same module.

A host span (``span``, ``TraceAnnotation``) inside a hot function is
flagged too: it opens once while tracing and never while the program
runs.

Shape math is exempt: ``int(x.shape[0])``, ``float(len(xs))`` and
friends never touch the device.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from tools.analysis.engine import (Finding, Module, Project, Rule,
                                   dotted_name, register)

_WRAPPERS = {"jax.jit", "jit", "jax.vmap", "vmap", "jax.grad", "grad",
             "jax.value_and_grad", "value_and_grad", "jax.pmap", "pmap",
             "scan_phase", "sharded_scan_phase", "jax.checkpoint",
             "jax.remat"}

_CASTS = {"int", "float", "bool", "complex"}
# host spans (``repro/obs.py``): inside a traced function they open once,
# at trace time, and never again when the program runs
_SPANS = {"span", "obs.span", "TraceAnnotation",
          "jax.profiler.TraceAnnotation", "profiler.TraceAnnotation"}
_NP_SYNCS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
             "onp.asarray", "onp.array", "jax.device_get", "device_get"}

# attribute/call tokens that mark an argument as static shape math
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "nbytes", "itemsize"}
_STATIC_CALLS = {"len", "range", "round", "min", "max", "abs"}

# the round loop: runs once per federated round on the host, so casts on
# device values here are per-round syncs (the PR 3 regression class)
_ROUND_LOOP_NAMES = {"run_round", "run_rounds"}

# blessed explicit host-read helpers: a cast over one of these already
# paid for its sync on purpose
_HOST_READS = {"_host", "fetch", "fetch_tree", "device_get"}


def _round_loop_arg_ok(node: ast.AST) -> bool:
    """Cast argument already host-side (explicit read / numpy / static)?"""
    if _is_static(node):
        return True
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            name = dotted_name(n.func) or ""
            leaf = name.rsplit(".", 1)[-1]
            if leaf in _HOST_READS or name.split(".")[0] in (
                    "np", "numpy", "onp"):
                return True
    return False


def _is_static(node: ast.AST) -> bool:
    """Does the cast argument only involve shapes/python scalars?"""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            return True
        if isinstance(n, ast.Call):
            name = dotted_name(n.func)
            if name in _STATIC_CALLS:
                return True
    return bool(isinstance(node, ast.Constant))


def _wrapped_arg_name(call: ast.Call) -> Optional[str]:
    """Name (or 'self.attr') of the function handed to a jit-like call."""
    name = dotted_name(call.func)
    if name not in _WRAPPERS:
        return None
    if call.args:
        return dotted_name(call.args[0])
    for kw in call.keywords:
        if kw.arg in ("fun", "f", "step"):
            return dotted_name(kw.value)
    return None


class _HotSet:
    """Per-module set of hot function names (incl. `self.x` aliases)."""

    def __init__(self, module: Module):
        self.module = module
        self.funcs: dict[str, ast.AST] = {}
        self.self_alias: dict[str, str] = {}
        hot: set[str] = set()

        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.funcs[node.name] = node
                for dec in node.decorator_list:
                    dname = dotted_name(dec if not isinstance(dec, ast.Call)
                                        else dec.func)
                    if dname in _WRAPPERS or dname == "partial" or \
                            dname == "functools.partial":
                        if dname in _WRAPPERS:
                            hot.add(node.name)
                        elif isinstance(dec, ast.Call) and dec.args and \
                                dotted_name(dec.args[0]) in _WRAPPERS:
                            hot.add(node.name)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                        and isinstance(node.value, ast.Name)):
                    self.self_alias[f"self.{t.attr}"] = node.value.id
            if isinstance(node, ast.Call):
                target = _wrapped_arg_name(node)
                if target:
                    hot.add(self.self_alias.get(target, target))

        # second pass: `self.x = fn` aliases discovered after the
        # jit call that referenced them
        for alias, fn in self.self_alias.items():
            if alias in hot:
                hot.add(fn)

        # same-module transitive closure: helpers called from hot bodies
        changed = True
        while changed:
            changed = False
            for name in list(hot):
                node = self.funcs.get(name)
                if node is None:
                    continue
                for n in ast.walk(node):
                    if isinstance(n, ast.Call):
                        callee = dotted_name(n.func)
                        if callee in self.funcs and callee not in hot:
                            hot.add(callee)
                            changed = True
        self.hot = {n for n in hot if n in self.funcs}


@register
class HostSyncInHotPath(Rule):
    code = "RL002"
    name = "host-sync-in-hot-path"
    summary = ("int()/float()/bool()/.item()/np.asarray on device values "
               "inside jitted/scanned step functions")

    def check_module(self, module: Module,
                     project: Project) -> Iterable[Finding]:
        if not (module.is_library or "benchmarks/" in module.relpath):
            return
        hs = _HotSet(module)
        for name in sorted(hs.hot):
            fn = hs.funcs[name]
            # walk the body only — skip nested defs that are themselves
            # separate entries (they are in hs.funcs and visited if hot)
            for n in ast.walk(fn):
                if not isinstance(n, ast.Call):
                    continue
                cname = dotted_name(n.func)
                if cname in _CASTS and n.args and \
                        not _is_static(n.args[0]):
                    yield Finding(
                        module.relpath, n.lineno, self.code,
                        f"{cname}() on a (potentially) device value inside "
                        f"hot function '{name}' — forces a host sync; use "
                        "lax ops or hoist to the host boundary")
                elif cname in _NP_SYNCS and n.args and \
                        not _is_static(n.args[0]):
                    yield Finding(
                        module.relpath, n.lineno, self.code,
                        f"{cname}() inside hot function '{name}' — device "
                        "transfer in a traced/hot path; use jnp or hoist")
                elif cname in _SPANS:
                    yield Finding(
                        module.relpath, n.lineno, self.code,
                        f"host span {cname}() inside hot function '{name}' "
                        "— it records the trace, not the run; open it "
                        "around the call on the host")
                elif isinstance(n.func, ast.Attribute) and \
                        n.func.attr == "item" and not n.args:
                    yield Finding(
                        module.relpath, n.lineno, self.code,
                        f".item() inside hot function '{name}' — forces a "
                        "host sync; keep the value on device")

        # part B: the round loop.  Casts here run per round — they must
        # go through an explicit host-read helper so the sync is visible
        # and transfer-guard-safe.
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name not in _ROUND_LOOP_NAMES:
                continue
            for n in ast.walk(node):
                if isinstance(n, ast.Call) and \
                        dotted_name(n.func) in _CASTS and n.args and \
                        not _round_loop_arg_ok(n.args[0]):
                    yield Finding(
                        module.relpath, n.lineno, self.code,
                        f"{dotted_name(n.func)}() on a device value in the "
                        f"round loop '{node.name}' — implicit per-round "
                        "host sync; read through _host()/device_get first")
