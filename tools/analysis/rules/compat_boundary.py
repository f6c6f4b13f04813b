"""RL001 — version-drifted JAX APIs only via ``src/repro/compat.py``.

The APIs whose home or spelling has moved between JAX releases
(``shard_map``'s home and check kwarg, ``make_mesh``'s ``axis_types``,
``AxisType`` itself, the mesh-context spelling, the Pallas TPU
compiler-params class) are imported once, in ``compat.py``, so the next
move is absorbed in one file instead of at every call site.
"""
from __future__ import annotations

import ast
from typing import Iterable

from tools.analysis.engine import (Finding, Module, Project, Rule,
                                   dotted_name, register)

# module paths that must not be imported outside compat.py
_BANNED_MODULES = (
    "jax.experimental.shard_map",
    "jax.experimental.pallas",
)

# names that must not be imported `from <mod> import <name>`
_BANNED_FROM = {
    "jax": {"make_mesh", "shard_map", "set_mesh"},
    "jax.sharding": {"AxisType", "use_mesh"},
    "jax.experimental": {"shard_map", "pallas"},
    "jax.experimental.shard_map": {"shard_map"},
    "jax.experimental.pallas": {"tpu"},
    "jax.experimental.pallas.tpu": {"TPUCompilerParams", "CompilerParams"},
}

# dotted attribute uses that must not appear outside compat.py
_BANNED_ATTRS = {
    "jax.make_mesh", "jax.shard_map", "jax.set_mesh",
    "jax.sharding.AxisType", "jax.sharding.use_mesh",
    "jax.experimental.shard_map", "jax.experimental.pallas",
}

_HINT = "use repro.compat instead (the only module allowed to touch " \
        "version-drifted JAX APIs)"


@register
class CompatBoundary(Rule):
    code = "RL001"
    name = "compat-boundary"
    summary = ("version-drifted JAX APIs (shard_map, make_mesh, AxisType, "
               "use_mesh, Pallas surface) imported outside repro.compat")

    def check_module(self, module: Module,
                     project: Project) -> Iterable[Finding]:
        if module.is_compat:
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if any(a.name == b or a.name.startswith(b + ".")
                           for b in _BANNED_MODULES):
                        yield Finding(module.relpath, node.lineno, self.code,
                                      f"import of drifted module "
                                      f"'{a.name}'; {_HINT}")
            elif isinstance(node, ast.ImportFrom) and node.module:
                banned = _BANNED_FROM.get(node.module, set())
                mod_banned = any(node.module == b
                                 or node.module.startswith(b + ".")
                                 for b in _BANNED_MODULES)
                for a in node.names:
                    if mod_banned or a.name in banned:
                        yield Finding(
                            module.relpath, node.lineno, self.code,
                            f"'from {node.module} import {a.name}' is a "
                            f"drifted API; {_HINT}")
            elif isinstance(node, ast.Attribute):
                name = dotted_name(node)
                if name in _BANNED_ATTRS:
                    yield Finding(module.relpath, node.lineno, self.code,
                                  f"direct use of drifted API '{name}'; "
                                  f"{_HINT}")
