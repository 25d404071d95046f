"""Checker 1 — shim discipline (ROADMAP "JAX mesh APIs").

JAX's mesh APIs (``jax.make_mesh``, ``jax.set_mesh``, ``jax.shard_map``,
``jax.sharding.Mesh``/``AxisType``/``use_mesh``) have changed name and
signature from release to release. ALL mesh construction, ambient-mesh
installs and shard_map calls therefore go through
``src/repro/launch/mesh.py`` (``make_mesh``, ``use_mesh``,
``shard_map_compat``, and its ``Mesh`` re-export for type annotations) —
in src, tests and benchmarks alike — so the next API change is one edit in
that file. This checker turns that rule into an error on any other module.
"""

from __future__ import annotations

import ast
from typing import List

from .base import Checker, Config, ModuleContext, Violation, dotted_name, \
    path_matches

HINT = ("route through the shims in src/repro/launch/mesh.py "
        "(make_mesh / use_mesh / shard_map_compat / its Mesh re-export)")

# names that may not be imported from jax.sharding outside the shim module
_BANNED_FROM_JAX_SHARDING = {"Mesh", "AxisType", "use_mesh"}
# names that may not be imported from the top-level jax namespace
_BANNED_FROM_JAX = {"shard_map", "set_mesh", "make_mesh"}
# banned attribute chains (exact, or any deeper access on the last ones)
_BANNED_DOTTED = {
    "jax.set_mesh", "jax.make_mesh", "jax.shard_map",
    "jax.sharding.Mesh", "jax.sharding.AxisType", "jax.sharding.use_mesh",
}
_BANNED_PREFIXES = ("jax.experimental.shard_map",)


class ShimDiscipline(Checker):
    id = "shim-discipline"

    def check(self, ctx: ModuleContext, cfg: Config) -> List[Violation]:
        if path_matches(ctx.path, cfg.shim_allowed):
            return []
        out: List[Violation] = []
        # local names bound by a banned import, to also flag the use site
        # (e.g. `Mesh(...)` construction after `from jax.sharding import Mesh`)
        banned_bindings = {}

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                for alias in node.names:
                    bad = (
                        (mod == "jax.sharding"
                         and alias.name in _BANNED_FROM_JAX_SHARDING)
                        or (mod == "jax" and alias.name in _BANNED_FROM_JAX)
                        or mod.startswith("jax.experimental.shard_map")
                    )
                    if bad:
                        out.append(self.violation(
                            ctx, node,
                            f"raw JAX mesh-API import "
                            f"'from {mod} import {alias.name}'", HINT))
                        banned_bindings[alias.asname or alias.name] = (
                            f"{mod}.{alias.name}")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith(_BANNED_PREFIXES):
                        out.append(self.violation(
                            ctx, node, f"raw import of '{alias.name}'", HINT))
                        banned_bindings[alias.asname or alias.name] = alias.name
            elif isinstance(node, ast.Attribute):
                name = dotted_name(node)
                if name and (name in _BANNED_DOTTED
                             or name.startswith(_BANNED_PREFIXES)):
                    out.append(self.violation(
                        ctx, node, f"raw jax API use '{name}'", HINT))
            elif isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Name) and fn.id in banned_bindings:
                    out.append(self.violation(
                        ctx, node,
                        f"call of '{fn.id}' (bound to "
                        f"{banned_bindings[fn.id]}) outside the shim module",
                        HINT))
        return out
