"""FLX004 — version-gated JAX API accessed without the compat shim.

``jax.shard_map`` exists only in newer jax releases (older ones spell it
``jax.experimental.shard_map.shard_map`` with ``check_rep`` instead of
``check_vma``); ``jax.tree_map`` is removed in newer ones. Bare access works
on the developer's jax and AttributeErrors on the deployment's — the
ROADMAP's production posture needs every such attribute to go through one
shim (``flox_tpu/parallel/mesh.py::shard_map``) so the version fallback
lives in exactly one place. The shim itself carries an inline
``# floxlint: disable=FLX004``."""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding
from .common import ImportMap, dotted_name

#: canonical attribute paths that MUST be reached through a compat shim,
#: mapped to the remediation message
_GATED_APIS = {
    "jax.shard_map": "use flox_tpu.parallel.mesh.shard_map",
    "jax.experimental.shard_map": "import it only inside the flox_tpu.parallel.mesh.shard_map shim",
    "jax.lax.axis_size": "use flox_tpu.parallel.mesh.axis_size",
    "jax.tree_map": "removed in newer jax; use jax.tree.map",
    "jax.tree_multimap": "removed in newer jax; use jax.tree.map",
    "jax.tree_util.tree_multimap": "removed in newer jax; use jax.tree.map",
}


class VersionGatedApiRule:
    id = "FLX004"
    name = "version-gated-api"
    description = (
        "bare access to a jax API that only exists in some jax versions "
        "(jax.shard_map, jax.tree_map, ...) — must go through the compat shim"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        imports = ImportMap.from_tree(ctx.tree)
        reported: set[tuple[int, str]] = set()

        def report(node: ast.AST, api: str) -> Iterator[Finding]:
            if (node.lineno, api) in reported:
                return
            reported.add((node.lineno, api))
            yield Finding(
                path=ctx.display_path,
                line=node.lineno,
                col=node.col_offset,
                rule=self.id,
                message=f"version-gated API `{api}`: {_GATED_APIS[api]}",
            )

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                resolved = imports.resolve(node)
                if resolved is None:
                    continue
                if resolved in _GATED_APIS:
                    yield from report(node, resolved)
                else:
                    for api in _GATED_APIS:
                        if resolved.startswith(api + "."):
                            yield from report(node, api)
                            break
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for api in _GATED_APIS:
                    if node.module == api or node.module.startswith(api + "."):
                        yield from report(node, api)
                    else:
                        for a in node.names:
                            if f"{node.module}.{a.name}" in _GATED_APIS:
                                yield from report(node, f"{node.module}.{a.name}")
            elif isinstance(node, ast.Import):
                for a in node.names:
                    for api in _GATED_APIS:
                        if a.name == api or a.name.startswith(api + "."):
                            yield from report(node, api)
