"""host-sync-in-step — no device round-trips inside jit-traced code.

The framework's whole performance story is host-drives/device-computes:
the train loop dispatches step N+1 while N executes, the serve engine
keeps one fused decode program hot. A ``float()`` / ``bool()`` /
``.item()`` / ``np.asarray()`` / ``jax.device_get()`` on a traced value
inside a jit-compiled step either fails at trace time (concretization
error) or — worse, when it slips through on a re-traced python value —
silently serializes dispatch with execution.

What counts as jit-reachable (PROJECT-SCOPE since the v2 engine —
analysis/callgraph.py holds the resolution contract):

- functions decorated with ``jax.jit`` / ``jit`` / ``pjit`` /
  ``jax.pmap`` (bare or via ``functools.partial``);
- functions passed to those wrappers anywhere in the lint run —
  including across modules (``jax.jit(decode_lib.prefill)``,
  ``jax.jit(partial(prefill, model))``);
- the framework's step-function naming convention: ``train_step`` /
  ``eval_step`` / ``decode_step`` / ``prefill``, which are jitted by
  factories in *other* modules (train/step.jit_train_step,
  serve/decode.jit_prefill) — the names are part of the framework
  contract;
- anything those functions call transitively, across module
  boundaries: bare names, from-imported symbols, module-alias dotted
  calls, ``self.`` methods, ``partial`` targets, and function refs
  passed to trace-context primitives (``lax.scan`` bodies run under
  the caller's trace). Nested defs are scanned with their enclosing
  function.

``float()``/``bool()`` on literal constants are ignored (static config
arithmetic, not a sync). Numpy aliases are resolved from the module's
imports; ``jnp.asarray`` is device-side and never flagged. When a
function is reachable only through another module, the finding says
which root reached it — cross-module reachability is exactly what the
v1 per-module engine could not see.
"""

from __future__ import annotations

import ast
from typing import Iterator

from .. import callgraph as cg
from ..core import Finding, LintContext, Module, Rule, dotted_name, register

#: re-exported for compatibility: the naming contract lives with the
#: graph engine now
STEP_FUNCTION_NAMES = cg.STEP_FUNCTION_NAMES
_JIT_WRAPPERS = cg.JIT_WRAPPERS

#: method-call syncs on any receiver
_SYNC_METHODS = frozenset({"item"})


def _numpy_aliases(tree: ast.Module) -> set[str]:
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    aliases.add(a.asname or "numpy")
    return aliases


@register
class HostSyncRule(Rule):
    name = "host-sync-in-step"
    summary = ("float()/bool()/.item()/np.asarray()/jax.device_get() "
               "inside a jit-reachable step/decode function "
               "(reachability follows calls across modules)")

    def check_module(self, module: Module,
                     ctx: LintContext) -> Iterator[Finding]:
        graph = cg.get_callgraph(ctx)
        parents = ctx.scratch.get("host_sync_reachable")
        if parents is None:
            parents = graph.jit_reachable()
            ctx.scratch["host_sync_reachable"] = parents
        mname = cg.module_name(module.path)
        mnode = graph.nodes.get(mname)
        if mnode is None or mnode.module is not module:
            # duplicate module names in one run (two files with the same
            # stem): the graph kept one; scan the other module-locally
            # so nothing is silently skipped
            solo = cg.CallGraph([module])
            mnode = solo.nodes[cg.module_name(module.path)]
            parents = solo.jit_reachable()
        np_aliases = _numpy_aliases(module.tree)

        seen_lines: set[tuple[int, int]] = set()
        for key in sorted(parents):
            if key[0] != mnode.name:
                continue
            origin = self._origin(parents, key)
            for d in mnode.defs.get(key[1], ()):
                for node in ast.walk(d):
                    if not isinstance(node, ast.Call):
                        continue
                    hit = self._sync_kind(node, np_aliases)
                    if hit is None:
                        continue
                    pos = (node.lineno, node.col_offset)
                    if pos in seen_lines:
                        continue  # defs overlap when nested
                    seen_lines.add(pos)
                    yield Finding(
                        self.name, module.path, node.lineno,
                        node.col_offset,
                        f"{hit} inside jit-reachable function "
                        f"{key[1]!r}{origin} forces a host sync (or a "
                        f"trace-time concretization error); compute it "
                        f"with jnp on-device or move it outside the "
                        f"jitted step",
                    )

    @staticmethod
    def _origin(parents, key) -> str:
        """' (reached from X in mod)' when jit-ness arrived from another
        module — the provenance the per-module v1 engine couldn't name."""
        node = key
        while parents.get(node) is not None:
            node = parents[node]
        if node[0] == key[0]:
            return ""
        return f" (reached from {node[1]!r} in {node[0]})"

    @staticmethod
    def _sync_kind(call: ast.Call, np_aliases: set[str]) -> str | None:
        dn = dotted_name(call.func)
        if dn in ("float", "bool") and call.args:
            if all(isinstance(a, ast.Constant) for a in call.args):
                return None  # float("inf") etc: static config, no sync
            return f"{dn}() on a traced value"
        if dn in ("jax.device_get", "device_get"):
            return "jax.device_get()"
        if dn is not None and "." in dn:
            head, _, method = dn.rpartition(".")
            if method == "asarray" and head.split(".")[0] in np_aliases | {"np"}:
                return f"{dn}() (numpy materializes the device array)"
            if method == "array" and head.split(".")[0] in np_aliases | {"np"}:
                return f"{dn}() (numpy materializes the device array)"
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _SYNC_METHODS and not call.args:
            return f".{call.func.attr}()"
        return None
