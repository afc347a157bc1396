"""metric-naming — registered metrics are SHAPED like their kind.

PR 7's closed-vocab rule pins WHICH metric names may exist (the
docs/observability.md tables are the vocabulary). This rule pins how
names are SHAPED, so the scrape surface stays mechanically queryable:

- **counters end ``_total``** (`serve_admitted_total`,
  `retry_attempts_total`) — the Prometheus convention every dashboard
  and the goodput ledger's keyed lookups rely on;
- **gauges and histograms never end ``_total``** — a `_total` gauge
  reads as a counter and silently breaks rate() queries;
- **second-valued histograms end ``_seconds``** — a histogram whose
  help text says seconds/latency/duration/wall-clock must carry the
  unit in its name (`train_step_seconds`, `serve_ttft_seconds`);
- **no sub-second unit tokens** (``ms`` / ``us`` / ``ns`` /
  ``millis`` … anywhere between underscores, so ``lat_ms_total``
  can't hide one before the counter suffix): the exposition base unit
  is seconds; milliseconds live in *presentation*
  (``goodput.latency_percentiles_ms``), never in a registered name;
- **registration kind matches the documented kind**: registering
  `goodput_fraction` as a counter when the docs table says gauge is
  vocabulary drift the membership check can't see;
- **the docs tables themselves obey the shape rules** — the
  vocabulary and its convention move together, so a misshapen name
  cannot enter through the documentation side either.

Names are literals or module-level string constants (same resolution
as closed-vocab); dynamic names (`f"train_{key}"`) are invisible by
design. The docs tables are parsed, never imported: rows of the form
``| `name{labels}` | counter/gauge/histogram | … |``, with multiple
backticked names per row sharing the row's kind.
"""

from __future__ import annotations

import re
from typing import Iterator

import ast

from ..core import Finding, LintContext, Module, Rule, register

DOCS_PATH = "docs/observability.md"

_KINDS = ("counter", "gauge", "histogram")

_NAME_RE = re.compile(r"`([A-Za-z_][A-Za-z0-9_]*)")

_SECONDS_HELP_RE = re.compile(
    r"\b(seconds|latency|duration|wall[- ]?clock|wall time)\b",
    re.IGNORECASE,
)

#: sub-second unit TOKENS — banned anywhere in a name, not just as a
#: suffix, so "serve_lat_ms_total" can't smuggle milliseconds past the
#: counter suffix
_SUBSECOND_TOKENS = frozenset({
    "ms", "millis", "milliseconds", "us", "usec", "micros",
    "microseconds", "ns", "nanos", "nanoseconds",
})


def _shape_problem(name: str, kind: str, help_text: str | None) -> str | None:
    """The convention violation for (name, kind), or None."""
    if kind == "counter" and not name.endswith("_total"):
        return (f"counter {name!r} must end in '_total' (Prometheus "
                f"convention; the goodput ledger and every rate() query "
                f"rely on it)")
    if kind in ("gauge", "histogram") and name.endswith("_total"):
        return (f"{kind} {name!r} ends in '_total', the counter suffix — "
                f"it will read as a counter on the scrape surface; drop "
                f"the suffix (or register a counter)")
    bad_units = _SUBSECOND_TOKENS.intersection(name.split("_"))
    if bad_units:
        return (f"metric {name!r} carries a sub-second unit token "
                f"{sorted(bad_units)[0]!r} — the exposition base unit "
                f"is seconds; record seconds and keep millisecond "
                f"formatting in presentation code")
    if kind == "histogram" and help_text is not None \
            and _SECONDS_HELP_RE.search(help_text) \
            and not name.endswith("_seconds"):
        return (f"histogram {name!r} observes seconds (per its help "
                f"text) but does not end in '_seconds' — the unit "
                f"belongs in the name")
    return None


def _docs_kinds(ctx: LintContext) -> dict[str, tuple[str, int]]:
    """``name -> (kind, docs line)`` parsed from the metric tables."""
    cached = ctx.scratch.get("docs_metric_kinds")
    if cached is not None:
        return cached
    out: dict[str, tuple[str, int]] = {}
    docs = ctx.read_repo_file(DOCS_PATH)
    if docs:
        for lineno, line in enumerate(docs.splitlines(), 1):
            cells = [c.strip() for c in line.split("|")]
            # a table row is "| cell | cell | cell |": split yields
            # leading/trailing empties
            if len(cells) < 4 or cells[0] or cells[2].lower() not in _KINDS:
                continue
            kind = cells[2].lower()
            for name in _NAME_RE.findall(cells[1]):
                out[name] = (kind, lineno)
    ctx.scratch["docs_metric_kinds"] = out
    return out


@register
class MetricNamingRule(Rule):
    name = "metric-naming"
    summary = ("counters end _total, second-valued histograms end "
               "_seconds, no sub-second suffixes, and registration "
               "kinds match the docs/observability.md tables")

    def check_module(self, module: Module,
                     ctx: LintContext) -> Iterator[Finding]:
        docs = _docs_kinds(ctx)
        constants = module.constant_strings()
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute) \
                    or node.func.attr not in _KINDS or not node.args:
                continue
            kind = node.func.attr
            name = self._literal(node.args[0], constants)
            if name is None:
                continue
            help_text = None
            if len(node.args) >= 2:
                help_text = self._literal(node.args[1], constants)
            problem = _shape_problem(name, kind, help_text)
            if problem is not None:
                yield Finding(self.name, module.path, node.lineno,
                              node.col_offset, problem)
            documented = docs.get(name)
            if documented is not None and documented[0] != kind:
                yield Finding(
                    self.name, module.path, node.lineno, node.col_offset,
                    f"{name!r} is registered as a {kind} but "
                    f"{DOCS_PATH}:{documented[1]} documents it as a "
                    f"{documented[0]} — the table is the contract; fix "
                    f"the registration or the docs",
                )

    @staticmethod
    def _literal(node: ast.AST, constants: dict[str, str]) -> str | None:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            return constants.get(node.id)
        return None

    def finalize(self, ctx: LintContext) -> Iterator[Finding]:
        # the documentation side of the vocabulary obeys the same shape
        # rules — checked once per run, anchored at the docs line
        for name, (kind, lineno) in sorted(_docs_kinds(ctx).items()):
            problem = _shape_problem(name, kind, help_text=None)
            if problem is not None:
                yield Finding(self.name, DOCS_PATH, lineno, 0,
                              f"{problem} (documented in the metric "
                              f"table)")
