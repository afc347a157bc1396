"""dtflint — every rule: positive fixture (detected, right file:line,
right rule id), negative fixture (clean code passes), suppression
fixture (marker silences it); plus the CLI exit-code contract and the
shipped-tree-is-clean acceptance gate."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from distributed_tensorflow_tpu.analysis import (
    RULES, Finding, lint_paths, lint_sources,
)
from distributed_tensorflow_tpu.analysis import fixtures

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "dtf_lint.py")

ALL_RULES = sorted(RULES)


def lint_snippet(src, path="snippet.py", rules=None):
    return lint_sources({path: textwrap.dedent(src)}, rules=rules)


# ---- the shipped fixture corpus ----------------------------------------


def test_every_rule_ships_all_three_fixtures():
    for rule in ALL_RULES:
        assert rule in fixtures.POSITIVE, rule
        assert rule in fixtures.NEGATIVE, rule
        assert rule in fixtures.SUPPRESSED, rule


@pytest.mark.parametrize("rule", ALL_RULES)
def test_positive_fixture_fires_at_marked_line(rule):
    src = fixtures.POSITIVE[rule]
    want_line = fixtures.expected_line(src)
    path = fixtures.fixture_path(rule, "positive")
    found = lint_sources({path: src})
    assert found, f"{rule}: positive fixture produced nothing"
    assert all(f.rule == rule for f in found), found
    assert any(f.line == want_line for f in found), (
        f"{rule}: fired at {[f.line for f in found]}, want {want_line}")
    # findings carry the path they were given (file:line anchoring)
    assert all(f.path == path for f in found)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_negative_fixture_is_clean(rule):
    found = lint_sources(
        {fixtures.fixture_path(rule, "negative"): fixtures.NEGATIVE[rule]})
    assert found == [], [f.format() for f in found]


@pytest.mark.parametrize("rule", ALL_RULES)
def test_suppression_comment_silences(rule):
    found = lint_sources(
        {fixtures.fixture_path(rule, "suppressed"):
         fixtures.SUPPRESSED[rule]})
    assert found == [], [f.format() for f in found]


def test_file_level_suppression():
    src = ("# dtflint: disable-file=exception-hygiene\n"
           + fixtures.POSITIVE["exception-hygiene"])
    assert lint_sources({"f.py": src}) == []


def test_self_check_green():
    assert fixtures.self_check() == []


# ---- rule-specific behaviors beyond the basic corpus -------------------


def test_host_sync_step_name_convention():
    # train_step is jitted by a factory in ANOTHER module; the naming
    # convention must make it reachable without a local jax.jit
    found = lint_snippet(
        """
        import numpy as onp

        def train_step(state, batch):
            host = onp.asarray(batch["x"])
            return state, {"x": host}
        """,
        rules=["host-sync-in-step"],
    )
    assert len(found) == 1 and found[0].rule == "host-sync-in-step"
    assert "asarray" in found[0].message


def test_host_sync_transitive_helper_and_item():
    found = lint_snippet(
        """
        import jax

        def helper(x):
            return x.mean().item()

        @jax.jit
        def decode(tokens):
            return helper(tokens)

        fast = jax.jit(decode)
        """,
        rules=["host-sync-in-step"],
    )
    # helper is reachable from the jitted decode
    assert [f.rule for f in found] == ["host-sync-in-step"]
    assert ".item()" in found[0].message


def test_host_sync_float_on_constant_is_static_config():
    found = lint_snippet(
        """
        import jax

        @jax.jit
        def train_step(state, batch):
            eps = float("1e-6")
            return state, eps
        """,
        rules=["host-sync-in-step"],
    )
    assert found == []


def test_donation_framework_factory_convention():
    # jit_prefill's donate_argnums lives in serve/decode.py — the rule
    # must know the factory contract without seeing that module
    found = lint_snippet(
        """
        from distributed_tensorflow_tpu.serve import decode as decode_lib

        class Eng:
            def __init__(self, model):
                self._prefill = decode_lib.jit_prefill(model)

            def bad(self, params, cache, toks):
                logits, new_cache = self._prefill(params, cache, 0, toks, 3)
                stale = cache.k  # the donated pytree
                return logits, stale
        """,
        rules=["donation-after-use"],
    )
    assert len(found) == 1
    assert "'cache'" in found[0].message


def test_donation_same_line_rebind_is_clean():
    found = lint_snippet(
        """
        import jax

        def _step(s, b):
            return s

        step = jax.jit(_step, donate_argnums=(0,))

        class T:
            def fit(self, batch):
                self.state, metrics = step(self.state, batch)
                return self.state
        """,
        rules=["donation-after-use"],
    )
    assert found == []


def test_lock_discipline_prefix_registry_get_regression():
    # the exact pre-fix Registry.get shape: lock-free dict read while
    # merge() inserts under the lock (fixed in this PR)
    found = lint_snippet(
        """
        import threading

        class Registry:
            def __init__(self):
                self._metrics = {}
                self._lock = threading.Lock()

            def register(self, key, m):
                with self._lock:
                    self._metrics[key] = m

            def get(self, key):
                return self._metrics.get(key)
        """,
        rules=["lock-discipline"],
    )
    assert len(found) == 1 and "_metrics" in found[0].message


def test_lock_discipline_unlocked_helper_convention():
    found = lint_snippet(
        """
        import threading

        class Registry:
            def __init__(self):
                self._metrics = {}
                self._lock = threading.Lock()

            def register(self, key, m):
                with self._lock:
                    self._metrics[key] = m

            def _dump_unlocked(self):
                return dict(self._metrics)

            def dump(self):
                with self._lock:
                    return self._dump_unlocked()
        """,
        rules=["lock-discipline"],
    )
    assert found == []


def test_vocab_metric_name_must_be_documented():
    path = "distributed_tensorflow_tpu/serve/fake_engine.py"
    found = lint_sources({path: textwrap.dedent(
        """
        class E:
            def __init__(self, r):
                self._m = r.counter("serve_undocumented_total", "nope")
        """
    )}, rules=["closed-vocab"])
    assert len(found) == 1 and "docs/observability.md" in found[0].message
    # the same registration OUTSIDE the package (tools, tests) is fine:
    # smoke checks register scratch names
    assert lint_sources({"tools/fake_check.py": textwrap.dedent(
        """
        def main(r):
            r.counter("scratch_smoke_total", "x").inc()
        """
    )}, rules=["closed-vocab"]) == []


def test_vocab_single_mfu_multiplier_site():
    src = """
    from distributed_tensorflow_tpu.utils import flops as flops_lib

    def my_mfu(fwd, sps):
        return fwd * flops_lib.train_flops_multiplier() * sps
    """
    found = lint_sources(
        {"tools/fake_bench.py": textwrap.dedent(src)},
        rules=["closed-vocab"])
    assert len(found) == 1 and "ONE site" in found[0].message
    # the real site is allowed
    assert lint_sources(
        {"distributed_tensorflow_tpu/obs/goodput.py": textwrap.dedent(src)},
        rules=["closed-vocab"]) == []


def test_vocab_waste_cause():
    found = lint_snippet(
        """
        from distributed_tensorflow_tpu.obs import goodput

        def lose_time(reg):
            goodput.note_wasted("bikeshedding", 1.0, registry=reg)
        """,
        rules=["closed-vocab"],
    )
    assert len(found) == 1 and "WASTE_CAUSES" in found[0].message


def test_exception_seam_narrow_silent_flagged():
    seam = "distributed_tensorflow_tpu/resilience/fake_seam.py"
    src = """
    def restore(path):
        try:
            return open(path).read()
        except OSError:
            pass
    """
    found = lint_sources({seam: textwrap.dedent(src)},
                         rules=["exception-hygiene"])
    assert len(found) == 1 and "seam" in found[0].message
    # identical code outside the seams is accepted (best-effort cleanup)
    assert lint_sources({"distributed_tensorflow_tpu/utils/fake.py":
                         textwrap.dedent(src)},
                        rules=["exception-hygiene"]) == []


def test_donation_taint_never_crosses_scope_boundaries():
    # a closure's same-named variable is a DIFFERENT binding, and line
    # order says nothing about execution order across scopes: exactly
    # one finding (the inner use-after-donate), nothing on the outer
    # call that textually follows it
    found = lint_snippet(
        """
        import jax

        def _step(s, b):
            return s

        step = jax.jit(_step, donate_argnums=(0,))

        def outer(state, batch):
            def inner(state, batch):
                new = step(state, batch)
                print(state.params)
                return new
            return inner(state, batch)
        """,
        rules=["donation-after-use"],
    )
    assert len(found) == 1, [f.format() for f in found]
    assert found[0].line == 12  # the inner print, once


# ---- the v2 cross-module engine (analysis/callgraph.py) ----------------


HELPER_MOD = """
def helper(x):
    return x.mean().item()
"""

STEP_MOD = """
import jax

from helper_mod import helper


@jax.jit
def decode(tokens):
    return helper(tokens)
"""


def test_cross_module_reachability_v1_provably_missed():
    """A step fn in one module calling a host-syncing helper in
    another: the helper module ALONE is clean (nothing jit-roots it —
    exactly the v1 per-module blind spot), but linted together the
    finding lands in the helper's file."""
    alone = lint_sources({"helper_mod.py": HELPER_MOD},
                         rules=["host-sync-in-step"])
    assert alone == [], [f.format() for f in alone]

    both = lint_sources(
        {"helper_mod.py": HELPER_MOD, "step_mod.py": STEP_MOD},
        rules=["host-sync-in-step"])
    assert len(both) == 1, [f.format() for f in both]
    assert both[0].path == "helper_mod.py"
    assert ".item()" in both[0].message
    # the finding explains WHERE jit-ness came from
    assert "step_mod" in both[0].message


def test_cross_module_jit_wrap_and_partial():
    # jax.jit(partial(fn, model)) in one module roots fn in another,
    # through a module alias — the serve/decode.py factory shape
    found = lint_sources({
        "kernels.py": """
import numpy as np


def prefill_impl(model, params, tokens):
    return np.asarray(tokens)
""",
        "factory.py": """
import jax
from functools import partial

import kernels


def make(model):
    return jax.jit(partial(kernels.prefill_impl, model),
                   donate_argnums=(1,))
""",
    }, rules=["host-sync-in-step"])
    assert len(found) == 1 and found[0].path == "kernels.py"
    assert "asarray" in found[0].message


def test_cross_module_donation_via_import():
    # the donating binding lives in another module; the import carries
    # its donate_argnums with it
    srcs = {
        "steplib.py": """
import jax


def _step(state, batch):
    return state


jitted_step = jax.jit(_step, donate_argnums=(0,))
""",
        "driver.py": """
from steplib import jitted_step


def run_once(state, batch):
    new_state = jitted_step(state, batch)
    print(state.params)
    return new_state
""",
    }
    found = lint_sources(srcs, rules=["donation-after-use"])
    assert len(found) == 1 and found[0].path == "driver.py"
    assert "'state'" in found[0].message
    # module-alias call form resolves too
    srcs["driver.py"] = """
import steplib


def run_once(state, batch):
    new_state = steplib.jitted_step(state, batch)
    print(state.params)
    return new_state
"""
    found = lint_sources(srcs, rules=["donation-after-use"])
    assert len(found) == 1 and found[0].path == "driver.py"


def test_cross_module_relative_imports_resolve_in_package():
    # the real package layout: a step helper under the package root,
    # reached through `from ..ops import helpers`
    found = lint_sources({
        "distributed_tensorflow_tpu/ops/helpers.py": """
def fetch_scalar(x):
    return float(x.sum())
""",
        "distributed_tensorflow_tpu/serve/dec.py": """
import jax

from ..ops import helpers


def decode_step(cache, tokens):
    return helpers.fetch_scalar(tokens)
""",
    }, rules=["host-sync-in-step"])
    assert len(found) == 1
    assert found[0].path == "distributed_tensorflow_tpu/ops/helpers.py"


def test_step_name_contract_still_roots_without_jit():
    # the v1 naming-convention behavior survives the engine swap
    found = lint_snippet(
        """
        import numpy as onp

        def train_step(state, batch):
            host = onp.asarray(batch["x"])
            return state, {"x": host}
        """,
        rules=["host-sync-in-step"],
    )
    assert len(found) == 1 and "asarray" in found[0].message


# ---- wall-clock-in-seam ------------------------------------------------


def test_wall_clock_fires_only_in_seams():
    src = """
    import time

    def build(index):
        return {"t": time.monotonic()}
    """
    seam = lint_sources(
        {"distributed_tensorflow_tpu/data/records2.py":
         textwrap.dedent(src)}, rules=["wall-clock-in-seam"])
    assert len(seam) == 1 and "wall clock" in seam[0].message
    # identical code outside the seams: telemetry's whole job
    assert lint_sources(
        {"distributed_tensorflow_tpu/obs/clocky.py": textwrap.dedent(src)},
        rules=["wall-clock-in-seam"]) == []


def test_wall_clock_seams_are_segment_anchored():
    src = """
    import os
    import time

    def f():
        return time.time(), os.urandom(4)
    """
    # package-relative invocation (cwd inside the package) still a seam
    rel = lint_sources({"resilience/x.py": textwrap.dedent(src)},
                       rules=["wall-clock-in-seam"])
    assert len(rel) == 2, [f.format() for f in rel]
    # look-alike segments are NOT seams: neither strict nor scaffolding
    for path in ("myresilience/x.py", "latests/x.py", "testdata/x.py"):
        found = lint_sources({path: textwrap.dedent(src)},
                             rules=["wall-clock-in-seam"])
        assert found == [], (path, [f.format() for f in found])


def test_wall_clock_seeded_rng_and_injectable_default_clean():
    found = lint_sources({
        "distributed_tensorflow_tpu/data/aug2.py": """
import time

import numpy as np


def make(seed, index, clock=time.monotonic):
    rng = np.random.RandomState(seed + index)
    r2 = np.random.default_rng(seed)
    return rng.uniform(size=(2,)), r2, clock()
""",
    }, rules=["wall-clock-in-seam"])
    assert found == [], [f.format() for f in found]


def test_wall_clock_unseeded_randomness_and_aliases():
    found = lint_sources({
        "distributed_tensorflow_tpu/resilience/jitterbug.py": """
import random
from time import monotonic as now

import numpy as np


def schedule():
    a = random.random()
    b = np.random.default_rng()
    c = now()
    return a, b, c
""",
    }, rules=["wall-clock-in-seam"])
    msgs = [f.message for f in found]
    assert len(found) == 3, msgs
    assert any("random.random" in m for m in msgs)
    assert any("default_rng() without a seed" in m for m in msgs)
    assert any("wall clock" in m for m in msgs)


def test_wall_clock_test_scaffolding_tier_relaxed():
    # tests/: deadlines are process control (clean); entropy is not
    src = """
    import os
    import time

    def wait_and_corrupt(path):
        deadline = time.monotonic() + 5
        return os.urandom(8), deadline
    """
    found = lint_sources({"tests/test_fake.py": textwrap.dedent(src)},
                         rules=["wall-clock-in-seam"])
    assert len(found) == 1 and "urandom" in found[0].message
    # chaos_worker is the bit-identity oracle: full strictness
    strict = lint_sources({"tests/chaos_worker.py": textwrap.dedent(src)},
                          rules=["wall-clock-in-seam"])
    assert len(strict) == 2, [f.format() for f in strict]


# ---- atomic-durable-write ----------------------------------------------


def test_durable_write_keyword_trigger_and_atomic_shape():
    bare = """
    import json
    import os

    def dump_quarantine(directory, doc):
        path = os.path.join(directory, "quarantine.json")
        with open(path, "w") as f:
            json.dump(doc, f)
    """
    found = lint_sources({"anywhere.py": textwrap.dedent(bare)},
                         rules=["atomic-durable-write"])
    assert len(found) == 1 and "tmp" in found[0].message
    atomic = """
    import json
    import os

    def dump_quarantine(directory, doc):
        path = os.path.join(directory, "quarantine.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    """
    assert lint_sources({"anywhere.py": textwrap.dedent(atomic)},
                        rules=["atomic-durable-write"]) == []


def test_durable_write_module_trigger_and_append_exempt():
    # in a durable-state module EVERY truncating write is in scope,
    # no keyword needed — but append-mode streams stay exempt
    src = """
    def note(path, text):
        with open(path, "w") as f:
            f.write(text)

    def stream(path, text):
        with open(path, "a") as f:
            f.write(text)
    """
    found = lint_sources(
        {"distributed_tensorflow_tpu/resilience/fleet.py":
         textwrap.dedent(src)}, rules=["atomic-durable-write"])
    assert len(found) == 1 and found[0].line == 3
    # same code in a neutral module without durable keywords: clean
    assert lint_sources({"distributed_tensorflow_tpu/utils/scratch.py":
                         textwrap.dedent(src)},
                        rules=["atomic-durable-write"]) == []


def test_durable_write_judged_per_write_not_per_function():
    # a bare in-place manifest write must NOT be blessed by a correct
    # atomic write of a DIFFERENT file in the same function
    src = """
    import json
    import os

    def save_checkpoint_meta(d, manifest, extra):
        with open(os.path.join(d, "MANIFEST.json"), "w") as f:
            json.dump(manifest, f)
        tmp = os.path.join(d, "extra.json") + ".tmp"
        with open(tmp, "w") as f:
            json.dump(extra, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(d, "extra.json"))
    """
    found = lint_sources({"anywhere.py": textwrap.dedent(src)},
                         rules=["atomic-durable-write"])
    assert len(found) == 1 and found[0].line == 6, (
        [f.format() for f in found])


# ---- metric-naming -----------------------------------------------------


def test_metric_naming_counter_and_histogram_shapes():
    found = lint_snippet(
        """
        def setup(r):
            a = r.counter("serve_retries", "retries")
            b = r.histogram("serve_wait", "queue wait in seconds")
            c = r.gauge("serve_depth_total", "queue depth")
            d = r.histogram("serve_lat_ms", "latency")
        """,
        rules=["metric-naming"],
    )
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 4, msgs
    assert "_total" in msgs and "_seconds" in msgs and "sub-second" in msgs


def test_metric_naming_subsecond_token_not_just_suffix():
    # "ms" hidden before the counter suffix must still be flagged
    found = lint_snippet(
        """
        def setup(r):
            a = r.counter("serve_lat_ms_total", "latency")
        """,
        rules=["metric-naming"],
    )
    assert len(found) == 1 and "sub-second" in found[0].message
    # ...but ordinary words containing the letters are fine
    clean = lint_snippet(
        """
        def setup(r):
            a = r.counter("serve_status_checks_total", "status probes")
        """,
        rules=["metric-naming"],
    )
    assert clean == [], [f.format() for f in clean]


def test_metric_naming_resolves_constants_and_accepts_clean():
    found = lint_snippet(
        """
        STEPS_TOTAL = "train_widget_steps_total"

        def setup(r):
            a = r.counter(STEPS_TOTAL, "steps")
            b = r.histogram("widget_step_seconds", "wall seconds per step")
            c = r.gauge("widget_occupancy", "slots in use")
        """,
        rules=["metric-naming"],
    )
    assert found == [], [f.format() for f in found]


def test_metric_naming_kind_must_match_docs_table():
    # goodput_fraction is documented as a gauge; registering it as a
    # counter is vocabulary drift (and a shape violation to boot)
    found = lint_snippet(
        """
        def setup(r):
            g = r.counter("goodput_fraction", "productive share")
        """,
        rules=["metric-naming"],
    )
    assert any("documents it as a gauge" in f.message for f in found), (
        [f.format() for f in found])


def test_suppression_markers_inside_strings_are_inert():
    # a disable-file marker in a DOCSTRING must not disarm the rule —
    # only real comment tokens count (the silent-rot hole otherwise)
    src = (
        '"""docs quoting the syntax: # dtflint: disable-file=lock-discipline"""\n'
        + fixtures.POSITIVE["lock-discipline"]
    )
    found = lint_sources({"doc.py": src})
    assert [f.rule for f in found] == ["lock-discipline"]


def test_cli_is_stdlib_only():
    """The linter must run without the framework: no jax, no numpy, no
    distributed_tensorflow_tpu package import (whose __init__ pulls
    both and runs the chip-lock pin side effect)."""
    code = (
        "import sys, runpy\n"
        f"sys.argv = ['dtf_lint.py', '--list-rules']\n"
        "try:\n"
        f"    runpy.run_path({LINT!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert (e.code or 0) == 0, e.code\n"
        "for mod in ('jax', 'numpy', 'distributed_tensorflow_tpu'):\n"
        "    assert mod not in sys.modules, f'linter imported {mod}'\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr


def test_finding_format_and_json():
    f = Finding("closed-vocab", "a/b.py", 12, 4, "boom")
    assert f.format() == "a/b.py:12:4: closed-vocab: boom"
    assert f.to_json() == {"rule": "closed-vocab", "path": "a/b.py",
                           "line": 12, "col": 4, "message": "boom"}


def test_unknown_rule_name_raises():
    with pytest.raises(KeyError):
        lint_snippet("x = 1", rules=["no-such-rule"])


# ---- CLI exit-code contract + acceptance gate --------------------------


def _run_cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, LINT, *args],
                          capture_output=True, text=True, timeout=120,
                          cwd=cwd)


def test_cli_flags_injected_fixture_with_rule_and_location(tmp_path):
    """The acceptance contract: inject any shipped positive fixture into
    a linted tree → non-zero exit naming the rule id and file:line.
    Seam rules inject at their seam-shaped relative path
    (fixtures.injection_path)."""
    pkg = tmp_path / "victim"
    pkg.mkdir()
    (pkg / "clean.py").write_text("x = 1\n")
    for rule, src in fixtures.POSITIVE.items():
        bad = pkg / fixtures.injection_path(rule)
        bad.parent.mkdir(parents=True, exist_ok=True)
        bad.write_text(src)
        want_line = fixtures.expected_line(src)
        proc = _run_cli("--strict", str(pkg))
        assert proc.returncode == 1, (rule, proc.stdout, proc.stderr)
        assert f"{bad}:{want_line}" in proc.stdout, (rule, proc.stdout)
        assert f" {rule}: " in proc.stdout, (rule, proc.stdout)
        bad.unlink()
    proc = _run_cli("--strict", str(pkg))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(fixtures.POSITIVE["exception-hygiene"])
    proc = _run_cli("--json", str(bad))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload and payload[0]["rule"] == "exception-hygiene"
    assert payload[0]["line"] == fixtures.expected_line(
        fixtures.POSITIVE["exception-hygiene"])


def test_cli_usage_errors():
    assert _run_cli().returncode == 2  # no paths
    assert _run_cli("--rules", "bogus", "tools").returncode == 2
    assert _run_cli("/no/such/path").returncode == 2


def test_cli_self_check_green():
    proc = _run_cli("--self-check")
    assert proc.returncode == 0, proc.stderr
    assert "self-check OK" in proc.stderr


def test_cli_changed_only_reports_only_the_diff(tmp_path):
    """--changed-only lints the whole tree for cross-module context but
    reports (and exits on) only files changed vs --base — a committed
    violation stays out of the report, an uncommitted one fails it."""
    def git(*args):
        subprocess.run(["git", *args], cwd=tmp_path, check=True,
                       capture_output=True,
                       env={**os.environ,
                            "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                            "GIT_COMMITTER_NAME": "t",
                            "GIT_COMMITTER_EMAIL": "t@t"})

    git("init", "-q")
    committed_bad = tmp_path / "old_violation.py"
    committed_bad.write_text(fixtures.POSITIVE["exception-hygiene"])
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")

    # nothing changed: fast-path success, committed violation not relinted
    proc = _run_cli("--changed-only", "--strict", ".", cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no python/docs files changed" in proc.stderr

    # an uncommitted (untracked) violation IS reported; the committed
    # one still is not
    new_bad = tmp_path / "new_violation.py"
    new_bad.write_text(fixtures.POSITIVE["lock-discipline"])
    proc = _run_cli("--changed-only", "--strict", ".", cwd=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "new_violation.py" in proc.stdout
    assert "old_violation.py" not in proc.stdout

    # a bogus base ref is a usage error, not a silent full lint
    proc = _run_cli("--changed-only", "--base", "no-such-ref", ".",
                    cwd=tmp_path)
    assert proc.returncode == 2


def test_shipped_tree_is_clean():
    """The CI gate's exact invocation must pass on the shipped tree —
    every violation the new rules found was fixed (or carries a
    reviewed suppression)."""
    found = lint_paths([
        os.path.join(REPO, "distributed_tensorflow_tpu"),
        os.path.join(REPO, "tools"),
    ])
    assert found == [], "\n".join(f.format() for f in found)


# ---- v3 partitioning family (PR 14) ------------------------------------


def test_shard_rules_table_name_must_be_unique_across_run():
    table = '''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel.sharding import \\
            partition_rules

        T = partition_rules(
            "dup-model", ((r".*", P()),), coverage=("a/kernel",))
    '''
    found = lint_sources({
        "models/a.py": textwrap.dedent(table),
        "models/b.py": textwrap.dedent(table),
    }, rules=["shard-rules-coverage"])
    assert [f.rule for f in found] == ["shard-rules-coverage"]
    assert "already defined at models/a.py" in found[0].message
    assert found[0].path == "models/b.py"


def test_shard_rules_missing_coverage_fixture_flagged():
    found = lint_snippet('''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel.sharding import \\
            partition_rules

        T = partition_rules("no-cov", ((r".*", P()),))
    ''', rules=["shard-rules-coverage"])
    assert len(found) == 1
    assert "ships no coverage fixture" in found[0].message


def test_shard_rules_catch_all_constant_resolved_not_opaque():
    """A symbolic sharding.CATCH_ALL final row must not disable the
    simulation — the dead rule hiding above it is still found."""
    found = lint_snippet('''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel import sharding

        T = sharding.partition_rules(
            "cdm",
            (
                (r"kernel$", P(None, "model")),
                (r"kernle$", P("model")),
                (sharding.CATCH_ALL, sharding.REPLICATED),
            ),
            coverage=("layer/kernel", "layer/bias"),
        )
    ''', rules=["shard-rules-coverage"])
    assert len(found) == 1
    assert "'kernle$'" in found[0].message
    assert "dead rule" in found[0].message


def test_shard_rules_shadowed_row_is_dead_the_wide_deep_regression():
    """The pre-engine wide&deep bug, now a lint error: an unanchored
    earlier row swallows every path the later row was written for."""
    found = lint_snippet('''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel.sharding import \\
            partition_rules

        T = partition_rules(
            "wd-regression",
            (
                (r"table_\\d+", P("model", None)),
                (r"wide_table_\\d+", P("model", None)),
                (r".*", P()),
            ),
            coverage=("table_0", "wide_table_0", "deep_0/kernel"),
        )
    ''', rules=["shard-rules-coverage"])
    assert len(found) == 1
    assert "wide_table_" in found[0].message
    assert "shadowed" in found[0].message


def test_shard_rules_coverage_resolves_module_constant():
    found = lint_snippet('''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel.sharding import \\
            partition_rules

        _COV = ("layer/kernel", "layer/bias")

        T = partition_rules(
            "const-cov", ((r"kernel$", P(None, "model")),), coverage=_COV)
    ''', rules=["shard-rules-coverage"])
    # bias path unmatched — found THROUGH the constant reference
    assert len(found) == 1
    assert "'layer/bias'" in found[0].message and "not total" in found[0].message


def test_mesh_axis_vocab_tuple_entries_and_scope():
    src = '''
        from jax.sharding import PartitionSpec as P

        GOOD = P(("data", "fsdp"), None)
        BAD = P(("data", "fsdpp"), None)
    '''
    # in scope: only the typo'd tuple entry fires
    found = lint_sources(
        {"distributed_tensorflow_tpu/train/x.py": textwrap.dedent(src)},
        rules=["mesh-axis-closed-vocab"])
    assert [f.line for f in found] == [5]
    assert "'fsdpp'" in found[0].message
    # outside the mesh-consuming dirs: silent
    assert lint_sources(
        {"distributed_tensorflow_tpu/obs/x.py": textwrap.dedent(src)},
        rules=["mesh-axis-closed-vocab"]) == []


def test_mesh_axis_collective_positional_and_keyword():
    found = lint_sources({"ops/x.py": textwrap.dedent('''
        from jax import lax

        from distributed_tensorflow_tpu.parallel import collectives as col


        def f(x):
            a = lax.psum(x, "data")            # fine
            b = col.all_reduce(x, "modell")    # typo, positional
            return lax.pmean(b, axis_name="bad_axis")
    ''')}, rules=["mesh-axis-closed-vocab"])
    assert [(f.line, "modell" in f.message or "bad_axis" in f.message)
            for f in found] == [(9, True), (10, True)]


def test_mesh_axis_silent_when_vocab_unreadable(tmp_path):
    """No mesh.py to parse (foreign tree) → stay silent, never guess."""
    found = lint_sources(
        {"parallel/x.py": 'from jax import lax\n'
                          'def f(x):\n'
                          '    return lax.psum(x, "dtaa")\n'},
        rules=["mesh-axis-closed-vocab"], root=str(tmp_path))
    assert found == []


def test_seam_bypass_carve_outs_and_scope():
    body = '''
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P


        def attn_rules():
            return ((r"kernel$", P(None, "model")),)


        def island(mesh, x):
            f = jax.shard_map(lambda a: a, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data"))
            return f(x)


        def bypass(mesh, x):
            import jax
            return jax.device_put(x, NamedSharding(mesh, P("data")))
    '''
    found = lint_sources(
        {"distributed_tensorflow_tpu/serve/x.py": textwrap.dedent(body)},
        rules=["sharding-seam-bypass"])
    # only the bypass function fires (NamedSharding + P on line 18)
    assert {f.line for f in found} == {18}
    assert len(found) == 2
    # the seam file itself, analysis/, and tests/ are exempt
    for exempt in ("distributed_tensorflow_tpu/parallel/sharding.py",
                   "distributed_tensorflow_tpu/analysis/x.py",
                   "tests/x.py"):
        assert lint_sources(
            {exempt: textwrap.dedent(body)},
            rules=["sharding-seam-bypass"]) == [], exempt


def test_seam_bypass_rules_table_rows_exempt():
    found = lint_sources({"distributed_tensorflow_tpu/models/m.py":
        textwrap.dedent('''
        from jax.sharding import PartitionSpec as P

        from ..parallel import sharding

        TABLE = sharding.partition_rules(
            "m", ((r"kernel$", P(None, "model")),
                  (sharding.CATCH_ALL, sharding.REPLICATED)),
            coverage=("a/kernel", "a/bias"))
    ''')}, rules=["sharding-seam-bypass"])
    assert found == []


def test_shard_rules_coverage_resolves_annotated_constant():
    """An annotated module constant (`_COV: tuple = (...)`) must not
    silently opt the table out of the simulation."""
    found = lint_snippet('''
        from jax.sharding import PartitionSpec as P
        from distributed_tensorflow_tpu.parallel.sharding import \\
            partition_rules

        _COV: tuple = ("layer/kernel", "layer/bias")

        T = partition_rules(
            "ann-cov", ((r"kernel$", P(None, "model")),), coverage=_COV)
    ''', rules=["shard-rules-coverage"])
    assert len(found) == 1
    assert "'layer/bias'" in found[0].message and "not total" in found[0].message
