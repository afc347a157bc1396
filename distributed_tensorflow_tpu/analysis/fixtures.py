"""Shipped lint fixtures — the self-check corpus.

For every rule: a POSITIVE snippet (must produce exactly that rule at
the line marked ``# fires-here``), a NEGATIVE snippet (the idiomatic
clean version — must produce nothing), and a SUPPRESSED snippet (the
positive plus a ``# dtflint: disable=<rule>`` marker — must produce
nothing). ``tools/dtf_lint.py --self-check`` runs all three for every
rule before the tree lint, so the CI gate can never rot silently: a
rule that stops firing on its own positive fixture fails the gate even
though the (now-unprotected) tree still lints clean.

tests/test_lint.py drives the same corpus through the library API and
additionally pins file:line anchoring and the exit-code contract.
"""

from __future__ import annotations

FIRES_MARKER = "# fires-here"

#: rules whose fixtures are PATH-SENSITIVE (seam rules fire only on
#: seam paths) lint under a seam-shaped path instead of the default
#: ``<fixture:rule:corpus>`` pseudo-path
FIXTURE_PATHS: dict[str, str] = {
    "wall-clock-in-seam":
        "distributed_tensorflow_tpu/data/_fixture_{corpus}.py",
    # axis literals are checked only inside the mesh-consuming dirs
    "mesh-axis-closed-vocab":
        "distributed_tensorflow_tpu/parallel/_fixture_{corpus}.py",
    # placement constructions are checked across the package dirs,
    # outside the seam file itself
    "sharding-seam-bypass":
        "distributed_tensorflow_tpu/serve/_fixture_{corpus}.py",
}


def fixture_path(rule: str, corpus: str) -> str:
    """The path a fixture lints under (seam rules need seam paths)."""
    tmpl = FIXTURE_PATHS.get(rule)
    if tmpl is None:
        return f"<fixture:{rule}:{corpus}>"
    return tmpl.format(corpus=corpus)


def injection_path(rule: str) -> str:
    """Relative on-disk path at which the positive fixture must fire
    when a tree containing it is linted (tests/test_lint.py's CLI
    injection gate writes fixtures at these paths)."""
    tmpl = FIXTURE_PATHS.get(rule)
    if tmpl is None:
        return f"bad_{rule.replace('-', '_')}.py"
    return tmpl.format(corpus="positive")


def expected_line(source: str) -> int:
    """1-based line carrying the ``# fires-here`` marker."""
    for i, line in enumerate(source.splitlines(), 1):
        if FIRES_MARKER in line:
            return i
    raise ValueError("fixture has no fires-here marker")


POSITIVE: dict[str, str] = {
    "host-sync-in-step": '''\
import jax
import numpy as np


@jax.jit
def train_step(state, batch):
    grads = batch["x"] * 2.0
    loss = float(grads.sum())  # fires-here
    return state, {"loss": loss}
''',
    "donation-after-use": '''\
import jax


def _step(state, batch):
    return state


step = jax.jit(_step, donate_argnums=(0,))


def run_once(state, batch):
    new_state = step(state, batch)
    print(state.params)  # fires-here
    return new_state
''',
    "lock-discipline": '''\
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def push(self, x):
        with self._lock:
            self._items.append(x)

    def size(self):
        return len(self._items)  # fires-here
''',
    "closed-vocab": '''\
class Engine:
    def __init__(self, flightrec):
        self.flightrec = flightrec

    def poke(self):
        self.flightrec.emit("warp_core_breach", step=1)  # fires-here
''',
    "exception-hygiene": '''\
def best_effort_cleanup(path):
    try:
        open(path).close()
    except:  # fires-here
        pass
''',
    "wall-clock-in-seam": '''\
import time


def stamp_batch(batch):
    batch["t"] = time.time()  # fires-here
    return batch
''',
    "atomic-durable-write": '''\
import json
import os


def write_manifest(directory, doc):
    path = os.path.join(directory, "MANIFEST.json")
    with open(path, "w") as f:  # fires-here
        json.dump(doc, f)
''',
    "metric-naming": '''\
class Worker:
    def __init__(self, registry):
        self._m_restarts = registry.counter(  # fires-here
            "worker_restarts", "restarts observed")
''',
    "shard-rules-coverage": '''\
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.parallel.sharding import partition_rules

TABLE = partition_rules(
    "fixture-model",
    (
        (r"kernel$", P(None, "model")),
        (r"kernle$", P("model")),  # fires-here
        (r".*", P()),
    ),
    coverage=("layer_0/kernel", "layer_0/bias"),
)
''',
    "mesh-axis-closed-vocab": '''\
from jax import lax


def global_sum(x):
    return lax.psum(x, "dtaa")  # fires-here
''',
    "sharding-seam-bypass": '''\
import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def place_batch(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P("data")))  # fires-here
''',
}


NEGATIVE: dict[str, str] = {
    "host-sync-in-step": '''\
import jax
import jax.numpy as jnp


@jax.jit
def train_step(state, batch):
    grads = batch["x"] * 2.0
    loss = jnp.sum(grads)
    return state, {"loss": loss}


def report(metrics):
    # host side, outside the jitted step: syncing is the point
    return float(metrics["loss"])
''',
    "donation-after-use": '''\
import jax


def _step(state, batch):
    return state


step = jax.jit(_step, donate_argnums=(0,))


def run_once(state, batch):
    state = step(state, batch)
    return state.params
''',
    "lock-discipline": '''\
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def push(self, x):
        with self._lock:
            self._items.append(x)

    def size(self):
        with self._lock:
            return len(self._items)

    def _size_unlocked(self):
        return len(self._items)
''',
    "closed-vocab": '''\
class Engine:
    def __init__(self, flightrec, reqtrace):
        self.flightrec = flightrec
        self.reqtrace = reqtrace

    def poke(self):
        self.flightrec.emit("serve_admit", uid=1, slot=0)
        self.reqtrace.transition(7, "decode_gap", n=1)
''',
    "exception-hygiene": '''\
import logging

logger = logging.getLogger(__name__)


def best_effort_cleanup(path):
    try:
        open(path).close()
    except OSError:
        logger.exception("cleanup of %s failed", path)
''',
    "wall-clock-in-seam": '''\
import time

import numpy as np


def make_batch(seed, index, clock=time.monotonic):
    # the sanctioned idioms: seeded generator, injectable clock seam
    rng = np.random.RandomState((seed + index) & 0x7FFFFFFF)
    return {"x": rng.uniform(size=(4,)), "queued_at": clock()}
''',
    "atomic-durable-write": '''\
import json
import os


def write_manifest(directory, doc):
    path = os.path.join(directory, "MANIFEST.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
''',
    "metric-naming": '''\
class Worker:
    def __init__(self, registry):
        self._m_restarts = registry.counter(
            "worker_restarts_total", "restarts observed")
        self._m_step = registry.histogram(
            "worker_step_seconds", "wall-clock seconds per step")
        self._m_occupancy = registry.gauge(
            "worker_occupancy", "active slots at the last step")
''',
    "shard-rules-coverage": '''\
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.parallel.sharding import partition_rules

TABLE = partition_rules(
    "fixture-model",
    (
        (r"kernel$", P(None, "model")),
        (r".*", P()),
    ),
    coverage=("layer_0/kernel", "layer_0/bias"),
)
''',
    "mesh-axis-closed-vocab": '''\
from jax import lax

from ..parallel import mesh as mesh_lib


def global_sum(x):
    # vocabulary axes — as literals or (better) the mesh_lib constants
    partial = lax.psum(x, "data")
    return lax.psum(partial, mesh_lib.MODEL)
''',
    "sharding-seam-bypass": '''\
import jax
from jax.sharding import PartitionSpec as P

from ..parallel import sharding


def cache_rules():
    # carve-out (a): *_rules row builders compose partition tables
    return ((r"(^|/)(k|v)$", P(None, "model")),)


def island_mean(mesh, x):
    # carve-out (b): specs inside a shard_map island describe the
    # island's local view, not persistent placement
    f = jax.shard_map(lambda a: a.mean(), mesh=mesh,
                      in_specs=P("data"), out_specs=P())
    return f(x)


def place_batch(mesh, x):
    # persistent placement goes through the seam helpers
    return sharding.shard_leading_dim(x, mesh, "data")
''',
}


SUPPRESSED: dict[str, str] = {
    "host-sync-in-step": '''\
import jax


@jax.jit
def train_step(state, batch):
    loss = float(batch.sum())  # dtflint: disable=host-sync-in-step
    return state, {"loss": loss}
''',
    "donation-after-use": '''\
import jax


def _step(state, batch):
    return state


step = jax.jit(_step, donate_argnums=(0,))


def run_once(state, batch):
    new_state = step(state, batch)
    # dtflint: disable=donation-after-use
    print(state.params)
    return new_state
''',
    "lock-discipline": '''\
import threading


class Ring:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def push(self, x):
        with self._lock:
            self._items.append(x)

    def size(self):
        return len(self._items)  # dtflint: disable=lock-discipline
''',
    "closed-vocab": '''\
class Engine:
    def __init__(self, flightrec):
        self.flightrec = flightrec

    def poke(self):
        # deliberate negative-path probe, e.g. a must-raise test
        self.flightrec.emit("warp_core_breach")  # dtflint: disable=closed-vocab
''',
    "exception-hygiene": '''\
def best_effort_cleanup(path):
    try:
        open(path).close()
    except:  # dtflint: disable=exception-hygiene
        pass
''',
    "wall-clock-in-seam": '''\
import time


def stamp_batch(batch):
    # informational metadata, reviewed: not a trajectory input
    batch["t"] = time.time()  # dtflint: disable=wall-clock-in-seam
    return batch
''',
    "atomic-durable-write": '''\
import json
import os


def write_manifest(directory, doc):
    path = os.path.join(directory, "MANIFEST.json")
    # reviewed: freshness over durability, torn records detected upstream
    with open(path, "w") as f:  # dtflint: disable=atomic-durable-write
        json.dump(doc, f)
''',
    "metric-naming": '''\
class Worker:
    def __init__(self, registry):
        # legacy dashboard name, reviewed
        self._m_restarts = registry.counter(  # dtflint: disable=metric-naming
            "worker_restarts", "restarts observed")
''',
    "shard-rules-coverage": '''\
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.parallel.sharding import partition_rules

TABLE = partition_rules(
    "fixture-model",
    (
        (r"kernel$", P(None, "model")),
        # variant row kept for an out-of-run tree, reviewed
        (r"kernle$", P("model")),  # dtflint: disable=shard-rules-coverage
        (r".*", P()),
    ),
    coverage=("layer_0/kernel", "layer_0/bias"),
)
''',
    "mesh-axis-closed-vocab": '''\
from jax import lax


def global_sum(x):
    # dynamically bound sub-axis, reviewed
    return lax.psum(x, "dtaa")  # dtflint: disable=mesh-axis-closed-vocab
''',
    "sharding-seam-bypass": '''\
import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def place_batch(mesh, x):
    # transitional call site, reviewed — migrating to the seam next PR
    # dtflint: disable=sharding-seam-bypass
    return jax.device_put(x, NamedSharding(mesh, P("data")))
''',
}


def self_check() -> list[str]:
    """Run every fixture through the real rule set; returns failure
    descriptions (empty == the lint layer is alive and precise)."""
    from .core import RULES, lint_sources

    failures: list[str] = []
    for rule in sorted(RULES):
        for corpus, name in ((POSITIVE, "positive"), (NEGATIVE, "negative"),
                             (SUPPRESSED, "suppressed")):
            if rule not in corpus:
                failures.append(f"{rule}: no {name} fixture shipped")
    for rule, src in POSITIVE.items():
        want_line = expected_line(src)
        found = lint_sources({fixture_path(rule, "positive"): src})
        hits = [f for f in found if f.rule == rule]
        if not hits:
            failures.append(
                f"{rule}: positive fixture produced no finding — the "
                f"rule went dead")
        elif all(f.line != want_line for f in hits):
            failures.append(
                f"{rule}: positive fixture fired at line(s) "
                f"{[f.line for f in hits]}, expected {want_line}")
        for f in found:
            if f.rule != rule:
                failures.append(
                    f"{rule}: positive fixture also tripped {f.rule} "
                    f"at line {f.line} — fixtures must isolate one rule")
    for rule, src in NEGATIVE.items():
        found = lint_sources({fixture_path(rule, "negative"): src})
        if found:
            failures.append(
                f"{rule}: negative fixture not clean: "
                f"{[f.format() for f in found]}")
    for rule, src in SUPPRESSED.items():
        found = lint_sources({fixture_path(rule, "suppressed"): src})
        if found:
            failures.append(
                f"{rule}: suppression marker ignored: "
                f"{[f.format() for f in found]}")
    return failures
