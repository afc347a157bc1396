"""distributed_tensorflow_tpu — a TPU-native distributed training framework.

A ground-up rebuild of the capability surface of the
``gctian/distributed-tensorflow`` parameter-server/worker harness (see
SURVEY.md for the structural analysis), designed TPU-first: one SPMD program
over a named device mesh, XLA collectives on ICI/DCN in place of the PS/gRPC
data plane, a jit-compiled train step in place of the SyncReplicasOptimizer
accumulator/token protocol, and a host-side callback loop with async
multi-host checkpointing in place of MonitoredTrainingSession and its hooks.
"""

__version__ = "0.1.0"

# Importing the package must initialise no jax backend: a launcher that
# imports it (and then starts the process that owns the chip) would
# otherwise take the chip itself (tests/test_chip_smoke.py pins this).
from . import data  # noqa: F401
from . import models  # noqa: F401
from . import obs  # noqa: F401
from . import parallel  # noqa: F401
from . import resilience  # noqa: F401
from . import serve  # noqa: F401
from . import train  # noqa: F401
from . import utils  # noqa: F401
from . import workloads  # noqa: F401
