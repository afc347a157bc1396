"""What the benchmark knows about the program under test and no model
family owns: which of its entry points a driver calls, how its engine is
built, where its optimizer keeps Adam's state, its compile cache and its
span ring. What depends on the model (config mapping, parameter-tree paths,
the workload's name, the engine's settings) is the family's adapter,
`families/<model_type>/adapter.py`, found by the configuration file's
`model_type`. This module and the adapters are the only importers of the
program under `benchmark/`.
"""

from __future__ import annotations

import jax

from benchmark import families, reference


def train_overrides(cfg: dict, job: dict, seed: int, n_chips: int) -> list:
    """`--section.key=value` overrides that turn the default run of the
    family's workload into this cell's job."""
    seed31 = int(seed) & 0x7FFFFFFF
    out = families.adapter(cfg).train_overrides(cfg, job) + [
        f"--data.dataset={job['dataset']}",
        f"--data.seq_len={job['seq_len']}",
        f"--data.global_batch_size={job['sequences_per_chip'] * n_chips}",
        f"--data.seed={seed31}", f"--train.seed={seed31}",
        f"--mesh.data={n_chips}",
        # the window ends the run; nothing logs, evaluates or saves in it
        "--train.num_steps=1000000000", "--train.log_every=1000000000",
    ]
    out += [f"--optimizer.{k}={v}" for k, v in job["optimizer"].items()]
    out += list(job.get("overrides", []))
    return out


def run_training(cfg: dict, overrides: list, callback):
    """The program's own training entry with one more callback."""
    from distributed_tensorflow_tpu import workloads

    return workloads.run_workload(families.adapter(cfg).TRAIN_WORKLOAD,
                                  overrides, extra_callbacks=[callback])


def callback_base():
    from distributed_tensorflow_tpu.train import callbacks as cb

    return cb.Callback


def adam_mu(opt_state):
    """Adam's first moment inside the program's optax state."""
    for part in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state (mu) in the optimizer state")


def configure_compile_cache() -> str:
    from distributed_tensorflow_tpu.parallel import cluster

    return cluster.configure_compile_cache()


def make_engine(cfg: dict, seed: int):
    """The program's serving engine on the benchmark's weights (made from
    ``seed``), with the deployment's settings from the configuration file."""
    from distributed_tensorflow_tpu import serve

    adapter = families.adapter(cfg)
    return serve.ServeEngine(adapter.model_config(cfg),
                             program_weights(cfg, seed),
                             **adapter.engine_args(cfg))


def program_weights(cfg: dict, seed: int, shardings=None) -> dict:
    """The seed's weights in the program's tree, made on the device in one
    jitted call; ``shardings`` is a tree like the program's parameters."""
    tree = families.adapter(cfg).to_program_tree(
        reference.for_config(cfg).make_weights(cfg, seed, stacked=False))
    return tree if shardings is None else jax.device_put(tree, shardings)


def span_ring() -> list:
    """The completed spans of the program's default tracer (`obs/trace.py`),
    oldest first, on that tracer's clock (`time.perf_counter`, the engine's
    and the trainer's too)."""
    from distributed_tensorflow_tpu import obs

    return list(obs.default_tracer().events)
