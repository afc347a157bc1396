"""One place per model family, found by the `model_type` of the
configuration file, as `reference/<model_type>.py` is. A family is new
files only:

- `families/<model_type>/adapter.py`, the program side: the program's model
  config for a configuration file (`model_config`), the two tree mappings
  (`to_program_tree`, `from_program_tree`), the workload a training job
  runs (`TRAIN_WORKLOAD`) with the model's part of its overrides
  (`train_overrides`), and how the file's `serving` block becomes the
  engine's arguments (`engine_args`). With `program.py`, the adapters are
  the only importers of the program under `benchmark/`.
- `families/<model_type>/counts.py`, the yardstick side, free of the
  program: `param_count`, needed FLOPs of a trained token and of a served
  forward (`train_flops_per_token`, `forward_flops`), the `(heads,
  head_dim)` the flash roofline is reckoned at (`attention_shape`), bytes
  of cache one attended token costs in one layer and in how many layers
  (`kv_bytes_per_token`, `cache_layers`).

Nothing else under `benchmark/` knows a family's key names.
"""

import importlib


def adapter(cfg: dict):
    return importlib.import_module(
        f"benchmark.families.{cfg['model_type']}.adapter")


def counts(cfg: dict):
    return importlib.import_module(
        f"benchmark.families.{cfg['model_type']}.counts")
