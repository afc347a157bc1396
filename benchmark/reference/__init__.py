"""Plain references, one module a model family, named by the `model_type`
of the configuration file: `reference/<model_type>.py`. A reference imports
nothing of the program and takes nothing that the program has made. The
drivers ask of it `make_weights`, `leaf_norms`, `train_steps` (training
cells) and `served_gaps` (serving cells)."""

import importlib


def for_config(cfg: dict):
    return importlib.import_module(f"benchmark.reference.{cfg['model_type']}")
