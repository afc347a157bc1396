"""Workload 3 — ResNet-50 / ImageNet (BASELINE.json:9): the primary-metric
run (images/sec/chip; ≥50% MFU north star on v4-32, BASELINE.json:5).

Reference analog: ResNet-50 PS/worker script whose structural bottleneck was
two gRPC round trips per variable per step (SURVEY.md §3.1). Here: bf16
SPMD step over the data axis; input pipeline synthetic by default (real
ImageNet plugs in via npz:/grain on the TPU-VM host)."""

from __future__ import annotations

from ..data import DataConfig, make_dataset
from ..models import common
from ..models.resnet import (
    RESNET_RULES, ResNet50, ResNetConfig, flops_per_example,
)
from ..parallel import MeshSpec
from ..train import OptimizerConfig
from .runner import RunConfig, TrainSection, WorkloadParts


def default_config() -> RunConfig:
    return RunConfig(
        workload="resnet50_imagenet",
        # space_to_depth conv0 (the MLPerf TPU stem) + bf16 BN output:
        # +28% images/sec over the naive stem/f32-BN config on a v5e
        # (previous toolchain — PERF.md "Earlier chip findings").
        model=ResNetConfig(stem="space_to_depth"),
        mesh=MeshSpec(data=-1),
        data=DataConfig(
            dataset="synthetic", global_batch_size=1024,
            image_size=224, channels=3, num_classes=1000,
        ),
        # 90-epoch ImageNet recipe at bs=1024: lr = 0.1 * bs/256 (linear
        # scaling), 5-epoch warmup, cosine to zero over 90 * 1.281e6 / 1024
        # ≈ 112590 steps.
        # weight decay rides the optimizer (coupled L2 on kernels, fused
        # into the update pass) rather than the loss graph — same math,
        # one fewer full-parameter pass per step
        optimizer=OptimizerConfig(
            name="momentum", learning_rate=0.4, momentum=0.9,
            schedule="warmup_cosine", warmup_steps=6255, total_steps=112590,
            weight_decay=1e-4,
        ),
        train=TrainSection(num_steps=112590, log_every=100),
    )


def build(cfg: RunConfig, mesh=None) -> WorkloadParts:
    model = ResNet50(cfg.model, mesh)
    input_shape = (cfg.data.image_size, cfg.data.image_size, cfg.data.channels)
    return WorkloadParts(
        init_fn=common.make_init_fn(model, input_shape),
        loss_fn=common.classification_loss_fn(model, label_smoothing=0.1),
        eval_fn=common.classification_eval_fn(model),
        dataset_fn=lambda start: make_dataset(cfg.data, index_offset=start),
        eval_dataset_fn=lambda n: make_dataset(
            cfg.data, n, index_offset=10**6, train=False),
        flops_per_step=flops_per_example(cfg.model, cfg.data.image_size)
        * cfg.data.global_batch_size,
        # pure DP: the one-row catch-all table — same replicated layout
        # as before, but now DECLARED through the rules engine
        param_rules=RESNET_RULES,
        batch_size=cfg.data.global_batch_size,
    )
