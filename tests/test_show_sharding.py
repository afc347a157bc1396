"""tools/show_sharding.py — the placement-inspection surface referenced
by MIGRATION.md. Run as a subprocess (the tool owns its own device-count
setup), assert the plan it prints."""

import os
import subprocess
import pytest
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "show_sharding.py")


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, TOOL, *args], capture_output=True, text=True,
        env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.slow
def test_bert_tp_fsdp_plan():
    out = _run("bert_pretrain", "--mesh.data=2", "--mesh.fsdp=2",
               "--mesh.model=2")
    # megatron rules visible: qkv column-parallel, attn_out row-parallel
    assert "PartitionSpec(None, 'model')" in out
    assert "PartitionSpec('model', None)" in out
    # sharding actually reduces per-device bytes
    line = [l for l in out.splitlines() if "reduction" in l][0]
    factor = float(line.split("(")[1].split("x")[0])
    assert factor > 1.5, line


@pytest.mark.slow
def test_pipelined_plan_uses_explicit_specs():
    out = _run(
        "bert_pretrain", "--mesh.pipe=2", "--mesh.model=2", "--mesh.data=2",
        "--model.num_layers=2", "--model.d_model=32", "--model.num_heads=4",
        "--model.d_ff=64", "--model.vocab_size=128", "--data.vocab_size=128",
        "--data.seq_len=16", "--model.max_len=16",
    )
    # stacked [S, V, ...] leaves: pipe leads, model on kernel dims
    assert "PartitionSpec('pipe', None, None, 'model')" in out  # qkv kernel
    assert "PartitionSpec('pipe', None, 'model', None)" in out  # attn_out


def test_wildcard_mesh_with_nondividing_fixed_axis():
    """A -1 wildcard with a fixed axis that doesn't divide 8 (e.g.
    pipe=3) must still size a representable fake mesh (max(8, 3)=8,
    which 3 doesn't divide, would fail build_mesh)."""
    out = _run(
        "bert_pretrain", "--mesh.pipe=3", "--mesh.data=-1",
        "--model.num_layers=3", "--model.d_model=32", "--model.num_heads=4",
        "--model.d_ff=64", "--model.vocab_size=128", "--data.vocab_size=128",
        "--data.seq_len=16", "--model.max_len=16",
    )
    assert "pipe=3" in out


def test_rules_attribution_view():
    """--rules prints which table row won each param (index, regex,
    spec) — the coverage-failure debugging surface."""
    out = _run("wide_deep", "--rules", "--mesh.data=2", "--mesh.model=4")
    assert "table 'wide-deep': 3 rule(s)" in out
    assert (
        "table_0  <-  rule[0] '(^|/)table_\\\\d+$' "
        "-> PartitionSpec('model', None)" in out
    )
    assert (
        "wide_table_0  <-  rule[1] '(^|/)wide_table_\\\\d+$' "
        "-> PartitionSpec('model', None)" in out
    )
    assert "deep_0/kernel  <-  rule[2] '.*' -> PartitionSpec()" in out
    assert "UNMATCHED" not in out and "DEAD" not in out
