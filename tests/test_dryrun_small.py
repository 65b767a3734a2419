"""Reduced-mesh dry-run in a subprocess (the only place allowed to force
a multi-device host): proves lower+compile works for a (2,2) and a
(2,2,2) multi-pod mesh over the same machinery as launch/dryrun.py."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.common.config import get_config, ShapeConfig, TrainConfig
from repro.common.sharding import merge_rules, tree_shardings
from repro.common.hlo_cost import analyze
from repro.layers.initializers import abstract_tree
from repro.models.api import build_model
from repro.training.optimizer import state_specs
from repro.training.train_step import make_train_step

multi_pod = %(multi_pod)s
mesh_shape, axes = (((2, 2, 2), ("pod", "data", "model")) if multi_pod
                    else ((2, 2), ("data", "model")))
mesh = jax.make_mesh(mesh_shape, axes,
                     axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
cfg = get_config("%(arch)s", smoke=True)
rules = merge_rules(None)
bundle = build_model(cfg, mesh=mesh, rules=rules)
tcfg = TrainConfig()
ss = state_specs(bundle.specs, tcfg)
sds = abstract_tree(ss, jnp.float32, tree_shardings(ss, rules, mesh))
shape = ShapeConfig("t", "train", 32, 8)
bs = bundle.batch_specs(shape)
bsds = abstract_tree(bs, jnp.bfloat16, tree_shardings(bs, rules, mesh))
step = make_train_step(bundle, tcfg)
with mesh:
    compiled = jax.jit(step, donate_argnums=(0,)).lower(sds, bsds).compile()
rep = analyze(compiled.as_text())
ma = compiled.memory_analysis()
print(json.dumps({
    "flops": rep.flops,
    "collective_bytes": rep.collective_bytes,
    "temp": int(ma.temp_size_in_bytes),
}))
"""


def _run(arch, multi_pod):
    code = SCRIPT % {"arch": arch, "multi_pod": multi_pod}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "JAX_PLATFORMS": "cpu"},
        timeout=600, cwd=str(REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "granite-moe-3b-a800m"])
def test_small_mesh_dryrun(arch):
    rec = _run(arch, multi_pod=False)
    assert rec["flops"] > 0
    assert rec["collective_bytes"] > 0     # gradient sync must appear
    assert rec["temp"] > 0


def test_small_multipod_dryrun():
    rec = _run("tinyllama-1.1b", multi_pod=True)
    assert rec["flops"] > 0
    assert rec["collective_bytes"] > 0
