"""Dry-run machinery on a small in-process mesh (8 fake devices).

The full 512-device production dry-run runs via
``python -m repro.launch.dryrun --all`` (results in EXPERIMENTS.md §Dry-run);
here we verify the same build path lowers+compiles for every arch on a
(2, 4) mesh inside pytest, using a subprocess so the forced device count
never leaks into other tests.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.configs.base import ARCH_IDS

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.configs.base import get_reduced
from repro.launch.mesh import ShardingCtx
from repro.launch.roofline import count_params
from repro.launch.hlo_analysis import analyze
from repro.models.api import Model, ShapeSpec
from repro.launch.train import make_train_step
from repro.optim import adamw

arch = {arch!r}
cfg = get_reduced(arch)
mesh = jax.make_mesh(
    (2, 4), ("data", "model"),
    axis_types=(jax.sharding.AxisType.Auto,) * 2,
)
ctx = ShardingCtx(mesh, cfg)
model = Model.for_config(cfg)
shape = ShapeSpec("small_train", seq_len=32, global_batch=4, kind="train")
params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
p_shard = ctx.param_shardings(params_shape)
batch = model.input_specs(shape)
b_shard = ctx.batch_shardings(batch)
opt_shape = jax.eval_shape(lambda: adamw.init_state(params_shape))
o_shard = {{
    "step": ctx.replicated(opt_shape["step"]),
    "m": ctx.param_shardings(opt_shape["m"]),
    "v": ctx.param_shardings(opt_shape["v"]),
}}
step = make_train_step(model, adamw.AdamWConfig(), constrain=ctx.constrain, remat=True)
with mesh:
    compiled = jax.jit(
        step, in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
    ).lower(params_shape, opt_shape, batch).compile()
    mem = compiled.memory_analysis()
cost = analyze(compiled.as_text())
print(json.dumps({{
    "ok": True,
    "flops": cost.flops,
    "bytes": cost.bytes,
    "temp": getattr(mem, "temp_size_in_bytes", 0),
}}))
"""


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_small_mesh_dryrun(arch):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, f"{arch} dry-run failed:\n{r.stderr[-3000:]}"
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0 and res["temp"] > 0
