"""Expert-parallel MoE dispatch == dense reference (8 fake devices,
subprocess-isolated).  Covers E % M == 0, E == M, and the virtual-split
path (E_v = E * split), plus gradient flow through the all_to_all pair."""

import json
import os
import subprocess
import sys
from pathlib import Path


SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from repro.configs import reduced_config
from repro.models.common import ModelConfig
from repro.models.moe import moe_init, moe_apply, _moe_dense
from repro.sharding.context import activation_rules

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = {{"moe_ep_axis": "model", "moe_dp_axes": ("data",), "mesh": mesh}}
out = {{}}

cases = [
    ("deepseek-v2-236b", {{}}),                                   # epr=2
    ("mixtral-8x22b", {{}}),                                      # E==M
    ("mixtral-8x22b", {{"n_experts": 2, "moe_virtual_split": 2}}),  # split
]
for i, (arch, over) in enumerate(cases):
    cfg = reduced_config(arch)
    cfg = ModelConfig(**{{**cfg.__dict__, "capacity_factor": 8.0, **over}})
    params = moe_init(jax.random.PRNGKey(i), cfg)
    rng = np.random.default_rng(i)
    x = jnp.asarray(rng.standard_normal((4, 8, cfg.d_model)).astype(np.float32))
    dense, _ = _moe_dense(params, x, cfg)
    with jax.set_mesh(mesh), activation_rules(rules):
        ep, _ = jax.jit(lambda p, xx: moe_apply(p, xx, cfg))(params, x)
        g = jax.jit(jax.grad(lambda p, xx: moe_apply(p, xx, cfg)[0].sum()))(
            params, x
        )
    err = float(jnp.abs(ep - dense).max())
    gn = float(sum(jnp.sum(t.astype(jnp.float32) ** 2)
                   for t in jax.tree.leaves(g))) ** 0.5
    out[f"case{{i}}"] = {{"err": err, "grad_norm": gn}}

print("RESULT " + json.dumps(out))
"""


def test_moe_ep_subprocess():
    code = SCRIPT.format(src=SRC)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    for case, rec in out.items():
        assert rec["err"] < 5e-4, (case, rec)
        assert rec["grad_norm"] > 0 and rec["grad_norm"] < 1e9, (case, rec)
