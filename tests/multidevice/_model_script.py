"""Subprocess body: sharded model train/decode on a 2x2x2 pod mesh, checking
that results match the single-device reference."""

import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")

import dataclasses
import numpy as np
import jax
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.configs import InputShape, get_config
from repro.core.layouts import AXIS_DATA, AXIS_MODEL, AXIS_POD
from repro.models import build_model
from repro.models.registry import make_batch

mesh = jax.make_mesh(
    (2, 2, 2), (AXIS_POD, AXIS_DATA, AXIS_MODEL), axis_types=(AxisType.Auto,) * 3
)
single = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), (AXIS_DATA, AXIS_MODEL))

shape = InputShape("md", seq_len=32, global_batch=4, kind="train")

for arch in ("qwen2-1.5b", "olmoe-1b-7b", "mamba2-130m"):
    cfg = dataclasses.replace(get_config(arch, smoke=True), compute_dtype="float32")
    if cfg.moe is not None:  # drop-free so 1-dev and 8-dev routing agree
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.num_experts))
        )
    batch = make_batch(cfg, shape, jax.random.PRNGKey(1))

    # reference on one device
    model_1 = build_model(cfg, single)
    params = model_1.init(jax.random.PRNGKey(0))
    with single:
        ref_loss, _ = jax.jit(model_1.loss)(params, batch)

    # sharded on the pod mesh
    model_8 = build_model(cfg, mesh)
    specs = model_8.param_partition_specs()
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    with mesh:
        loss_8, _ = jax.jit(model_8.loss)(sharded, batch)

    err = abs(float(ref_loss) - float(loss_8))
    assert err < 1e-3, f"{arch}: sharded loss differs by {err}"
    print(f"{arch}: 1-dev {float(ref_loss):.5f} vs 8-dev {float(loss_8):.5f} OK")

print("MULTIDEVICE_MODEL_OK")
