"""Distribution plumbing: spec filtering, logical rules, a real 8-device
SPMD train step in a subprocess, and MoE shard_map parity on a 1x1 mesh."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_filter_spec_divisibility():
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert shd.filter_spec(P("data", "model"), (32, 32), mesh) == P("data", "model")
    assert shd.filter_spec(P("data", "model"), (32, 8), mesh) == P("data", None)
    assert shd.filter_spec(P(("data", "model")), (256,), mesh) == P(("data", "model"))
    assert shd.filter_spec(P(("data", "model")), (128,), mesh) == P(None)
    # shorter spec than rank pads with None
    assert shd.filter_spec(P("data"), (16, 4), mesh) == P("data", None)


def test_logical_spec_pod_expansion():
    mesh_no_pod = _FakeMesh({"data": 2, "model": 4})
    with shd.use_mesh(mesh_no_pod):
        assert shd.logical_spec("batch") == P("data")
    mesh_pod = _FakeMesh({"pod": 2, "data": 2, "model": 4})
    with shd.use_mesh(mesh_pod):
        assert shd.logical_spec("batch") == P(("pod", "data"))


def test_constraint_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert shd.constraint(x, "batch", None) is x


def test_moe_shard_map_matches_local():
    """On a (1,1) mesh the distributed MoE must equal the local path."""
    from repro.configs.registry import get_config
    from repro.models import moe as MOE
    from repro.models.params import init_params

    cfg = get_config("granite_moe_1b_a400m").smoke()
    defs = MOE.moe_defs(cfg)
    params = init_params(jax.random.key(0), defs)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, cfg.d_model)),
                    jnp.float32)
    out_local, aux_local = MOE.apply_moe(params, cfg, x)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    with shd.use_mesh(mesh):
        out_dist, aux_dist = MOE.apply_moe(params, cfg, x)
    np.testing.assert_allclose(
        np.asarray(out_local), np.asarray(out_dist), atol=1e-5
    )
    assert abs(float(aux_local) - float(aux_dist)) < 1e-5


_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.inputs import make_batch
    from repro.configs.base import RunShape
    from repro.models.transformer import LM
    from repro.optim import adamw as opt_mod
    from repro.train.step import build_train_step

    cfg = get_config("granite_moe_1b_a400m").smoke()
    mesh = make_smoke_mesh(2, 4)
    shd.set_mesh(mesh)
    model = LM(cfg, attn_impl="chunked", remat="full")
    params = model.init(jax.random.key(0))
    opt = opt_mod.init_opt_state(params)
    batch = make_batch(cfg, RunShape("t", 32, 4, "train"))
    step = jax.jit(build_train_step(model, opt_mod.AdamWConfig()),
                   donate_argnums=(0, 1))
    params, opt, metrics = step(params, opt, batch)
    l1 = float(metrics["loss"])

    # compare against the single-device (no-mesh) loss on the same inputs
    shd.set_mesh(None)
    model2 = LM(cfg, attn_impl="chunked", remat="full")
    params2 = model2.init(jax.random.key(0))
    l2 = float(model2.train_loss(params2, batch))
    print(json.dumps({"dist_loss": l1, "local_loss": l2}))
    """
)


def test_spmd_train_step_8_devices():
    """End-to-end: MoE model train step on a 2x4 mesh numerically matches
    the unsharded loss (run in a subprocess so the 8-device XLA_FLAGS does
    not leak into this process)."""
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             # the script forces 8 *host* devices — never let jax try to
             # initialize a real accelerator plugin in the bare subprocess
             "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # distributed loss == local forward loss on identical params/batch
    assert abs(rec["dist_loss"] - rec["local_loss"]) < 5e-3, rec
