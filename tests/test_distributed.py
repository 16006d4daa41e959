"""Distribution-layer tests: sharding rule resolution, optimizer state
axes, compression, and an 8-device end-to-end subprocess check (device
count must be set before jax initializes, hence the subprocess)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SRC = str(Path(__file__).resolve().parent.parent / "src")
AUTO2 = (jax.sharding.AxisType.Auto,) * 2


def _child_env() -> dict:
    """A clean child environment on the CPU backend: the child sets its
    own virtual device count and must not load the TPU library."""
    return {"PYTHONPATH": SRC,
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}


# ----------------------------------------------------------- rule logic
def test_spec_divisibility_fallback():
    from repro.distributed.sharding import spec_for
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = {"q_heads": ("model",), "embed": ("data",)}
    # trivially divisible by 1
    assert spec_for(mesh, rules, (9, 64), ("q_heads", "embed")) == \
        P("model", "data")


def test_spec_axis_used_once():
    from repro.distributed.sharding import spec_for
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)
    rules = {"experts": ("model",), "ffn": ("model",), "embed": ("data",)}
    # model axis consumed by experts; ffn must stay unsharded
    spec = spec_for(mesh, rules, (16, 4, 128), ("experts", "embed", "ffn"))
    assert spec == P("model", "data", None)


def test_opt_state_axes_match_params():
    from repro.configs import get_smoke
    from repro.models import abstract_params, param_logical_axes
    from repro.optim import make_optimizer, opt_state_logical_axes
    for arch in ("smollm-135m", "llama3-405b"):
        cfg = get_smoke(arch)
        p_abs = abstract_params(cfg)
        p_axes = param_logical_axes(cfg)
        opt_init, _ = make_optimizer(cfg.optimizer)
        o_abs = jax.eval_shape(opt_init, p_abs)
        o_axes = opt_state_logical_axes(cfg.optimizer, p_axes, p_abs)
        # same tree structure => tree_shardings can zip them
        jax.tree.map(lambda a, b: None, o_abs, o_axes,
                     is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------- compression
def test_compression_error_feedback_converges():
    from repro.distributed.compression import (
        compress_grads_with_feedback, init_error_state)
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal(1000), jnp.float32)}
    err = init_error_state(g)
    applied = jnp.zeros(1000)
    for _ in range(30):
        out, err = compress_grads_with_feedback(g, err)
        applied = applied + out["w"]
    # error feedback: accumulated applied updates track the true sum
    true = 30 * g["w"]
    rel = float(jnp.linalg.norm(applied - true) / jnp.linalg.norm(true))
    assert rel < 0.01


def test_compression_single_round_bounded_error():
    from repro.distributed.compression import (
        compress_grads_with_feedback, init_error_state)
    g = {"w": jnp.linspace(-1, 1, 512)}
    out, err = compress_grads_with_feedback(g, init_error_state(g))
    assert float(jnp.max(jnp.abs(out["w"] - g["w"]))) < 1.5 / 127


# --------------------------------------------- 8-device subprocess e2e
_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    AUTO2 = (jax.sharding.AxisType.Auto,) * 2
    from repro.configs import get_smoke
    from repro.train.steps import (init_train_state, make_train_step,
                                   batch_shardings, input_specs)
    from repro.distributed.sharding import default_rules
    cfg = get_smoke("smollm-135m")
    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
    rules = default_rules(mesh)
    step_fn, shardings, _ = make_train_step(cfg, mesh)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32)}
    with mesh:
        jit_step = jax.jit(step_fn, in_shardings=(shardings, None),
                           out_shardings=(shardings, None),
                           donate_argnums=(0,))
        for _ in range(3):
            state, metrics = jit_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # elastic reshard: move restored state to a (2, 4) mesh
    from repro.checkpoint import LSMCheckpointStore, flatten_state
    from repro.checkpoint.restore import reshard_restore
    from repro.train.steps import train_state_axes
    import tempfile
    store = LSMCheckpointStore(tempfile.mkdtemp())
    host = jax.tree.map(np.asarray, state)
    store.put_delta(0, flatten_state(host))
    mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
    restored, _ = reshard_restore(store, mesh2, train_state_axes(cfg))
    step_fn2, sh2, _ = make_train_step(cfg, mesh2)
    with mesh2:
        state2, m2 = jax.jit(step_fn2, in_shardings=(sh2, None),
                             out_shardings=(sh2, None))(restored, batch)
    assert np.isfinite(float(m2["loss"]))
    print("SUBPROC_OK")
""")


@pytest.mark.slow
def test_multidevice_train_and_elastic_reshard():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600,
                       env=_child_env())
    assert "SUBPROC_OK" in r.stdout, r.stdout + r.stderr


_SUBPROC_MOE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    AUTO2 = (jax.sharding.AxisType.Auto,) * 2
    from repro.configs import get_smoke
    from repro.models import init_params, train_loss
    from repro.distributed.sharding import default_rules, make_constrainer

    cfg = get_smoke("phi3.5-moe-42b-a6.6b")
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # dropless
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16),
                                          0, cfg.vocab)}
    # reference: single-device dispatch path
    ref_loss, _ = jax.jit(lambda p, b: train_loss(cfg, p, b))(params, batch)
    # expert-parallel shard_map path on a (4, 2) mesh (model axis = 2
    # divides the 4 smoke experts)
    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
    sh = make_constrainer(mesh, default_rules(mesh))
    with mesh:
        ep_loss, _ = jax.jit(lambda p, b: train_loss(cfg, p, b, sh=sh))(
            params, batch)
    err = abs(float(ref_loss) - float(ep_loss))
    assert err < 2e-4, (float(ref_loss), float(ep_loss))
    print("MOE_EP_OK", err)
""")


@pytest.mark.slow
def test_moe_expert_parallel_matches_single_device():
    """The shard_map EP dispatch computes the same loss as the pure path
    (dropless capacity so routing is identical)."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_MOE],
                       capture_output=True, text=True, timeout=600,
                       env=_child_env())
    assert "MOE_EP_OK" in r.stdout, r.stdout + r.stderr
