"""Per-architecture smoke tests: reduced config of the same family runs
one forward/train step + prefill + decode on CPU, asserts output shapes
and finiteness (assignment deliverable f)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke
from repro.models import (decode_step, init_params, param_count, prefill,
                          train_loss)
from repro.models.config import SHAPES, cell_applicable


def _batch(cfg, B=2, S=32, key=None):
    key = key or jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_frames, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(lambda p, b: train_loss(cfg, p, b))(params,
                                                                batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    grads = jax.grad(lambda p: train_loss(cfg, p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    cache, logits = jax.jit(lambda p, b: prefill(cfg, p, b, 32))(params,
                                                                 batch)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):
        cache, logits = jax.jit(
            lambda p, c, t: decode_step(cfg, p, c, t))(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert np.all(np.isfinite(np.asarray(logits)))
    assert int(cache["len"]) == S + 3 + (cfg.n_patches
                                         if cfg.family == "vlm" else 0)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "mamba2-1.3b": dict(n_layers=48, d_model=2048, vocab=50_280,
                            ssm_state=128),
        "gemma-7b": dict(n_layers=28, d_model=3072, n_heads=16,
                         n_kv_heads=16, d_ff=24_576, vocab=256_000),
        "nemotron-4-340b": dict(n_layers=96, d_model=18_432, n_heads=96,
                                n_kv_heads=8, d_ff=73_728, vocab=256_000),
        "llama3-405b": dict(n_layers=126, d_model=16_384, n_heads=128,
                            n_kv_heads=8, d_ff=53_248, vocab=128_256),
        "smollm-135m": dict(n_layers=30, d_model=576, n_heads=9,
                            n_kv_heads=3, d_ff=1_536, vocab=49_152),
        "whisper-base": dict(n_layers=6, n_enc_layers=6, d_model=512,
                             n_heads=8, d_ff=2_048, vocab=51_865),
        "phi3.5-moe-42b-a6.6b": dict(n_layers=32, d_model=4_096,
                                     n_heads=32, n_kv_heads=8, d_ff=6_400,
                                     vocab=32_064, n_experts=16, top_k=2),
        "kimi-k2-1t-a32b": dict(n_layers=61, d_model=7_168, n_heads=64,
                                n_kv_heads=8, d_ff=2_048, vocab=163_840,
                                n_experts=384, top_k=8),
        "zamba2-2.7b": dict(n_layers=54, d_model=2_560, n_heads=32,
                            n_kv_heads=32, d_ff=10_240, vocab=32_000,
                            ssm_state=64),
        "paligemma-3b": dict(n_layers=18, d_model=2_048, n_heads=8,
                             n_kv_heads=1, d_ff=16_384, vocab=257_216),
    }[arch]
    for k, v in expected.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_counts_in_range():
    """Total params land near the architectures' nameplate sizes."""
    approx = {
        "mamba2-1.3b": (1.0e9, 1.7e9),
        "gemma-7b": (7.0e9, 10.0e9),       # gemma counts exclude embeddings
        "nemotron-4-340b": (300e9, 380e9),
        "llama3-405b": (390e9, 430e9),
        "smollm-135m": (0.12e9, 0.15e9),
        "kimi-k2-1t-a32b": (0.9e12, 1.2e12),
        "zamba2-2.7b": (2.2e9, 3.2e9),
        "paligemma-3b": (2.2e9, 3.5e9),    # backbone only (SigLIP stubbed)
    }
    for arch, (lo, hi) in approx.items():
        n = param_count(get_config(arch))
        assert lo <= n <= hi, (arch, f"{n:.3e}")


def test_long_context_applicability():
    long = SHAPES["long_500k"]
    runs = [a for a in ARCHS
            if cell_applicable(get_config(a), long)[0]]
    assert sorted(runs) == ["mamba2-1.3b", "zamba2-2.7b"]


def test_input_specs_cover_all_cells():
    """Every applicable (arch x shape) cell has well-formed abstract
    inputs — the dry-run's contract."""
    from repro.train.steps import input_specs
    from repro.models.config import SHAPES
    import jax
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, why = cell_applicable(cfg, shape)
            if not ok:
                assert why, (arch, shape.name)
                continue
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            for leaf in jax.tree.leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)
            if shape.kind == "decode":
                assert "cache" in specs
                assert specs["tokens"].shape == (shape.global_batch,)
            else:
                assert specs["tokens"].shape[0] == shape.global_batch


def test_effective_microbatches_divisibility():
    from repro.train.steps import effective_microbatches
    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in ARCHS:
        cfg = get_config(arch)
        for gb in (256, 32, 8):
            mb = effective_microbatches(cfg, mesh, gb)
            assert gb % mb == 0 and mb >= 1
