"""Tensor-layer tests on the virtual 8-device CPU mesh (conftest.py).

Mirrors the reference's strategy (SURVEY.md section 4): distributed behavior
exercised with many in-process devices — SPMD output must match the
single-device path bit-for-bit-ish (fp32 tolerance).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.tensor.config import MeshSpec, ModelConfig
from brpc_tpu.tensor.model import (
    Params,
    forward_local,
    init_params,
    make_spmd_forward,
    make_spmd_train_step,
)
from brpc_tpu.tensor.ring_attention import local_attention, ring_attention


# expert_capacity_factor == n_experts guarantees zero token drops, so the
# sharded MoE (per-device routing, smaller local capacity) is exactly
# equivalent to the local path.
CFG = ModelConfig(
    vocab=64, d_model=32, n_heads=4, d_head=8, d_ff=32, n_layers=1,
    n_experts=4, expert_capacity_factor=4.0, dtype="float32",
)


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_ring_attention_matches_local():
    from jax.sharding import Mesh, PartitionSpec as P

    B, T, H, Dh = 2, 32, 4, 8
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (B, T, H, Dh), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expect = local_attention(q, k, v, causal=True)

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    got = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-5)


def test_forward_local_shapes_and_finite():
    params = init_params(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab)
    logits = jax.jit(lambda p, t: forward_local(p, t, CFG))(params, tokens)
    assert logits.shape == (2, 16, CFG.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize(
    "spec",
    [
        MeshSpec(dp=2, tp=2, sp=2),  # 8 devices
        MeshSpec(dp=2, pp=2, ep=2),
        MeshSpec(pp=2, tp=2, sp=2),
    ],
    ids=["dp-tp-sp", "dp-pp-ep", "pp-tp-sp"],
)
def test_spmd_forward_matches_local(spec):
    params = init_params(CFG, jax.random.PRNGKey(0), pp_stages=spec.pp)
    batch = spec.dp * 2
    seq = spec.sp * 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, CFG.vocab)
    expect = forward_local(params, tokens, CFG)
    _, fwd = make_spmd_forward(CFG, spec, n_microbatches=1)
    got = fwd(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=3e-4)


def test_spmd_train_step_decreases_loss():
    spec = MeshSpec(dp=2, pp=2, tp=2)
    cfg = CFG
    mesh, step = make_spmd_train_step(cfg, spec, n_microbatches=2, lr=0.1)
    params = init_params(cfg, jax.random.PRNGKey(0), pp_stages=spec.pp)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    loss0, params = step(params, tokens, labels)
    loss = loss0
    for _ in range(5):
        loss, params = step(params, tokens, labels)
    assert bool(jnp.isfinite(loss0)) and bool(jnp.isfinite(loss))
    assert float(loss) < float(loss0)


def test_graft_entry_contract():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]
    ge.dryrun_multichip(8)


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set (and nothing else is set);
    otherwise the cache sits at a fixed path inside the checkout."""
    from brpc_tpu import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable()
        if env_dir is None:
            assert got == compile_cache.CHECKOUT_CACHE_DIR
            assert got == os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
