"""Flagship model: a 5D-parallel transformer LM built on the tensor layer.

Composes the transport primitives into the model used by __graft_entry__ and
the TPU benches:

  dp — batch sharding, gradient merge by psum (the ParallelChannel +
       ResponseMerger mapping, SURVEY.md section 2.12)
  pp — spmd_pipeline over stages (cascade/streaming)
  tp — megatron head/ffn sharding with identity-fwd/psum-bwd boundaries
  sp — ring attention over the sequence (long-context first-class)
  ep — expert-parallel MoE via all_to_all

Everything is pure JAX under jit: static shapes, lax.scan for layer loops,
collectives only via named mesh axes inside shard_map.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from brpc_tpu.tensor.config import MeshSpec, ModelConfig
from brpc_tpu.tensor.moe import MoEParams, init_moe, moe_layer
from brpc_tpu.tensor.pipeline import spmd_pipeline
from brpc_tpu.tensor.ring_attention import local_attention, ring_attention


class LayerParams(NamedTuple):
    ln1: jax.Array  # [L, D]
    wq: jax.Array  # [L, D, H*Dh]
    wk: jax.Array  # [L, D, H*Dh]
    wv: jax.Array  # [L, D, H*Dh]
    wo: jax.Array  # [L, H*Dh, D]
    ln2: jax.Array  # [L, D]
    moe: MoEParams  # router [L,D,E], w_in [L,E,D,F], w_out [L,E,F,D]


class Params(NamedTuple):
    embed: jax.Array  # [V, D] (tied unembedding)
    layers: LayerParams  # stacked over ALL layers (n_layers * pp)
    final_norm: jax.Array  # [D]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_params(cfg: ModelConfig, key, pp_stages: int = 1) -> Params:
    dt = _dtype(cfg)
    n_total = cfg.n_layers * pp_stages
    keys = jax.random.split(key, 6)
    d, dq = cfg.d_model, cfg.d_qkv

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) / np.sqrt(fan_in)).astype(dt)

    moe_keys = jax.random.split(keys[5], n_total)
    moes = [init_moe(mk, d, cfg.d_ff, cfg.n_experts, dt) for mk in moe_keys]
    moe = MoEParams(*(jnp.stack(t) for t in zip(*moes)))
    return Params(
        embed=dense(keys[0], (cfg.vocab, d), d),
        layers=LayerParams(
            ln1=jnp.ones((n_total, d), dt),
            wq=dense(keys[1], (n_total, d, dq), d),
            wk=dense(keys[2], (n_total, d, dq), d),
            wv=dense(keys[3], (n_total, d, dq), d),
            wo=dense(keys[4], (n_total, dq, d), dq),
            ln2=jnp.ones((n_total, d), dt),
            moe=moe,
        ),
        final_norm=jnp.ones((d,), dt),
    )


def params_pspecs(cfg: ModelConfig) -> Params:
    """PartitionSpecs: pp shards the stacked layer dim, tp the head dims, ep
    the expert dim; embed/final_norm replicated."""
    return Params(
        embed=P(None, None),
        layers=LayerParams(
            ln1=P("pp", None),
            wq=P("pp", None, "tp"),
            wk=P("pp", None, "tp"),
            wv=P("pp", None, "tp"),
            wo=P("pp", "tp", None),
            ln2=P("pp", None),
            moe=MoEParams(
                router=P("pp", None, None),
                w_in=P("pp", "ep", None, None),
                w_out=P("pp", "ep", None, None),
            ),
        ),
        final_norm=P(None),
    )


def _rmsnorm(x, scale):
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return ((xf / rms) * scale.astype(jnp.float32)).astype(x.dtype)


def _identity_fwd_psum_bwd(axis_name):
    """Megatron 'f': activations replicated fwd; cotangent psum'd bwd so
    replicated-weight grads stay identical across the tp group."""

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def _psum_fwd_identity_bwd(axis_name):
    """Megatron 'g': partial outputs summed fwd; cotangent passes through."""

    @jax.custom_vjp
    def g(x):
        return lax.psum(x, axis_name)

    def fwd(x):
        return lax.psum(x, axis_name), None

    def bwd(_, gr):
        return (gr,)

    g.defvjp(fwd, bwd)
    return g


def _layer(
    x,  # [B, T, D] local activation shard
    lp,  # one layer's params (local shards)
    cfg: ModelConfig,
    tp_axis: Optional[str],
    sp_axis: Optional[str],
    ep_axis: Optional[str],
    n_heads_local: int,
):
    B, T, D = x.shape
    h = _rmsnorm(x, lp.ln1)
    if tp_axis is not None:
        h = _identity_fwd_psum_bwd(tp_axis)(h)
    q = (h @ lp.wq).reshape(B, T, n_heads_local, cfg.d_head)
    k = (h @ lp.wk).reshape(B, T, n_heads_local, cfg.d_head)
    v = (h @ lp.wv).reshape(B, T, n_heads_local, cfg.d_head)
    if sp_axis is not None:
        attn = ring_attention(q, k, v, sp_axis, causal=True)
    else:
        attn = local_attention(q, k, v, causal=True)
    y = attn.reshape(B, T, n_heads_local * cfg.d_head) @ lp.wo
    if tp_axis is not None:
        y = _psum_fwd_identity_bwd(tp_axis)(y)
    x = x + y

    h2 = _rmsnorm(x, lp.ln2)
    m = moe_layer(
        lp.moe,
        h2.reshape(B * T, D),
        n_experts=cfg.n_experts,
        capacity_factor=cfg.expert_capacity_factor,
        ep_axis=ep_axis,
    )
    return x + m.reshape(B, T, D)


def _stack_scan(layers: LayerParams, x, layer_fn):
    """Run the stacked layers with lax.scan (static unrolled graph size 1)."""

    def body(carry, lp):
        return layer_fn(carry, lp), None

    out, _ = lax.scan(body, x, layers)
    return out


def forward_local(params: Params, tokens, cfg: ModelConfig):
    """Single-device forward (the jittable entry() path): identical math to
    the SPMD path with every mesh axis of size 1."""
    x = jnp.take(params.embed, tokens, axis=0)
    layer_fn = functools.partial(
        _layer,
        cfg=cfg,
        tp_axis=None,
        sp_axis=None,
        ep_axis=None,
        n_heads_local=cfg.n_heads,
    )
    x = _stack_scan(params.layers, x, lambda c, lp: layer_fn(c, lp))
    x = _rmsnorm(x, params.final_norm)
    return (x @ params.embed.T).astype(jnp.float32)


def _loss_from_logits(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.sum()


def make_mesh(spec: MeshSpec) -> Mesh:
    devs = np.array(jax.devices()[: spec.n_devices]).reshape(
        spec.dp, spec.pp, spec.tp, spec.sp, spec.ep
    )
    return Mesh(devs, MeshSpec.AXIS_NAMES)


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off: masked psum broadcasts and
    all_to_all-replicated values are mathematically replicated but opaque to
    the checker."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)


def make_spmd_forward(cfg: ModelConfig, spec: MeshSpec, n_microbatches: int = 1):
    """Forward over the full 5-axis mesh; returns (mesh, jitted fn)."""
    mesh = make_mesh(spec)
    fwd = _make_spmd_fwd_inner(cfg, spec, n_microbatches)
    mapped = _shard_map(
        fwd,
        mesh,
        in_specs=(params_pspecs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp", None),
    )
    return mesh, jax.jit(mapped)


def _make_spmd_fwd_inner(cfg: ModelConfig, spec: MeshSpec, n_microbatches: int):
    tp_axis = "tp" if spec.tp > 1 else None
    sp_axis = "sp"  # always ring over sp (size-1 ring degenerates correctly)
    ep_axis = "ep" if spec.ep > 1 else None
    n_heads_local = cfg.n_heads // spec.tp
    assert n_heads_local * spec.tp == cfg.n_heads, "n_heads must divide tp"
    if ep_axis is not None:
        assert cfg.n_experts % spec.ep == 0, "n_experts must divide ep"

    layer_fn = functools.partial(
        _layer,
        cfg=cfg,
        tp_axis=tp_axis,
        sp_axis=sp_axis,
        ep_axis=ep_axis,
        n_heads_local=n_heads_local,
    )

    def stage_fn(stage_layers, x_mb):
        return _stack_scan(stage_layers, x_mb, lambda c, lp: layer_fn(c, lp))

    def fwd(params: Params, tokens):
        B, T = tokens.shape  # local shard: [B/dp, T/sp]
        x = jnp.take(params.embed, tokens, axis=0)
        assert B % n_microbatches == 0, "local batch must divide microbatches"
        mb = B // n_microbatches
        x = x.reshape(n_microbatches, mb, T, cfg.d_model)
        if spec.pp > 1:
            out = spmd_pipeline(stage_fn, params.layers, x, "pp")
        else:
            out = jax.vmap(lambda m: stage_fn(params.layers, m))(x)
        x = out.reshape(B, T, cfg.d_model)
        x = _rmsnorm(x, params.final_norm)
        return (x @ params.embed.T).astype(jnp.float32)

    return fwd


def make_spmd_train_step(
    cfg: ModelConfig,
    spec: MeshSpec,
    n_microbatches: int = 1,
    lr: float = 1e-2,
):
    """Full training step over the 5-axis mesh: fwd, bwd, gradient merge
    (psum over dp+sp; pp for shared leaves), SGD update. Returns
    (mesh, jitted (params, tokens, labels) -> (loss, new_params))."""
    mesh = make_mesh(spec)
    fwd = _make_spmd_fwd_inner(cfg, spec, n_microbatches)
    pspecs = params_pspecs(cfg)

    n_global_tokens_factor = spec.dp * spec.sp  # local count * this = global

    def step(params: Params, tokens, labels):
        def loss_fn(p):
            logits = fwd(p, tokens)
            local = _loss_from_logits(logits, labels)
            total = lax.psum(local, ("dp", "sp"))
            n = tokens.size * n_global_tokens_factor
            return total / n

        loss, grads = jax.value_and_grad(loss_fn)(params)

        def sync(g, spec_leaf):
            g = lax.psum(g, ("dp", "sp"))
            # Leaves not stacked over pp (embed, final_norm) get partial
            # contributions per stage -> reduce over pp too.
            if not (len(spec_leaf) > 0 and spec_leaf[0] == "pp"):
                g = lax.psum(g, "pp")
            return g

        grads = jax.tree.map(
            sync, grads, pspecs, is_leaf=lambda x: isinstance(x, P)
        )
        new_params = jax.tree.map(
            lambda p, g: (p - lr * g.astype(jnp.float32).astype(p.dtype)),
            params,
            grads,
        )
        return loss, new_params

    mapped = _shard_map(
        step,
        mesh,
        in_specs=(pspecs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(P(), pspecs),
    )
    return mesh, jax.jit(mapped)
