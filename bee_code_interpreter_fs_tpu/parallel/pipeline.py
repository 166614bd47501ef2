"""Pipeline parallelism: a GPipe-style microbatch schedule as pure SPMD.

TPU-idiomatic formulation (no per-stage programs, no host scheduling): the
stacked layer weights [L, ...] reshape to [n_stages, L/S, ...] and shard
their leading dimension over a "pp" mesh axis; inside one shard_map'd
computation every device runs the same `lax.fori_loop` of M + S - 1 ticks,
processing its stage's layers each tick and handing activations to the next
stage with a single neighbor `ppermute` hop — the classic pipeline schedule,
but expressed as one jitted SPMD program XLA can overlap (the ppermute of
tick t runs concurrently with tick t+1's compute).

Bubble fraction is the usual (S-1)/(M+S-1); pick n_microbatches >= a few
times the stage count. Composition: the non-pp dimensions of the activations
stay ordinary GSPMD — dp/tp shardings on the microbatch/feature dims pass
through untouched; ring attention (sp) inside a stage is not supported in
this schedule (sequence and pipeline both want the collective budget; pick
one per deployment, as the scaling-book recipe does).

The reference has no parallelism of any kind (SURVEY.md §2 census); this is
part of the TPU-native framework's first-class distributed toolkit alongside
ring attention (sp), expert parallelism (ep), and tensor parallelism (tp).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from bee_code_interpreter_fs_tpu.parallel.mesh import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, microbatches, *, axis_name: str):
    """Run the pipeline schedule. CALL INSIDE shard_map with `axis_name`
    bound: `stage_params` is this device's stage slice, `microbatches`
    [M, mb, ...] is replicated input. Returns [M, mb, ...] — the fully
    processed microbatches, valid on the LAST stage (zeros elsewhere; the
    caller's out_spec exposes the pp dimension so it can slice them out).

    `stage_fn(stage_params, x) -> x` must preserve the activation shape
    (true for transformer blocks).
    """
    n_stages = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    out = jnp.zeros_like(microbatches)
    state = jnp.zeros_like(microbatches[0])

    def tick(carry, t):
        state, out = carry
        # Stage 0 injects microbatch t (clamped: late ticks re-inject the
        # last microbatch; its results never land in `out`, see below).
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        state = jnp.where(idx == 0, inject, state)
        state = stage_fn(stage_params, state)
        # The last stage finished microbatch t-(S-1) at tick t.
        done = t - (n_stages - 1)
        updated = lax.dynamic_update_index_in_dim(
            out, state, jnp.clip(done, 0, n_micro - 1), 0
        )
        collect = jnp.logical_and(idx == n_stages - 1, done >= 0)
        out = jnp.where(collect, updated, out)
        # Hand to the next stage; the ring edge S-1 -> 0 is harmless (stage
        # 0 overwrites with its injection).
        state = lax.ppermute(state, axis_name, perm)
        return (state, out), None

    # scan (not fori_loop): the tick count is static, and scan is reverse-
    # differentiable — jax.grad flows through the whole schedule, so the
    # pipeline trains, not just infers (the backward pass is the mirrored
    # pipeline: ppermute's transpose is the reverse-direction ring).
    (_, out), _ = lax.scan(
        tick, (state, out), jnp.arange(n_micro + n_stages - 1)
    )
    return out


def pipeline_stages(layer_tree, n_stages: int):
    """Reshape stacked layer weights [L, ...] -> [n_stages, L/S, ...] so the
    leading dimension can shard over "pp"."""

    def split(w):
        n_layers = w.shape[0]
        if n_layers % n_stages != 0:
            raise ValueError(
                f"{n_layers} layers do not split into {n_stages} stages"
            )
        return w.reshape(n_stages, n_layers // n_stages, *w.shape[1:])

    return jax.tree.map(split, layer_tree)


def pipelined_transformer(params, tokens, cfg, *, mesh: Mesh,
                          n_microbatches: int):
    """Llama forward with the decoder blocks pipelined over the mesh's "pp"
    axis (embedding and the final norm/head stay data-local — they are a
    sliver of the FLOPs). Matches `models.llama.forward` numerically.
    """
    from bee_code_interpreter_fs_tpu.models.llama import (
        _expand_gqa,
        _plain_causal_attention,
        _rms_norm,
        transformer_block,
    )

    dt = jnp.dtype(cfg.dtype)
    scale = cfg.head_dim ** -0.5
    n_stages = mesh.shape["pp"]
    batch, seq = tokens.shape
    if batch % n_microbatches != 0:
        raise ValueError(f"batch {batch} not divisible by {n_microbatches}")

    x = params["embed"].astype(dt)[tokens]  # [b, t, dim]
    micro = x.reshape(n_microbatches, batch // n_microbatches, seq, -1)

    def stage_fn(stage_layers, x):
        # shard_map delivers this stage's block with the pp dimension still
        # leading ([1, layers_per_stage, ...]) — strip it so the scan
        # iterates LAYERS. (Without this, a single-layer stage silently
        # "works" by matmul broadcasting and a multi-layer stage scans the
        # wrong axis.)
        stage_layers = jax.tree.map(lambda w: w[0], stage_layers)

        def attn_fn(q, k, v):
            return _plain_causal_attention(
                q, *_expand_gqa(k, v, cfg.n_heads), scale,
                window=cfg.sliding_window, sinks=cfg.attention_sinks,
            )

        def one(x, lp):
            return transformer_block(x, lp, cfg, attn_fn), None

        x, _ = lax.scan(one, x, stage_layers)
        return x

    stages = pipeline_stages(params["layers"], n_stages)
    stage_spec = jax.tree.map(lambda _: P("pp"), stages)
    piped = shard_map(
        partial(pipeline_apply, stage_fn, axis_name="pp"),
        mesh=mesh,
        in_specs=(stage_spec, P()),
        out_specs=P("pp"),
        check_vma=False,
    )(stages, micro)
    # out_specs exposes pp as the leading dim: [S*M, mb, t, dim]; only the
    # last stage's slab holds the processed microbatches.
    x = piped[-n_microbatches:].reshape(batch, seq, -1)
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    # Function-level import: models.llama imports parallel.* at module scope,
    # so a top-level import here would cycle through the package __init__s.
    from bee_code_interpreter_fs_tpu.models.llama import _w

    return (x @ _w(params["lm_head"], dt)).astype(jnp.float32)
