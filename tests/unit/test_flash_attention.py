"""Pallas flash-attention kernel vs the dense oracle (interpret mode on the
CPU test platform; the identical kernel lowers via Mosaic on TPU, where it
was measured faster than XLA's fused dense attention at t=2048 bf16 and,
unlike it, never materializes the [t, t] score matrix)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee_code_interpreter_fs_tpu.models.llama import (
    LlamaConfig,
    _expand_gqa,
    _plain_causal_attention,
    forward,
    init_params,
)
from bee_code_interpreter_fs_tpu.ops.flash_attention import flash_attention


@pytest.mark.parametrize(
    "b,t,h,d,bq,bk",
    [
        (2, 64, 4, 16, 16, 16),
        (1, 100, 2, 32, 32, 16),  # t not divisible by blocks: padding path
        (1, 16, 1, 8, 64, 64),  # blocks larger than the sequence
        # Unequal defaults with t between them and not a tile multiple: the
        # clamped block must round back to a power of two dividing the
        # shared padded length (regression: block_k clamped to 900 over an
        # array padded to 1024 for block_q=512 satisfied neither of
        # Mosaic's rules).
        (1, 900, 1, 16, 512, 1024),
    ],
)
def test_matches_dense_oracle(b, t, h, d, bq, bk):
    key = jax.random.PRNGKey(t)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    got = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    want = _plain_causal_attention(q, k, v, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gqa_via_expand():
    b, t, nh, nkv, d = 1, 32, 4, 2, 16
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, nh, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, nkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, nkv, d), jnp.float32)
    ke, ve = _expand_gqa(k, v, nh)
    got = flash_attention(q, ke, ve, block_q=16, block_k=16, interpret=True)
    want = _plain_causal_attention(q, ke, ve, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_with_flash_impl_matches_plain():
    cfg_plain = LlamaConfig.tiny(dtype="float32")
    cfg_flash = LlamaConfig.tiny(dtype="float32", attn_impl="flash")
    params = init_params(jax.random.PRNGKey(0), cfg_plain)
    tokens = jax.random.randint(
        jax.random.PRNGKey(14), (2, 24), 0, cfg_plain.vocab_size
    )
    want = forward(params, tokens, cfg_plain)
    got = forward(params, tokens, cfg_flash)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_partial_kernel_single_chunk_equals_full():
    """Folding one chunk from a zero carry must equal full flash/dense
    attention (the ring step's base case)."""
    from bee_code_interpreter_fs_tpu.ops.flash_attention import (
        flash_attention_partial,
    )

    b, t, h, d = 1, 64, 2, 16
    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    acc = jnp.zeros((b, h, t, d), jnp.float32)
    m = jnp.full((b, h, t), -1e30, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    acc, m, l = flash_attention_partial(
        q, k, v, acc, m, l, q_offset=0, k_offset=0, block_q=16, block_k=16,
        interpret=True,
    )
    got = (acc / l[..., None]).transpose(0, 2, 1, 3)
    want = _plain_causal_attention(q, k, v, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_with_flash_kernel():
    """ring_attention(use_flash=True) on the sp mesh — the Pallas kernel
    inside the ring schedule — must match plain causal attention, including
    the fully-masked future chunks the ring streams past each device."""
    from functools import partial as fpartial

    from bee_code_interpreter_fs_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    from bee_code_interpreter_fs_tpu.parallel import (
        best_mesh_shape,
        make_mesh,
        ring_attention,
    )

    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    b, t, h, d = 2, 64, 4, 16
    key = jax.random.PRNGKey(4)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    want = _plain_causal_attention(q, k, v, d ** -0.5)
    got = shard_map(
        fpartial(
            ring_attention, axis_name="sp", use_flash=True,
            flash_interpret=True, flash_block=16,
        ),
        mesh=mesh,
        in_specs=(P("dp", "sp", "tp", None),) * 3,
        out_specs=P("dp", "sp", "tp", None),
        check_vma=False,
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_non_divisible_chunks():
    """Per-device chunks that don't divide the kernel blocks must pad
    internally (a config the einsum ring path always handled)."""
    from functools import partial as fpartial

    from bee_code_interpreter_fs_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    from bee_code_interpreter_fs_tpu.parallel import (
        best_mesh_shape,
        make_mesh,
        ring_attention,
    )

    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    b, t, h, d = 2, 48, 4, 16  # per-device chunk 24, blocks 16 -> padding
    key = jax.random.PRNGKey(5)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    want = _plain_causal_attention(q, k, v, d ** -0.5)
    got = shard_map(
        fpartial(
            ring_attention, axis_name="sp", use_flash=True,
            flash_interpret=True, flash_block=16,
        ),
        mesh=mesh,
        in_specs=(P("dp", "sp", "tp", None),) * 3,
        out_specs=P("dp", "sp", "tp", None),
        check_vma=False,
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_ring_flash_composition():
    """Full model: sp mesh + attn_impl='flash' routes attention through the
    ring schedule with the Pallas partial kernel inside."""
    from bee_code_interpreter_fs_tpu.parallel import (
        best_mesh_shape,
        make_mesh,
        shard_pytree,
    )
    from bee_code_interpreter_fs_tpu.models import param_specs

    cfg = LlamaConfig.tiny(dtype="float32", attn_impl="flash")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(18), (2, 32), 0, cfg.vocab_size)
    want = forward(params, tokens, LlamaConfig.tiny(dtype="float32"))

    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    sharded = shard_pytree(mesh, params, param_specs(cfg))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


def test_shape_mismatch_rejected():
    q = jnp.zeros((1, 8, 2, 4))
    k = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k, k)


def test_sliding_window_matches_plain():
    """flash_attention(window=w) == the masked-dense formulation with the
    same window, including non-divisible lengths (padding) and a window
    that doesn't align with tile boundaries."""
    from bee_code_interpreter_fs_tpu.models.llama import _plain_causal_attention
    from bee_code_interpreter_fs_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 2, 100, 2, 16
    q, k, v = (
        jax.random.normal(s, (b, t, h, d), jnp.float32)
        for s in jax.random.split(jax.random.PRNGKey(11), 3)
    )
    for w in (1, 7, 33, 100, 0):
        want = _plain_causal_attention(q, k, v, d ** -0.5, window=w)
        got = flash_attention(
            q, k, v, block_q=16, block_k=32, window=w, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"window={w}",
        )


def test_attention_sinks_match_plain():
    """window + sinks in the kernel == the masked-dense formulation,
    including sink counts that don't align with tile boundaries and sinks
    inside/outside the window's reach."""
    from bee_code_interpreter_fs_tpu.models.llama import _plain_causal_attention
    from bee_code_interpreter_fs_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 2, 100, 2, 16
    q, k, v = (
        jax.random.normal(s, (b, t, h, d), jnp.float32)
        for s in jax.random.split(jax.random.PRNGKey(12), 3)
    )
    for w, sinks in ((7, 4), (7, 33), (33, 1), (100, 4)):
        want = _plain_causal_attention(q, k, v, d ** -0.5, window=w, sinks=sinks)
        got = flash_attention(
            q, k, v, block_q=16, block_k=32, window=w, sinks=sinks,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"window={w} sinks={sinks}",
        )
