"""parallel/: mesh factorization, sharding helpers, ring attention vs the
plain-attention oracle on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P
from bee_code_interpreter_fs_tpu.parallel.mesh import shard_map

from bee_code_interpreter_fs_tpu.parallel import (
    best_mesh_shape,
    make_mesh,
    ring_attention,
    shard_pytree,
)
from bee_code_interpreter_fs_tpu.models.llama import _plain_causal_attention


def test_best_mesh_shape_factors():
    assert best_mesh_shape(8).shape == (2, 1, 1, 4)
    assert best_mesh_shape(8, tp=2, sp=2).shape == (2, 2, 1, 2)
    assert best_mesh_shape(8, tp=2, sp=2, ep=2).shape == (1, 2, 2, 2)
    assert best_mesh_shape(1).shape == (1, 1, 1, 1)
    assert best_mesh_shape(6, tp=2).shape == (3, 1, 1, 2)
    with pytest.raises(ValueError):
        best_mesh_shape(8, tp=3)
    with pytest.raises(ValueError):
        best_mesh_shape(8, tp=2, sp=2, ep=3)


def test_make_mesh_axes():
    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    assert mesh.shape == {"dp": 2, "sp": 2, "ep": 1, "tp": 2}
    assert len(mesh.devices.flatten()) == 8


def test_shard_pytree_places_shards():
    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    tree = {"a": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones((8,))}
    specs = {"a": P("dp", "tp"), "b": P(None)}
    out = shard_pytree(mesh, tree, specs)
    assert out["a"].sharding.spec == P("dp", "tp")
    np.testing.assert_allclose(out["a"], tree["a"])


def test_ring_all_reduce_matches_psum():
    """The manual ppermute ring schedule must agree with XLA's native psum
    on the 8-device mesh, including non-divisible payload sizes (padding)."""
    from bee_code_interpreter_fs_tpu.parallel.collectives import ring_all_reduce

    mesh = make_mesh(best_mesh_shape(8, tp=1, sp=8))
    for size in (8, 13, 160):  # 13: not divisible by 8 -> exercises padding
        x = jax.random.normal(jax.random.PRNGKey(size), (8, size), jnp.float32)

        def both(shard):
            return (
                ring_all_reduce(shard, "sp"),
                jax.lax.psum(shard, "sp"),
            )

        ring, psum = shard_map(
            both, mesh=mesh, in_specs=(P("sp", None),), out_specs=(P("sp", None),) * 2
        )(x)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(psum), rtol=1e-5)


def test_reduce_scatter_sum_shards():
    from bee_code_interpreter_fs_tpu.parallel.collectives import reduce_scatter_sum

    mesh = make_mesh(best_mesh_shape(8, tp=1, sp=8))
    x = jnp.ones((8, 16), jnp.float32)

    out = shard_map(
        lambda s: reduce_scatter_sum(s, "sp", scatter_axis=1),
        mesh=mesh,
        in_specs=(P("sp", None),),
        out_specs=P("sp", None),
    )(x)
    # Each of the 8 devices contributed a (1, 16) shard of ones; the sum over
    # the axis is 8 everywhere, scattered back across devices.
    np.testing.assert_allclose(np.asarray(out), np.full((8, 2), 8.0))


def test_pipeline_apply_identity_schedule():
    """The schedule itself: with stage_fn = +1 per stage, every microbatch
    must come out incremented by exactly n_stages, in order."""
    from bee_code_interpreter_fs_tpu.parallel import MeshSpec, pipeline_apply

    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))
    micro = jnp.arange(6 * 2 * 3, dtype=jnp.float32).reshape(6, 2, 3)

    out = shard_map(
        partial(
            pipeline_apply, lambda p, x: x + p, jnp.float32(1.0), axis_name="pp"
        ),
        mesh=mesh,
        in_specs=(P(),),
        out_specs=P("pp"),
        check_vma=False,
    )(micro)
    # pp is the leading out dim: [4*6, 2, 3]; the last stage's slab holds
    # the processed microbatches.
    result = out[-6:]
    np.testing.assert_allclose(np.asarray(result), np.asarray(micro) + 4.0)


def test_pipelined_transformer_matches_forward():
    """pp=4 pipelined Llama forward == plain forward (f32)."""
    from bee_code_interpreter_fs_tpu.models import (
        LlamaConfig,
        forward,
        init_params,
    )
    from bee_code_interpreter_fs_tpu.parallel import (
        MeshSpec,
        pipelined_transformer,
    )

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (4, 16), 0, cfg.vocab_size)
    expected = forward(params, tokens, cfg)

    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))
    got = jax.jit(
        lambda p, t: pipelined_transformer(p, t, cfg, mesh=mesh, n_microbatches=2)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=5e-3, atol=5e-3
    )


def test_pipelined_transformer_multiple_layers_per_stage():
    """n_layers=8 over pp=4: each stage scans TWO layers — pins the
    stage-block axis handling (a single-layer stage can pass by matmul
    broadcasting even when the scan axis is wrong)."""
    from bee_code_interpreter_fs_tpu.models import (
        LlamaConfig,
        forward,
        init_params,
    )
    from bee_code_interpreter_fs_tpu.parallel import (
        MeshSpec,
        pipelined_transformer,
    )

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(21), (4, 16), 0, cfg.vocab_size)
    expected = forward(params, tokens, cfg)

    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))
    got = jax.jit(
        lambda p, t: pipelined_transformer(p, t, cfg, mesh=mesh, n_microbatches=2)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=5e-3, atol=5e-3
    )


def test_pipelined_moe_transformer_matches_forward():
    """Composition: MoE decoder blocks staged over pp — expert weights
    reshape into stages like any stacked layer weight."""
    from bee_code_interpreter_fs_tpu.models import (
        LlamaConfig,
        forward,
        init_params,
    )
    from bee_code_interpreter_fs_tpu.parallel import (
        MeshSpec,
        pipelined_transformer,
    )

    cfg = LlamaConfig.tiny(
        dtype="float32", n_layers=4, n_experts=4, n_experts_per_token=2,
        n_heads=4, n_kv_heads=2,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(20), (4, 16), 0, cfg.vocab_size)
    expected = forward(params, tokens, cfg)

    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))
    got = jax.jit(
        lambda p, t: pipelined_transformer(p, t, cfg, mesh=mesh, n_microbatches=2)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=5e-3, atol=5e-3
    )


def test_pipelined_transformer_gradients_match():
    """The pipeline must TRAIN, not just infer: gradients through the full
    pp=4 schedule (reverse pipeline via ppermute transpose) must match
    gradients through the plain forward."""
    from bee_code_interpreter_fs_tpu.models import (
        LlamaConfig,
        forward,
        init_params,
    )
    from bee_code_interpreter_fs_tpu.parallel import (
        MeshSpec,
        pipelined_transformer,
    )

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(19), (4, 16), 0, cfg.vocab_size)
    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))

    def plain_loss(p):
        return forward(p, tokens, cfg).astype(jnp.float32).mean()

    def piped_loss(p):
        return pipelined_transformer(
            p, tokens, cfg, mesh=mesh, n_microbatches=2
        ).mean()

    g_plain = jax.grad(plain_loss)(params)
    g_piped = jax.jit(jax.grad(piped_loss))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3
        ),
        g_plain,
        g_piped,
    )


def test_ring_attention_matches_plain():
    """Exact match (fp32) against single-device causal attention."""
    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    b, t, h, d = 2, 32, 4, 8
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, h, d), jnp.float32)

    expected = _plain_causal_attention(q, k, v, d ** -0.5)

    ring = shard_map(
        partial(ring_attention, axis_name="sp"),
        mesh=mesh,
        in_specs=(P("dp", "sp", "tp", None),) * 3,
        out_specs=P("dp", "sp", "tp", None),
        check_vma=False,
    )
    got = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_sp4():
    """Different ring size (sp=4) still exact."""
    mesh = make_mesh(best_mesh_shape(8, tp=1, sp=4))
    b, t, h, d = 2, 64, 2, 4
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(s, (b, t, h, d), jnp.float32)
               for s in jax.random.split(key, 3))
    expected = _plain_causal_attention(q, k, v, d ** -0.5)
    ring = shard_map(
        partial(ring_attention, axis_name="sp"),
        mesh=mesh,
        in_specs=(P("dp", "sp", None, None),) * 3,
        out_specs=P("dp", "sp", None, None),
        check_vma=False,
    )
    got = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_plain():
    """All-to-all sequence parallelism (parallel/ulysses.py): exact match
    (fp32) against single-device causal attention, dense local path."""
    from bee_code_interpreter_fs_tpu.parallel import ulysses_attention

    mesh = make_mesh(best_mesh_shape(8, tp=2, sp=2))
    b, t, h, d = 2, 32, 4, 8
    q, k, v = (
        jax.random.normal(s, (b, t, h, d), jnp.float32)
        for s in jax.random.split(jax.random.PRNGKey(7), 3)
    )
    expected = _plain_causal_attention(q, k, v, d ** -0.5)
    got = jax.jit(
        shard_map(
            partial(ulysses_attention, axis_name="sp"),
            mesh=mesh,
            in_specs=(P("dp", "sp", "tp", None),) * 3,
            out_specs=P("dp", "sp", "tp", None),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_sp4_with_flash():
    """sp=4 with the Pallas flash kernel over the gathered sequence — the
    long-context composition Ulysses exists for."""
    from bee_code_interpreter_fs_tpu.parallel import ulysses_attention

    mesh = make_mesh(best_mesh_shape(8, tp=1, sp=4))
    b, t, h, d = 2, 64, 4, 16
    q, k, v = (
        jax.random.normal(s, (b, t, h, d), jnp.float32)
        for s in jax.random.split(jax.random.PRNGKey(8), 3)
    )
    expected = _plain_causal_attention(q, k, v, d ** -0.5)
    got = jax.jit(
        shard_map(
            partial(
                ulysses_attention, axis_name="sp", use_flash=True,
                flash_interpret=True,
            ),
            mesh=mesh,
            in_specs=(P("dp", "sp", "tp", None),) * 3,
            out_specs=P("dp", "sp", "tp", None),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_unexpanded_kv_both_paths():
    """GQA kv heads enter ulysses UNexpanded. When the local kv head count
    divides sp, the comm-saving path expands AFTER the all-to-all; when it
    doesn't, the fallback expands before. Both must match plain attention
    over the expanded heads."""
    from bee_code_interpreter_fs_tpu.models.llama import _expand_gqa
    from bee_code_interpreter_fs_tpu.parallel import ulysses_attention

    b, t, h, d = 4, 32, 4, 8  # b divides the dp=4 the 8-device mesh implies
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    for n_kv, spec_axes in ((2, (None, "sp", None, None)),  # 2 % sp(2) == 0
                            (1, (None, "sp", None, None))):  # 1 % 2 != 0
        k = jax.random.normal(kk, (b, t, n_kv, d), jnp.float32)
        v = jax.random.normal(kv_, (b, t, n_kv, d), jnp.float32)
        expected = _plain_causal_attention(q, *_expand_gqa(k, v, h), d ** -0.5)
        mesh = make_mesh(best_mesh_shape(8, tp=1, sp=2))
        got = jax.jit(
            shard_map(
                partial(ulysses_attention, axis_name="sp"),
                mesh=mesh,
                in_specs=(P("dp", "sp", None, None),) * 3,
                out_specs=P("dp", "sp", None, None),
                check_vma=False,
            )
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5, err_msg=f"n_kv={n_kv}")


def test_pipelined_transformer_respects_sliding_window():
    """pp path parity for cfg.sliding_window: the pipelined forward must
    match forward() under the same window (and so differ from full
    causal)."""
    from bee_code_interpreter_fs_tpu.models import (
        LlamaConfig,
        forward,
        init_params,
    )
    from bee_code_interpreter_fs_tpu.parallel import (
        MeshSpec,
        pipelined_transformer,
    )

    cfg = LlamaConfig.tiny(dtype="float32", n_layers=4, sliding_window=5)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(13), (4, 16), 0, cfg.vocab_size)
    expected = forward(params, tokens, cfg)
    mesh = make_mesh(MeshSpec(shape=(4,), axes=("pp",)))
    got = jax.jit(
        lambda p, t: pipelined_transformer(p, t, cfg, mesh=mesh, n_microbatches=2)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=5e-3, atol=5e-3
    )
