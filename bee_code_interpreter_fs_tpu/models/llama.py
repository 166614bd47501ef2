"""Llama-class decoder-only transformer, TPU-first.

This is the framework's flagship compute payload: BASELINE config 5 runs
Llama-class inference *through Execute*, and `__graft_entry__.py` jits this
model's forward/train step for the driver's single-chip and multi-chip
checks. Design choices are TPU-native, not a port of any torch code:

- Parameters are a flat pytree of jnp arrays; the whole model is pure
  functions — jit/grad/shard_map compose directly.
- bfloat16 activations/weights on the matmul path (MXU-native), float32 for
  RMSNorm statistics, softmax accumulation, and the final logits/loss.
- Distribution is declarative: `param_specs()` returns a PartitionSpec pytree
  (tensor parallel over the "tp" mesh axis: attention heads and MLP hidden
  sharded; XLA inserts the per-block collectives). Batch rides "dp",
  sequence rides "sp" via ring attention (parallel/ring_attention.py) wrapped
  in shard_map — exact causal attention over sequence shards.
- Layers are stacked (scan-style weight layout [n_layers, ...]) and iterated
  with `lax.scan` so compile time stays flat in depth.

No reference-code lineage: the reference (MikeDepies/bee-code-interpreter-fs)
contains no model code at all.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from bee_code_interpreter_fs_tpu.parallel.mesh import shard_map

from bee_code_interpreter_fs_tpu.parallel.ring_attention import ring_attention

NEG_INF_LOGIT = -1e30  # finite mask value for truncated-sampling logits


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Mixture-of-experts (Mixtral-class): 0 = dense MLP. With n_experts > 0
    # every layer's MLP becomes n_experts expert MLPs with top-k routing;
    # experts shard over the "ep" mesh axis (param_specs).
    n_experts: int = 0
    n_experts_per_token: int = 2
    # Single-shard attention implementation: "plain" (XLA fused dense) or
    # "flash" (the Pallas kernel, ops/flash_attention.py — O(t·d) HBM
    # instead of O(t²), the long-context choice). Ring attention (mesh with
    # sp > 1) takes precedence over either.
    attn_impl: str = "plain"
    # Rematerialize decoder blocks on the backward pass (jax.checkpoint
    # around the layer-scan body, dot-saveable policy): activation memory
    # for training drops from O(n_layers·b·t·dim) to ~one block, for one
    # extra forward's FLOPs — how long-context training fits HBM.
    remat: bool = False
    # MoE dispatch implementation: "dense" computes every expert over every
    # token (zero dynamic shapes, ep-shardable via param specs — the right
    # trade at small scale) while "capacity" routes each token to only its
    # top-k experts through a fixed per-expert capacity buffer
    # (scatter/gather, FLOPs drop ~E/(k·factor)-fold; tokens overflowing an
    # expert's buffer lose that expert's contribution, the standard
    # GShard/Switch trade). Single-shard path; meshes keep dense dispatch.
    moe_impl: str = "dense"
    moe_capacity_factor: float = 1.25
    # Sequence-parallel strategy when the mesh's "sp" axis is > 1:
    # "ring" streams K/V chunks around the ring (bandwidth-optimal,
    # parallel/ring_attention.py) while "ulysses" repartitions via two
    # all-to-alls and runs full-sequence attention on a head subset per
    # device (latency-friendly; heads are also tp-sharded, so it needs
    # (n_heads / tp) % sp == 0 — parallel/ulysses.py).
    sp_impl: str = "ring"
    # Sliding-window attention (Mistral-style): each position attends to
    # at most the last `sliding_window` keys (itself included). 0 = full
    # causal. Applies to prefill (plain and flash paths — the flash kernel
    # skips out-of-window tiles' DMAs AND FLOPs, so prefill scales
    # O(t·window)) and to the KV-cache decode path. Not composed with
    # sequence parallelism (sp > 1 raises).
    sliding_window: int = 0
    # StreamingLLM attention sinks: with a sliding window, keep the first
    # `attention_sinks` positions visible to EVERY query — the trick that
    # keeps windowed models stable far past their window. 0 = none;
    # ignored without a window.
    attention_sinks: int = 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Small config for tests / driver dry-runs (shapes divisible by an
        8-way mesh: heads % tp, batch % dp, seq % sp)."""
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
            hidden_dim=128, max_seq_len=128,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()  # defaults are the 7B shape

    @staticmethod
    def llama2_13b() -> "LlamaConfig":
        """Llama-2-13B geometry: 40L / 5120 / 13824, MHA."""
        return LlamaConfig(
            vocab_size=32000, dim=5120, n_layers=40, n_heads=40,
            n_kv_heads=40, hidden_dim=13824, max_seq_len=4096,
        )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        """Llama-3-8B geometry: GQA 32q/8kv, 128k vocab, theta 5e5."""
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336, max_seq_len=8192,
            rope_theta=500000.0,
        )

    @staticmethod
    def mixtral_8x7b() -> "LlamaConfig":
        """Mixtral-8x7B geometry: 8 experts, top-2 routing, GQA 32q/8kv."""
        return LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336, max_seq_len=32768,
            rope_theta=1000000.0, n_experts=8, n_experts_per_token=2,
        )


# ---------------------------------------------------------------- params

def init_params(key, cfg: LlamaConfig):
    """Stacked-layer parameter pytree ([n_layers, ...] leading axis)."""
    dt = jnp.dtype(cfg.dtype)
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k_emb, k_attn, k_mlp, k_out = jax.random.split(key, 4)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    L = cfg.n_layers
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 4)
    if cfg.n_experts > 0:
        E = cfg.n_experts
        mlp = {
            "router": dense(km[3], (L, cfg.dim, E), cfg.dim),
            "w_gate": dense(km[0], (L, E, cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_up": dense(km[1], (L, E, cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_down": dense(
                km[2], (L, E, cfg.hidden_dim, cfg.dim), cfg.hidden_dim
            ),
        }
    else:
        mlp = {
            "w_gate": dense(km[0], (L, cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_up": dense(km[1], (L, cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_down": dense(km[2], (L, cfg.hidden_dim, cfg.dim), cfg.hidden_dim),
        }
    return {
        "embed": dense(k_emb, (cfg.vocab_size, cfg.dim), 1.0),
        "layers": {
            "attn_norm": jnp.ones((L, cfg.dim), jnp.float32),
            "wq": dense(ka[0], (L, cfg.dim, nh * hd), cfg.dim),
            "wk": dense(ka[1], (L, cfg.dim, nkv * hd), cfg.dim),
            "wv": dense(ka[2], (L, cfg.dim, nkv * hd), cfg.dim),
            "wo": dense(ka[3], (L, nh * hd, cfg.dim), nh * hd),
            "mlp_norm": jnp.ones((L, cfg.dim), jnp.float32),
            **mlp,
        },
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": dense(k_out, (cfg.dim, cfg.vocab_size), cfg.dim),
    }


def param_specs(cfg: LlamaConfig):
    """PartitionSpec pytree mirroring init_params: tensor parallel on "tp".

    Projections shard their head/hidden dimension; wo/w_down shard the
    contracting dimension so each block needs exactly one psum (XLA inserts
    it). Embedding shards the vocab dim; norms replicate. MoE experts shard
    their expert dimension over "ep" AND their hidden dimension over "tp" —
    the weighted combine over experts becomes the per-layer ep psum.
    """
    if cfg.n_experts > 0:
        mlp = {
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        }
    else:
        mlp = {
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        }
    return {
        "embed": P("tp", None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            **mlp,
        },
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


# ---------------------------------------------------------------- forward

def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, theta, offset=0):
    """Rotary embedding over [b, t, h, d]; `offset` shifts the position
    index (incremental decoding: the single new token sits at `pos`).
    `offset` may be a scalar (whole batch at one position) or a [b] vector
    (continuous batching: every slot decodes at its own sequence length —
    models/serving.py)."""
    b, t, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    off = jnp.asarray(offset, dtype=jnp.float32)
    # [b, t] positions; a scalar offset broadcasts to identical rows.
    positions = jnp.arange(t, dtype=jnp.float32)[None, :] + jnp.atleast_1d(off)[:, None]
    angles = positions[..., None] * freqs[None, None, :]  # [b|1, t, d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(b, t, h, d)


def _route(h, lp, cfg: LlamaConfig):
    """Top-k expert routing (softmax over router logits, renormalized over
    the selected k) — the ONE routing rule both MoE dispatch
    implementations share; works over any leading dims."""
    router_logits = (h @ lp["router"].astype(h.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_i = lax.top_k(probs, cfg.n_experts_per_token)
    return top_w / top_w.sum(axis=-1, keepdims=True), top_i


def _moe_mlp(h, lp, cfg: LlamaConfig):
    """Mixtral-class top-k MoE MLP, SPMD-first dense dispatch.

    Router picks k of E experts per token (softmax over the top-k logits
    renormalized); the expert computation is written as einsums over a
    stacked [E, dim, hidden] weight tensor, so GSPMD partitions the E
    dimension across the "ep" mesh axis from the param shardings alone —
    each device runs its local experts over the full token set and the
    weighted combine over E lowers to one psum on ep per layer. Dense
    dispatch trades FLOPs (every expert sees every token, inflation E/k)
    for zero dynamic shapes and no all-to-all — the right trade below the
    scale where ragged dispatch kernels pay for themselves; swap in a
    Pallas ragged dispatch at Mixtral-8x7B scale.
    """
    top_w, top_i = _route(h, lp, cfg)  # [b, t, k]
    # Dense per-token expert weights: zero outside the top-k.
    weights = (
        jax.nn.one_hot(top_i, cfg.n_experts, dtype=jnp.float32)
        * top_w[..., None]
    ).sum(axis=-2)  # [b, t, E]
    gate = jax.nn.silu(jnp.einsum("btd,edh->bteh", h, _w(lp["w_gate"], h.dtype)))
    up = jnp.einsum("btd,edh->bteh", h, _w(lp["w_up"], h.dtype))
    y = jnp.einsum("bteh,ehd->bted", gate * up, _w(lp["w_down"], h.dtype))
    return jnp.einsum("bted,bte->btd", y, weights.astype(y.dtype))


def _moe_mlp_capacity(h, lp, cfg: LlamaConfig):
    """Capacity-based top-k MoE dispatch (GShard/Switch style), the
    FLOP-efficient alternative to `_moe_mlp`'s dense dispatch: each token
    reaches only its k routed experts through fixed [E, capacity] buffers
    — expert compute drops from E token-passes to ~factor·k — with
    linear-cost scatter/gather (no quadratic one-hot dispatch matmuls).

    capacity = ceil(factor · k · T / E) is static (shapes only). A token
    slot that overflows its expert's buffer is DROPPED for that expert
    (its routing weight contributes nothing; the residual stream still
    carries the token) — the standard trade; factor >= E/k makes drops
    impossible and the result equals dense dispatch exactly (tested).
    Single-shard implementation: mesh runs keep the ep-shardable dense
    path."""
    b, t, d = h.shape
    E, k = cfg.n_experts, cfg.n_experts_per_token
    T = b * t
    x = h.reshape(T, d)
    top_w, top_i = _route(x, lp, cfg)                       # [T, k]

    import math

    cap = max(1, math.ceil(cfg.moe_capacity_factor * k * T / E))
    flat_e = top_i.reshape(T * k)                           # expert per slot
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # [T*k, E]
    # Position of each slot within its expert's buffer: count of earlier
    # slots routed to the same expert.
    pos = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    keep = pos < cap
    # Overflowing slots scatter into a trash row past the buffers.
    slot_idx = jnp.where(keep, flat_e * cap + pos, E * cap)
    xk = jnp.repeat(x, k, axis=0)                           # [T*k, d]
    xe = jnp.zeros((E * cap + 1, d), h.dtype).at[slot_idx].add(xk)
    xe = xe[:-1].reshape(E, cap, d)

    gate = jax.nn.silu(
        jnp.einsum("ecd,edh->ech", xe, _w(lp["w_gate"], h.dtype))
    )
    up = jnp.einsum("ecd,edh->ech", xe, _w(lp["w_up"], h.dtype))
    ye = jnp.einsum("ech,ehd->ecd", gate * up, _w(lp["w_down"], h.dtype))

    yk = ye.reshape(E * cap, d)[jnp.where(keep, slot_idx, 0)]
    w_slot = (top_w.reshape(T * k) * keep).astype(yk.dtype)
    y = (yk * w_slot[:, None]).reshape(T, k, d).sum(axis=1)
    return y.reshape(b, t, d)


def _plain_causal_attention(q, k, v, scale, window: int = 0, sinks: int = 0):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    if window > 0:
        # Sliding window: drop keys older than q_pos - window + 1 — except
        # the first `sinks` keys (StreamingLLM attention sinks), which
        # every query keeps seeing.
        visible = jnp.tril(jnp.ones((t, t), bool), -window) == 0
        if sinks > 0:
            visible |= (jnp.arange(t) < sinks)[None, :]
        mask &= visible
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _expand_gqa(k, v, n_heads):
    """Repeat kv heads up to n_heads (full-sequence attention paths; the
    decode path contracts against unexpanded kv instead — no cache copy)."""
    rep = n_heads // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _w(leaf, dt):
    """Matmul-weight accessor: dense arrays pass through (cast is a no-op at
    the model dtype); int8-quantized {"q","s"} and int4-packed {"q4","s4"}
    leaves (models/quant.py) dequantize HERE, at the use site inside the
    layer scan — XLA then reads 1 (or 0.5) byte/param from HBM and fuses
    unpack/convert/scale into the matmul operand path, which is the whole
    point of weight-only quantization on a decode path that is
    weight-bandwidth-bound.

    LoRA composite leaves {"base","lora_a","lora_b"} (models/lora.py)
    resolve recursively to base + a @ b — the base may itself be a
    quantized leaf (QLoRA), and every model path (forward, fused decode,
    serving, pipeline) picks adapters up through this one accessor."""
    from bee_code_interpreter_fs_tpu.models.quant import (
        dequantize,
        dequantize4,
        is_quantized,
        is_quantized4,
    )

    from bee_code_interpreter_fs_tpu.models.lora import is_lora_leaf

    if isinstance(leaf, dict) and "lora_a_stack" in leaf:
        raise TypeError(
            "multi-adapter LoRA leaves select weights PER BATCH ROW and "
            "have no single-matrix form; they are consumed activation-side "
            "by _mm (all model matmuls route through it)"
        )
    if is_lora_leaf(leaf):
        # Correctness fallback only: materializes the full [in, out] delta.
        # Every model matmul goes through _mm below, which applies the
        # low-rank update activation-side and never builds this product.
        return _w(leaf["base"], dt) + (
            leaf["lora_a"].astype(dt) @ leaf["lora_b"].astype(dt)
        )
    if is_quantized(leaf):
        return dequantize(leaf, dt)
    if is_quantized4(leaf):
        return dequantize4(leaf, dt)
    return leaf.astype(dt)


def _mm(h, leaf, dt):
    """``h @ W`` for any weight-leaf kind. LoRA composite leaves apply
    activation-side — ``h @ base + (h @ a) @ b`` — so the update costs two
    skinny matmuls (in×r, r×out) and the dense [in, out] delta is never
    materialized; the (possibly int8/int4-quantized — QLoRA) base keeps its
    reduced HBM traffic on the weight-bandwidth-bound decode path."""
    from bee_code_interpreter_fs_tpu.models.lora import is_lora_leaf

    if isinstance(leaf, dict) and "lora_a_stack" in leaf:
        # Multi-adapter serving (lora.multi_lora_wrap): batch row i applies
        # adapter lora_ids[i] — gather the per-row [in, r]/[r, out] pair
        # and run two batched skinny matmuls. Inside the layer scan the
        # stacks are [N, in, r]/[N, r, out] and lora_ids is [b].
        ids = leaf["lora_ids"]
        # Gather BEFORE casting: convert only the b selected adapters, not
        # the whole bank.
        a_sel = leaf["lora_a_stack"][ids].astype(dt)
        b_sel = leaf["lora_b_stack"][ids].astype(dt)
        delta = jnp.einsum("btr,bro->bto",
                           jnp.einsum("btd,bdr->btr", h, a_sel), b_sel)
        return _mm(h, leaf["base"], dt) + delta
    if is_lora_leaf(leaf):
        return _mm(h, leaf["base"], dt) + (
            h @ leaf["lora_a"].astype(dt)
        ) @ leaf["lora_b"].astype(dt)
    return h @ _w(leaf, dt)


def transformer_block(x, lp, cfg: LlamaConfig, attn_fn, *, rope_offset=0):
    """One pre-norm decoder block: attention + (dense | MoE) MLP, residual
    around each. `attn_fn(q, k, v) -> attn` receives UNexpanded kv heads
    ([b, t, n_kv_heads, hd]) so callers can swap plain causal attention,
    ring attention (sp), or a KV-cached variant without duplicating the
    block arithmetic; `rope_offset` positions incremental-decode tokens."""
    b, t, _ = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dt = x.dtype
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = _mm(h, lp["wq"], dt).reshape(b, t, nh, hd)
    k = _mm(h, lp["wk"], dt).reshape(b, t, nkv, hd)
    v = _mm(h, lp["wv"], dt).reshape(b, t, nkv, hd)
    q = _rope(q, cfg.rope_theta, offset=rope_offset)
    k = _rope(k, cfg.rope_theta, offset=rope_offset)
    attn = attn_fn(q, k, v)
    x = x + _mm(attn.reshape(b, t, nh * hd), lp["wo"], dt)

    h = _rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts > 0:
        if cfg.moe_impl not in ("dense", "capacity"):
            raise ValueError(
                f"unknown moe_impl {cfg.moe_impl!r}; use 'dense' or "
                "'capacity'"
            )
        moe = _moe_mlp_capacity if cfg.moe_impl == "capacity" else _moe_mlp
        x = x + moe(h, lp, cfg)
    else:
        gate = jax.nn.silu(_mm(h, lp["w_gate"], dt))
        x = x + _mm(gate * _mm(h, lp["w_up"], dt), lp["w_down"], dt)
    return x


def forward(params, tokens, cfg: LlamaConfig, *, mesh: Mesh | None = None):
    """Token ids [b, t] -> logits [b, t, vocab] (float32).

    If `mesh` has an "sp" axis of size > 1, attention runs sequence-parallel
    with the strategy cfg.sp_impl selects — "ring" (shard_map + ppermute
    K/V streaming) or "ulysses" (two all_to_alls, full-sequence attention
    on a head subset per device); otherwise plain fused causal attention.
    XLA's GSPMD handles dp/tp either way.
    """
    dt = jnp.dtype(cfg.dtype)
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    scale = hd ** -0.5
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1
    if (mesh is not None and cfg.n_experts > 0
            and cfg.moe_impl == "capacity"):
        raise ValueError(
            "moe_impl='capacity' is the single-shard dispatch (its flat "
            "scatter defeats ep sharding); meshes use the ep-shardable "
            "dense dispatch — drop the mesh or set moe_impl='dense'"
        )
    if use_ring and cfg.sliding_window > 0:
        raise ValueError(
            "sliding_window is not composed with sequence parallelism "
            "(windowing across ring/ulysses shards is unimplemented); "
            "use a mesh without an sp axis"
        )
    if use_ring:
        # attn_impl="flash" composes with BOTH sp strategies: ring uses the
        # Pallas partial kernel per step (no per-chunk-pair score tensor);
        # ulysses runs the full flash kernel over the gathered sequence.
        if cfg.sp_impl == "ulysses":
            from bee_code_interpreter_fs_tpu.parallel.ulysses import (
                ulysses_attention,
            )

            sp_fn = ulysses_attention
        elif cfg.sp_impl == "ring":
            sp_fn = ring_attention
        else:
            raise ValueError(
                f"sp_impl must be 'ring' or 'ulysses', got {cfg.sp_impl!r}"
            )
        ring = shard_map(
            partial(
                sp_fn,
                axis_name="sp",
                scale=scale,
                use_flash=cfg.attn_impl == "flash",
                flash_interpret=jax.default_backend() != "tpu",
            ),
            mesh=mesh,
            in_specs=(P("dp", "sp", "tp", None),) * 3,
            out_specs=P("dp", "sp", "tp", None),
            check_vma=False,
        )

    x = params["embed"].astype(dt)[tokens]  # [b, t, dim]
    if use_ring:
        if cfg.sp_impl == "ulysses":
            # Ulysses takes UNexpanded kv: when the kv head count divides
            # sp it rides the all-to-alls at 1/rep the bytes and expands
            # after the repartition (parallel/ulysses.py).
            attn_fn = lambda q, k, v: ring(q, k, v)  # noqa: E731
        else:
            attn_fn = lambda q, k, v: ring(q, *_expand_gqa(k, v, nh))  # noqa: E731
    elif cfg.attn_impl == "flash":
        from bee_code_interpreter_fs_tpu.ops.flash_attention import (
            flash_attention,
        )

        # Pallas lowers via Mosaic on TPU; elsewhere (tests, CPU dev) the
        # same kernel runs interpreted.
        interpret = jax.default_backend() != "tpu"
        attn_fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, *_expand_gqa(k, v, nh), scale=scale,
            window=cfg.sliding_window, sinks=cfg.attention_sinks,
            interpret=interpret,
        )
    else:
        attn_fn = lambda q, k, v: _plain_causal_attention(  # noqa: E731
            q, *_expand_gqa(k, v, nh), scale,
            window=cfg.sliding_window, sinks=cfg.attention_sinks,
        )

    def layer(x, lp):
        return transformer_block(x, lp, cfg, attn_fn), None

    if cfg.remat:
        # Rematerialize each block on the backward pass: activation
        # residency drops from O(n_layers · b · t · dim) to one block's
        # worth (the scan carry), bought with one extra forward — the
        # standard long-context training trade on HBM-limited chips.
        # Matmul results still save (they're the expensive thing to
        # recompute); only cheap elementwise/norm work replays.
        # prevent_cse=False: safe (and documented as the right call) under
        # lax.scan, and skips optimization barriers that would block XLA
        # fusion inside every iteration.
        layer = jax.checkpoint(
            layer,
            prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    x, _ = lax.scan(layer, x, params["layers"])
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x @ _w(params["lm_head"], dt)).astype(jnp.float32)


# ---------------------------------------------------------------- decoding

def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int | None = None):
    """Stacked per-layer KV cache (unexpanded GQA heads — memory scales with
    n_kv_heads, not n_heads): {"k"|"v": [L, b, max_len, n_kv, head_dim]}."""
    max_len = max_len or cfg.max_seq_len
    shape = (
        cfg.n_layers,
        batch_size,
        max_len,
        cfg.n_kv_heads,
        cfg.head_dim,
    )
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def resolve_cache_len(needed: int, max_len: int | None, *,
                      what: str = "prompt+new") -> int:
    """The generation-cache sizing contract, in ONE place: default max_len
    to exactly what the generation needs; reject an explicit max_len that
    can't hold it (dynamic_update_slice would silently clamp writes past
    the cache's end — wrong generations with no error)."""
    max_len = max_len or needed
    if max_len < needed:
        raise ValueError(
            f"max_len={max_len} < {what}={needed}: cache too small"
        )
    return max_len


def decode_valid_mask(q_pos, max_len, cfg: LlamaConfig):
    """Which cache positions queries at positions `q_pos` [n] may attend:
    causal prefix, minus anything a sliding window retires, plus
    StreamingLLM sinks. Returns bool [n, max_len]. The ONE home of the
    window/sinks visibility formula for every cached-decode path
    (decode_chunk, decode_step via decode_chunk, the continuous-batching
    engine's per-slot step in models/serving.py)."""
    valid = jnp.arange(max_len)[None, :] <= q_pos[:, None]
    if cfg.sliding_window > 0:
        visible = (
            jnp.arange(max_len)[None, :] > q_pos[:, None] - cfg.sliding_window
        )
        if cfg.attention_sinks > 0:
            visible |= (jnp.arange(max_len) < cfg.attention_sinks)[None, :]
        valid &= visible
    return valid


def _cached_gqa_attention(q, keys, values, valid, scale):
    """Attention of `q` [b, t, nh, hd] against an UNexpanded cache
    ([b, max, nkv, hd]) via a grouped contraction — no jnp.repeat copy of
    the whole cache on the per-token hot path (the n_kv_heads memory saving
    init_cache advertises must hold at read time too)."""
    b, t, nh, hd = q.shape
    nkv = keys.shape[2]
    rep = nh // nkv
    qg = q.reshape(b, t, nkv, rep, hd)
    s = jnp.einsum("btgrd,bkgd->bgrtk", qg, keys).astype(jnp.float32) * scale
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    attn = jnp.einsum("bgrtk,bkgd->btgrd", p, values)
    return attn.reshape(b, t, nh, hd)


def decode_step(params, tokens, cache, pos, cfg: LlamaConfig):
    """One incremental decoding step.

    tokens: [b, 1] int32 — the token at position `pos` (a traced scalar, so
    one compile serves every step). Returns (logits [b, vocab] float32,
    updated cache). Attention reads the cache up to and including `pos`
    (static cache length + a position mask — no dynamic shapes under jit).
    Jit with ``donate_argnums=(2,)`` so the cache updates in place instead
    of copying [L, b, max, nkv, hd] twice per token (generate() does).
    """
    # The s=1 case of decode_chunk (the valid mask degenerates to
    # arange(max_len) <= pos) — delegated so the cache-write and
    # masked-attention plumbing exists exactly once.
    logits, cache = decode_chunk(params, tokens, cache, pos, cfg)
    return logits[:, 0], cache


def prefill(params, tokens, cache, cfg: LlamaConfig):
    """Process the whole prompt in ONE forward pass, writing every K/V
    position into the cache (one device dispatch and one cache write per
    layer — not prompt_len sequential decode steps). Returns (last-position
    logits [b, vocab] float32, updated cache)."""
    dt = jnp.dtype(cfg.dtype)
    scale = cfg.head_dim ** -0.5
    t = tokens.shape[1]
    if t > cache["k"].shape[2]:
        raise ValueError(
            f"prompt length {t} exceeds cache max_len {cache['k'].shape[2]}"
        )
    x = params["embed"].astype(dt)[tokens]

    def layer(x, inputs):
        lp, ck, cv = inputs
        cell = {}

        def attn_fn(q, k, v):
            cell["kv"] = (
                lax.dynamic_update_slice(ck, k, (0, 0, 0, 0)),
                lax.dynamic_update_slice(cv, v, (0, 0, 0, 0)),
            )
            return _plain_causal_attention(
                q, *_expand_gqa(k, v, cfg.n_heads), scale,
                window=cfg.sliding_window, sinks=cfg.attention_sinks,
            )

        x = transformer_block(x, lp, cfg, attn_fn)
        return x, cell["kv"]

    x, (new_k, new_v) = lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, t - 1] @ _w(params["lm_head"], dt)).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def decode_chunk(params, tokens, cache, pos, cfg: LlamaConfig):
    """Process `s` tokens at positions pos..pos+s-1 against the cache — the
    chunked middle ground between prefill() (pos=0, empty cache) and
    decode_step() (s=1): each chunk token attends to every cache position
    up to itself (cache prefix + the chunk's own causal prefix). Returns
    (logits [b, s, vocab] float32 for ALL s positions, updated cache).

    This is speculative decoding's verify pass (score γ draft tokens in one
    target forward) and doubles as chunked prefill for prompts longer than
    one pass should materialize.
    """
    dt = jnp.dtype(cfg.dtype)
    scale = cfg.head_dim ** -0.5
    s = tokens.shape[1]
    max_len = cache["k"].shape[2]
    # Chunk-local query i (global pos+i) sees cache positions <= pos+i
    # (and, with a sliding window, none older than pos+i-window+1).
    valid = decode_valid_mask(pos + jnp.arange(s), max_len, cfg)[None, None, None]
    x = params["embed"].astype(dt)[tokens]

    def layer(x, inputs):
        lp, ck, cv = inputs
        cell = {}

        def attn_fn(q, k, v):
            new_k = lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
            new_v = lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))
            cell["kv"] = (new_k, new_v)
            return _cached_gqa_attention(q, new_k, new_v, valid, scale)

        x = transformer_block(x, lp, cfg, attn_fn, rope_offset=pos)
        return x, cell["kv"]

    x, (new_k, new_v) = lax.scan(layer, x, (params["layers"], cache["k"], cache["v"]))
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ _w(params["lm_head"], dt)).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def _spec_setup(draft_params, target_params, prompt_tokens, cfg_draft,
                cfg_target, *, max_new_tokens, gamma, max_len, plain_decoder):
    """Shared speculative preamble: validation, cache sizing (slack: the
    last pass may overshoot max_new_tokens by up to γ), dual prefill, and
    the output buffer with the prompt written. Mirrors greedy/
    sample_generate on max_len: an explicit value that can't hold the
    generation is a caller error, never silently enlarged — a caller sizing
    sharded caches by max_len must get what it asked for."""
    if cfg_draft.vocab_size != cfg_target.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    if gamma < 1:
        raise ValueError(
            "gamma must be >= 1 (0 proposals leaves nothing to verify; "
            f"use {plain_decoder} for plain decoding)"
        )
    b, p = prompt_tokens.shape
    total = p + max_new_tokens + gamma + 1
    max_len = resolve_cache_len(total, max_len, what="prompt+new+gamma+1")
    d_cache = init_cache(cfg_draft, b, max_len)
    t_cache = init_cache(cfg_target, b, max_len)
    t_logits, t_cache = prefill(target_params, prompt_tokens, t_cache, cfg_target)
    _, d_cache = prefill(draft_params, prompt_tokens, d_cache, cfg_draft)
    buf = jnp.zeros((b, total), jnp.int32)
    buf = lax.dynamic_update_slice(buf, prompt_tokens, (0, 0))
    return b, p, total, d_cache, t_cache, t_logits, buf


@partial(
    jax.jit,
    static_argnames=("cfg_draft", "cfg_target", "max_new_tokens", "gamma", "max_len"),
)
def speculative_generate(draft_params, target_params, prompt_tokens,
                         cfg_draft: LlamaConfig, cfg_target: LlamaConfig, *,
                         max_new_tokens: int, gamma: int = 4,
                         max_len: int | None = None):
    """Greedy speculative decoding, fully jitted: a cheap DRAFT model
    proposes γ tokens autoregressively, the TARGET scores all of them in
    ONE decode_chunk forward, and the longest agreeing prefix plus the
    target's own next token are emitted — up to γ+1 tokens per target
    pass instead of 1. The output is EXACTLY greedy_generate(target): the
    draft only decides how many target tokens each pass yields, never what
    they are (greedy acceptance = token equality, so every emitted token is
    the target's argmax given its prefix).

    Batch rows advance in lockstep by the BATCH-MINIMUM acceptance (per-row
    positions would need ragged caches); rows that agreed longer simply
    re-derive the same tokens next pass — wasteful, never wrong, and the
    classic single-sequence latency case (b=1) loses nothing. Throughput
    gain ≈ (mean acceptance + 1) / (1 + γ·cost_draft/cost_target); a draft
    that rarely agrees makes this SLOWER than greedy_generate — measure
    acceptance before deploying a draft.
    """
    b, p, total, d_cache, t_cache, t_logits, buf = _spec_setup(
        draft_params, target_params, prompt_tokens, cfg_draft, cfg_target,
        max_new_tokens=max_new_tokens, gamma=gamma, max_len=max_len,
        plain_decoder="greedy_generate",
    )
    buf = buf.at[:, p].set(jnp.argmax(t_logits, axis=-1).astype(jnp.int32))
    # Invariant at the top of each pass: n_done tokens emitted; both caches
    # hold positions 0..L-1 where L = p + n_done - 1; the newest emitted
    # token sits at buf[:, L] and has not been fed to either model yet.

    def cond(state):
        _, n_done, _, _ = state
        return n_done < max_new_tokens

    def body(state):
        buf, n_done, d_cache, t_cache = state
        L = p + n_done - 1
        pending = lax.dynamic_slice(buf, (0, L), (b, 1))[:, 0]

        # Draft rollout: γ+1 steps. Step j feeds the token at position L+j;
        # steps 0..γ-1 produce the proposals d_1..d_γ, and the extra step
        # feeds d_γ so the draft cache covers position L+γ — required when
        # every proposal is accepted (next pass starts at L+γ+1).
        def droll(carry, j):
            tok, cache = carry
            logits, cache = decode_step(
                draft_params, tok[:, None], cache, L + j, cfg_draft
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, d_cache), props = lax.scan(
            droll, (pending, d_cache), jnp.arange(gamma + 1)
        )
        drafts = props[:gamma].T  # [b, γ]; d_j = drafts[:, j-1]

        # Verify: target scores [pending, d_1..d_γ] at positions L..L+γ in
        # one chunk; t_preds[:, j-1] is the target's choice for buf[L+j].
        chunk = jnp.concatenate([pending[:, None], drafts], axis=1)
        v_logits, t_cache = decode_chunk(
            target_params, chunk, t_cache, L, cfg_target
        )
        t_preds = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)  # [b, γ+1]

        # Longest agreeing prefix per row, then batch-min (lockstep).
        agree = drafts == t_preds[:, :gamma]
        row_accept = jnp.where(
            agree.all(axis=1), gamma, jnp.argmin(agree, axis=1)
        )
        accept = jnp.min(row_accept)

        # Emit t_1..t_{accept+1}. Writing the whole γ+1 prediction row is
        # safe: positions past the acceptance point are exactly the ones the
        # next pass rewrites (L' + 1 = L + accept + 2), and the final slice
        # never reaches past the last genuinely emitted token.
        buf = lax.dynamic_update_slice(buf, t_preds, (0, L + 1))
        return buf, n_done + accept + 1, d_cache, t_cache

    buf, _, _, _ = lax.while_loop(
        cond, body, (buf, jnp.int32(1), d_cache, t_cache)
    )
    return buf[:, : p + max_new_tokens]


@partial(
    jax.jit,
    static_argnames=("cfg_draft", "cfg_target", "max_new_tokens", "gamma", "max_len"),
)
def speculative_sample_generate(draft_params, target_params, prompt_tokens,
                                key, cfg_draft: LlamaConfig,
                                cfg_target: LlamaConfig, *,
                                max_new_tokens: int, gamma: int = 4,
                                temperature=1.0, max_len: int | None = None):
    """SAMPLED speculative decoding (the accept/resample algorithm of
    speculative sampling — PAPERS.md; `speculative_generate` above is its
    greedy special case). Per pass: the draft samples γ proposals
    autoregressively at `temperature`; the target scores the chunk in one
    decode_chunk forward; proposal d_j is accepted with probability
    min(1, p_j(d_j)/q_j(d_j)), the first rejection resamples from
    norm(max(0, p_j − q_j)), and a fully-accepted pass samples one extra
    token from p_{γ+1}. The emitted sequence is distributed EXACTLY as
    target-only ancestral sampling at the same temperature — the draft
    decides speed, never the distribution.

    Batch rows advance in lockstep by the BATCH-MINIMUM acceptance (same
    trade as speculative_generate): the token at the boundary position is
    per-row correct — rows that accepted further keep their accepted draft
    token, rows that rejected there get the residual resample — and every
    later position is rewritten by the next pass before it can be emitted.
    `temperature` is traced; the whole generation is ONE jitted program.
    """
    temp = jnp.maximum(temperature, 1e-6)
    b, p, total, d_cache, t_cache, t_logits, buf = _spec_setup(
        draft_params, target_params, prompt_tokens, cfg_draft, cfg_target,
        max_new_tokens=max_new_tokens, gamma=gamma, max_len=max_len,
        plain_decoder="sample_generate",
    )
    key, k0 = jax.random.split(key)
    buf = buf.at[:, p].set(
        jax.random.categorical(k0, t_logits / temp).astype(jnp.int32)
    )
    # Same invariant as speculative_generate: n_done emitted, caches cover
    # 0..L-1, newest emitted token at buf[:, L] not yet fed to either model.

    def cond(state):
        _, n_done, _, _, _ = state
        return n_done < max_new_tokens

    def body(state):
        buf, n_done, d_cache, t_cache, key = state
        key, k_draft, k_accept, k_res, k_extra = jax.random.split(key, 5)
        L = p + n_done - 1
        pending = lax.dynamic_slice(buf, (0, L), (b, 1))[:, 0]

        # Draft rollout, γ+1 steps (the extra step keeps the draft cache
        # covering L+γ for the all-accepted case), SAMPLING each proposal
        # and keeping its full logits row for the acceptance ratio.
        def droll(carry, inputs):
            j, step_key = inputs
            tok, cache = carry
            logits, cache = decode_step(
                draft_params, tok[:, None], cache, L + j, cfg_draft
            )
            nxt = jax.random.categorical(step_key, logits / temp)
            return (nxt.astype(jnp.int32), cache), (nxt.astype(jnp.int32), logits)

        (_, d_cache), (props, q_logits) = lax.scan(
            droll,
            (pending, d_cache),
            (jnp.arange(gamma + 1), jax.random.split(k_draft, gamma + 1)),
        )
        drafts = props[:gamma].T  # [b, γ]
        q_probs = jax.nn.softmax(
            q_logits[:gamma].transpose(1, 0, 2) / temp, axis=-1
        )  # [b, γ, V]

        chunk = jnp.concatenate([pending[:, None], drafts], axis=1)
        v_logits, t_cache = decode_chunk(
            target_params, chunk, t_cache, L, cfg_target
        )
        p_probs = jax.nn.softmax(v_logits / temp, axis=-1)  # [b, γ+1, V]

        # Acceptance: d_j accepted with prob min(1, p_j(d_j)/q_j(d_j)).
        p_at_draft = jnp.take_along_axis(
            p_probs[:, :gamma], drafts[..., None], axis=-1
        )[..., 0]
        q_at_draft = jnp.take_along_axis(
            q_probs, drafts[..., None], axis=-1
        )[..., 0]
        ratio = p_at_draft / jnp.maximum(q_at_draft, 1e-30)
        u = jax.random.uniform(k_accept, (b, gamma))
        # Strict <: uniform() can return exactly 0.0, and 0.0 <= 0.0 would
        # accept a token the target gives ZERO probability (visible in the
        # greedy limit, where disagreeing proposals underflow to p=0).
        accepted = u < ratio
        row_accept = jnp.where(
            accepted.all(axis=1), gamma, jnp.argmin(accepted, axis=1)
        )
        accept = jnp.min(row_accept)

        # Boundary token at position L+1+accept, per row:
        # - rows still accepting there keep their draft token;
        # - rows rejecting there resample from the residual
        #   norm(max(0, p − q)) (+eps so an exact p==q tie — a
        #   probability-zero rejection — stays finite);
        # - when every row accepted everything (accept == γ), sample the
        #   bonus token from p_{γ+1}.
        idx = jnp.minimum(accept, gamma - 1)
        p_at = lax.dynamic_index_in_dim(p_probs, accept, 1, keepdims=False)
        q_at = lax.dynamic_index_in_dim(q_probs, idx, 1, keepdims=False)
        residual = jnp.clip(p_at - q_at, 0.0, None)
        resample = jax.random.categorical(
            k_res, jnp.log(residual + 1e-30)
        ).astype(jnp.int32)
        extra = jax.random.categorical(
            k_extra, jnp.log(p_at + 1e-30)
        ).astype(jnp.int32)
        rejected_token = jnp.where(accept == gamma, extra, resample)
        draft_token = lax.dynamic_index_in_dim(
            drafts, idx, 1, keepdims=False
        )
        final = jnp.where(row_accept > accept, draft_token, rejected_token)

        # Emit d_1..d_accept then `final`; junk past the boundary is
        # rewritten by the next pass before it can be emitted (same
        # argument as speculative_generate's whole-row write).
        row = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1
        )
        row = jnp.where(
            jnp.arange(gamma + 1)[None, :] == accept, final[:, None], row
        )
        buf = lax.dynamic_update_slice(buf, row, (0, L + 1))
        return buf, n_done + accept + 1, d_cache, t_cache, key

    buf, _, _, _, _ = lax.while_loop(
        cond, body, (buf, jnp.int32(1), d_cache, t_cache, key)
    )
    return buf[:, : p + max_new_tokens]


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "max_len"))
def greedy_generate(params, prompt_tokens, cfg: LlamaConfig, *,
                    max_new_tokens: int, max_len: int | None = None,
                    eos_id=None):
    """Whole-generation greedy decode as ONE jitted program: batched prefill
    then a lax.scan over decode steps, token selection included. One device
    dispatch serves the entire generation — the per-step host round-trip
    that dominates a Python decode loop (milliseconds per token on a
    networked device) disappears. Returns [b, prompt + max_new_tokens].

    `eos_id` (None = off): a row that emits it has every LATER position
    pinned to eos_id — the fused scan's shape is static, so "stopping" is
    per-row pinning, not early exit (the saved work would be a partial
    scan's; batched serving pads to the longest row anyway). The value is
    traced: changing eos ids never recompiles.
    `generate()` below is the step-by-step reference implementation."""
    b, prompt_len = prompt_tokens.shape
    max_len = resolve_cache_len(prompt_len + max_new_tokens, max_len)
    cache = init_cache(cfg, b, max_len)
    logits, cache = prefill(params, prompt_tokens, cache, cfg)

    def body(carry, i):
        logits, cache, done = carry
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if eos_id is not None:
            token = jnp.where(done, eos_id, token)
            done = done | (token == eos_id)
        logits, cache = decode_step(
            params, token[:, None], cache, prompt_len + i, cfg
        )
        return (logits, cache, done), token

    _, new_tokens = lax.scan(
        body,
        (logits, cache, jnp.zeros((b,), bool)),
        jnp.arange(max_new_tokens),
    )
    return jnp.concatenate([prompt_tokens, new_tokens.T], axis=1)


def nucleus_mask(scaled, top_p):
    """Top-p (nucleus) truncation: keep the smallest logit-sorted prefix
    whose cumulative probability reaches top_p. A token survives when the
    mass STRICTLY BEFORE it is < top_p — this always keeps the argmax and
    includes the token that crosses the threshold. `top_p` broadcasts
    against the leading dims (a scalar, or [b] -> pass [b, 1]); 1.0 masks
    nothing bit-exactly. The ONE nucleus rule — sample_generate and both
    serving engines share it."""
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    kept = jnp.where(mass_before < top_p, sorted_desc, jnp.inf)
    cutoff = jnp.min(kept, axis=-1, keepdims=True)
    return jnp.where(scaled < cutoff, NEG_INF_LOGIT, scaled)


def sample_generate(params, prompt_tokens, key, cfg: LlamaConfig, *,
                    max_new_tokens: int, temperature=1.0, top_k: int = 0,
                    top_p=None, max_len: int | None = None, eos_id=None):
    """Stochastic generation, fully jitted like greedy_generate: temperature
    scaling plus optional top-k and/or nucleus (top-p) truncation, sampled
    with jax.random (counter-based PRNG — same key, same output, any
    device). `temperature` and the top_p VALUE are traced scalars (sweeping
    settings never recompiles); `top_k` is static (it changes shapes) and
    `top_p=None` statically omits the nucleus block. With both set, top-k
    applies first, then the nucleus is taken within the surviving set — the
    usual composition. `eos_id` pins a row's positions after its first eos
    (see greedy_generate). Returns [b, prompt + max_new_tokens]."""
    if isinstance(top_p, (int, float)) and not 0.0 < top_p <= 1.0:
        # top_p=0 would otherwise mask EVERY logit (empty nucleus) and
        # degenerate to uniform sampling over the vocab — the opposite of
        # what a caller passing 0 ("basically greedy") means. Validated
        # HERE, outside jit, where top_p is still a python number (inside
        # the jitted impl it is a tracer); a traced top_p from a caller's
        # own jit is their contract to keep in range.
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    return _sample_generate_jit(
        params, prompt_tokens, key, cfg, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p, max_len=max_len,
        eos_id=eos_id,
    )


@partial(
    jax.jit, static_argnames=("cfg", "max_new_tokens", "top_k", "max_len")
)
def _sample_generate_jit(params, prompt_tokens, key, cfg: LlamaConfig, *,
                         max_new_tokens: int, temperature, top_k: int,
                         top_p, max_len: int | None, eos_id):
    b, prompt_len = prompt_tokens.shape
    max_len = resolve_cache_len(prompt_len + max_new_tokens, max_len)
    cache = init_cache(cfg, b, max_len)
    logits, cache = prefill(params, prompt_tokens, cache, cfg)

    def pick(step_key, logits):
        scaled = logits / jnp.maximum(temperature, 1e-6)
        if top_k > 0:
            kth = lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, NEG_INF_LOGIT, scaled)
        if top_p is not None:
            scaled = nucleus_mask(scaled, top_p)
        return jax.random.categorical(step_key, scaled).astype(jnp.int32)

    def body(carry, step_key):
        logits, cache, pos, done = carry
        token = pick(step_key, logits)
        if eos_id is not None:
            token = jnp.where(done, eos_id, token)
            done = done | (token == eos_id)
        logits, cache = decode_step(params, token[:, None], cache, pos, cfg)
        return (logits, cache, pos + 1, done), token

    step_keys = jax.random.split(key, max_new_tokens)
    _, new_tokens = lax.scan(
        body,
        (logits, cache, jnp.int32(prompt_len), jnp.zeros((b,), bool)),
        step_keys,
    )
    return jnp.concatenate([prompt_tokens, new_tokens.T], axis=1)


def generate(params, prompt_tokens, cfg: LlamaConfig, *, max_new_tokens: int,
             max_len: int | None = None):
    """Greedy autoregressive generation: one batched prefill pass over the
    prompt, then jitted single-token decode steps with the cache donated
    (updated in place) and the position carried as a traced scalar — one
    compile each for prefill and decode serves any lengths.
    Returns [b, prompt + max_new_tokens] int32.
    """
    b, prompt_len = prompt_tokens.shape
    max_len = resolve_cache_len(prompt_len + max_new_tokens, max_len)
    cache = init_cache(cfg, b, max_len)
    step = jax.jit(partial(decode_step, cfg=cfg), donate_argnums=(2,))

    logits, cache = jax.jit(partial(prefill, cfg=cfg), donate_argnums=(2,))(
        params, prompt_tokens, cache
    )
    tokens = prompt_tokens
    for i in range(max_new_tokens):
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        tokens = jnp.concatenate([tokens, next_token], axis=1)
        if i + 1 < max_new_tokens:
            logits, cache = step(
                params, next_token, cache, jnp.int32(prompt_len + i)
            )
    return tokens


# ---------------------------------------------------------------- training

def loss_fn(params, batch, cfg: LlamaConfig, *, mesh: Mesh | None = None):
    """Next-token cross-entropy. batch = {"tokens": [b, t+1] int32}."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, mesh=mesh)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_train_step(cfg: LlamaConfig, optimizer, *, mesh: Mesh | None = None,
                    accum_steps: int = 1):
    """Returns `train_step(params, opt_state, batch) -> (params, opt_state,
    loss)` — pure, jittable; shard via jit's in_shardings or device_put on
    the arguments (GSPMD propagates; grads of tp-sharded params come out
    tp-sharded, dp reduction is the implicit psum from the mean loss).

    `accum_steps > 1` splits the batch's leading dim into that many
    microbatches, accumulates gradients in float32 over a lax.scan, and
    applies ONE optimizer update — the effective-batch lever when
    activations for the full batch don't fit HBM (composes with
    cfg.remat, which shrinks depth-wise residency the same way this
    shrinks batch-wise)."""

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, cfg, mesh=mesh
            )
        else:
            b = batch["tokens"].shape[0]
            if b % accum_steps:
                raise ValueError(
                    f"batch size {b} not divisible by accum_steps={accum_steps}"
                )
            # Microbatch the WHOLE batch tree, not just "tokens": any field
            # loss_fn grows later (a loss mask, say) must split identically
            # or the accum path would silently train on different data.
            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, b // accum_steps, *x.shape[1:]),
                batch,
            )

            def accumulate(carry, micro_batch):
                loss_sum, grad_sum = carry
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, micro_batch, cfg, mesh=mesh
                )
                grad_sum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grad_sum, grads
                )
                return (loss_sum + loss, grad_sum), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss_sum, grad_sum), _ = lax.scan(
                accumulate, (jnp.float32(0), zeros), micro
            )
            loss = loss_sum / accum_steps
            grads = jax.tree.map(
                lambda g, p: (g / accum_steps).astype(p.dtype), grad_sum, params
            )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step
