"""Continuous-batching inference engine — slot-scheduled serving on TPU.

The reference project serves model workloads one Execute call at a time
(`/root/reference/src/code_interpreter/services/code_executor.py` runs each
request in its own sandbox); concurrent inference is purely
process-per-request. This module adds the TPU-native alternative for the
config-5 concurrency story (BASELINE.json): ONE resident model instance that
serves many requests by iteration-level (continuous) batching, the way
production LLM servers schedule — requests join and leave the running batch
at token boundaries instead of waiting for a full-batch generation to
drain.

TPU-first design constraints drive the shape of everything here:

- **Static shapes only.** The decode batch is a fixed bank of `n_slots`
  cache slots; "joining the batch" means writing a prompt's K/V into a free
  slot, not growing a dimension. Finished slots keep computing (masked)
  until the next sync — XLA never sees a dynamic batch.
- **Bucketed prefill.** Admission pads the prompt to a small set of bucket
  lengths, so prompt ingestion compiles once per bucket (not once per
  prompt length). Padded positions write garbage K/V beyond the prompt's
  true length — provably never attended, because a decode step at position
  p first overwrites slot p and only ever reads positions <= p.
- **Fused decode bursts.** Between scheduler syncs the engine runs
  `steps_per_sync` decode steps as one `lax.scan` program (one device
  dispatch), amortizing the host<->device round trip that dominates
  per-token dispatch. Per-slot sequence lengths
  ride through the whole model as a [n_slots] position vector (per-slot
  RoPE offsets + per-slot causal masks), and cache writes are per-slot
  scatters at each slot's own frontier.

Scheduling (admission, retirement, queueing) is host-side Python between
bursts; everything inside a burst is compiled. EOS and per-request token
budgets deactivate slots in-device so a burst never generates past a
request's end; deactivated slots are retired and refilled at the next sync.
"""

from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bee_code_interpreter_fs_tpu.models.quant import (
    dequantize_kv,
    quantize_kv,
)
from bee_code_interpreter_fs_tpu.models.llama import (
    LlamaConfig,
    _cached_gqa_attention,
    _rms_norm,
    _w,
    decode_chunk,
    decode_valid_mask,
    init_cache,
    nucleus_mask,
    prefill,
    transformer_block,
)

__all__ = ["ServingEngine", "Request"]


@dataclass
class Request:
    """One queued generation request (host-side bookkeeping)."""

    rid: int
    prompt: np.ndarray  # [prompt_len] int32 (the suffix when prefix_id set)
    max_new_tokens: int
    prefix_id: int | None = None
    temperature: float = 0.0  # 0 = greedy
    seed: int | None = None
    adapter: str | None = None  # multi-LoRA adapter name (None = base)
    on_token: object = None  # callable(list[int]) | None — streaming sink
    want_logprobs: bool = False
    top_p: float = 1.0  # nucleus truncation (1.0 = off)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    generated: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)


def _perslot_decode_step(params, tokens, cache, pos, cfg: LlamaConfig):
    """One decode step where every slot sits at its OWN position.

    tokens: [b, 1] int32; pos: [b] int32 — slot i's token is at global
    position pos[i]. The per-slot generalization of
    ``llama.decode_step`` (scalar pos): the causal mask, RoPE offset, and
    cache write are all vectors over the batch. Returns
    (logits [b, vocab] f32, updated cache).
    """
    dt = jnp.dtype(cfg.dtype)
    scale = cfg.head_dim ** -0.5
    quant = "kq" in cache  # int8 KV cache (engine kv_quant=True)
    max_len = (cache["kq"] if quant else cache["k"]).shape[2]
    # Slot i sees cache positions <= pos[i] (its own prefix + itself);
    # broadcast the [b, max] mask over [b, g, r, t, k].
    valid = decode_valid_mask(pos, max_len, cfg)[:, None, None, None, :]
    x = params["embed"].astype(dt)[tokens]
    bidx = jnp.arange(tokens.shape[0])

    # One layer body for both cache formats: only the row write and the
    # K/V handed to attention differ — the shared strategy factory keeps
    # the int8 recipe in ONE place for the dense and paged engines alike.
    # Per-slot scatter at each slot's own frontier (the [b] pos vector
    # rules out one dynamic_update_slice for the batch).
    cache_keys, write_read = _kv_write_read(
        quant, lambda c, x: c.at[bidx, pos].set(x), lambda c: c, dt
    )

    def layer(x, inputs):
        lp = inputs[0]
        cs = inputs[1:]
        cell = {}

        def attn_fn(q, k, v):
            new, keys, vals = write_read(cs, k[:, 0], v[:, 0])
            cell["kv"] = new
            return _cached_gqa_attention(q, keys, vals, valid, scale)

        x = transformer_block(x, lp, cfg, attn_fn, rope_offset=pos)
        return x, cell["kv"]

    x, new_leaves = lax.scan(
        layer, x, (params["layers"],) + tuple(cache[k] for k in cache_keys)
    )
    new_cache = dict(zip(cache_keys, new_leaves))
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ _w(params["lm_head"], dt)).astype(jnp.float32)
    return logits, new_cache


def _kv_write_read(quant: bool, write_at, read_tf, dt):
    """Build the per-layer KV (cache_keys, write_read) strategy shared by
    the dense and paged decode steps: `write_at(cache_leaf, value)` places
    the new token's K/V (row scatter vs block scatter) and `read_tf`
    produces the attention-readable view (identity vs block-table gather).
    With `quant`, values quantize at the write and dequantize AT THE READ —
    HBM streams int8 + scales and the multiply fuses into the attention
    contraction; the recipe exists exactly once for both engines."""
    if quant:
        keys = ("kq", "ks", "vq", "vs")

        def write_read(cs, k, v):
            ckq, cks, cvq, cvs = cs
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            new = (write_at(ckq, kq), write_at(cks, ks),
                   write_at(cvq, vq), write_at(cvs, vs))
            return new, dequantize_kv(
                read_tf(new[0]), read_tf(new[1]), dt
            ), dequantize_kv(read_tf(new[2]), read_tf(new[3]), dt)
    else:
        keys = ("k", "v")

        def write_read(cs, k, v):
            new = (write_at(cs[0], k), write_at(cs[1], v))
            return new, read_tf(new[0]), read_tf(new[1])

    return keys, write_read


def _sample_next(logits, temp, keys, pos, top_p=None):
    """Next token per slot: greedy where temp == 0, else a categorical draw
    whose key is fold_in(slot key, the sampled token's position) — the ONE
    definition of the engine's sampling stream (the paged engine's burst
    uses it too, so both engines are stream-identical). `top_p` ([b] or
    None — a STATIC distinction, compiled separately) truncates to the
    nucleus before drawing."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    subkeys = jax.vmap(jax.random.fold_in)(keys, pos + 1)
    scaled = logits / jnp.where(temp > 0, temp, 1.0)[:, None]
    if top_p is not None:
        scaled = nucleus_mask(scaled, top_p[:, None])
    sampled = jax.vmap(jax.random.categorical)(subkeys, scaled)
    return jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)


def _burst_scan(step_fn, store, pos, last_tok, remaining, active, temp,
                keys, steps: int, eos_id, with_logprobs: bool,
                top_p=None, penalties=None):
    """The ONE burst loop body both engines run: step_fn produces logits and
    the updated KV store; everything else — the sampling stream, emit
    bookkeeping, budget/EOS masking — lives here so the dense and paged
    engines cannot drift.

    `penalties` (static None = off): (presence [b], frequency [b],
    counts [b, vocab] int32) — OpenAI-style repetition control. Penalties
    shape token CHOICE (greedy argmax included); reported logprobs stay
    raw-model, like temperature."""

    def one(carry, _):
        if penalties is None:
            store, pos, tok, remaining, active = carry
        else:
            store, pos, tok, remaining, active, counts = carry
        logits, store = step_fn(store, tok[:, None], pos, active)
        if penalties is None:
            choice_logits = logits
        else:
            presence, frequency = penalties
            choice_logits = (
                logits
                - presence[:, None] * (counts > 0)
                - frequency[:, None] * counts
            )
        nxt = _sample_next(choice_logits, temp, keys, pos, top_p)
        if with_logprobs:
            # Chosen-token log-prob under the RAW model distribution (the
            # OpenAI-style convention: temperature shapes sampling, not
            # the reported likelihoods).
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=1
            )[:, 0]
        else:
            # Static no-logprob variant: no vocab-wide softmax in the hot
            # loop; the lane stays shape-stable as zeros.
            lp = jnp.zeros((logits.shape[0],), jnp.float32)
        tok = jnp.where(active, nxt, tok)
        emitted = active
        pos = pos + active.astype(jnp.int32)
        remaining = remaining - active.astype(jnp.int32)
        active = active & (remaining > 0)
        if eos_id is not None:
            active = active & (tok != eos_id)
        if penalties is None:
            return (store, pos, tok, remaining, active), (tok, emitted, lp)
        counts = counts.at[jnp.arange(tok.shape[0]), tok].add(
            emitted.astype(jnp.int32)
        )
        return (store, pos, tok, remaining, active, counts), (
            tok, emitted, lp
        )

    if penalties is None:
        carry0 = (store, pos, last_tok, remaining, active)
    else:
        presence, frequency, counts0 = penalties
        penalties = (presence, frequency)
        carry0 = (store, pos, last_tok, remaining, active, counts0)
    carry, (toks, emitted, lps) = lax.scan(one, carry0, None, length=steps)
    store, pos, tok, remaining, active = carry[:5]
    counts = carry[5] if len(carry) > 5 else None
    return store, pos, tok, remaining, active, toks, emitted, lps, counts


@partial(jax.jit,
         static_argnames=("cfg", "steps", "eos_id", "with_logprobs",
                          "with_top_p", "with_penalties"),
         donate_argnames=("cache",))
def _decode_burst(params, cache, pos, last_tok, remaining, active,
                  temp, keys, top_p, presence, frequency, counts,
                  cfg: LlamaConfig, steps: int, eos_id,
                  with_logprobs: bool = False, with_top_p: bool = False,
                  with_penalties: bool = False):
    """`steps` continuous-batching decode steps as ONE compiled program.

    Carry per slot: position, last emitted token, remaining token budget,
    active flag. Inactive slots still flow through the (static-shape)
    computation but are fully masked: their position doesn't advance, their
    token doesn't change, and their cache row only rewrites its own frontier
    with values nothing ever attends to.

    Per-slot sampling: `temp` [b] f32 (0 = greedy) and `keys` [b, 2]
    uint32 per-request PRNG keys. Each sampled token's randomness is
    `fold_in(key, position)` — the key never advances, so a request's
    stream depends only on its seed and token positions, not on scheduling
    (the same request replays identically whatever traffic shares the
    batch).

    Returns (cache, pos, last_tok, remaining, active, toks [steps, b],
    emitted [steps, b], lps [steps, b]) — toks[s, i] is a real generated
    token for slot i iff emitted[s, i]; lps[s, i] its model log-prob.
    """

    def step_fn(cache, tokens, pos, active):
        del active  # a dense slot's idle frontier rewrite is harmless
        return _perslot_decode_step(params, tokens, cache, pos, cfg)

    return _burst_scan(step_fn, cache, pos, last_tok, remaining, active,
                       temp, keys, steps, eos_id, with_logprobs,
                       top_p if with_top_p else None,
                       (presence, frequency, counts) if with_penalties
                       else None)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _admit(params, cache, tokens, slot, true_len, cfg: LlamaConfig):
    """Prefill one bucketed prompt and install it into cache slot `slot`.

    tokens: [1, bucket_len] (prompt right-padded to the bucket); `slot` and
    `true_len` are traced scalars, so one compile serves every admission at
    this bucket length. Returns (cache, last_logits) — the prompt's
    last-position logits, from which the host picks the first generated
    token (greedy or sampled per the request). K/V written for padded
    positions (>= true_len) are garbage by construction and provably never
    attended (see module doc).

    The scratch cache is BUCKET-sized, not max_len-sized, so prefill
    attention costs O(bucket²) rather than O(bucket·max_len); the slot
    row's tail beyond the bucket keeps its previous occupant's stale K/V,
    which is safe by the same overwrite-before-read invariant (a stale
    position j only becomes visible once pos >= j, and the decode step at
    pos == j rewrites it first).
    """
    bucket = tokens.shape[1]
    slot_cache = init_cache(cfg, 1, bucket)
    logits_all, slot_cache = decode_chunk(params, tokens, slot_cache, 0, cfg)
    last_logits = logits_all[0, true_len - 1]
    new_k = lax.dynamic_update_slice(
        cache["k"], slot_cache["k"], (0, slot, 0, 0, 0)
    )
    new_v = lax.dynamic_update_slice(
        cache["v"], slot_cache["v"], (0, slot, 0, 0, 0)
    )
    return {"k": new_k, "v": new_v}, last_logits


@partial(jax.jit, static_argnames=("cfg", "pad_to"))
def _prefill_scratch(params, tokens, true_len, cfg: LlamaConfig, pad_to: int):
    """Prefill a bucketed prompt into a BLOCK-ALIGNED contiguous scratch
    ([L, 1, pad_to, ...]); returns (last_logits, scratch kv)."""
    scratch = init_cache(cfg, 1, pad_to)
    logits_all, scratch = decode_chunk(params, tokens, scratch, 0, cfg)
    return logits_all[0, true_len - 1], scratch


@partial(jax.jit, static_argnames=("cfg", "pad_to"))
def _prefill_scratch_prefixed(params, pk, pv, tokens, true_len,
                              cfg: LlamaConfig, pad_to: int):
    """Prefix-cached variant: install the prefix K/V then chunk-prefill the
    suffix at rope offset plen, all in one block-aligned scratch."""
    plen = pk.shape[2]
    scratch = init_cache(cfg, 1, pad_to)
    scratch = {
        "k": lax.dynamic_update_slice(scratch["k"], pk, (0, 0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(scratch["v"], pv, (0, 0, 0, 0, 0)),
    }
    logits_all, scratch = decode_chunk(params, tokens, scratch, plen, cfg)
    return logits_all[0, true_len - 1], scratch


@partial(jax.jit, static_argnames=("cfg", "chunk"))
def _chunked_scratch_prefill(params, tokens, true_len, cfg: LlamaConfig,
                             chunk: int):
    """Prefill a (bucketed, chunk-aligned) prompt in fixed-size chunks: a
    lax.scan feeds `chunk` tokens at a time against the growing scratch
    cache, so attention's score tensor peaks at O(chunk x bucket) instead
    of O(bucket^2) — the long-prompt admission path. Returns (last_logits
    [vocab] at true_len-1, scratch kv [L, 1, bucket, ...])."""
    bucket = tokens.shape[1]
    if bucket % chunk:
        raise ValueError(
            f"bucket {bucket} is not a multiple of prefill chunk {chunk} — "
            "the tail would silently never prefill"
        )
    n_chunks = bucket // chunk
    scratch = init_cache(cfg, 1, bucket)
    vocab = cfg.vocab_size

    def body(carry, i):
        scratch, out = carry
        chunk_toks = lax.dynamic_slice(tokens, (0, i * chunk), (1, chunk))
        logits, scratch = decode_chunk(params, chunk_toks, scratch,
                                       i * chunk, cfg)
        # The prompt's last real position lives in exactly one chunk.
        sel = (true_len - 1) // chunk == i
        out = jnp.where(sel, logits[0, (true_len - 1) % chunk], out)
        return (scratch, out), None

    (scratch, last_logits), _ = lax.scan(
        body, (scratch, jnp.zeros((vocab,), jnp.float32)),
        jnp.arange(n_chunks),
    )
    return last_logits, scratch


@partial(jax.jit, donate_argnames=("cache",))
def _install_row_quant(cache, scratch, slot):
    """Quantize a DENSE prefill scratch and install it into an int8 KV
    cache row: prompts prefill at full precision (exact logits for the
    first token), and only the stored cache pays the quantization."""
    kq, ks = quantize_kv(scratch["k"])
    vq, vs = quantize_kv(scratch["v"])
    at = (0, slot, 0, 0, 0)
    return {
        "kq": lax.dynamic_update_slice(cache["kq"], kq, at),
        "ks": lax.dynamic_update_slice(cache["ks"], ks, at),
        "vq": lax.dynamic_update_slice(cache["vq"], vq, at),
        "vs": lax.dynamic_update_slice(cache["vs"], vs, at),
    }


@partial(jax.jit, donate_argnames=("cache",))
def _install_row(cache, scratch, slot):
    """Install a contiguous scratch ([L, 1, T <= max_len, ...]) into dense
    cache row `slot` (the chunked-admission counterpart of _admit's
    in-jit install)."""
    return {
        "k": lax.dynamic_update_slice(
            cache["k"], scratch["k"], (0, slot, 0, 0, 0)
        ),
        "v": lax.dynamic_update_slice(
            cache["v"], scratch["v"], (0, slot, 0, 0, 0)
        ),
    }


# One compile per distinct prefix length, paid at registration time.
# prefill (not decode_chunk): it projects logits only at the LAST position,
# so registering a long system prompt never materializes a [plen, vocab]
# logits buffer it would immediately discard.
_prefix_prefill = jax.jit(prefill, static_argnames=("cfg",))


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _admit_prefixed(params, cache, pk, pv, tokens, slot, true_len,
                    cfg: LlamaConfig):
    """Admission with a cached prefix: install the prefix's precomputed K/V
    (positions 0..plen-1) and chunk-prefill only the SUFFIX at
    rope_offset=plen. One compile per (prefix length, suffix bucket) pair;
    the prefix forward itself was paid ONCE at register_prefix time no
    matter how many requests share it."""
    plen = pk.shape[2]
    bucket = tokens.shape[1]
    scratch = init_cache(cfg, 1, plen + bucket)
    scratch = {
        "k": lax.dynamic_update_slice(scratch["k"], pk, (0, 0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(scratch["v"], pv, (0, 0, 0, 0, 0)),
    }
    logits_all, scratch = decode_chunk(params, tokens, scratch, plen, cfg)
    last_logits = logits_all[0, true_len - 1]
    new_k = lax.dynamic_update_slice(
        cache["k"], scratch["k"], (0, slot, 0, 0, 0)
    )
    new_v = lax.dynamic_update_slice(
        cache["v"], scratch["v"], (0, slot, 0, 0, 0)
    )
    return {"k": new_k, "v": new_v}, last_logits


@partial(jax.jit, donate_argnames=("cache",))
def _admit_prefix_only(cache, pk, pv, slot):
    """Admission of a request whose whole prompt IS a cached prefix: pure
    K/V installation — zero model FLOPs on the admission path."""
    new_k = lax.dynamic_update_slice(cache["k"], pk, (0, slot, 0, 0, 0))
    new_v = lax.dynamic_update_slice(cache["v"], pv, (0, slot, 0, 0, 0))
    return {"k": new_k, "v": new_v}


class ServingEngine:
    """Continuous-batching greedy serving over a fixed slot bank.

    >>> eng = ServingEngine(params, cfg, n_slots=4, max_len=512)
    >>> rid = eng.submit([1, 5, 9], max_new_tokens=32)
    >>> results = eng.run()          # {rid: np.ndarray of generated tokens}

    Tokens returned are the GENERATED continuation only (the prompt is the
    caller's). With `eos_id` set, generation stops at (and includes) the
    first eos token — matching `greedy_generate`'s pinning semantics
    truncated at the first eos.
    """

    def __init__(self, params, cfg: LlamaConfig, *, n_slots: int = 4,
                 max_len: int | None = None, steps_per_sync: int = 8,
                 prefill_buckets: tuple = (), eos_id: int | None = None,
                 seed: int = 0, adapters: dict | None = None,
                 lora_alpha: float = 16.0, prefill_chunk: int | None = None,
                 kv_quant: bool = False):
        """`adapters`: {name: lora tree (models/lora.init_lora shape)} —
        multi-tenant adapter serving. Every request picks one by name (or
        None for the bare base model); one resident base plus one stacked
        adapter bank serve them all in the same bursts, with index 0 the
        zero adapter so un-adapted rows compute the exact base model."""
        self.params = params
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len or cfg.max_seq_len)
        self.steps_per_sync = int(steps_per_sync)
        self.eos_id = eos_id
        self.kv_quant = bool(kv_quant)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and not (
            1 <= self.prefill_chunk < self.max_len
        ):
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be in "
                f"[1, max_len={self.max_len}) — a chunk that can never "
                "fire is a misconfiguration"
            )
        if prefill_buckets:
            self.buckets = tuple(sorted(int(b) for b in prefill_buckets))
            if self.buckets[0] < 1 or self.buckets[-1] > self.max_len:
                raise ValueError(
                    f"prefill_buckets must lie in [1, max_len={self.max_len}]"
                    f", got {self.buckets}"
                )
        else:
            # Powers of two, topped by the largest admissible prompt length
            # (max_len - 1: at least one generated token must fit).
            pows = [b for b in (2 ** i for i in range(4, 32))
                    if b < self.max_len - 1]
            self.buckets = tuple(pows + [self.max_len - 1])
        if self.prefill_chunk is not None:
            # Chunked admission scans fixed-size chunks, so add chunk-
            # aligned bucket variants — but KEEP the original top bucket:
            # capacity never shrinks (an unaligned bucket simply routes
            # through the single-pass path).
            c = self.prefill_chunk
            aligned = {
                min(-(-b // c) * c, (self.max_len // c) * c)
                for b in self.buckets
            }
            aligned = {b for b in aligned if b > 0}
            self.buckets = tuple(sorted(aligned | {max(self.buckets)}))
        self._init_device_state()
        self.pos = jnp.zeros((self.n_slots,), jnp.int32)
        self.last_tok = jnp.zeros((self.n_slots,), jnp.int32)
        self.remaining = jnp.zeros((self.n_slots,), jnp.int32)
        self.active = jnp.zeros((self.n_slots,), bool)
        self._slot_req: list[Request | None] = [None] * self.n_slots
        self._queue: deque[Request] = deque()
        self._results: dict[int, np.ndarray] = {}
        self._logprob_results: dict[int, np.ndarray] = {}
        self._rid = itertools.count()
        self._prefixes: dict[int, dict] = {}
        self._prefix_id = itertools.count()
        self.temp = jnp.zeros((self.n_slots,), jnp.float32)
        self.top_p = jnp.ones((self.n_slots,), jnp.float32)
        self.presence = jnp.zeros((self.n_slots,), jnp.float32)
        self.frequency = jnp.zeros((self.n_slots,), jnp.float32)
        # [n_slots, vocab] i32, allocated lazily at the first penalized
        # admission — a no-penalty deployment never pays the residency.
        self.counts = None
        self._counts_dummy = jnp.zeros((self.n_slots, 1), jnp.int32)
        self.keys = jnp.zeros((self.n_slots, 2), jnp.uint32)
        self._base_seed = int(seed)
        self._lora_alpha = float(lora_alpha)
        self._stacked = None
        self._adapter_idx: dict = {None: 0}
        self._slot_adapter = np.zeros((self.n_slots,), np.int32)
        if adapters:
            from bee_code_interpreter_fs_tpu.models.lora import (
                stack_loras,
                zero_lora,
            )

            names = list(adapters)
            first = adapters[names[0]]["layers"]
            targets = tuple(first)
            for n in names[1:]:
                if tuple(adapters[n]["layers"]) != targets:
                    raise ValueError(
                        f"adapters must share one target set: {names[0]!r} "
                        f"has {targets}, {n!r} has "
                        f"{tuple(adapters[n]['layers'])} (pad the smaller "
                        "adapter with zero targets or retrain)"
                    )
            rank = next(iter(first.values()))["a"].shape[-1]
            zero = zero_lora(cfg, rank=rank, targets=targets)
            self._stacked = stack_loras(
                [zero] + [adapters[n] for n in names], targets=targets,
                alpha=self._lora_alpha,
            )
            self._adapter_idx.update(
                {n: i + 1 for i, n in enumerate(names)}
            )

    def _init_device_state(self):
        """Device-side KV state. The base engine holds one dense
        [n_slots, max_len] cache — int8-quantized per head-dim vector when
        kv_quant is on (the context-length-proportional HBM term halves);
        PagedServingEngine overrides with a block pool + tables."""
        if self.kv_quant:
            cfg = self.cfg
            shape = (cfg.n_layers, self.n_slots, self.max_len,
                     cfg.n_kv_heads, cfg.head_dim)
            sshape = shape[:-1] + (1,)
            self.cache = {
                "kq": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(sshape, jnp.float32),
                "vq": jnp.zeros(shape, jnp.int8),
                "vs": jnp.zeros(sshape, jnp.float32),
            }
        else:
            self.cache = init_cache(self.cfg, self.n_slots, self.max_len)

    # ------------------------------------------------------------- intake

    def register_prefix(self, tokens, adapter: str | None = None) -> int:
        """Prefill a shared prompt prefix ONCE and cache its K/V; requests
        submitted with the returned id skip the prefix's prefill entirely
        (the classic system-prompt amortization). Costs one [L, 1, plen]
        K/V buffer in device memory per registered prefix."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("empty prefix")
        if tokens.size >= self.max_len:
            raise ValueError(
                f"prefix ({tokens.size}) leaves no room in max_len "
                f"{self.max_len}"
            )
        if adapter is not None and adapter not in self._adapter_idx:
            raise ValueError(f"unknown adapter {adapter!r}")
        plen = int(tokens.size)
        p = self._params_for([self._adapter_idx.get(adapter, 0)])
        if self.prefill_chunk is not None and plen > self.prefill_chunk:
            # Long system prompts are where chunked prefill matters most:
            # registration memory peaks at O(chunk x plen), not O(plen^2).
            c = self.prefill_chunk
            pad = -(-plen // c) * c
            padded = np.zeros((1, pad), np.int32)
            padded[0, :plen] = tokens
            row_logits, scratch = _chunked_scratch_prefill(
                p, jnp.asarray(padded), jnp.int32(plen), self.cfg, c
            )
            scratch = {
                "k": scratch["k"][:, :, :plen],
                "v": scratch["v"][:, :, :plen],
            }
        else:
            scratch = init_cache(self.cfg, 1, plen)
            batch_logits, scratch = _prefix_prefill(
                p, jnp.asarray(tokens[None, :]), scratch, self.cfg
            )
            row_logits = batch_logits[0]
        pid = next(self._prefix_id)
        self._prefixes[pid] = {
            "k": scratch["k"],
            "v": scratch["v"],
            "last_logits": np.asarray(row_logits, np.float32),
            "len": plen,
            "adapter": adapter,
        }
        return pid

    def unregister_prefix(self, prefix_id: int) -> None:
        """Release a registered prefix's device K/V (including any engine-
        side memos keyed off it, e.g. the paged engine's block-aligned
        copy), reclaiming its memory in a long-lived engine. Requests
        already ADMITTED with it copied what they needed and are
        unaffected; raises while QUEUED requests still reference it (they
        would crash at admission after the K/V is gone)."""
        if prefix_id not in self._prefixes:
            raise ValueError(f"unknown prefix_id {prefix_id}")
        users = [r.rid for r in self._queue if r.prefix_id == prefix_id]
        if users:
            raise ValueError(
                f"prefix {prefix_id} is referenced by queued request(s) "
                f"{users}; drain or cancel them first"
            )
        del self._prefixes[prefix_id]

    def submit(self, prompt, max_new_tokens: int,
               prefix_id: int | None = None, *, temperature: float = 0.0,
               seed: int | None = None, adapter: str | None = None,
               on_token=None, logprobs: bool = False,
               top_p: float = 1.0, presence_penalty: float = 0.0,
               frequency_penalty: float = 0.0) -> int:
        """Queue a prompt (sequence of int token ids); returns request id.
        With `prefix_id`, `prompt` is the SUFFIX after that registered
        prefix (may be empty — the prefix alone is the prompt).

        `on_token` (callable taking a list[int]) streams the request's new
        tokens at every scheduler sync — burst-granular (up to
        steps_per_sync tokens per call), in order, concatenating to
        exactly the final result. Exceptions from a callback propagate out
        of step()/run() only after every slot's tokens are recorded and
        every other sink is delivered — a broken sink never corrupts any
        request's results (resume by calling run() again).
        `temperature` > 0 samples instead of greedy decoding; the request's
        random stream is `fold_in(key, token position)`, so with an explicit
        `seed` the output is reproducible regardless of what other traffic
        shares the batch or how the scheduler slices bursts (seed=None
        derives a key from the engine seed and the request id).
        `presence_penalty` / `frequency_penalty` follow the OpenAI
        convention: they count GENERATED tokens only (prompt and prefix
        text never feed the histogram), shape token choice (greedy argmax
        included), and leave reported logprobs raw-model."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if adapter is not None and adapter not in self._adapter_idx:
            raise ValueError(f"unknown adapter {adapter!r}")
        plen = 0
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(f"unknown prefix_id {prefix_id}")
            pf = self._prefixes[prefix_id]
            if pf["adapter"] != adapter:
                raise ValueError(
                    f"prefix {prefix_id} was registered under adapter "
                    f"{pf['adapter']!r}; request uses {adapter!r} — prefix "
                    "K/V is adapter-specific"
                )
            plen = pf["len"]
        elif prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plen + prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix ({plen}) + prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds cache max_len {self.max_len}"
            )
        if (prefix_id is None and prompt.size > 0
                and prompt.size > max(self.buckets)):
            # Prefixed suffixes skip this gate: _suffix_bucket's exact-
            # remainder fallback (max_len - plen) holds any suffix the
            # total-length check above admitted, even when the caller
            # configured only small custom prefill_buckets.
            raise ValueError(
                f"prompt length {prompt.size} exceeds largest prefill "
                f"bucket {max(self.buckets)}"
            )
        rid = next(self._rid)
        self._queue.append(
            Request(rid, prompt, int(max_new_tokens), prefix_id,
                    float(temperature), seed, adapter, on_token,
                    bool(logprobs), float(top_p), float(presence_penalty),
                    float(frequency_penalty))
        )
        return rid

    def _suffix_bucket(self, plen: int, n: int) -> int:
        """Smallest bucket holding an n-token suffix beside a plen-token
        prefix; the exact remainder is the (rare, its own compile) fallback
        and holds n by submit's total-length check."""
        return next(
            (b for b in self.buckets if b >= n and plen + b <= self.max_len),
            self.max_len - plen,
        )

    @staticmethod
    def _padded_prompt(prompt: np.ndarray, bl: int) -> np.ndarray:
        padded = np.zeros((1, bl), np.int32)
        padded[0, : prompt.size] = prompt
        return padded

    def _bucket_len(self, n: int) -> int:
        plain = next((b for b in self.buckets if n <= b), None)
        if plain is None:
            raise ValueError(f"no bucket holds prompt of length {n}")
        c = self.prefill_chunk
        if c is not None and plain > c and plain % c:
            # An unaligned bucket above the chunk size routes through the
            # O(bucket^2) single-pass admit — exactly the long-prompt range
            # chunked prefill exists for. Prefer the smallest chunk-aligned
            # bucket that also holds the prompt; keep the unaligned bucket
            # only when no aligned one can (capacity never shrinks).
            aligned = next(
                (b for b in self.buckets
                 if n <= b and b > c and b % c == 0),
                None,
            )
            if aligned is not None:
                return aligned
        return plain

    def _params_for(self, ids) -> dict:
        """Base params, or the multi-adapter wrapped tree selecting adapter
        ids[i] for batch row i. The wrap rebuilds only composite-leaf dicts
        around the same arrays — structure is identical across calls, so
        the jitted programs never recompile on adapter churn."""
        if self._stacked is None:
            return self.params
        from bee_code_interpreter_fs_tpu.models.lora import multi_lora_wrap

        return multi_lora_wrap(
            self.params, self._stacked, jnp.asarray(ids, jnp.int32)
        )

    def _req_params(self, req: Request) -> dict:
        return self._params_for([self._adapter_idx[req.adapter]])

    def _req_key(self, req: Request):
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(
            jax.random.PRNGKey(self._base_seed), req.rid
        )

    def _pick_first(self, req: Request, last_logits, prompt_end: int) -> int:
        """First generated token from admission logits: greedy, or sampled
        with the same fold_in(key, position) stream the burst continues.
        Records the token's model log-prob when the request asked for
        logprobs."""
        last_logits = jnp.asarray(last_logits)
        raw_logits = last_logits
        # Penalties count GENERATED tokens only (the OpenAI convention the
        # API names): at admission nothing has been generated, so the first
        # token's choice is unpenalized by construction.
        if req.temperature <= 0:
            # Device-side argmax: a greedy admission moves one scalar to
            # host, never the vocab-wide logits row.
            tok = int(jnp.argmax(last_logits))
        else:
            sub = jax.random.fold_in(self._req_key(req), prompt_end)
            scaled = last_logits / req.temperature
            if req.top_p < 1.0:
                scaled = nucleus_mask(scaled[None, :], req.top_p)[0]
            tok = int(jax.random.categorical(sub, scaled))
        if req.want_logprobs:
            req.logprobs.append(
                float(jax.nn.log_softmax(raw_logits)[tok])
            )
        return tok

    # ---------------------------------------------------------- scheduling

    def _retire(self):
        active_np = np.asarray(self.active)
        for i in range(self.n_slots):
            req = self._slot_req[i]
            if req is not None and not active_np[i]:
                self._record_result(req)
                self._slot_req[i] = None
                self._on_retire(i)

    def _install(self, req: Request, i: int):
        """Prefill `req`'s prompt into slot `i`'s KV storage. Returns
        (first_token, prompt_end), or None when the engine cannot place the
        request right now (paged engine out of blocks) — the caller
        requeues it and stops admitting."""
        n = req.prompt.size
        install = _install_row_quant if self.kv_quant else _install_row
        if req.prefix_id is not None:
            pf = self._prefixes[req.prefix_id]
            plen = pf["len"]
            if n == 0:
                if self.kv_quant:
                    # Prefixes are stored dense (exact); the cache copy is
                    # where quantization happens.
                    self.cache = _install_row_quant(
                        self.cache, {"k": pf["k"], "v": pf["v"]},
                        jnp.int32(i),
                    )
                else:
                    self.cache = _admit_prefix_only(
                        self.cache, pf["k"], pf["v"], jnp.int32(i)
                    )
                first = self._pick_first(req, pf["last_logits"], plen)
            else:
                bl = self._suffix_bucket(plen, n)
                padded = self._padded_prompt(req.prompt, bl)
                if self.kv_quant:
                    last_logits, scratch = _prefill_scratch_prefixed(
                        self._req_params(req), pf["k"], pf["v"],
                        jnp.asarray(padded), jnp.int32(n), self.cfg,
                        plen + bl,
                    )
                    self.cache = install(self.cache, scratch, jnp.int32(i))
                else:
                    self.cache, last_logits = _admit_prefixed(
                        self._req_params(req), self.cache, pf["k"], pf["v"],
                        jnp.asarray(padded), jnp.int32(i), jnp.int32(n),
                        self.cfg,
                    )
                first = self._pick_first(req, last_logits, plen + n)
            return first, plen + n
        bl = self._bucket_len(n)
        padded = self._padded_prompt(req.prompt, bl)
        if (self.prefill_chunk is not None and bl > self.prefill_chunk
                and bl % self.prefill_chunk == 0):
            last_logits, scratch = _chunked_scratch_prefill(
                self._req_params(req), jnp.asarray(padded), jnp.int32(n),
                self.cfg, self.prefill_chunk,
            )
            self.cache = install(self.cache, scratch, jnp.int32(i))
        elif self.kv_quant:
            last_logits, scratch = _prefill_scratch(
                self._req_params(req), jnp.asarray(padded), jnp.int32(n),
                self.cfg, bl,
            )
            self.cache = install(self.cache, scratch, jnp.int32(i))
        else:
            self.cache, last_logits = _admit(
                self._req_params(req), self.cache, jnp.asarray(padded),
                jnp.int32(i), jnp.int32(n), self.cfg,
            )
        return self._pick_first(req, last_logits, n), n

    def _record_result(self, req: Request) -> None:
        """THE one place a finished/cancelled request's channels land."""
        self._results[req.rid] = np.asarray(req.generated, np.int32)
        if req.want_logprobs:
            self._logprob_results[req.rid] = np.asarray(
                req.logprobs, np.float32
            )

    def _on_retire(self, i: int) -> None:
        """Hook: slot i's request just finished (paged engine frees its
        blocks here)."""

    def _admit_waiting(self) -> list:
        """Admit queued requests into free slots. Returns the admission-time
        streaming deliveries [(callback, [token]), ...] for step() to fire
        AFTER all bookkeeping — a raising sink must never abort remaining
        admissions or the burst (the two-phase guarantee submit promises)."""
        fired: list = []
        for i in range(self.n_slots):
            if self._slot_req[i] is not None:
                continue
            # A request whose whole budget is the prefill token (or that
            # emits eos immediately) finishes during admission and never
            # occupies the slot — keep feeding the slot from the queue.
            while self._queue:
                req = self._queue.popleft()
                placed = self._install(req, i)
                if placed is None:
                    self._queue.appendleft(req)
                    return fired
                first, prompt_end = placed
                req.generated.append(first)
                done = req.max_new_tokens <= 1 or (
                    self.eos_id is not None and first == self.eos_id
                )
                if done:
                    self._record_result(req)
                    # The slot was never occupied, but _install may have
                    # claimed per-slot resources (the paged engine's block
                    # reservation) — release them.
                    self._on_retire(i)
                    if req.on_token is not None:
                        fired.append((req.on_token, [first]))
                    continue
                self._slot_req[i] = req
                self._slot_adapter[i] = self._adapter_idx[req.adapter]
                self.pos = self.pos.at[i].set(prompt_end)
                self.temp = self.temp.at[i].set(req.temperature)
                self.top_p = self.top_p.at[i].set(req.top_p)
                self.presence = self.presence.at[i].set(
                    req.presence_penalty
                )
                self.frequency = self.frequency.at[i].set(
                    req.frequency_penalty
                )
                if req.presence_penalty or req.frequency_penalty:
                    # Generated-only histogram (OpenAI semantics): starts
                    # at zero, counting just the admission token — prompt
                    # and prefix text never feed the penalties.
                    hist = np.zeros((self.cfg.vocab_size,), np.int32)
                    hist[first] = 1
                    if self.counts is None:  # lazy: [n_slots, vocab] i32
                        self.counts = jnp.zeros(
                            (self.n_slots, self.cfg.vocab_size), jnp.int32
                        )
                    self.counts = self.counts.at[i].set(jnp.asarray(hist))
                self.keys = self.keys.at[i].set(
                    jnp.asarray(self._req_key(req), jnp.uint32)
                )
                self.last_tok = self.last_tok.at[i].set(first)
                self.remaining = self.remaining.at[i].set(
                    req.max_new_tokens - 1
                )
                self.active = self.active.at[i].set(True)
                # Deliveries are deferred to step(): by fire time every
                # token is recorded and all slot/block bookkeeping (this
                # admission AND later ones) is consistent.
                if req.on_token is not None:
                    fired.append((req.on_token, [first]))
                break
        return fired

    def step(self):
        """One scheduler iteration: retire, admit, one fused decode burst."""
        self._retire()
        fired = self._admit_waiting()
        if not bool(np.asarray(self.active).any()):
            self._deliver(fired)
            return
        want_lp = any(
            r is not None and r.want_logprobs for r in self._slot_req
        )
        want_tp = any(
            r is not None and r.top_p < 1.0 and r.temperature > 0
            for r in self._slot_req
        )
        want_pen = any(
            r is not None and (r.presence_penalty or r.frequency_penalty)
            for r in self._slot_req
        )
        toks, emitted, lps = self._run_burst(want_lp, want_tp, want_pen)
        toks = np.asarray(toks)
        emitted = np.asarray(emitted)
        if want_lp:
            lps = np.asarray(lps)
        # Two phases: record EVERY slot's tokens, then fire callbacks
        # (admission-time deliveries included) — a raising callback must
        # never cost another request (or a later chunk of its own request)
        # its recorded tokens or a sibling sink its delivery.
        for i in range(self.n_slots):
            req = self._slot_req[i]
            if req is None:
                continue
            new = toks[emitted[:, i], i].tolist()
            req.generated.extend(new)
            if req.want_logprobs:
                req.logprobs.extend(lps[emitted[:, i], i].tolist())
            if req.on_token is not None and new:
                fired.append((req.on_token, new))
        self._deliver(fired)

    @staticmethod
    def _deliver(fired: list) -> None:
        """Fire streaming sinks; every sink gets its delivery before the
        first exception (if any) propagates."""
        first_exc = None
        for cb, new in fired:
            try:
                cb(new)
            except Exception as e:  # noqa: BLE001 — deliver to all sinks
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc

    def _run_burst(self, with_logprobs: bool = False,
                   with_top_p: bool = False,
                   with_penalties: bool = False):
        (self.cache, self.pos, self.last_tok, self.remaining, self.active,
         toks, emitted, lps, counts) = _decode_burst(
            self._params_for(self._slot_adapter), self.cache, self.pos,
            self.last_tok,
            self.remaining, self.active, self.temp, self.keys, self.top_p,
            self.presence, self.frequency,
            self.counts if self.counts is not None else self._counts_dummy,
            self.cfg, self.steps_per_sync, self.eos_id, with_logprobs,
            with_top_p, with_penalties,
        )
        if counts is not None:
            self.counts = counts
        return toks, emitted, lps

    def take_logprobs(self, rid: int):
        """Pop the finished request's per-token model log-probs (aligned
        1:1 with its result tokens). None unless it was submitted with
        logprobs=True and has finished."""
        return self._logprob_results.pop(rid, None)

    def cancel(self, rid: int) -> bool:
        """Cancel a request: queued requests are dropped, active ones stop
        at the next sync boundary; either way the tokens generated so far
        become the request's result. Returns False when the rid is unknown
        or already finished (its result, if any, is untouched)."""
        for idx, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[idx]
                self._record_result(req)
                return True
        for i in range(self.n_slots):
            req = self._slot_req[i]
            if req is not None and req.rid == rid:
                # A slot can hold a request that already FINISHED in the
                # last burst but hasn't been swept yet — that's a
                # completion, not a cancellation.
                was_active = bool(np.asarray(self.active)[i])
                self.active = self.active.at[i].set(False)
                self._retire()  # one retirement path for all bookkeeping
                return was_active
        return False

    def stats(self) -> dict:
        """Scheduler snapshot: queue depth, slot occupancy, finished-but-
        uncollected results (the paged engine adds pool utilization)."""
        return {
            "queued": len(self._queue),
            "active_slots": int(np.asarray(self.active).sum()),
            "occupied_slots": sum(
                r is not None for r in self._slot_req
            ),
            "n_slots": self.n_slots,
            "results_pending": len(self._results),
        }

    def run(self) -> dict[int, np.ndarray]:
        """Drain the queue and all active slots; returns {rid: generated}."""
        while self._queue or any(r is not None for r in self._slot_req):
            self.step()
        self._retire()
        out, self._results = self._results, {}
        # Unclaimed logprobs from EARLIER drains would pile up forever in a
        # long-lived engine: keep only the batch being returned (poppable
        # via take_logprobs until the next run() returns).
        self._logprob_results = {
            r: v for r, v in self._logprob_results.items() if r in out
        }
        return out
