"""Autoregressive decode for ``models/transformer.TransformerLM`` with a
preallocated device-resident KV cache — bitwise-consistent with
full-prefix recompute.

The training stack computes every position's attention from scratch each
forward; serving must emit one token at a time, and recomputing the whole
prefix per token is O(S^2) work per sequence. The classic fix is the KV
cache: each block's key/value projections are computed ONCE per position
and kept in device memory; a decode step projects only the newest token
and attends its single query against the cached keys.

The consistency contract here is stronger than "numerically close": a
decode step's logits are **bitwise identical** to the same position's row
of a full-prefix forward pass (asserted for 64+ generated tokens by
tests/test_serving.py). Three design choices make that hold:

- **One block implementation.** The prefill runs the model's own
  ``_attn_half_kv``/``_mlp_half`` (models/transformer.py) — the exact
  functions the training forward composes — capturing each block's (k, v)
  as a side output. The decode step re-expresses the same ops for a
  single position (same einsum strings, same dtype-cast order, same
  scale placement as ``ops.attention.multi_head_attention``).
- **Fixed cache capacity = ``model.seq_len``.** Every attention row is a
  softmax over exactly ``seq_len`` scores with future positions masked to
  ``-inf`` (giving exact zeros after exp) — the decode step's masked row
  has the same length, the same mask pattern, and therefore the same
  reduction shapes as the corresponding row of the full forward. Masked
  cache entries multiply probabilities that are exactly ``0.0``, so the
  pad/garbage content beyond the current position cannot perturb a bit.
  (This also matches the model's own contract: ``apply`` requires
  ``S == seq_len`` — the positional table broadcasts, it is not sliced.)
- **Per-position ops only elsewhere.** Embedding rows, layernorm, and the
  residual adds are elementwise per position, so the single-position step
  computes literally the same scalar expressions.

No MoE / sequence-parallel support (those models route per-batch state
through collectives); ``attn_block`` models decode fine — the cache step
computes the dense triangle the blockwise form equals.
"""

from __future__ import annotations

import time

import numpy as np

from distributed_tensorflow_tpu.models.transformer import (
    TransformerLM,
    _attn_half_kv,
    _layernorm,
    _mlp_half,
)
from distributed_tensorflow_tpu.ops import nn
from distributed_tensorflow_tpu.ops.attention import multi_head_attention
from distributed_tensorflow_tpu.serving import reqtrace


def check_decodable(model) -> None:
    """Loud rejection of model configs the KV-cache step cannot serve."""
    if not isinstance(model, TransformerLM):
        raise ValueError(f"KV-cache decode serves TransformerLM; got "
                         f"{type(model).__name__}")
    if model.seq_axis is not None:
        raise ValueError("KV-cache decode does not run inside the "
                         "sequence-parallel shard_map step; serve with "
                         "seq_axis=None")
    if model.moe_experts:
        raise ValueError("KV-cache decode does not support MoE blocks yet")
    if getattr(model, "loop_passes", 1) > 1:
        raise ValueError("KV-cache decode holds one set of keys and values "
                         "a layer; a layer run loop_passes times holds "
                         "that many (and a token may leave after fewer "
                         "passes): such a model is not served yet")
    if getattr(model, "layer_plan", ""):
        raise ValueError("KV-cache decode runs one kind of layer under one "
                         "cache layout; a model with a layer_plan (window "
                         "layers, head counts of their own) is not served "
                         "yet")


def make_prefill(model, jit: bool = True):
    """(params, tokens (B, C) int32) -> (logits (B, C, V) f32, cache).

    ``C`` must equal ``model.seq_len`` (the cache capacity); tokens beyond
    the real prompt are pad — their cache entries are overwritten as
    decode proceeds and their scores are causally masked meanwhile.
    ``cache`` is a tuple of per-block (k, v) pairs, each (B, C, H, Dh).
    The computation is the model's own dense-causal forward (one shared
    block implementation) with the head applied to every position, so
    ``logits[:, t]`` is bitwise the full-recompute answer at ``t``.
    """
    check_decodable(model)
    import jax
    import jax.numpy as jnp

    cd = model.compute_dtype
    attn = lambda q, k, v: multi_head_attention(q, k, v, causal=True)

    def prefill(params, tokens):
        h = jnp.take(params["tok"], tokens, axis=0)
        h = h + params["pos"].astype(h.dtype)
        if cd is not None:
            h = h.astype(cd)
        cache = []
        for blk in params["blocks"]:
            h, k, v = _attn_half_kv(h, blk, attn, cd)
            h = _mlp_half(h, blk, cd)
            cache.append((k, v))
        h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
        logits = nn.dense(h, params["head"]["w"], params["head"]["b"],
                          compute_dtype=cd)
        return logits.astype(jnp.float32), tuple(cache)

    return jax.jit(prefill) if jit else prefill


def make_decode_step(model, jit: bool = True):
    """(params, cache, tok (B,) int32, t int32) -> (logits (B, V) f32,
    cache) — one KV-cache decode tick at absolute position ``t``.

    Writes the new token's (k, v) into every block's cache at ``t``, then
    attends the single query row against the full cache with positions
    ``> t`` masked to ``-inf`` — the same masked row, shapes included,
    that the full forward computes at position ``t``. The cache is
    DONATED under jit so the preallocated buffers are updated in place
    dispatch-to-dispatch."""
    check_decodable(model)
    import jax
    import jax.numpy as jnp
    from jax import lax

    cd = model.compute_dtype
    capacity = model.seq_len
    dh = model.d_model // model.num_heads

    def step(params, cache, tok, t):
        h = jnp.take(params["tok"], tok[:, None], axis=0)  # (B, 1, d)
        pos_t = lax.dynamic_slice_in_dim(params["pos"], t, 1, axis=0)
        h = h + pos_t.astype(h.dtype)
        if cd is not None:
            h = h.astype(cd)
        # row t of the causal mask, full cache width — same pattern as
        # multi_head_attention's arange(sk) <= arange(sq) triangle
        mask = jnp.arange(capacity)[None, :] <= t
        new_cache = []
        for blk, (k_cache, v_cache) in zip(params["blocks"], cache):
            y = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
            qkv = jnp.einsum("bsd,dthe->tbshe", y,
                             blk["qkv"].astype(y.dtype))
            k_cache = lax.dynamic_update_slice_in_dim(
                k_cache, qkv[1].astype(k_cache.dtype), t, axis=1)
            v_cache = lax.dynamic_update_slice_in_dim(
                v_cache, qkv[2].astype(v_cache.dtype), t, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qkv[0],
                           k_cache).astype(jnp.float32)
            s = s / jnp.sqrt(jnp.float32(dh))
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            # the p @ V contraction runs at q-width 2 (row duplicated,
            # row 0 kept): a width-1 dot takes the GEMV kernel, whose
            # k-accumulation order differs from the GEMM the full
            # forward uses — the one op where shape specialization
            # breaks bitwise parity (1-ulp drift, measured). Width >= 2
            # selects the GEMM kernel, whose per-row reduction order is
            # independent of the row count.
            p2 = jnp.concatenate([p, p], axis=2).astype(qkv[0].dtype)
            a = jnp.einsum("bhqk,bkhd->bqhd", p2, v_cache)[:, :1]
            a = a.reshape(*a.shape[:2], -1)  # (B, 1, H*Dh)
            h = h + nn.dense(a, blk["proj"], compute_dtype=cd)
            h = _mlp_half(h, blk, cd)
            new_cache.append((k_cache, v_cache))
        h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
        logits = nn.dense(h, params["head"]["w"], params["head"]["b"],
                          compute_dtype=cd)
        return logits.astype(jnp.float32)[:, 0], tuple(new_cache)

    if jit:
        return jax.jit(step, donate_argnums=(1,))
    return step


def make_slot_pools(model, page_size: int, num_pages: int):
    """Device KV pools for the paged slot step: a tuple, one
    ``(k_pool, v_pool)`` pair per block, each
    ``(num_pages + 1, page_size, H, Dh)`` zeros in the cache dtype.

    Row 0 is the reserved SCRATCH page: a free slot's page-table row is
    all zeros, so its (masked, discarded) reads and its writes land
    here instead of clobbering a live request's pages. One extra row
    buys a branch-free step — no "is this slot live" select inside the
    traced computation."""
    check_decodable(model)
    import jax.numpy as jnp

    cd = model.compute_dtype
    dh = model.d_model // model.num_heads
    dtype = cd if cd is not None else jnp.float32
    shape = (num_pages + 1, page_size, model.num_heads, dh)
    return tuple((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                 for _ in range(model.num_blocks))


def make_slot_step(model, page_size: int, jit: bool = True):
    """(params, pools, page_table (S, P) i32, tok (S,) i32, t (S,) i32)
    -> (logits (S, V) f32, pools) — one iteration-level decode tick over
    ``S`` independent slots against a PAGED KV cache.

    The continuous scheduler's single traced computation (r21). Each
    slot ``i`` feeds token ``tok[i]`` at its own absolute position
    ``t[i]``; ``page_table[i, j]`` names the physical pool row backing
    logical page ``j`` of slot ``i`` (0 = the scratch page for
    free/unmapped entries — see ``make_slot_pools``). The step scatters
    the new (k, v) into ``pools[block][t // page_size][t % page_size]``
    and attends each slot's query against its GATHERED dense view
    ``pool[page_table].reshape(S, capacity, H, Dh)``.

    Bitwise contract: the body is ``make_decode_step`` verbatim — same
    einsum strings, same dtype-cast order, same scale placement, same
    width->=2 p@V trick — with the batch's shared scalar ``t`` widened to
    a per-slot vector and the dense cache update replaced by the
    page scatter/gather (index plumbing: gathers and scatters move
    bits, they do not do arithmetic). Free slots run the same ops
    against scratch garbage: every score beyond ``t[i]`` is masked to
    ``-inf`` pre-softmax (exact 0.0 probability) and a free slot's
    logits row is discarded by the scheduler, so garbage cannot reach a
    live request. ``S >= 2`` keeps every contraction on the GEMM (not
    GEMV) kernel, whose per-row reduction order is row-count
    independent — the property the whole-batch pin already relies on.

    Shapes are static (slot count, page table, pools), so continuous
    mode adds exactly ONE traced signature however requests come and
    go. Pools are DONATED under jit — updated in place
    dispatch-to-dispatch."""
    check_decodable(model)
    import jax
    import jax.numpy as jnp

    cd = model.compute_dtype
    capacity = model.seq_len
    dh = model.d_model // model.num_heads
    if page_size < 1 or capacity % page_size:
        raise ValueError(
            f"page_size ({page_size}) must be >= 1 and divide the cache "
            f"capacity ({capacity}) so a slot's logical pages tile it "
            f"exactly")

    def step(params, pools, page_table, tok, t):
        s_count = tok.shape[0]
        h = jnp.take(params["tok"], tok[:, None], axis=0)  # (S, 1, d)
        pos_t = jnp.take(params["pos"], t, axis=0)[:, None, :]
        h = h + pos_t.astype(h.dtype)
        if cd is not None:
            h = h.astype(cd)
        # row t[i] of the causal mask per slot, full cache width
        mask = (jnp.arange(capacity)[None, :] <= t[:, None])[:, None, None, :]
        rows = jnp.arange(s_count)
        dest = page_table[rows, t // page_size]  # (S,) physical pages
        offset = t % page_size
        new_pools = []
        for blk, (k_pool, v_pool) in zip(params["blocks"], pools):
            y = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
            qkv = jnp.einsum("bsd,dthe->tbshe", y,
                             blk["qkv"].astype(y.dtype))
            k_pool = k_pool.at[dest, offset].set(
                qkv[1][:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[dest, offset].set(
                qkv[2][:, 0].astype(v_pool.dtype))
            k_cache = k_pool[page_table].reshape(
                s_count, capacity, model.num_heads, dh)
            v_cache = v_pool[page_table].reshape(
                s_count, capacity, model.num_heads, dh)
            s = jnp.einsum("bqhd,bkhd->bhqk", qkv[0],
                           k_cache).astype(jnp.float32)
            s = s / jnp.sqrt(jnp.float32(dh))
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            # width-2 p @ V — see the GEMV note in make_decode_step
            p2 = jnp.concatenate([p, p], axis=2).astype(qkv[0].dtype)
            a = jnp.einsum("bhqk,bkhd->bqhd", p2, v_cache)[:, :1]
            a = a.reshape(*a.shape[:2], -1)  # (S, 1, H*Dh)
            h = h + nn.dense(a, blk["proj"], compute_dtype=cd)
            h = _mlp_half(h, blk, cd)
            new_pools.append((k_pool, v_pool))
        h = _layernorm(h, params["ln_f"]["g"], params["ln_f"]["b"])
        logits = nn.dense(h, params["head"]["w"], params["head"]["b"],
                          compute_dtype=cd)
        return logits.astype(jnp.float32)[:, 0], tuple(new_pools)

    if jit:
        return jax.jit(step, donate_argnums=(1,))
    return step


def generate(model, params, prompts, max_new_tokens: int, *,
             temperature: float = 0.0, rng=None,
             prefill_fn=None, step_fn=None):
    """Greedy (``temperature == 0``) or temperature-sampled decode.

    ``prompts``: int array (B, P) with 1 <= P and
    P + max_new_tokens <= model.seq_len (the cache capacity — serving
    stays inside the trained context window). Returns
    ``{"tokens": (B, P + N), "logits": (B, N, V)}`` — ``logits[:, i]``
    is the distribution the (P + i)'th token was drawn from, each row
    bitwise equal to the full-prefix recompute at that position.

    ``prefill_fn``/``step_fn`` let the engine pass its per-bucket cached
    jitted functions; omitted, fresh jitted ones are built (fine for
    one-off library use, wasteful per request)."""
    import jax
    import jax.numpy as jnp

    check_decodable(model)
    prompts = np.asarray(prompts)
    if prompts.ndim != 2 or prompts.shape[1] < 1:
        raise ValueError(f"prompts must be (B, P>=1); got {prompts.shape}")
    if prompts.size and (prompts.min() < 0
                         or prompts.max() >= model.vocab_size):
        # jnp.take would silently CLAMP an out-of-vocab id to the edge
        # embedding — a tokenizer/vocab mismatch must be a loud 400,
        # not a 200 with wrong tokens
        raise ValueError(
            f"prompt ids must be in [0, {model.vocab_size}); got range "
            f"[{prompts.min()}, {prompts.max()}]")
    b, p = prompts.shape
    n = int(max_new_tokens)
    if n < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {n}")
    capacity = model.seq_len
    if p + n > capacity:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({n}) exceeds the model's "
            f"context window / cache capacity ({capacity})")
    if prefill_fn is None:
        prefill_fn = make_prefill(model)
    if step_fn is None:
        step_fn = make_decode_step(model)
    if temperature > 0.0 and rng is None:
        rng = jax.random.PRNGKey(0)

    # a single sequence is served as a row-duplicated pair: at batch 1
    # the per-token dense layers take the GEMV kernel (see the q-width
    # note in make_decode_step) and bitwise parity with the batched
    # full forward is lost
    b_real = b
    if b == 1:
        prompts = np.concatenate([prompts, prompts], axis=0)
        b = 2

    padded = np.zeros((b, capacity), dtype=np.int32)
    padded[:, :p] = prompts
    # request plane: the prompt pass (prefill_fn dispatch + the first
    # logits readback) is the "prefill" phase; the autoregressive loop
    # below is "decode" with one tick per generated token
    t0 = time.perf_counter()
    logits_all, cache = prefill_fn(params, jnp.asarray(padded))
    step_logits = np.asarray(logits_all[:, p - 1])
    reqtrace.note_phase("prefill", time.perf_counter() - t0)

    t0 = time.perf_counter()
    out_tokens = [prompts.astype(np.int32)]
    out_logits = []
    for i in range(n):
        out_logits.append(step_logits)
        if temperature > 0.0:
            key = jax.random.fold_in(rng, i)
            tok = np.asarray(jax.random.categorical(
                key, jnp.asarray(step_logits) / temperature, axis=-1),
                dtype=np.int32)
        else:
            tok = step_logits.argmax(axis=-1).astype(np.int32)
        out_tokens.append(tok[:, None])
        if i + 1 < n:
            step_logits, cache = step_fn(params, cache,
                                         jnp.asarray(tok),
                                         jnp.int32(p + i))
            step_logits = np.asarray(step_logits)
    reqtrace.note_phase("decode", time.perf_counter() - t0, ticks=n)
    return {"tokens": np.concatenate(out_tokens, axis=1)[:b_real],
            "logits": np.stack(out_logits, axis=1)[:b_real]}
