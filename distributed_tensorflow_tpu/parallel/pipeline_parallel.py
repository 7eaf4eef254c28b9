"""Pipeline parallelism: transformer blocks staged over the "model" axis.

The reference has no model parallelism at all (SURVEY.md §2c); the mesh
keeps a "model" axis open, and this module makes it real a THIRD way
(after tensor_parallel's Megatron split and sequence_parallel's token
sharding): GPipe-style PIPELINE parallelism — each device owns a
contiguous run of transformer blocks (a STAGE), the global batch splits
into M microbatches, and activations flow stage-to-stage on the ring
while every stage works on a different microbatch each tick.

TPU-idiomatic formulation (static schedule table, no host control):

- Stage parameters are the model's ``blocks`` list STACKED on a leading
  axis and sharded over "model" — each device holds (L, ...) leaves,
  L = num_blocks / K. ``stack_block_params`` / ``unstack_block_params``
  convert to/from the standard layout so CHECKPOINTS stay in the one
  shared pytree format (SURVEY.md §7 hard part d). With
  ``virtual_stages=V`` (interleaved schedule, Megatron-LM, Narayanan
  et al. 2021) the stacking order is ROUND-ROBIN
  (``pp_schedule.block_permutation``): device ``s`` owns the V
  noncontiguous block groups ``s, s+K, ..., s+(V-1)K`` — checkpoints
  still store the standard list order, so saves/restores are
  layout-independent across V.
- One ``lax.scan`` over ticks inside ``shard_map``, driven by the
  static (K, M, V) tick table from ``pp_schedule.build_pp_schedule``:
  at tick t, device s runs block group ``chunk_index[t, s]`` on
  microbatch ``micro_index[t, s]`` (GPipe V=1: group 0, microbatch
  t - s over M + K - 1 ticks; interleaved V>1: M*V + K - 1 ticks of
  1/V-sized groups — the fill/drain bubble shrinks ~V-fold). Stage 0
  ingests (embeds) a microbatch when its scheduled group is 0, the
  last stage computes the loss when its scheduled group is V-1. One
  ``ppermute`` per tick moves activations to the next stage — the
  schedule satisfies T(m, j+1) = T(m, j) + 1, so a single carried
  activation slot suffices for any V. Out-of-range ticks are masked —
  every device runs the identical program (SPMD), and the bubble
  ticks contribute exact zeros.
- The BACKWARD pipeline is not written at all: reverse-mode AD of the
  scan + ppermute IS the backward schedule (ppermute's transpose is
  the reverse rotation, carrying output cotangents back through the
  stages in reverse tick order) — the same property the ring
  attention backward builds on.

Gradient reduction (cf. sequence_parallel's two derivations): the loss
is a ``psum`` over the stage axis of the last stage's masked
contributions, so each device's AD computes exact PARTIALS of the
global loss: stage-sharded block leaves need NO cross-stage reduction
(they are different shards of the stacked tree), while the replicated
leaves (embeddings, final norm, head) get nonzero gradients only on
the stages that use them (0 and K-1) — one ``psum`` over the stage
axis totals them. Then the usual pmean over "data" for DP.

Exactness: the pipeline computes literally the same function as
running each microbatch through all blocks sequentially, so gradients
match the gradient-accumulation step (``compute_grads(accum_steps=M)``)
to float tolerance — pinned by tests/test_pipeline_parallel.py.
Dropout draws a distinct key per microbatch exactly as accumulation
does, so trajectories match WITH dropout too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models.transformer import (
    _layernorm,
    _remat,
    _transformer_block,
)
from distributed_tensorflow_tpu.ops import nn
from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from distributed_tensorflow_tpu.parallel.pp_schedule import (
    ZB_B,
    ZB_F,
    ZB_W,
    block_permutation,
    build_pp_schedule,
    build_zb_schedule,
    normalize_pp_schedule,
    validate_pp_layout,
    validate_zb_layout,
)
from distributed_tensorflow_tpu.training.train_state import (
    TrainState,
    apply_updates,
)


def stack_block_params(params, perm=None):
    """Standard layout (``blocks`` = list of per-block dicts) -> stacked
    (one dict whose leaves carry a leading num_blocks axis). Everything
    else passes through. The stacked form is what shards over the
    stage axis; checkpoints always store the standard form. ``perm``
    (``pp_schedule.block_permutation``) reorders the stacking for the
    interleaved layout — position p stores original block perm[p];
    None keeps the contiguous GPipe order."""
    blocks = params["blocks"]
    order = range(len(blocks)) if perm is None else perm
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[blocks[int(b)] for b in order])
    out = dict(params)
    out["blocks"] = stacked
    return out


def unstack_block_params(params, num_blocks: int, perm=None):
    """Inverse of ``stack_block_params`` (host-side: checkpoint fetch):
    returns the standard list order whatever stacking order ``perm``
    produced the stacked array."""
    stacked = params["blocks"]
    pos_of = (range(num_blocks) if perm is None
              else {int(b): p for p, b in enumerate(perm)})
    blocks = [jax.tree.map(lambda x, i=pos_of[b]: x[i], stacked)
              for b in range(num_blocks)]
    out = dict(params)
    out["blocks"] = blocks
    return out


def _map_params_shaped(entry, pstruct, fn, passthrough):
    """Apply ``fn`` to every opt-state subtree that structurally mirrors
    params; recurse through dict containers; ``passthrough`` handles
    everything else (scalar slots, step counts). The ONE implementation
    of the rule every PP state transform needs — stack, unstack,
    shardings, specs — so a future non-dict slot container gets fixed
    in one place."""
    if jax.tree.structure(entry) == pstruct:
        return fn(entry)
    if isinstance(entry, dict):
        return {k: _map_params_shaped(v, pstruct, fn, passthrough)
                for k, v in entry.items()}
    return passthrough(entry)


def pp_state_sharding(state: TrainState, mesh):
    """Shardings for a STACKED-params TrainState: block leaves split on
    their leading (stage) axis over "model", everything else
    replicated; optimizer slots follow their params (structure-matched:
    slot subtrees that mirror params take the params shardings, scalars
    replicate). Derived from ``pp_state_specs`` — one statement of the
    blocks-vs-replicated rule."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        pp_state_specs(state),
                        is_leaf=lambda v: isinstance(v, P))


def is_stage_leaf(path) -> bool:
    """True for param-tree paths under ``blocks`` — the leaves whose
    per-device values are DISTINCT stage shards (the stacked leading
    axis splits over "model"); everything else replicates. The ONE
    statement of the rule, shared by the spec derivation, the gradient
    reduction, and the axis-aware clip."""
    keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
    return keys[:1] == ("blocks",)


def pp_clip_transform(max_norm: float, virtual_stages: int = 1):
    """Axis-correct global-norm clip for INSIDE the PP ``shard_map``
    step: stage-sharded block leaves contribute exact partials of the
    squared norm, replicated leaves count once, and every device
    applies the SAME scale — so replicated leaves (tok/pos/ln_f/head)
    stay bit-identical across stages (the stage-local-norm divergence
    the plain ``clip_by_global_norm`` had here).

    The block contribution is accumulated in CANONICAL (original block
    index) order: each device computes a per-block-slot squared-sum
    vector, scatters it into the block's original position (undoing the
    ``virtual_stages`` round-robin permutation), and one ``psum``
    assembles the full [num_blocks] vector — each slot has exactly one
    nonzero contributor, so the psum is order-exact, and the final
    reduction runs over the same vector whatever the layout. That makes
    the clipped trajectory BIT-IDENTICAL across V (the V=2 == V=1
    exactness tests/test_pp_interleaved.py pins); a per-device psum of
    differently-grouped partials would wobble in the last ulp."""
    max_norm = float(max_norm)
    v = int(virtual_stages)

    def transform(grads):
        k = lax.axis_size(MODEL_AXIS)
        s_idx = lax.axis_index(MODEL_AXIS)
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        per_slot = None  # [L] squared sums, summed across block leaves
        rep = []
        for path, g in flat:
            sq = jnp.square(g.astype(jnp.float32))
            if is_stage_leaf(path):
                slot = jnp.sum(sq.reshape(sq.shape[0], -1), axis=1)
                per_slot = slot if per_slot is None else per_slot + slot
            else:
                rep.append(jnp.sum(sq))
        total = jnp.float32(0.0)
        if per_slot is not None:
            local = per_slot.shape[0]
            group = local // v
            # original block index of each local slot (stacked position
            # s_idx*L + vg*group + l holds block (vg*k + s_idx)*group + l)
            orig = ((jnp.arange(v)[:, None] * k + s_idx) * group
                    + jnp.arange(group)[None, :]).reshape(local)
            vec = jnp.zeros((local * k,), jnp.float32).at[orig].set(per_slot)
            total = total + jnp.sum(lax.psum(vec, MODEL_AXIS))
        # replicated-leaf grads are psum results — identical on every
        # stage already, so adding them locally keeps one scale everywhere
        for r in rep:
            total = total + r
        norm = jnp.sqrt(total)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)

    return transform


def pp_state_specs(state: TrainState) -> TrainState:
    """PartitionSpec pytree for a STACKED-params TrainState — the one
    place the blocks-split-over-model rule is written (shard_map specs
    and device shardings both derive from it)."""
    def block_or_rep(path, _leaf):
        return P(MODEL_AXIS) if is_stage_leaf(path) else P()

    pspecs = jax.tree_util.tree_map_with_path(block_or_rep, state.params)
    pstruct = jax.tree.structure(state.params)
    pleaves = jax.tree.leaves(pspecs, is_leaf=lambda v: isinstance(v, P))
    opt = _map_params_shaped(
        state.opt_state, pstruct,
        lambda e: jax.tree.unflatten(pstruct, pleaves),
        lambda e: jax.tree.map(lambda _: P(), e))
    return TrainState(params=pspecs, opt_state=opt, step=P(), rng=P(),
                      model_state=jax.tree.map(lambda _: P(),
                                               state.model_state))


def shard_state_pp(state: TrainState, mesh,
                   virtual_stages: int = 1) -> TrainState:
    """Stack the blocks list (round-robin order under
    ``virtual_stages > 1``) and place the state with the PP layout."""
    perm = None
    if int(virtual_stages) > 1:
        perm = block_permutation(len(state.params["blocks"]),
                                 mesh.shape[MODEL_AXIS], virtual_stages)
    stack = lambda p: stack_block_params(p, perm)
    stacked = state._replace(params=stack(state.params))
    stacked = stacked._replace(opt_state=_map_params_shaped(
        state.opt_state, jax.tree.structure(state.params),
        stack, lambda e: e))
    return jax.device_put(stacked, pp_state_sharding(stacked, mesh))


def fetch_state_pp(state: TrainState, model, k_stages: int | None = None,
                   virtual_stages: int = 1) -> TrainState:
    """PP-layout state -> host state in the STANDARD layout (checkpoint
    format): unstack blocks in params and any params-shaped opt slots,
    undoing the ``virtual_stages`` round-robin stacking (``k_stages``
    is required for V > 1) — so checkpoints are identical whatever
    (K, V) layout the run trained under."""
    host = jax.device_get(state)
    n = model.num_blocks
    perm = None
    if int(virtual_stages) > 1:
        if k_stages is None:
            raise ValueError("fetch_state_pp needs k_stages to invert "
                             "the virtual_stages>1 stacking order")
        perm = block_permutation(n, k_stages, virtual_stages)
    unstack = lambda p: unstack_block_params(p, n, perm)
    params = unstack(host.params)
    return host._replace(
        params=params,
        opt_state=_map_params_shaped(
            host.opt_state, jax.tree.structure(host.params),
            unstack, lambda e: e))


def _attn_for(model):
    """The model's single-device attention flavor (causal; dense or
    blockwise) — PP stages run the SAME block math the plain model
    runs, so the flavor selection IS the model's."""
    return model.attention_fn()


def _pp_step_fn(model, optimizer, mesh, microbatches: int,
                keep_prob: float, grad_transform,
                virtual_stages: int = 1, schedule: str = "auto"):
    """Validate the PP configuration and build the raw per-shard step
    ``(state, (x, y)) -> (state, metrics)`` — the body both the host-fed
    wrapper (``make_pp_train_step``) and the device-resident sampler
    (``training/device_step.make_pp_device_train_step``) run inside
    ``shard_map``. ``schedule`` picks the tick table: gpipe /
    interleaved differentiate the forward scan (AD is the backward
    schedule), zb runs the explicit F/B/W zero-bubble scan
    (``_pp_zb_grads``) — identical gradients either way (bit-pinned)."""
    if getattr(model, "seq_axis", None) is not None:
        raise ValueError("pipeline parallelism stages BLOCKS; it does "
                         "not compose with seq_axis (ring attention) — "
                         "pick one model-axis strategy")
    if any(getattr(x, "linear", False) for x in getattr(model, "plan", ())):
        raise ValueError("tensor parallelism has no linear-attention layer: the "
                         "gated delta rule's state and its conv are not "
                         "split over the model axis yet")
    if getattr(model, "layer_plan", ""):
        raise ValueError("pipeline parallelism stacks layers of one kind "
                         "into a stage's scan; a model with a layer_plan "
                         "(layers that differ) is not staged yet")
    if getattr(model, "loop_passes", 1) > 1:
        raise ValueError("pipeline parallelism sends a microbatch through "
                         "the stages once; a model of loop_passes > 1 runs "
                         "its stack several times (the last stage would "
                         "feed the first) and is not staged yet")
    if getattr(model, "moe_experts", 0):
        raise ValueError("pipeline parallelism is not wired for MoE "
                         "blocks (the stage scan runs the dense block "
                         "form and would drop the aux loss); use "
                         "--expert_parallel for MoE sharding")
    k_stages = mesh.shape[MODEL_AXIS]
    m = int(microbatches)
    v_stages = int(virtual_stages)
    sched_name = normalize_pp_schedule(schedule, v_stages)
    validate_pp_layout(model.num_blocks, k_stages, v_stages,
                       microbatches=m)
    if sched_name == "zb":
        # zb-specific constraints up front: >= 2 blocks per group (the
        # bit-identity boundary) and a buildable F/B/W table
        validate_zb_layout(model.num_blocks, k_stages, v_stages,
                           microbatches=m)
        build_zb_schedule(k_stages, m, v_stages)
    cd = model.compute_dtype

    def step(state: TrainState, batch):
        x, y = batch
        if x.shape[0] % m:
            raise ValueError(f"per-shard batch {x.shape[0]} must split "
                             f"into {m} microbatches")
        s_idx = lax.axis_index(MODEL_AXIS)
        rng, sub = jax.random.split(state.rng)
        sub = jax.random.fold_in(sub, lax.axis_index(DATA_AXIS))

        if sched_name == "zb":
            grads, loss, acc = _pp_zb_grads(
                model, state.params, x, y, sub, m, k_stages, s_idx,
                keep_prob, cd, v_stages)
        else:
            def loss_fn(params):
                return _pp_loss(model, params, x, y, sub, m, k_stages,
                                s_idx, keep_prob, cd, v_stages)

            grads, (loss, acc) = jax.grad(loss_fn, has_aux=True)(
                state.params)
        # the differentiated loss was LOCAL (nonzero on the last stage
        # only): psum totals it for reporting, and the same psum totals
        # the replicated leaves' per-stage partials. Stage-sharded block
        # leaves are exact partials already (distinct shards routed home
        # by the ppermute transposes) — no stage-axis reduction
        loss = lax.psum(loss, MODEL_AXIS)
        acc = lax.psum(acc, MODEL_AXIS)

        def reduce_g(path, g):
            if is_stage_leaf(path):
                return g
            return lax.psum(g, MODEL_AXIS)

        grads = jax.tree_util.tree_map_with_path(reduce_g, grads)
        grads = jax.tree.map(lambda g: lax.pmean(g, DATA_AXIS), grads)
        if grad_transform is not None:
            grads = grad_transform(grads)
        metrics = {"loss": lax.pmean(loss, DATA_AXIS),
                   "accuracy": lax.pmean(acc, DATA_AXIS)}
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params, state.step)
        params = apply_updates(state.params, updates)
        return (TrainState(params, opt_state, state.step + 1, rng,
                           state.model_state), metrics)

    return step


def make_pp_train_step(model, optimizer, mesh, microbatches: int,
                       keep_prob: float = 1.0, donate: bool = True,
                       grad_transform=None, virtual_stages: int = 1,
                       schedule: str = "auto"):
    """Compiled pipeline-parallel train step for ``TransformerLM``:
    (PP-layout state, staged batch) -> (state, metrics).

    The mesh's "model" axis size is the stage count K; ``microbatches``
    (M) must divide the per-data-shard batch. The model must be a plain
    (seq_axis=None) LM — attention flavors (dense or ``attn_block``)
    and the streamed CE head (``ce_block``) all work; blocks split K
    ways. ``virtual_stages=V`` runs the interleaved schedule on a
    state stacked by ``shard_state_pp(..., virtual_stages=V)`` —
    bit-identical trajectories to V=1, in M*V + K - 1 ticks of
    1/V-sized block groups instead of M + K - 1 full-stage ticks.
    ``schedule="zb"`` runs the zero-bubble F/B/W table on the SAME
    stacked layout (any V) — trajectories stay bit-identical to
    gpipe/interleaved; only the tick order changes. Matches
    ``compute_grads(accum_steps=M)`` trajectories (the per-microbatch
    rng fold is the same)."""
    step = _pp_step_fn(model, optimizer, mesh, microbatches, keep_prob,
                       grad_transform, virtual_stages, schedule)
    data_spec = (P(DATA_AXIS, None), P(DATA_AXIS, None))
    cache: dict = {}

    def call(state, batch):
        fn = cache.get("fn")
        if fn is None:
            sharded = jax.shard_map(
                step, mesh=mesh,
                in_specs=(pp_state_specs(state), data_spec),
                out_specs=(pp_state_specs(state), P()),
                check_vma=False)
            fn = cache["fn"] = jax.jit(
                sharded, donate_argnums=(0,) if donate else ())
        return fn(state, batch)

    return call


def _embed_fn(tok, pos, ids, cd):
    """Token embedding + learned positions — the ONE embed both the
    AD-schedules' tick body and the zb W(m, 0) re-linearization run, so
    the two paths cannot diverge bitwise."""
    h = jnp.take(tok, ids, axis=0) + pos.astype(tok.dtype)
    return h.astype(cd) if cd is not None else h


def _group_fwd_fn(blk_fn, attn, cd, blk, h):
    """One virtual-stage block group's forward: the inner scan over its
    (already gathered) stacked block leaves — shared by every schedule
    (and by the zb B/W vjp re-linearizations). The scan's loop boundary
    is ALSO the zb bit-identity mechanism: a length >= 2 loop body
    compiles as an isolated computation in both the AD backward and the
    explicit vjps, so the weight-grad contractions hit identical
    kernels; at length 1 XLA simplifies the loop away and fuses the zb
    branch's forward RECOMPUTE into the contraction (AD reads saved
    residuals instead), wobbling the projection grads by an ulp — which
    is why ``validate_zb_layout`` requires >= 2 blocks per group."""
    def body(hh, b):
        return blk_fn(hh, b, attn, cd), None

    h, _ = lax.scan(body, h, blk)
    return h


def _head_loss_fn(model, lnf, head, keep_prob, cd, h, targets, key):
    """Final-stage LN -> dropout -> LM head -> (loss, accuracy) —
    parametrized by the head weights so the zb W(m, KV-1) tick can
    differentiate it; the AD schedules close over the same function."""
    h = _layernorm(h, lnf["g"], lnf["b"])
    h = nn.dropout(h, keep_prob, key, deterministic=keep_prob >= 1.0)
    if getattr(model, "ce_block", None):
        return nn.streamed_softmax_ce_head(
            h, head["w"], head["b"], targets,
            block=model.ce_block, compute_dtype=cd)
    logits = nn.dense(h, head["w"], head["b"],
                      compute_dtype=cd).astype(jnp.float32)
    return (nn.softmax_cross_entropy(logits, targets),
            nn.accuracy(logits, targets))


def _pp_loss(model, params, x, y, sub, m, k_stages, s_idx, keep_prob, cd,
             v_stages: int = 1):
    """The pipelined forward + loss (see module docstring): returns
    (global mean loss, (loss, accuracy)) — grad'd with has_aux. The
    tick loop is driven by the static (K, M, V) schedule table; V=1 is
    exactly the GPipe schedule, V>1 the interleaved one. Per-microbatch
    PRNG folds and the masked-mean loss are identical for every V — the
    forward applies the same blocks to the same microbatches in the
    same order, so trajectories are bit-identical across V."""
    tok, pos = params["tok"], params["pos"]
    blocks = params["blocks"]
    lnf, head = params["ln_f"], params["head"]
    mb = x.shape[0] // m
    xm = x.reshape(m, mb, x.shape[1])
    ym = y.reshape(m, mb, y.shape[1])
    perm = [(i, (i + 1) % k_stages) for i in range(k_stages)]
    attn = _attn_for(model)
    blk_fn = _transformer_block
    if getattr(model, "remat", False):
        # same remat the plain model applies (apply_hidden): one
        # block's activations live at a time, recompute in the backward
        # (all but a blockwise attention's out and logsumexp)
        blk_fn = _remat(_transformer_block, (2, 3))

    sched = build_pp_schedule(k_stages, m, v_stages)
    chunk_tbl = jnp.asarray(sched.chunk_index)  # [T, K]
    mb_tbl = jnp.asarray(sched.micro_index)     # [T, K] (pre-clipped)
    valid_tbl = jnp.asarray(sched.valid)        # [T, K]
    # local shard: [L, ...] leaves -> [V, L/V, ...] virtual-stage groups
    # (group v on device s holds the blocks of virtual stage v*K + s —
    # the round-robin stacking order of shard_state_pp)
    vblocks = jax.tree.map(
        lambda a: a.reshape(v_stages, a.shape[0] // v_stages,
                            *a.shape[1:]),
        blocks)

    def embed(ids):
        return _embed_fn(tok, pos, ids, cd)

    def group_fwd(h, v):
        blk = jax.tree.map(lambda a: a[v], vblocks)
        return _group_fwd_fn(blk_fn, attn, cd, blk, h)

    def head_loss(h, targets, key):
        return _head_loss_fn(model, lnf, head, keep_prob, cd, h,
                             targets, key)

    def tick(carry, t):
        # embed/head are GATED with lax.cond on the scheduled unit, not
        # computed-then-masked: other stages/groups would otherwise burn
        # the full vocab-head FLOPs every tick — at large V (the
        # ce_block composition) that is comparable to a block's cost
        # and would eat the pipeline speedup
        h_cur = carry
        v = chunk_tbl[t, s_idx]
        mb_i = mb_tbl[t, s_idx]
        ok = valid_tbl[t, s_idx]
        h_in = lax.cond(
            (s_idx == 0) & (v == 0),
            lambda: embed(xm[mb_i]).astype(h_cur.dtype),
            lambda: h_cur)
        h_out = group_fwd(h_in, v)
        loss, acc = lax.cond(
            (s_idx == k_stages - 1) & (v == v_stages - 1) & ok,
            lambda: head_loss(h_out, ym[mb_i],
                              jax.random.fold_in(sub, mb_i)),
            lambda: (jnp.float32(0.0), jnp.float32(0.0)))
        h_next = lax.ppermute(h_out, MODEL_AXIS, perm)
        return h_next, (loss, acc)

    h0 = jnp.zeros((mb, x.shape[1], model.d_model),
                   cd if cd is not None else jnp.float32)
    _, (losses, accs) = lax.scan(tick, h0, jnp.arange(sched.num_ticks))
    # LOCAL loss only — no psum inside the differentiated function.
    # Grad seeds cotangent 1.0 on the last stage's (only nonzero) local
    # loss; the ppermute transposes route that backward through earlier
    # stages, so per-device grads EXACTLY PARTITION dL/dtheta (the SP
    # per-token derivation's pattern). A psum here instead would seed
    # every stage's replicated copy and K-scale every gradient (psum's
    # transpose is another psum — the known trap).
    return jnp.sum(losses) / m, (jnp.sum(losses) / m, jnp.sum(accs) / m)


def _pp_zb_grads(model, params, x, y, sub, m, k_stages, s_idx, keep_prob,
                 cd, v_stages: int = 1):
    """The zero-bubble pipelined forward+backward, written EXPLICITLY:
    one ``lax.scan`` over the combined F/B/W tick table
    (``pp_schedule.build_zb_schedule``) instead of reverse-mode AD of
    the forward scan. Returns ``(grads, local_loss, local_acc)`` with
    the same contracts as differentiating ``_pp_loss``: stage-sharded
    block grads are exact partials, replicated-leaf grads are nonzero
    only on the stages that use them (one outer psum totals them), the
    loss is LOCAL (nonzero on the last stage only).

    Tick semantics (the table's arrival columns route the ring):
    - **F**: forward one block group from the stashed input (stage 0
      group 0 embeds and stashes the embed output — its W needs it),
      send the activation on the forward ring.
    - **B**: activation grad. The last unit linearizes
      group_fwd∘head_loss from the stashed input and pulls (dh, loss,
      acc) out of one vjp (its forward IS the linearization — no
      separate F tick); middle units vjp group_fwd w.r.t. the input at
      the stashed cotangent. dh rides the reverse ring.
    - **W**: weight grad, deferred into the cooldown: vjp the same
      unit w.r.t. its PARAMS from the stashed (input, cotangent) pair
      (the first unit folds the embed backward in; the last the head
      backward), written into a per-microbatch buffer.

    Bit-identity with the AD schedules rests on three pinned facts:
    splitting one joint vjp into activation-only + params-only halves
    reproduces the joint backward bitwise (same primitive rules, same
    operands); re-linearizing from the stashed input reproduces the
    saved-residual backward bitwise (deterministic ops, identical
    inputs); and AD-of-scan accumulates closure-constant cotangents in
    REVERSE tick order — so the per-microbatch buffers fold in
    DESCENDING m after the scan, reproducing AD's addition order
    exactly. The buffers are the schedule's memory price: W deferral
    keeps M per-microbatch weight-grad slabs live within the step
    (they never cross the optimizer update — the fold runs before it).
    """
    tok, pos = params["tok"], params["pos"]
    blocks = params["blocks"]
    lnf, head = params["ln_f"], params["head"]
    mb = x.shape[0] // m
    xm = x.reshape(m, mb, x.shape[1])
    ym = y.reshape(m, mb, y.shape[1])
    fwd_perm = [(i, (i + 1) % k_stages) for i in range(k_stages)]
    bwd_perm = [(i, (i - 1) % k_stages) for i in range(k_stages)]
    attn = _attn_for(model)
    blk_fn = _transformer_block
    if getattr(model, "remat", False):
        blk_fn = _remat(_transformer_block, (2, 3))
    v = int(v_stages)
    sched = build_zb_schedule(k_stages, m, v)
    kind_tbl = jnp.asarray(sched.kind)
    mb_tbl = jnp.asarray(sched.micro_index)
    ch_tbl = jnp.asarray(sched.chunk_index)
    fiv = jnp.asarray(sched.fwd_in_valid)
    fim = jnp.asarray(sched.fwd_in_micro)
    fic = jnp.asarray(sched.fwd_in_chunk)
    biv = jnp.asarray(sched.bwd_in_valid)
    bim = jnp.asarray(sched.bwd_in_micro)
    bic = jnp.asarray(sched.bwd_in_chunk)

    vblocks = jax.tree.map(
        lambda a: a.reshape(v, a.shape[0] // v, *a.shape[1:]), blocks)
    hdt = cd if cd is not None else jnp.float32
    act = (mb, x.shape[1], model.d_model)
    # AD seeds each unit's loss cotangent with d(sum(losses)/m) = 1/m
    seed = jnp.ones((), jnp.float32) / m
    gfwd = lambda blk, h: _group_fwd_fn(blk_fn, attn, cd, blk, h)
    hloss = lambda ln, hd, h, tgt, key: _head_loss_fn(
        model, ln, hd, keep_prob, cd, h, tgt, key)
    zbuf = lambda tree: jax.tree.map(
        lambda a: jnp.zeros((m,) + a.shape, a.dtype), tree)

    carry0 = (
        jnp.zeros(act, hdt),              # forward ring payload
        jnp.zeros(act, hdt),              # backward (cotangent) payload
        jnp.zeros((m, v) + act, hdt),     # stash_h: unit inputs
        jnp.zeros((m, v) + act, hdt),     # stash_c: unit cotangents
        zbuf(vblocks),                    # wbuf [M, V, L/V, ...]
        (zbuf(tok), zbuf(pos)),           # embed grads per microbatch
        (zbuf(lnf), zbuf(head)),          # head grads per microbatch
    )

    def tick(carry, t):
        h_slot, c_slot, stash_h, stash_c, wbuf, embbuf, headbuf = carry
        # arrivals: payloads ppermuted at the end of tick t-1 land now
        stash_h = lax.cond(
            fiv[t, s_idx],
            lambda sh: sh.at[fim[t, s_idx], fic[t, s_idx]].set(h_slot),
            lambda sh: sh, stash_h)
        stash_c = lax.cond(
            biv[t, s_idx],
            lambda sc: sc.at[bim[t, s_idx], bic[t, s_idx]].set(c_slot),
            lambda sc: sc, stash_c)
        m_i = mb_tbl[t, s_idx]
        v_i = ch_tbl[t, s_idx]
        is_first = (s_idx == 0) & (v_i == 0)
        is_loss = (s_idx == k_stages - 1) & (v_i == v - 1)
        blk = jax.tree.map(lambda a: a[v_i], vblocks)
        h_in = stash_h[m_i, v_i]
        cot = stash_c[m_i, v_i]
        key = jax.random.fold_in(sub, m_i)
        zero_act = jnp.zeros(act, hdt)
        zero32 = jnp.float32(0.0)

        def do_noop(ops):
            stash_h, wbuf, embbuf, headbuf = ops
            return (zero_act, zero_act, stash_h, wbuf, embbuf, headbuf,
                    zero32, zero32)

        def do_f(ops):
            stash_h, wbuf, embbuf, headbuf = ops
            h0 = lax.cond(
                is_first,
                lambda: _embed_fn(tok, pos, xm[m_i], cd).astype(hdt),
                lambda: h_in)
            # the embed unit's W re-linearizes from the raw ids, but its
            # B-consumers downstream need the stashed input like anyone
            stash_h = lax.cond(is_first,
                               lambda sh: sh.at[m_i, v_i].set(h0),
                               lambda sh: sh, stash_h)
            return (gfwd(blk, h0), zero_act, stash_h, wbuf, embbuf,
                    headbuf, zero32, zero32)

        def do_b(ops):
            stash_h, wbuf, embbuf, headbuf = ops

            def loss_b():
                f = lambda hh: hloss(lnf, head, gfwd(blk, hh), ym[m_i],
                                     key)
                (l, a), vjp = jax.vjp(f, h_in)
                (dh,) = vjp((seed, zero32))
                return dh, l, a

            def mid_b():
                _, vjp = jax.vjp(lambda hh: gfwd(blk, hh), h_in)
                (dh,) = vjp(cot)
                return dh, zero32, zero32

            dh, l, a = lax.cond(is_loss, loss_b, mid_b)
            return (zero_act, dh, stash_h, wbuf, embbuf, headbuf, l, a)

        def do_w(ops):
            stash_h, wbuf, embbuf, headbuf = ops
            put = lambda buf, g: jax.tree.map(
                lambda b, gg: b.at[m_i].set(gg), buf, g)
            putw = lambda buf, g: jax.tree.map(
                lambda b, gg: b.at[m_i, v_i].set(gg), buf, g)

            def w_first(bufs):
                wbuf, embbuf, headbuf = bufs
                f = lambda tk, ps, bb: gfwd(
                    bb, _embed_fn(tk, ps, xm[m_i], cd).astype(hdt))
                _, vjp = jax.vjp(f, tok, pos, blk)
                dtok, dpos, dblk = vjp(cot)
                return (putw(wbuf, dblk),
                        (put(embbuf[0], dtok), put(embbuf[1], dpos)),
                        headbuf)

            def w_loss(bufs):
                wbuf, embbuf, headbuf = bufs
                f = lambda bb, ln, hd: hloss(ln, hd, gfwd(bb, h_in),
                                             ym[m_i], key)
                _, vjp = jax.vjp(f, blk, lnf, head)
                dblk, dlnf, dhead = vjp((seed, zero32))
                return (putw(wbuf, dblk), embbuf,
                        (put(headbuf[0], dlnf), put(headbuf[1], dhead)))

            def w_mid(bufs):
                wbuf, embbuf, headbuf = bufs
                _, vjp = jax.vjp(lambda bb: gfwd(bb, h_in), blk)
                (dblk,) = vjp(cot)
                return putw(wbuf, dblk), embbuf, headbuf

            wbuf, embbuf, headbuf = lax.cond(
                is_first, w_first,
                lambda bufs: lax.cond(is_loss, w_loss, w_mid, bufs),
                (wbuf, embbuf, headbuf))
            return (zero_act, zero_act, stash_h, wbuf, embbuf, headbuf,
                    zero32, zero32)

        ops = (stash_h, wbuf, embbuf, headbuf)
        branches = [do_noop] * 4
        branches[ZB_F], branches[ZB_B], branches[ZB_W] = do_f, do_b, do_w
        h_out, c_out, stash_h, wbuf, embbuf, headbuf, l, a = lax.switch(
            kind_tbl[t, s_idx], branches, ops)
        h_next = lax.ppermute(h_out, MODEL_AXIS, fwd_perm)
        c_next = lax.ppermute(c_out, MODEL_AXIS, bwd_perm)
        return (h_next, c_next, stash_h, stash_c, wbuf, embbuf,
                headbuf), (l, a)

    carry, (losses, accs) = lax.scan(tick, carry0,
                                     jnp.arange(sched.num_ticks))
    wbuf, embbuf, headbuf = carry[4], carry[5], carry[6]

    def fold_desc(buf):
        # AD-of-scan adds closure-constant cotangents in reverse tick
        # order — descending m per slot; reproduce that fold bitwise
        out = jnp.zeros(buf.shape[1:], buf.dtype)
        for mm in range(m - 1, -1, -1):
            out = out + buf[mm]
        return out

    grads = {
        "tok": fold_desc(embbuf[0]),
        "pos": fold_desc(embbuf[1]),
        "blocks": jax.tree.map(
            lambda b: fold_desc(b).reshape(b.shape[1] * b.shape[2],
                                           *b.shape[3:]),
            wbuf),
        "ln_f": jax.tree.map(fold_desc, headbuf[0]),
        "head": jax.tree.map(fold_desc, headbuf[1]),
    }
    return grads, jnp.sum(losses) / m, jnp.sum(accs) / m


def stage_batch_pp(mesh, batch):
    """(x, y) -> device arrays: batch split over "data", REPLICATED over
    the stage axis (ids are tiny; every stage sees the full token ids
    but only stage 0 embeds and only stage K-1 scores)."""
    from distributed_tensorflow_tpu.parallel.mesh import put_global

    return put_global(
        (NamedSharding(mesh, P(DATA_AXIS, None)),
         NamedSharding(mesh, P(DATA_AXIS, None))),
        batch,
    )


def pp_comm_rows(act_bytes_per_microbatch: int, k_stages: int,
                 microbatches: int, virtual_stages: int = 1,
                 schedule: str = "auto",
                 rep_grad_bytes: int = 0) -> list[dict]:
    """Static per-step boundary-transfer bytes for the stage ring — the
    comm ledger's PP rows, TICK-exact: the compiled step executes one
    ``ppermute`` of a full activation slot on EVERY tick of the static
    schedule (SPMD — masked bubble ticks move their zero payloads over
    the wire like any other; the pre-r18 ledger priced only the
    ``M*(K*V-1)`` useful hops and ``tools/dttcheck`` proved the
    undercount against the lowered jaxpr). Forward runs ``num_ticks``
    ring hops; the backward (AD transpose, or zb's explicit cotangent
    ring — which permutes every tick of the SAME combined table) runs
    the same count. Tiny schedule control traffic and the metrics
    psums are control-plane (dttcheck's scalar exemption).

    ``rep_grad_bytes`` prices the OTHER model-axis collective the step
    runs: the replicated leaves' (tok/pos/ln_f/head) gradient partials
    total under one psum over the stage axis (~2x bytes, all-reduce
    convention) — unpriced before r18.

    ``exposed_bytes`` per row is the analytic on-critical-path share:
    under gpipe/interleaved every hop sits on the tick boundary (the
    consumer uses it the very next tick), so everything is exposed;
    under zb the cotangent hops land in a stash and their consumers
    (B/W ticks) have slack from the deferred-W schedule, so the
    backward ring prices as overlapped (exposed 0)."""
    if k_stages * max(1, virtual_stages) < 2:
        return []  # a 1-stage "ring" has no boundary and no stage axis
    sched = normalize_pp_schedule(schedule, virtual_stages)
    if sched == "zb":
        ticks = build_zb_schedule(k_stages, microbatches,
                                  max(1, virtual_stages)).num_ticks
    else:
        ticks = build_pp_schedule(k_stages, microbatches,
                                  max(1, virtual_stages)).num_ticks
    fwd = ticks * act_bytes_per_microbatch
    bwd_note = ("the transpose ring permutes every backward tick "
                "in reverse" if sched != "zb" else
                "zb: the combined F/B/W table's cotangent ring fires "
                "every tick; stash-on-arrival + deferred-W slack hide "
                "it off the critical path")
    rows = [
        {"collective": "ppermute(activations, forward)", "axis": "model",
         "bytes": fwd, "exposed_bytes": fwd,
         "note": f"{ticks} schedule ticks x 1 activation slot "
                 f"({sched}; bubble ticks ride the wire too — "
                 f"dttcheck-proven)"},
        {"collective": "ppermute(cotangents, backward)", "axis": "model",
         "bytes": fwd, "exposed_bytes": 0 if sched == "zb" else fwd,
         "note": bwd_note},
    ]
    if rep_grad_bytes > 0:
        rows.append({
            "collective": "all_reduce(replicated-leaf grads)",
            "axis": "model", "bytes": 2 * rep_grad_bytes,
            "exposed_bytes": 2 * rep_grad_bytes,
            "note": "tok/pos/ln_f/head partials (nonzero on the stages "
                    "that use them) total under one psum over the "
                    "stage axis (~2x, all-reduce convention)"})
    return rows
