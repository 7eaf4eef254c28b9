"""Tensor parallelism over the mesh's "model" axis — GSPMD style.

The reference has no model parallelism of any kind (SURVEY.md §2c: its only
strategy is async PS data-parallelism, ``MNISTDist.py:110-111``); the mesh
keeps a "model" axis open precisely so wider models can shard without
reshaping the framework (parallel/mesh.py). This module makes that axis
real for the flagship CNN: the classic column/row split of the FC stack —

    wd1 [3136, 1024]  column-split  P(None, "model")   (bd1 follows)
    out [1024,   10]  row-split     P("model", None)

so the big matmul's output activations are sharded over "model", the
second matmul contracts over the sharded dimension, and XLA's SPMD
partitioner inserts the one ``psum`` the math needs. No manual collective
appears in this file: shardings are ANNOTATED on the arrays
(``NamedSharding``) and the step is a plain global-view ``jax.jit`` —
the "pick a mesh, annotate, let XLA insert collectives" recipe. Composes
with data parallelism on the same mesh: batch dims carry P("data").

Conv kernels and small biases stay replicated (their FLOPs don't pay for
collective traffic at these shapes).
"""

from __future__ import annotations

import copy

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from distributed_tensorflow_tpu.training.train_state import TrainState

# FC-stack split for the reference CNN's parameter names (models/cnn.py):
# first FC column-parallel, second FC row-parallel.
_CNN_TP_SPECS = {
    ("weights", "wd1"): P(None, MODEL_AXIS),
    ("biases", "bd1"): P(MODEL_AXIS),
    ("weights", "out"): P(MODEL_AXIS, None),
}


def _transformer_tp_table(all_keys) -> dict:
    """Megatron-style block split for the transformer families
    (models/transformer._block_params): attention HEADS over the model
    axis (qkv (d, 3, h, dh) on its head dim; proj (h*dh, d) row-split —
    the head-major flatten keeps the split on head boundaries), MLP
    column- then row-split (in/w + in/b over mlp_dim; out/w contracting
    over it). XLA's partitioner derives the one psum each row-split
    contraction needs. Embeddings / positional / layernorms / the vocab
    head replicate: at these widths their FLOPs don't pay for
    collective traffic, and the large-VOCAB memory problem is solved by
    the streamed CE head (ops/nn.py), not by sharding."""
    table = {}
    for keys in all_keys:
        if len(keys) >= 3 and keys[0] == "blocks":
            leaf = keys[2:]
            if leaf == ("qkv",):
                table[keys] = P(None, None, MODEL_AXIS, None)
            elif leaf == ("proj",):
                table[keys] = P(MODEL_AXIS, None)
            elif leaf == ("mlp_in", "w"):
                table[keys] = P(None, MODEL_AXIS)
            elif leaf == ("mlp_in", "b"):
                table[keys] = P(MODEL_AXIS)
            elif leaf == ("mlp_out", "w"):
                table[keys] = P(MODEL_AXIS, None)
    return table


def tp_param_specs(params) -> dict:
    """PartitionSpec pytree mirroring ``params``: the model family's
    split table over the model axis, everything else replicated. The
    CNN rule applies only when the params carry the full FC stack (wd1
    present) — a model that merely shares a leaf NAME with the table
    (e.g. the MLP's "out") must not have that one matmul split in
    isolation, which would buy a collective and shard nothing that
    matters. Transformer params (a "blocks" list of the shared block
    layout) get the Megatron block split."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    all_keys = {
        tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        for path, _ in flat
    }
    if ("weights", "wd1") in all_keys:
        table = _CNN_TP_SPECS
    elif ("blocks", 0, "qkv") in all_keys:
        table = _transformer_tp_table(all_keys)
    else:
        table = {}
    specs = {}
    for path, _ in flat:
        keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        specs[keys] = table.get(keys, P())
    # rebuild the nested shape
    out: dict = {}
    for keys, spec in specs.items():
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = spec
    return _listify(out)


def _listify(node):
    """Int-keyed dicts (list indices from the path walk) back to LISTS,
    so the returned spec tree STRUCTURALLY mirrors params — a caller's
    plain ``jax.tree.map(f, params, specs)`` must work (the transformer
    families' "blocks" list is the first input that exercises this)."""
    if isinstance(node, dict) and node and all(
            isinstance(k, int) for k in node):
        return [_listify(node[i]) for i in range(len(node))]
    if isinstance(node, dict):
        return {k: _listify(v) for k, v in node.items()}
    return node


def has_tp_specs(params) -> bool:
    """True when at least one leaf of ``params`` has a model-axis split —
    i.e. tensor parallelism would actually shard something. Models without
    matching names (e.g. the ResNets) would silently replicate everything
    over the model axis; callers use this to reject that loudly."""
    specs = tp_param_specs(params)
    return any(s != P() for s in
               jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))


def _map_specs(tree, specs_like, mesh):
    """NamedShardings for ``tree`` using a params-shaped spec tree."""
    leaves_specs = jax.tree.leaves(specs_like,
                                   is_leaf=lambda x: isinstance(x, P))
    structure = jax.tree.structure(tree)
    assert structure.num_leaves == len(leaves_specs), (
        "opt-state subtree does not mirror params"
    )
    return jax.tree.unflatten(
        structure, [NamedSharding(mesh, s) for s in leaves_specs]
    )


def _opt_sharding(entry, params_structure, pspecs, mesh, rep):
    """Shardings for one opt_state subtree, by structure rather than by
    optimizer name: a subtree that mirrors params (velocity/moment trees)
    takes the params specs; dicts recurse per slot; anything else (step
    counts and other scalar slots — e.g. a schedule's ``t``) replicates.
    This keeps every current and future slot layout working without a
    per-optimizer special case."""
    if jax.tree.structure(entry) == params_structure:
        return _map_specs(entry, pspecs, mesh)
    if isinstance(entry, dict):
        return {k: _opt_sharding(v, params_structure, pspecs, mesh, rep)
                for k, v in entry.items()}
    return jax.tree.map(lambda _: rep, entry)


def _check_divisibility(params, pspecs, mesh) -> None:
    """Every split dim must divide by the model-axis size — shape-based
    and at the LIBRARY layer, so every caller is protected (GSPMD would
    otherwise silently pad + reshard off head/column boundaries)."""
    ways = mesh.shape[MODEL_AXIS]
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(
                              pspecs, is_leaf=lambda x: isinstance(x, P))):
        for d, axis in enumerate(spec):
            if axis == MODEL_AXIS and leaf.shape[d] % ways:
                raise ValueError(
                    f"model-axis size {ways} must divide the sharded "
                    f"dim {d} (= {leaf.shape[d]}) of a leaf with shape "
                    f"{leaf.shape}; pick a --model_axis that divides "
                    f"the model's head count and MLP width")


def tp_state_sharding(state: TrainState, mesh: Mesh) -> TrainState:
    """Sharding pytree matching ``state``: params (and their optimizer
    slots) follow ``tp_param_specs``; scalars and rng replicated.
    Refuses shapes the model axis does not divide."""
    pspecs = tp_param_specs(state.params)
    _check_divisibility(state.params, pspecs, mesh)
    rep = NamedSharding(mesh, P())
    params_sh = _map_specs(state.params, pspecs, mesh)
    opt_sh = _opt_sharding(state.opt_state, jax.tree.structure(state.params),
                           pspecs, mesh, rep)
    model_state_sh = jax.tree.map(lambda _: rep, state.model_state)
    return TrainState(params=params_sh, opt_state=opt_sh, step=rep, rng=rep,
                      model_state=model_state_sh)


def shard_state_tp(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a host-built TrainState with the TP layout.

    Multi-process (one process per host over a global mesh): ``device_put``
    cannot address other hosts' devices, but every host holds the full
    value, so each leaf is assembled with ``make_array_from_callback`` —
    each host materializes exactly the shards its own devices need."""
    shardings = tp_state_sharding(state, mesh)
    if jax.process_count() > 1:
        import numpy as np

        def place(x, s):
            if isinstance(x, jax.Array) and x.sharding == s:
                return x  # already placed (restage of a fresh state)
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, s, lambda idx: host[idx])

        return jax.tree.map(place, state, shardings)
    return jax.device_put(state, shardings)


def shard_attention(model, mesh: Mesh):
    """``model`` with its blockwise attention run per (batch, head) shard.

    Everything else of a TP step is global-view and XLA partitions it, but
    the fused attention is a Mosaic kernel, which the partitioner refuses
    ("cannot be automatically partitioned"). Attention is independent
    across batch rows and heads — the two dims this layout shards, batch
    over "data" and heads over "model" — so a ``shard_map`` over exactly
    those is the same mathematics with local shapes, and each shard
    chooses kernel or scan from ITS shapes as a single chip would. Models
    without ``attn_block`` (dense attention is plain XLA) pass through."""
    if any(getattr(x, "linear", False) for x in getattr(model, "plan", ())):
        raise ValueError("tensor parallelism has no linear-attention layer: the "
                         "gated delta rule's state and its conv are not "
                         "split over the model axis yet")
    if getattr(model, "layer_plan", ""):
        raise ValueError("tensor parallelism splits one head count over "
                         "the model axis; a model with a layer_plan (head "
                         "counts and masks a layer) is not split yet")
    if getattr(model, "loop_passes", 1) > 1:
        raise ValueError("tensor parallelism's rules shard a stack that is "
                         "walked once; a model of loop_passes > 1 (a scan "
                         "over passes, an exit gate) is not split yet")
    if getattr(model, "attn_block", None) is None:
        return model
    spec = P(DATA_AXIS, None, MODEL_AXIS, None)
    per_shard = jax.shard_map(model.attention_fn(), mesh=mesh,
                              in_specs=(spec, spec, spec), out_specs=spec,
                              check_vma=False)
    model = copy.copy(model)
    model.attention_fn = lambda: per_shard
    return model


def make_tp_train_step(model, optimizer, mesh: Mesh, keep_prob: float = 1.0,
                       donate: bool = True, grad_transform=None,
                       accum_steps: int = 1, augment_fn=None):
    """Compiled TP(+DP) train step: (state, batch) -> (state, metrics).

    This IS ``make_train_step``: under GSPMD the program is global-view and
    parallelism comes entirely from the layouts committed on the input
    arrays (``shard_state_tp`` / ``stage_batch_tp``) — XLA's SPMD
    partitioner derives every collective (grad psum over "data", activation
    psum over "model") from those. ``mesh`` is the mesh the caller placed
    the state on; the one thing built on it here is the blockwise
    attention's ``shard_map`` (``shard_attention``).
    """
    from distributed_tensorflow_tpu.training.train_state import make_train_step

    return make_train_step(shard_attention(model, mesh), optimizer,
                           keep_prob=keep_prob,
                           grad_transform=grad_transform, donate=donate,
                           accum_steps=accum_steps, augment_fn=augment_fn)


def make_tp_eval_step(model, mesh: Mesh):
    """Global-view eval: shardings propagate from the committed params —
    the plain eval step, its blockwise attention per shard as in the
    train step."""
    from distributed_tensorflow_tpu.training.train_state import make_eval_step

    return make_eval_step(shard_attention(model, mesh))


def stage_batch_tp(mesh: Mesh, batch):
    """Batch staged with data-axis sharding (model axis untouched).

    Delegates to ``shard_batch``: identical layout, and its multi-process
    branch (per-host slices assembled via
    ``make_array_from_process_local_data``) applies unchanged to TP+DP."""
    from distributed_tensorflow_tpu.parallel.data_parallel import shard_batch

    return shard_batch(mesh, batch)


def tp_comm_rows(fwd_act_bytes: int, bwd_act_bytes: int) -> list[dict]:
    """Static per-step activation all-reduce bytes for the Megatron
    split — the comm ledger's TP rows, priced against what the GSPMD
    partitioner ACTUALLY inserts (machine-proven for the CNN by
    ``tools/dttcheck`` r18 from the compiled SPMD HLO). The two
    payloads differ because the two sync points sit at different
    widths: forward psums the ROW-SPLIT matmul's partial OUTPUTS
    (``fwd_act_bytes`` — for the CNN FC stack that is (B, num_classes),
    NOT the hidden activations the pre-r18 row priced, a ~100x
    overcount at the flagship shapes); backward psums the cotangent at
    the COLUMN-SPLIT input (``bwd_act_bytes`` — (B, fc_in) for the
    CNN). Transformer blocks are symmetric: both boundaries psum a
    (B, S, d_model) tensor per block, attention-out + MLP-down.
    All-reduce convention ~2x; callers pass the summed per-pass
    payload."""
    rows = []
    if fwd_act_bytes > 0:
        rows.append({
            "collective": "all_reduce(activations, forward)",
            "axis": "model", "bytes": 2 * fwd_act_bytes,
            "note": "row-split boundaries psum their partial outputs "
                    "(~2x, GSPMD-inserted)"})
    if bwd_act_bytes > 0:
        rows.append({
            "collective": "all_reduce(cotangents, backward)",
            "axis": "model", "bytes": 2 * bwd_act_bytes,
            "note": "the column-split inputs psum the backward "
                    "cotangent (~2x, GSPMD-inserted)"})
    return rows
