"""Shared pytree <-> path-keyed-dict conversion.

One implementation used by both the checkpoint writer and the PS-emulation
wire protocol, so the key scheme and dtype handling cannot drift between
them. Keys are '/'-joined tree paths ("weights/wd1"); bfloat16 leaves are
tagged and viewed as uint16 for serializers that can't store bf16 (npz).
"""

from __future__ import annotations

import jax
import numpy as np

_BF16_TAG = "__bf16__"


def locally_fetchable(leaf) -> bool:
    """True when this process can materialize ``leaf``'s full value without
    talking to other processes: host arrays, fully-addressable device
    arrays, fully-replicated global arrays, and global arrays whose
    addressable shards cover every index (e.g. a model-axis split that
    stays within this host, replicated over a cross-host data axis)."""
    if not isinstance(leaf, jax.Array):
        return True
    if leaf.is_fully_addressable or leaf.is_fully_replicated:
        return True
    try:
        imap = leaf.sharding.devices_indices_map(leaf.shape)
    except Exception:  # noqa: BLE001 — unknown sharding: assume remote
        return False
    pid = jax.process_index()
    local = {str(idx) for d, idx in imap.items() if d.process_index == pid}
    return local == {str(idx) for idx in imap.values()}


def needs_collective_fetch(tree) -> bool:
    """True when fetching ``tree`` to host requires other processes'
    cooperation (some leaf's data lives only on non-addressable devices).
    With GSPMD meshes the answer is identical on every process — the mesh
    is a regular grid over processes — which is what lets callers agree on
    whether to enter the collective path without communicating first."""
    return any(not locally_fetchable(l) for l in jax.tree_util.tree_leaves(tree))


def _fetch_leaves(leaves: list) -> list[np.ndarray]:
    """Leaves -> host ndarrays, transfers batched: locally-fetchable
    leaves go through ONE ``jax.device_get`` call (one transfer set-up
    instead of one per leaf), and
    cross-host-sharded leaves ride ONE ``process_allgather`` of the whole
    spanning subset (one DCN collective instead of one per leaf). The
    allgather is COLLECTIVE: every process must reach it with the same
    spanning leaves — guaranteed when all processes hold the same
    sharding layout (GSPMD meshes), which makes the local/spanning split
    identical everywhere."""
    out: list = [None] * len(leaves)
    local_idx, local_vals = [], []
    span_idx, span_vals = [], []
    for j, leaf in enumerate(leaves):
        if locally_fetchable(leaf):
            local_idx.append(j)
            local_vals.append(leaf)
        else:
            span_idx.append(j)
            span_vals.append(leaf)
    if span_vals:
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(span_vals, tiled=True)
        for j, v in zip(span_idx, gathered):
            out[j] = np.asarray(v)
    for j, v in zip(local_idx, jax.device_get(local_vals)):
        out[j] = np.asarray(v)
    return out


def join_collective_fetch(tree) -> None:
    """Participate in ``fetch_pytree``'s collective WITHOUT materializing
    the local leaves: gathers only the cross-host-sharded subset and
    discards it. Non-chief processes use this to pair up with the chief's
    full fetch during coordinated checkpoints/evals — paying the DCN
    collective they must join, but not a full-model device->host copy
    whose result nobody reads."""
    span = [l for l in jax.tree_util.tree_leaves(tree)
            if not locally_fetchable(l)]
    if span:
        from jax.experimental import multihost_utils

        multihost_utils.process_allgather(span, tiled=True)


def run_bounded(fn, timeout_s: float, *, what: str,
                grace_factor: float = 4.0):
    """Run ``fn`` on a daemon thread with a LOUD two-stage time bound.

    The pattern both exit-path collectives share (the agreement gather
    and the final save's fetch): the calling thread blocks in join() and
    dispatches nothing concurrent (rendezvous-deadlock note in PERF.md),
    so a peer that never joins cannot hang this process forever. After
    ``timeout_s`` a progress line is printed and the wait extends by
    ``grace_factor`` x — a collective completes for ALL processes or
    none, so a merely-slow link (a congested DCN) finishes within the grace
    and every process proceeds together; only a hard-dead peer exhausts
    it, on every live process alike.

    Returns ``(done, result)``: ``done`` False means the bound expired
    and the thread was ABANDONED (still blocked; ``fn`` must tolerate
    completing late — see the cancel event in supervisor's final save).
    ``fn`` exceptions are returned, not raised: ``result`` is the
    exception instance and ``done`` is True."""
    import threading

    box: dict = {}

    def _run():
        try:
            box["result"] = fn()
        except Exception as e:  # noqa: BLE001 — reported to the caller
            box["error"] = e

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        print(f"{what} slow (>{timeout_s:.0f}s); waiting up to "
              f"{grace_factor * timeout_s:.0f}s more before dying loudly "
              f"(a collective completes for all processes or none)")
        t.join(grace_factor * timeout_s)
    if t.is_alive():
        return False, None
    if "error" in box:
        return True, box["error"]
    return True, box.get("result")


def agree_clean_exit(clean: bool, timeout_s: float = 60.0,
                     return_token: bool = False):
    """All-process agreement gate ahead of a final COLLECTIVE save.

    Every process — cleanly exiting or unwinding an exception — joins one
    tiny allgather of its clean flag. Returns True only when EVERY process
    reported clean (the collective fetch may proceed), False when any peer
    failed (all processes skip symmetrically), and None when the agreement
    itself timed out (a peer died hard and will never join; the caller
    must skip, letting the job die loudly instead of hanging — the r3
    ADVICE failure mode: clean peers blocked forever in process_allgather
    while the raising process skipped it).

    ``return_token=True`` returns ``(verdict, token)`` instead: the same
    allgather additionally carries a random 8-hex attempt token from
    process 0 (the sharded checkpoint format's per-attempt nonce,
    checkpoint.py) — riding THIS bounded agreement keeps the sharded
    save itself collective-free, its documented contract. ``token`` is
    None whenever the verdict is not True.

    Bounded via ``run_bounded`` (two-stage timeout + grace; see its
    docstring for why the grace closes the asymmetric-abandon window)."""
    import secrets

    mine = secrets.randbits(31)

    def _gather():
        from distributed_tensorflow_tpu.utils.faults import fault_point
        from jax.experimental import multihost_utils

        # injection seam for the exit protocol: mode=error makes the
        # agreement fail (verdict None -> save skipped symmetrically);
        # mode=delay simulates the slow peer run_bounded's grace covers
        fault_point("exit_agreement", clean=clean)
        rows = multihost_utils.process_allgather(
            np.asarray([1 if clean else 0, mine], np.int32))
        rows = np.asarray(rows).reshape(-1, 2)
        return bool(np.all(rows[:, 0] > 0)), int(rows[0, 1])

    done, result = run_bounded(_gather, timeout_s, what="exit agreement")
    if not done:
        verdict, token = None, None
    elif isinstance(result, Exception):
        print(f"exit agreement failed: {result}")
        verdict, token = None, None
    else:
        verdict, token = result
    if not verdict:
        token = None
    if return_token:
        return verdict, (format(token, "08x") if token is not None else None)
    return verdict


def fetch_pytree(tree):
    """Pytree of arrays -> same-structure pytree of host ndarrays, the
    device->host transfers batched into one call.

    Collective whenever ``needs_collective_fetch(tree)`` — then EVERY
    process must call it with the same tree (checkpoint/eval paths vote on
    a step boundary first, training/loop._HostCoordinator)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, _fetch_leaves(leaves))


def _path_str(p) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def path_key(path) -> str:
    return "/".join(_path_str(p) for p in path)


def flatten_pytree(tree, *, tag_bf16: bool = False) -> dict[str, np.ndarray]:
    """Pytree -> {path_key: np.ndarray}. With ``tag_bf16``, bfloat16 leaves
    are stored as uint16 views under a tagged key (npz-safe).

    Collective when ``needs_collective_fetch(tree)``: leaves sharded across
    processes (a model axis spanning hosts) are gathered with
    ``process_allgather``, so every process must call this together —
    the coordinated-checkpoint protocol in training/supervisor.py. The
    device->host transfers for everything else batch into one call."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    fetched = _fetch_leaves([leaf for _, leaf in paths_leaves])
    flat = {}
    for (path, _), arr in zip(paths_leaves, fetched):
        key = path_key(path)
        if tag_bf16 and arr.dtype == jax.numpy.bfloat16:
            flat[_BF16_TAG + key] = arr.view(np.uint16)
        else:
            flat[key] = arr
    return flat


def unflatten_pytree(template, flat: dict[str, np.ndarray], *, check_shapes: bool = True):
    """{path_key: array} -> pytree with ``template``'s structure.

    Raises KeyError on missing keys and ValueError on shape mismatch (when
    ``check_shapes``); casts to the template leaf dtype."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths_leaves:
        key = path_key(path)
        if key in flat:
            arr = flat[key]
        elif _BF16_TAG + key in flat:
            arr = flat[_BF16_TAG + key].view(jax.numpy.bfloat16)
        else:
            raise KeyError(f"missing array for {key!r}")
        leaf_arr = np.asarray(leaf)
        if check_shapes and tuple(arr.shape) != tuple(leaf_arr.shape):
            raise ValueError(
                f"shape mismatch at {key!r}: got {arr.shape}, "
                f"expected {leaf_arr.shape}"
            )
        if arr.dtype != leaf_arr.dtype:
            arr = arr.astype(leaf_arr.dtype)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
