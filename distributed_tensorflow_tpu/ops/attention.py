"""Attention ops: dense multi-head attention, single-chip blockwise
(flash) attention, and RING attention for sequence/context parallelism.

The reference framework predates attention entirely — this module is the
build's long-context extension, designed TPU-first. All three are the same
mathematics; the blockwise and ring forms share the flash/online-softmax
recurrence (``_online_softmax_step``) and its backward
(``_flash_bwd_block``).

- ``blockwise_attention`` is the single-chip path (``--attn_block``). On a
  TPU, for bf16 causal self-attention at 128-aligned shapes, each (query
  tile, key tile) pair is computed inside one fused Pallas kernel
  (``ops/flash_attention.py``): scores, probabilities, ``dp`` and ``ds``
  live in VMEM, the tiles above the diagonal are skipped, and only q, k,
  v, o, the row statistics and the three gradients cross HBM. Everything
  else — other backends, f32 operands, tiny or ragged shapes — runs the
  same recurrence as a ``lax.scan`` over key blocks with all query rows at
  once, whose (B, H, Sq, block) panels XLA writes to memory (on a v5e that
  scan ran at the memory roofline of its panels, PERF.md §6 PR 26). The
  choice is made from the shapes and the lowering platform, never by a
  flag; the scan is the form the tests hold the kernels to.
- ``ring_attention``: the sequence axis is sharded over a mesh axis and
  the key/value blocks ROTATE around the ring with ``lax.ppermute`` (one
  ICI hop per step) while each device's queries accumulate the
  streaming-softmax statistics blockwise. Peak activation memory per
  device is one (q, k, v) block regardless of total sequence length, and
  the collective traffic rides neighbor-to-neighbor ICI links — the layout
  "How to Scale Your Model"-style context parallelism wants. It runs the
  scan form of the block step (no benchmark cell runs it).

Both flash forms carry a custom VJP (plain autodiff of the forward scan
would save every block's probability panel). Equivalence with dense
attention (fwd and grads) is pinned by tests/test_attention.py,
tests/test_lm.py and, for the kernels, tests/test_flash_kernel.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distributed_tensorflow_tpu.utils.profiling import (
    lowering_instant,
    scope,
    scoped,
)

# a finite stand-in for -inf in the masks that can leave a query row with
# no visible key inside one key block: exp(MASK_VALUE - m) is an exact 0
# and no inf - inf can arise (the causal scan keeps -inf: its first block
# shows every row key 0)
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# the flags of a step of ``Mask.live_tiles``
MASKED, FIRST, LAST = 1, 2, 4


@dataclasses.dataclass(frozen=True)
class Mask:
    """Which (query, key) pairs attend, as a small static description from
    which every form derives what it needs: the dense mask
    (``allowed`` on index arrays), the scan's per-block mask (the same),
    and for the fused kernels the mask inside a tile (the same again) and
    their schedule (``live_tiles``: the tiles that run, in the order they
    fold, each marked masked or not; made on the host from ``tile``, the
    three-way test of a tile: every pair attends, some pair, none). The
    kernels' grids are as long as that list, whatever the kind.

    - ``Mask("causal")``: key j <= query i.
    - ``Mask("window", window=W)``: the causal window, query i sees the W
      keys ``i - W < j <= i``. A query tile reaches ``window // tk + 1`` key
      tiles or so, whatever the sequence's length.
    - ``Mask("block_diffusion", half=S, block=L)``: the sequence is
      ``[noised ; clean]``, 2 S rows whose position is ``i mod S`` and whose
      diffusion block is ``position // L``. Noised rows see the noised rows
      of their own block and the clean rows of earlier blocks; clean rows
      see the clean rows of their own and earlier blocks; no row sees a
      noised row of another block. S^2 + S L of the (2 S)^2 pairs.
    """

    kind: str = "causal"
    half: int = 0
    block: int = 0
    window: int = 0

    def __post_init__(self):
        if self.kind not in ("causal", "window", "block_diffusion"):
            raise ValueError(f"unknown attention mask {self.kind!r}")
        if (self.kind == "window") != (self.window > 0):
            raise ValueError(f"a window of {self.window} keys and the mask "
                             f"{self.kind!r} do not go together")
        if self.kind == "block_diffusion" and (
                self.half < 1 or self.block < 1 or self.half % self.block):
            raise ValueError(f"block diffusion needs blocks of {self.block} "
                             f"that divide the sequence of {self.half}")

    @property
    def fill(self) -> float:
        return -jnp.inf if self.kind == "causal" else MASK_VALUE

    def _block_start(self, pos):
        """First position of the diffusion block that holds ``pos``."""
        if self.block & (self.block - 1) == 0:
            return pos & -self.block  # no vector division in a kernel
        return pos - pos % self.block

    def allowed(self, queries, keys):
        """Boolean mask of broadcastable int index arrays."""
        if self.kind == "causal":
            return keys <= queries
        if self.kind == "window":
            return jnp.logical_and(keys <= queries,
                                   keys > queries - self.window)
        s = self.half
        q_clean, k_clean = queries >= s, keys >= s
        start = self._block_start(jnp.where(q_clean, queries - s, queries))
        kp = jnp.where(k_clean, keys - s, keys)
        # noised -> noised: its own block; noised -> clean: earlier blocks;
        # clean -> clean: its own and earlier blocks; clean -> noised: never
        hi = start + jnp.where(q_clean == k_clean, self.block, 0)
        lo = jnp.where(jnp.logical_or(q_clean, k_clean), 0, start)
        seen = jnp.logical_and(kp >= lo, kp < hi)
        return jnp.logical_and(
            seen, jnp.logical_not(jnp.logical_and(q_clean,
                                                  jnp.logical_not(k_clean))))

    def tile(self, q0, q1, k0, k1):
        """(visible, runs) of the tile of queries q0..q1 and keys k0..k1
        (inclusive; host integers or arrays): every pair attends / some pair
        does. A block-diffusion tile lies within one half on each side
        (``tiles_fit``)."""
        if self.kind == "causal":
            return k1 <= q0, k0 <= q1
        if self.kind == "window":
            w = self.window
            return (np.logical_and(k1 <= q0, k0 > q1 - w),
                    np.logical_and(k0 <= q1, k1 > q0 - w))
        s, lb = self.half, self.block
        q_clean, k_clean = q0 >= s, k0 >= s
        bq0 = np.where(q_clean, q0 - s, q0) // lb
        bq1 = np.where(q_clean, q1 - s, q1) // lb
        bk0 = np.where(k_clean, k0 - s, k0) // lb
        bk1 = np.where(k_clean, k1 - s, k1) // lb
        nn = np.logical_not(np.logical_or(q_clean, k_clean))
        same = q_clean == k_clean
        runs = np.where(nn, np.logical_and(bk0 <= bq1, bq0 <= bk1),
                        np.where(same, bk0 <= bq1, bk0 < bq1))
        visible = np.where(
            nn, np.logical_and(np.logical_and(bq0 == bq1, bk0 == bk1),
                               bq0 == bk0),
            np.where(same, bk1 <= bq0, bk1 < bq0))
        never = np.logical_and(q_clean, np.logical_not(k_clean))
        return (np.logical_and(visible, np.logical_not(never)),
                np.logical_and(runs, np.logical_not(never)))

    def tiles_fit(self, seq_len: int, tq: int, tk: int) -> bool:
        """Whether tiles of tq queries and tk keys suit the kernels."""
        if self.kind != "block_diffusion":
            return True
        return (seq_len == 2 * self.half and self.half % tq == 0
                and self.half % tk == 0
                and self.block & (self.block - 1) == 0)

    def live_tiles(self, seq_len: int, tq: int, tk: int,
                   key_major: bool = False) -> np.ndarray:
        """The fused kernels' schedule, made on the host: the (query tile,
        key tile) pairs of one head that run, as an int32 (3, n) table of
        query tile, key tile and flags, one column a grid step. Query tile
        major with key tiles ascending (the forward's fold order), or
        ``key_major`` with query tiles ascending (the backward's). The
        flags: ``MASKED`` where only some pairs of the tile attend (it folds
        under the mask inside the tile), ``FIRST`` / ``LAST`` on the first
        and the last step of a row (of a column, ``key_major``), where the
        kernels reset and write what they accumulate."""
        visible, runs = self._tiles(seq_len, tq, tk)
        if key_major:
            visible, runs = visible.T, runs.T
        outer, inner = np.nonzero(runs)  # row major: both ascending
        if not np.array_equal(np.unique(outer), np.arange(runs.shape[0])):
            # its result would never be written
            raise ValueError(f"{self} leaves a tile row or column of "
                             f"({seq_len}, {tq}, {tk}) with nothing to run")
        edge = np.flatnonzero(np.diff(outer)) + 1
        flags = np.where(visible[outer, inner], 0, MASKED)
        flags[np.r_[0, edge]] |= FIRST
        flags[np.r_[edge - 1, -1]] |= LAST
        qi, kj = (inner, outer) if key_major else (outer, inner)
        return np.stack([qi, kj, flags]).astype(np.int32)

    def tiles_run(self, seq_len: int, tq: int, tk: int) -> int:
        """How many (query tile, key tile) pairs run, of one head."""
        return int(np.sum(self._tiles(seq_len, tq, tk)[1]))

    def _tiles(self, seq_len, tq, tk):
        """``tile`` of every (query tile, key tile) of one head: two
        (S / tq, S / tk) boolean arrays."""
        q0 = np.arange(0, seq_len, tq)[:, None]
        k0 = np.arange(0, seq_len, tk)[None, :]
        return tuple(np.broadcast_to(x, (q0.size, k0.size)) for x in
                     self.tile(q0, q0 + tq - 1, k0, k0 + tk - 1))


CAUSAL = Mask("causal")


def _as_mask(causal, mask):
    """The ``mask`` argument, else the ``causal`` flag, as a ``Mask`` or
    None (every pair attends)."""
    if mask is not None:
        return mask
    return CAUSAL if causal else None


def _scope_of(mask):
    """The scope an attention under ``mask`` runs in: ``attention``, or the
    window layers' own beside it (never inside it)."""
    if mask is not None and mask.kind == "window":
        return scope("attention_window")
    return scope("attention")


def multi_head_attention(q, k, v, causal: bool = False, mask=None):
    """Dense (all-to-all) multi-head attention.

    q, k, v: (B, S, H, Dh) -> (B, S, H, Dh). f32 softmax statistics
    regardless of input dtype (bf16-safe). ``causal`` masks j > i (the
    autoregressive/LM form); ``mask`` (a ``Mask``) overrides it. k and v
    may have fewer heads than q (grouped-query attention): query head n
    reads key/value head n // (H // Hkv).
    """
    mask = _as_mask(causal, mask)
    with _scope_of(mask):
        dh = q.shape[-1]
        k, v = _repeat_kv(q, k, v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(dh))
        if mask is not None:
            sq, sk = s.shape[-2], s.shape[-1]
            s = jnp.where(mask.allowed(jnp.arange(sq)[:, None],
                                       jnp.arange(sk)[None, :]), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _repeat_kv(q, k, v):
    """k and v with every head repeated for the query heads that read it
    (grouped-query attention); as they are where the head counts agree."""
    h, hkv = q.shape[2], k.shape[2]
    if h == hkv:
        return k, v
    if h % hkv:
        raise ValueError(f"{h} query heads do not divide over {hkv} "
                         f"key/value heads")
    return (jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2))


def _online_softmax_step(qf, scale, o, m, l, k_blk, v_blk, mask,
                         fill=-jnp.inf):
    """Fold one k/v block into the streaming-softmax accumulators.

    The one implementation of the flash/online-softmax recurrence, shared
    by ``ring_attention`` (blocks arrive over ICI) and
    ``blockwise_attention`` (blocks are scanned locally): running max m,
    denominator l, unnormalized numerator o, all f32. ``mask`` (broadcast
    to (B, H, Sq, Skb)) or None; ``fill`` stands where it is false."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, fill)
    m_new = jnp.maximum(m, s.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return o, m_new, l


def _flash_bwd_block(qf, gf, dD, lse, scale, k_blk, v_blk, mask,
                     fill=-jnp.inf):
    """One k/v block of the flash backward — the single implementation
    both ``_blockwise_bwd`` (local scan) and ``_ring_bwd`` (ring hops)
    run, mirroring how ``_online_softmax_step`` is the one forward.

    With p = exp(s - lse) the row-exact softmax probs recomputed from
    the saved logsumexp, and D_i = sum_d(do_i * o_i): dv = p^T do,
    ds = p * (do @ v^T - D), dq_contrib = ds @ k * scale,
    dk = ds^T @ q * scale — the textbook softmax-through-attention
    transpose, one block at a time. Masked entries give p = 0 and drop
    out of every product. Returns (dq_contrib BQHD, dk_blk, dv_blk)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, fill)
    p = jnp.exp(s - lse[..., None])  # masked entries: exp(-inf) = 0
    dv_blk = jnp.einsum("bhqk,bhqd->bkhd", p, gf)
    dp = jnp.einsum("bhqd,bkhd->bhqk", gf, v_blk.astype(jnp.float32))
    ds = p * (dp - dD[..., None])
    dq_contrib = jnp.einsum("bhqk,bkhd->bqhd", ds,
                            k_blk.astype(jnp.float32)) * scale
    dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
    return dq_contrib, dk_blk, dv_blk


def blockwise_attention(q, k, v, block_size: int, causal: bool = False,
                        mask=None):
    """Single-device FLASH attention with O(S * block) peak memory —
    forward AND backward.

    Same math as ``multi_head_attention`` (pinned by tests), computed
    one key block at a time with the online-softmax recurrence — the
    full (Sq, Sk) score matrix never materializes. The backward pass is
    a CUSTOM VJP (the flash backward): plain autodiff of the forward
    would save each block's probability panel as a residual — O(Sq * Sk)
    total, no better than dense (measured: WORSE, round-4 sweep) — so
    instead only (q, k, v, out, logsumexp) are saved and each block's
    probabilities are RECOMPUTED from them while dq, dk and dv
    accumulate. Of the five, ``out`` and the logsumexp carry names
    (``REMAT_KEPT``) for a checkpoint policy: a rematerialized block
    (``--remat``) keeps those two and makes q, k, v again, so its backward
    pass does not run this forward a second time. This is the single-chip
    half of the long-context story; ``ring_attention`` is the same
    recurrence with blocks arriving over the mesh.

    Two implementations of that one algorithm, chosen by what the code
    can observe (``_pick``):

    - **fused** — causal bf16 self-attention with S and ``block_size``
      multiples of 128 (``fusable``), in a program
      lowered for a TPU: the Pallas kernels of ``ops/flash_attention.py``.
      A (query tile, ``block_size`` keys) panel lives in VMEM only,
      the tiles above the diagonal are never visited and the mask is
      applied only in the tiles the diagonal crosses. On a TPU at such
      shapes the kernel compiles or the run fails: there is no fallback.
    - **scan** — everything else (f32 operands, tiny or ragged shapes,
      non-causal, any backend but the TPU): a ``lax.scan`` over key
      blocks with all Sq query rows at once, so each step makes one
      (B, H, Sq, block) panel that XLA writes to memory. Blocks
      entirely above the diagonal still run here (static scan length)
      and contribute exact zeros. It is the form the tests hold the
      kernels to.

    ``causal=True`` masks by absolute position, identical to the dense
    triangle; ``mask`` (a ``Mask``) overrides it, and both forms take what
    they need from that one description (block diffusion: 80 of the 256
    tiles of 512 run at 2 S = 8,192). k and v may have fewer heads than q
    (grouped-query attention): the kernels' index maps send a group's
    query heads to its one key/value head, and dk, dv are summed over the
    group. Each pass records which implementation its program was
    lowered with (the ``attention_path`` telemetry instant).
    """
    sk = k.shape[1]
    if sk % block_size:
        raise ValueError(f"key length {sk} must divide into blocks of "
                         f"{block_size}")
    return _blockwise(q, k, v, int(block_size), _as_mask(causal, mask))


def _by_platform(fused, scan, *args):
    """``fused(*args)`` in a program lowered for a TPU, ``scan(*args)``
    in one lowered for anything else (both are traced, one is lowered:
    a described-TPU compile on a CPU host gets the kernel)."""
    return lax.platform_dependent(*args, tpu=fused, default=scan)


def fusable(q, k, v, block_size: int, causal=True) -> bool:
    """Whether these operands are the fused kernels' to take: masked
    (``causal``: True or a ``Mask``) bf16 self-attention (Sq = Sk) with S
    and the key tile multiples of the 128-lane tile, a head width of 64,
    128 or 256, key/value heads that divide the query heads, and tiles
    the mask can be cut into."""
    mask = _as_mask(causal, None) if isinstance(causal, bool) else causal
    b, s, h, dh = q.shape
    if mask is None or k.shape != v.shape or len(k.shape) != 4 \
            or k.shape != (b, s, k.shape[2], dh) or h % max(k.shape[2], 1):
        return False
    if not (q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and q.shape[1] % 128 == 0 and block_size % 128 == 0
            and q.shape[-1] in (64, 128, 256)):
        return False
    from distributed_tensorflow_tpu.ops import flash_attention

    return mask.tiles_fit(q.shape[1],
                          flash_attention.query_tile(q.shape[1], mask),
                          block_size)


def _pick(pass_name, scan, q, k, v, block_size, mask, *rest):
    """Run one pass (``forward`` / ``backward``) of blockwise attention
    through the implementation its shapes and lowering platform select,
    marked with the ``attention_path`` instant."""
    s = q.shape[1]
    note = {"pass": pass_name, "seq_len": s, "k_tile": block_size,
            "dtype": q.dtype.name}
    if mask is not None and mask.kind != "causal":
        # told apart in the record only where there is something to tell
        note.update(mask=mask.kind, kv_heads=k.shape[2])
    if mask is not None and mask.kind == "window":
        note.update(window=mask.window)

    def run_scan(q, *xs):
        q = lowering_instant("attention_path", q, path="scan", q_tile=s,
                             **note)
        return scan(q, *xs, block_size, mask)

    if not fusable(q, k, v, block_size, mask):
        return run_scan(q, k, v, *rest)
    # imports Pallas: only a program that can take the kernels pays for it
    from distributed_tensorflow_tpu.ops import flash_attention

    fused = {"forward": flash_attention.flash_forward,
             "backward": flash_attention.flash_backward}[pass_name]

    def run_fused(q, *xs):
        tq = flash_attention.query_tile(s, mask)
        flags = mask.live_tiles(s, tq, block_size, pass_name == "backward")[2]
        # the tiles that run over the steps of the lowered pass's grid, a
        # head (the grid walks the live tiles only), and how many of them
        # pay for the mask inside the tile
        q = lowering_instant("attention_path", q, path="fused", q_tile=tq,
                             tiles_run=mask.tiles_run(s, tq, block_size),
                             grid_steps=flags.size,
                             masked_tiles=int(np.count_nonzero(flags & MASKED)),
                             **note)
        if mask.kind == "causal":  # today's call, and so today's trace
            return fused(q, *xs, block_size)
        return fused(q, *xs, block_size, mask)

    return _by_platform(run_fused, run_scan, q, k, v, *rest)


# jitted (here and the backward): every layer of a model traces both
# implementations, and shares one trace of each this way
@functools.partial(jax.jit, static_argnums=(3, 4))
def _scan_forward(q, k, v, block_size, mask):
    """Forward scan; returns (out BQHD in q.dtype, lse BHQ f32)."""
    b, sq, h, dh = q.shape
    k, v = _repeat_kv(q, k, v)
    sk = k.shape[1]
    n_blocks = sk // block_size
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    qf = q.astype(jnp.float32)
    rows = jnp.arange(sq)
    kb = jnp.moveaxis(k.reshape(b, n_blocks, block_size, h, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n_blocks, block_size, h, dh), 1, 0)

    def step(carry, inp):
        o, m, l = carry
        t, k_blk, v_blk = inp
        seen, fill = None, -jnp.inf
        if mask is not None:
            cols = t * block_size + jnp.arange(block_size)
            seen = mask.allowed(rows[:, None], cols[None, :])[None, None]
            fill = mask.fill
        o, m, l = _online_softmax_step(qf, scale, o, m, l, k_blk, v_blk,
                                       seen, fill)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l), _ = lax.scan(step, (o0, m0, l0),
                            (jnp.arange(n_blocks), kb, vb))
    o = o / l[..., None]
    lse = m + jnp.log(l)  # logsumexp per row: p_ij = exp(s_ij - lse_i)
    return jnp.einsum("bhqd->bqhd", o).astype(q.dtype), lse


def _forward(q, k, v, block_size, mask):
    """(out, lse) by the fused kernel or the scan."""
    with _scope_of(mask):
        return _pick("forward", _scan_forward, q, k, v, block_size, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blockwise(q, k, v, block_size, mask):
    return _forward(q, k, v, block_size, mask)[0]


# what a checkpoint policy may keep of a blockwise attention call, so that
# the backward pass of a rematerialized block does not run the forward
# (a whole kernel call) again: models/transformer.py:_remat keeps these
REMAT_KEPT = ("attention_out", "attention_lse")


def _blockwise_fwd(q, k, v, block_size, mask):
    out, lse = _forward(q, k, v, block_size, mask)
    # named once, in the form the backward reads ((B, S, H, Dh) and
    # (B, H, S)): not also the kernel's (H B, Dh, S) results under the
    # copies, or a layer would keep two buffers
    out, lse = map(checkpoint_name, (out, lse), REMAT_KEPT)
    return out, (q, k, v, out, lse)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _scan_backward(q, k, v, out, lse, g, block_size, mask):
    """The flash backward: one scan over k/v blocks, each block's
    probability panel recomputed from (q, lse) — never all at once.

    With p = softmax row-normalized probs, o = p @ v, and row constant
    D_i = sum_d(do_i * o_i): dv_j = p^T do, ds = p * (do @ v_j^T - D),
    dq += ds @ k_j * scale, dk_j = ds^T @ q * scale — the textbook
    softmax-through-attention transpose, evaluated blockwise. Exactness
    vs dense autodiff is pinned by tests/test_lm.py (values AND grads).
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    k, v = _repeat_kv(q, k, v)
    sk = k.shape[1]
    n_blocks = sk // block_size
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    qf = q.astype(jnp.float32)
    gf = jnp.einsum("bqhd->bhqd", g.astype(jnp.float32))
    rows = jnp.arange(sq)
    dD = jnp.einsum("bhqd,bqhd->bhq", gf, out.astype(jnp.float32))
    kb = jnp.moveaxis(k.reshape(b, n_blocks, block_size, h, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n_blocks, block_size, h, dh), 1, 0)

    def step(dq, inp):
        t, k_blk, v_blk = inp
        seen, fill = None, -jnp.inf
        if mask is not None:
            cols = t * block_size + jnp.arange(block_size)
            seen = mask.allowed(rows[:, None], cols[None, :])[None, None]
            fill = mask.fill
        dq_c, dk_blk, dv_blk = _flash_bwd_block(
            qf, gf, dD, lse, scale, k_blk, v_blk, seen, fill)
        return dq + dq_c, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    dq, (dkb, dvb) = lax.scan(step, dq0, (jnp.arange(n_blocks), kb, vb))
    dk = jnp.moveaxis(dkb, 0, 1).reshape(b, sk, h, dh)
    dv = jnp.moveaxis(dvb, 0, 1).reshape(b, sk, h, dh)
    if hkv != h:  # a key/value head's gradient: the sum over its group
        dk = dk.reshape(b, sk, hkv, h // hkv, dh).sum(axis=3)
        dv = dv.reshape(b, sk, hkv, h // hkv, dh).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _blockwise_bwd(block_size, mask, res, g):
    """(dq, dk, dv) by the fused kernel or the scan, from the residuals
    both forwards save in one form: (q, k, v, out, logsumexp)."""
    q, k, v, out, lse = res
    with _scope_of(mask):
        return _pick("backward", _scan_backward, q, k, v, block_size, mask,
                     out, lse, g)


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Ring attention over the mesh axis ``axis_name`` (sequence-sharded).

    Call INSIDE shard_map with the sequence dimension of q/k/v sharded
    over ``axis_name``: q, k, v are the LOCAL blocks (B, S/P, H, Dh).
    Each of the P ring steps attends the local queries against the
    currently-held k/v block, folds the result into the online-softmax
    accumulators (running max m, denominator l, numerator o), and passes
    the k/v block to the next device (``ppermute``; P-1 hops — the local
    block is consumed before the scan). After P steps every query has
    seen every key exactly once; the result equals dense attention over
    the gathered sequence (tested to fp tolerance).

    The backward pass is a CUSTOM VJP — the DISTRIBUTED flash backward.
    Plain autodiff of the forward scan saves each ring step's
    (B, H, Sq/P, Sk/P) probability panel as a residual (O(S_local *
    S_global) per device — the memory the ring exists to avoid); instead
    only (q, k, v, o, logsumexp) are saved per shard and the backward
    RE-ROTATES k/v around the ring, recomputing each block's panel and
    accumulating dq locally while (dk, dv) accumulators ride the ring
    WITH their blocks — P hops (one more than forward) so each block's
    gradient arrives back at its owner with every shard's contribution.
    Exactness vs dense autodiff is pinned by tests/test_attention.py and
    tests/test_lm.py (SP == dense trajectories).

    ``causal=True`` masks by GLOBAL token position: at ring step t this
    device holds the k/v block of shard (me - t) mod P, so the mask
    compares (my_shard * Sq + i) against (owner * Sk + j) — the
    blockwise form of the LM triangle. Attending the local block first
    guarantees the running max is finite from step one (the diagonal is
    never masked), so fully-masked later blocks contribute exact zeros.
    """
    return _ring(q, k, v, axis_name, bool(causal))


def _ring_mask(causal, owner, sk_blk, row_global):
    if not causal:
        return None
    col_global = owner * sk_blk + jnp.arange(sk_blk)
    return (col_global[None, :] <= row_global[:, None])[None, None]


@scoped("attention")
def _ring_forward(q, k, v, axis_name, causal):
    """Forward ring; returns (out BQHD q.dtype, o_f32 BHQD, lse BHQ)."""
    p_size = lax.axis_size(axis_name)
    dh = q.shape[-1]
    b, sq, h, _ = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    # accumulate in f32: the online-softmax recurrence is exact in exact
    # arithmetic; f32 keeps the rescaling stable for bf16 inputs
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    # axis_index only when the causal mask needs global positions: a
    # non-causal ring never reads it, so it stays out of the lowered
    # module
    me = lax.axis_index(axis_name) if causal else jnp.int32(0)
    row_global = me * sq + jnp.arange(sq)  # my queries' global positions

    def attend(o, m, l, k_blk, v_blk, owner):
        mask = _ring_mask(causal, owner, k_blk.shape[1], row_global)
        return _online_softmax_step(qf, scale, o, m, l, k_blk, v_blk, mask)

    def ring_step(carry, t):
        # DOUBLE-BUFFERED rotation: issue hop t+1's ppermute BEFORE
        # consuming block t, so the collective has no consumer until the
        # next iteration and XLA's async collective-permute overlaps it
        # with this step's attend — the hop leaves the critical path
        # (ICI hops are cheap; --sp_span_hosts DCN hops are the ones
        # this hides). After t rotations this device holds the block
        # ORIGINALLY owned by shard (me - t) mod P; accumulator math is
        # identical to the rotate-then-attend form (same blocks, same
        # order — trajectory-pinned by the SP tests).
        o, m, l, k_cur, v_cur = carry
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        o, m, l = attend(o, m, l, k_cur, v_cur, (me - t) % p_size)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    # P-1 scan iterations, each with one (prefetch) hop; the LAST block
    # is consumed outside so no trailing rotation is compiled. Step 0
    # attends the local block (owner = me) — causal masking needs it
    # first so the running max is finite from the start.
    (o, m, l, k_last, v_last), _ = lax.scan(
        ring_step, (o0, m0, l0, k, v), jnp.arange(p_size - 1))
    o, m, l = attend(o, m, l, k_last, v_last, (me - (p_size - 1)) % p_size)
    o = o / l[..., None]
    lse = m + jnp.log(l)
    out = jnp.einsum("bhqd->bqhd", o).astype(q.dtype)
    return out, o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring(q, k, v, axis_name, causal):
    return _ring_forward(q, k, v, axis_name, causal)[0]


def _ring_fwd(q, k, v, axis_name, causal):
    out, o, lse = _ring_forward(q, k, v, axis_name, causal)
    return out, (q, k, v, o, lse)


@scoped("attention")
def _ring_bwd(axis_name, causal, res, g):
    """Distributed flash backward.

    Same per-block math as ``_blockwise_bwd`` (p recomputed from lse,
    ds = p * (do @ v^T - D), dq/dk/dv contractions), with block traffic
    on the ring: step t attends the block of owner (me - t) mod P —
    attend-THEN-rotate, so the local block is step 0 and after the final
    attend one more rotation runs, P hops total, which is exactly what
    brings each block's (k, v, dk, dv) home to its owner with every
    shard's accumulated contribution."""
    q, k, v, o, lse = res
    p_size = lax.axis_size(axis_name)
    b, sq, h, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))
    qf = q.astype(jnp.float32)
    gf = jnp.einsum("bqhd->bhqd", g.astype(jnp.float32))
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    # same dead-PartitionId gate as the forward ring
    me = lax.axis_index(axis_name) if causal else jnp.int32(0)
    row_global = me * sq + jnp.arange(sq)
    dD = jnp.sum(gf * o, axis=-1)  # (B, H, Sq)

    def step(carry, t):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        owner = (me - t) % p_size
        mask = _ring_mask(causal, owner, k_cur.shape[1], row_global)
        # half-double-buffered: the k/v prefetch hops are issued BEFORE
        # the block compute (no consumer until next step — XLA overlaps
        # them with _flash_bwd_block), halving the permute bytes left on
        # the critical path. dk/dv genuinely depend on this step's
        # output, so their hops follow the compute — they ride the ring
        # WITH their blocks and arrive home after P hops regardless.
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dq_c, dk_blk, dv_blk = _flash_bwd_block(
            qf, gf, dD, lse, scale, k_cur, v_cur, mask)
        dq = dq + dq_c
        dk_cur = lax.ppermute(dk_cur + dk_blk, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur + dv_blk, axis_name, perm)
        return (dq, k_nxt, v_nxt, dk_cur, dv_cur), None

    dq0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    z = jnp.zeros((b, k.shape[1], h, dh), jnp.float32)
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, z, z), jnp.arange(p_size))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_fwd, _ring_bwd)
