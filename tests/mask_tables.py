"""The fused attention kernels' schedule (``Mask.live_tiles``) held to the
dense mask, for the tests of each mask (``test_flash_kernel.py``,
``test_layer_plan.py``, ``test_routed_diffusion.py``)."""

import numpy as np

from distributed_tensorflow_tpu.ops.attention import FIRST, LAST, MASKED


def dense_tiles(mask, seq, tq, tk):
    """(some, every) of ``mask.allowed`` cut into (tq, tk) tiles: whether a
    tile holds a pair that attends, and whether all of its pairs do. A
    strip of query rows at a time: the cells' 8,192 rows squared are not
    held at once."""
    keys = np.arange(seq)[None, :]
    strips = [np.asarray(mask.allowed(np.arange(q0, q0 + tq)[:, None], keys))
              .reshape(tq, seq // tk, tk) for q0 in range(0, seq, tq)]
    return (np.stack([s.any(axis=(0, 2)) for s in strips]),
            np.stack([s.all(axis=(0, 2)) for s in strips]))


def assert_tables_follow(mask, seq, tq, tk):
    """Both tables list exactly the tiles in which the dense mask has a
    pair, once each: the forward's by row with key tiles ascending, the
    backward's by column with query tiles ascending; one ``FIRST`` and one
    ``LAST`` flag a row (a column), on its first and last step; ``MASKED``
    off where the dense tile is all true. Returns (live, masked) tiles."""
    some, every = dense_tiles(mask, seq, tq, tk)
    assert some.any(axis=1).all() and some.any(axis=0).all()
    for key_major in (False, True):
        qi, kj, flags = mask.live_tiles(seq, tq, tk, key_major)
        assert qi.dtype == kj.dtype == flags.dtype == np.int32
        outer, inner = (kj, qi) if key_major else (qi, kj)
        # row major order of the live tiles: rows in order, each ascending
        want = np.argwhere(some.T if key_major else some)
        assert np.array_equal(np.stack([outer, inner], axis=1), want)
        assert np.array_equal(flags & MASKED == 0, every[qi, kj])
        for row in range(want[-1, 0] + 1):
            steps = flags[outer == row]
            assert list(np.flatnonzero(steps & FIRST)) == [0]
            assert list(np.flatnonzero(steps & LAST)) == [steps.size - 1]
        assert not np.any(flags & ~(MASKED | FIRST | LAST))
        assert outer.size == mask.tiles_run(seq, tq, tk) == some.sum()
    return int(some.sum()), int((some & ~every).sum())
