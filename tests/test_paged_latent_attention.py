"""The paged latent-attention kernel (ops/attention.py, ISSUE 28) against
the XLA form of the same function, in the interpreter on the CPU, at
``xing4_tiny``'s shapes (4 heads, a 128-wide leaf whose first 32 columns
are the values, blocks of 8, float32).

Every case draws a load of its own — slots in prefill (``n_new == C``),
in decode (``n_new == 1``), part-way (``1 < n_new < C``) and dead
(``n_new == 0``), fills on and off block edges — puts the slots' blocks
in shuffled places of the arena, leaves −1 and out-of-range ids in the
table behind each slot's live blocks, and fills every arena row that no
live position maps to with NaN: a kernel that read one of them as a key or
a value would hand the NaN on.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu.ops import _config
from apex_example_tpu.ops import attention as A

pytestmark = pytest.mark.serve

H, W, KR, BS, C = 4, 128, 32, 8, 8
SCALE = 0.37

# (fill, n_new) a slot
LOADS = {
    "mixed": [(0, 8), (13, 1), (16, 0), (40, 8), (5, 3), (63, 1)],
    "block_edges": [(8, 1), (7, 1), (16, 8), (15, 1), (24, 8), (0, 1)],
    "decode_only": [(1, 1), (30, 1), (47, 1), (9, 1)],
    "prefill_only": [(0, 8), (8, 8), (48, 8), (56, 8)],
    "part_lanes": [(3, 5), (20, 2), (33, 7), (0, 4)],
    "all_dead": [(12, 0), (0, 0), (40, 0)],
}
# (pages a compute tile, rows a row tile); None = the op's own choice
TILES = {"op_default": (None, None), "page_by_lane": (1, H),
         "two_pages_two_lanes": (2, 2 * H), "three_pages": (3, C * H)}


def _case(load, seed, max_blocks=9, num_blocks=64):
    rng = np.random.default_rng(seed)
    fill = np.asarray([f for f, _ in load], np.int32)
    n_new = np.asarray([n for _, n in load], np.int32)
    S = len(load)
    qf = rng.normal(size=(S, C, H, W)).astype(np.float32)
    qf[..., 40:] = 0.0                              # the pad lanes
    arena = rng.normal(size=(num_blocks, BS, W)).astype(np.float32)
    table = rng.choice([-1, num_blocks, num_blocks + 7, 3],
                       size=(S, max_blocks)).astype(np.int32)
    blocks = np.where(n_new > 0, -(-(fill + n_new) // BS), 0)
    place = rng.permutation(num_blocks)
    live = np.zeros((num_blocks, BS), bool)
    at = 0
    for s in range(S):
        table[s, :blocks[s]] = place[at:at + blocks[s]]
        at += blocks[s]
        if n_new[s]:
            for pos in range(fill[s] + n_new[s]):
                live[table[s, pos // BS], pos % BS] = True
    zeroed, poisoned = arena.copy(), arena.copy()
    zeroed[~live], poisoned[~live] = 0.0, np.nan
    args = tuple(jnp.asarray(a) for a in (table, fill, n_new))
    return jnp.asarray(qf), jnp.asarray(zeroed), jnp.asarray(poisoned), \
        args, blocks


@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("load", sorted(LOADS))
def test_kernel_is_the_gather_path_and_never_reads_a_stale_row(load, tiles):
    qf, zeroed, poisoned, args, blocks = _case(
        LOADS[load], seed=sorted(LOADS).index(load))
    want, walked_all = A.paged_latent_attention_reference(
        qf, zeroed, *args, SCALE, KR)
    pages, row_tile = TILES[tiles]
    got, walked = jax.jit(lambda q, a, *rest: A._paged_latent_pallas(
        q, a, *rest, SCALE, KR, True, pages=pages, row_tile=row_tile))(
            qf, poisoned, *args)
    got = np.asarray(got)
    assert got.shape == (len(blocks), C, H, KR) and got.dtype == np.float32
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # lanes past n_new and dead slots: zeros, on both forms
    dead = np.arange(C)[None, :] >= np.asarray(args[2])[:, None]
    assert not got[dead].any() and not np.asarray(want)[dead].any()
    # the kernel walked each slot's live blocks and no other; the XLA form
    # every position of every slot
    assert np.asarray(walked).tolist() == (blocks * BS).tolist()
    assert np.asarray(walked_all).tolist() == [9 * BS] * len(blocks)


def test_live_rows_see_their_own_position_and_nothing_later():
    """Lane j of a slot attends positions <= fill + j: moving a later
    position's row moves no earlier lane's result."""
    qf, zeroed, _, args, _ = _case([(10, 8)], seed=7)
    table = np.asarray(args[0])
    run = jax.jit(lambda a: A._paged_latent_pallas(
        qf, a, *args, SCALE, KR, True, pages=1, row_tile=H)[0])
    base = np.asarray(run(zeroed))
    pos = 14                                       # lane 4's own position
    moved = zeroed.at[table[0, pos // BS], pos % BS].add(1.0)
    after = np.asarray(run(moved))
    assert np.array_equal(after[0, :4], base[0, :4])
    assert np.abs(after[0, 4:] - base[0, 4:]).max() > 1e-3


@pytest.mark.parametrize("path", ["kernel", "force_xla", "no_interpreter"])
def test_dispatch_is_the_backends(path, monkeypatch):
    """One op, no flag: the kernel under the interpreter (as on the TPU),
    the XLA form under FORCE_XLA and on a backend that is neither."""
    qf, zeroed, _, args, blocks = _case(LOADS["mixed"], seed=3)
    if path == "no_interpreter":
        monkeypatch.setattr(_config, "INTERPRET", False)
    with _config.force_xla(path == "force_xla"):
        ol, walked = A.paged_latent_attention(qf, zeroed, *args, scale=SCALE,
                                              kr=KR)
    want, _ = A.paged_latent_attention_reference(qf, zeroed, *args, SCALE,
                                                 KR)
    np.testing.assert_allclose(ol, want, atol=2e-5)
    expect = blocks * BS if path == "kernel" else np.full(len(blocks), 72)
    assert np.asarray(walked).tolist() == expect.tolist()
