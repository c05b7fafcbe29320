"""The block-paged cache: its layout, a serve tick's three device
operations on it, and what a pool may know of a cache tree.

**Layout.**  A served model caches in *arena leaves* of the flax ``cache``
collection, one set a layer, shared by every request slot through the
host's per-slot block tables (serve/slots.BlockPool).  A *payload* leaf is
``[num_blocks, block_size, W]`` with whatever the model keeps a token in
ONE merged last dimension (heads times head size; a latent's rank plus
rotary size); stored low-bit it has a *scale table* ``[num_blocks,
block_size]`` beside it (quant/kv.py).  One merged dimension, so that the
COW block copy, the per-token write (through the flat ``[num_blocks *
block_size, W]`` view, a bitcast of the same tiles) and the block gather
all index the leading dimension of one tiled layout: with ``[.., H, D]``
each gets a tiling of its own on the TPU and XLA converts the whole arena
between every pair.  One layout and a donated cache (serve/engine.py):
the tick updates the arena in place (tests/test_arena_inplace.py).

**Device operations** (plain functions called inside the models' attention
layers, so a leaf's path is the calling module's): :func:`cow`,
:func:`write_rows` + :func:`write`, :func:`gather`, each under the scope
the device trace reads it by (benchmarks/layer_metrics/
kv_relayout_time_pct.py).  What a model does to its rows on the way
(quantize, pad, dequantize) is the model's, under the same scope name.

**The second kind: per-slot leaves.**  A recurrent layer keeps a state of
fixed size for each request *slot* (a state-space layer's ``[H, P, N]``
state and its few convolution rows): ``[num_slots, ...]``, row ``s`` slot
``s``'s.  It is not paged, cannot be shared by block and is not addressed
through the block table; the model resets it inside the tick where a slot
starts (``fill == 0``), and a handoff carries the slot's row beside its
blocks.  Such a leaf is *declared* where it is created
(:func:`slot_variable`: the declaration is the key the leaf is stored
under) and never recognised by its shape, which may well coincide with a
block leaf's (``num_slots == num_blocks``).  A cache tree that holds one
cannot share prefixes: the state at a prefix boundary is held nowhere
(serve/slots.BlockPool reads :func:`slot_leaves` for that).

**The third kind: window leaves.**  A layer that attends a sliding window
of ``W`` positions needs a slot's last ``W`` tokens and never an older one,
so its payload leaves live in a second, smaller arena of their own:
``[num_slots * ring_blocks, block_size, W']`` with ``ring_blocks =
ceil((W + chunk) / block_size) + 1`` (:func:`ring_blocks`: the window, the
tick's write span and one block to turn over), addressed through a second
per-slot table ``[num_slots, ring_blocks]`` in which logical block ``j``
stands in column ``j mod ring_blocks``.  The host hands a block back while
the request still runs, once the slot's fill has passed it by a window
(serve/slots.BlockPool).  Declared where it is created
(:func:`window_variable`; the key carries the window), as a per-slot leaf
is: its shape may coincide with a block leaf's.  It is written through the
ring (:func:`write_rows` with ``ring=True``), read where it lies by the
paged kernel (ops/attention.paged_gqa_attention) and never gathered whole;
it has no copy-on-write (nothing of it is shared) and no scale table.

The kinds, then: PAYLOAD and SCALE (block leaves, by shape), PER_SLOT and
WINDOW (declared).

**The pool's side**: :func:`block_leaves`, :func:`slot_leaves`,
:func:`window_leaves`, :func:`shard`, :func:`extract`, :func:`insert`.
That discovery of block leaves goes by shape is private to this module.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAYLOAD, SCALE, PER_SLOT, WINDOW = "payload", "scale", "per_slot", "window"
# a per-slot leaf's key in its module's ``cache`` dict: the declaration
_SLOT_KEY = "slot:"
# a window leaf's: ``window:<W>:<name>``
_WINDOW_KEY = "window:"


def lane_tiles(width: int) -> int:
    """``width`` in whole 128-lane tiles.  Memory is padded to them either
    way, but at a logical width that is not one XLA gives the arena one
    layout coming into the tick and another going out and copies it twice
    a layer (AOT compile for v5e, PR 27).  Pad lanes hold zeros."""
    return -(-width // 128) * 128


def variable(module, name: str, num_blocks: int, block_size: int, dtype,
             width: Optional[int] = None):
    """``module``'s ``cache`` variable ``name`` (called inside its
    ``nn.compact`` method, so the leaf lies at the module's own path): a
    zeroed payload leaf, or with no ``width`` a scale table."""
    if num_blocks < 1 or block_size < 1:
        raise ValueError(
            "slot_decode is block-paged: clone the model with "
            "kv_num_blocks/kv_block_size >= 1 "
            f"(got {num_blocks}/{block_size})")
    if name.startswith((_SLOT_KEY, _WINDOW_KEY)):
        raise ValueError(f"{name!r}: the {_SLOT_KEY!r} and {_WINDOW_KEY!r} "
                         "keys are the per-slot and the window leaves' "
                         "(slot_variable, window_variable)")
    shape = (num_blocks, block_size) + (() if width is None else (width,))
    return module.variable("cache", name, jnp.zeros, shape, dtype)


def ring_blocks(window: int, block_size: int) -> int:
    """Blocks a slot's ring holds for a window of ``window`` positions and
    a tick that writes up to ``block_size`` (the engine's prefill chunk):
    what a lane may see and the tick writes, in whole blocks, and one more
    to turn over."""
    return -(-window // block_size) + 2


def window_variable(module, name: str, num_slots: int, window: int,
                    block_size: int, dtype, width: int):
    """``module``'s window payload leaf ``name``: zeroed ``[num_slots *
    ring_blocks, block_size, width]``, declared by the key it is stored
    under, which carries ``window``."""
    if window < 1 or block_size < 1:
        raise ValueError(f"a window leaf needs window and block_size >= 1 "
                         f"(got {window}/{block_size})")
    shape = (num_slots * ring_blocks(window, block_size), block_size, width)
    return module.variable("cache", f"{_WINDOW_KEY}{window}:{name}",
                           jnp.zeros, shape, dtype)


def has_window_variable(module, name: str, window: int) -> bool:
    """Does ``module`` hold the window leaf already (a tick), or is this
    the init trace that allocates it?"""
    return module.has_variable("cache", f"{_WINDOW_KEY}{window}:{name}")


def slot_variable(module, name: str, num_slots: int, shape: Tuple[int, ...],
                  dtype):
    """``module``'s per-slot ``cache`` variable ``name``: a zeroed
    ``[num_slots, *shape]`` leaf, row ``s`` the state slot ``s`` carries
    from tick to tick.  Declared per-slot by the key it is stored under;
    the model zeroes a slot's row itself where the slot starts."""
    return module.variable("cache", _SLOT_KEY + name, jnp.zeros,
                           (num_slots,) + tuple(shape), dtype)


def has_slot_variable(module, name: str) -> bool:
    """Does ``module`` hold the per-slot leaf ``name`` already (a tick), or
    is this the init trace that allocates it?"""
    return module.has_variable("cache", _SLOT_KEY + name)


# ------------------------------------------------------- device operations
# ``leaves``: one arena leaf or a pytree of them (a layer's K and V); the
# indices they share are computed once.  ``constrain``: what a TP caller
# pins a payload leaf with after every update (heads over 'model'), so that
# GSPMD does not gather the arena through the chain of in-place updates.

def cow(leaves, cow_src, cow_dst, constrain: Optional[Callable] = None):
    """Copy-on-write: block ``cow_src[s]`` onto ``cow_dst[s]`` for every
    slot whose next write lands in a shared block; ``cow_dst < 0`` (no
    copy) indexes row ``num_blocks`` and drops.  Scale tables are copied
    too, or the copy would dequantize under a fresh block's zero scales."""
    NB = jax.tree_util.tree_leaves(leaves)[0].shape[0]
    with jax.named_scope("kv_cow"):
        src = jnp.clip(cow_src, 0, NB - 1)
        dst = jnp.where(cow_dst >= 0, cow_dst, NB)

        def copied(leaf):
            out = leaf.at[dst].set(leaf[src], mode="drop")
            return out if constrain is None else constrain(out)

        return jax.tree_util.tree_map(copied, leaves)


def write_rows(table, pos, n_new, num_blocks: int, block_size: int,
               ring: bool = False):
    """Flat arena rows ``[S * C]`` of this tick's tokens: lane ``j`` of
    slot ``s``, at logical position ``pos[s, j]``, is row ``table[s, pos //
    block_size] * block_size + pos % block_size``.  Lanes past ``n_new[s]``
    get row ``num_blocks * block_size`` and drop; the host maps only
    exclusively owned blocks over a write span.  With ``ring`` the table
    is a window leaf's: logical block ``j`` stands in column ``j mod
    table.shape[1]`` (``num_blocks`` then the window arena's)."""
    with jax.named_scope("kv_write"):
        col = (pos // block_size) % table.shape[1] if ring \
            else jnp.clip(pos // block_size, 0, table.shape[1] - 1)
        blk = jnp.take_along_axis(table, col, axis=1)
        flat = blk * block_size + pos % block_size
        valid = jnp.arange(pos.shape[1])[None, :] < n_new[:, None]
        return jnp.where(valid, flat, num_blocks * block_size).reshape(-1)


def write(leaves, flat, rows, constrain: Optional[Callable] = None):
    """``rows`` (a tree like ``leaves``, each ``[S, C, ...]`` with what
    follows merged into its leaf's width) written at ``flat`` through each
    leaf's flat ``[num_blocks * block_size, ...]`` view."""
    def written(leaf, new):
        tail = leaf.shape[2:]
        out = leaf.reshape((-1,) + tail).at[flat].set(
            new.reshape((-1,) + tail), mode="drop").reshape(leaf.shape)
        return out if constrain is None else constrain(out)

    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map(written, leaves, rows)


def gather(leaves, table, heads: Optional[int] = None):
    """Every slot's whole row of the block table as its logical view:
    ``[S, L, W]`` (``[S, L]`` of a scale table), or with ``heads`` the
    merged dimension split again, ``[S, L, heads, W // heads]``.  Rows
    past a slot's fill are stale or unwritten; the caller masks them."""
    NB = jax.tree_util.tree_leaves(leaves)[0].shape[0]

    def view(leaf):
        tail = leaf.shape[2:] if heads is None \
            else (heads, leaf.shape[2] // heads)
        return leaf[tbl].reshape((table.shape[0], -1) + tail)

    with jax.named_scope("kv_gather"):
        tbl = jnp.clip(table, 0, NB - 1)
        return jax.tree_util.tree_map(view, leaves)


# ------------------------------------------------------------ pool's side

def _path_str(path) -> str:
    return "/".join(getattr(p, "key", getattr(p, "name", str(p)))
                    for p in path)


def _declared(path, prefix: str) -> bool:
    return bool(path) and str(getattr(path[-1], "key", "")).startswith(
        prefix)


def _declared_per_slot(path) -> bool:
    return _declared(path, _SLOT_KEY)


def _kind(path, leaf, num_blocks: int, block_size: int) -> Optional[str]:
    """A per-slot or a window leaf by its declaration (the key
    ``slot_variable`` or ``window_variable`` stored it under); a block leaf
    by shape alone: the first two dimensions are the geometry's, three
    dimensions a payload, two a scale table."""
    if _declared_per_slot(path):
        return PER_SLOT
    if _declared(path, _WINDOW_KEY):
        return WINDOW
    if leaf.shape[:2] != (num_blocks, block_size):
        return None
    return {3: PAYLOAD, 2: SCALE}.get(leaf.ndim)


def block_leaves(cache, num_blocks: int,
                 block_size: int) -> List[Tuple[str, object, str]]:
    """``(path, leaf, kind)`` of every block-resident leaf of a cache
    tree, ``kind`` PAYLOAD or SCALE; ``path`` as one string
    (``layer_0/attention/cached_key``), the key a handoff payload carries
    the leaf under on both sides of the transport."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        kind = _kind(path, leaf, num_blocks, block_size)
        if kind in (PAYLOAD, SCALE):
            out.append((_path_str(path), leaf, kind))
    return out


def slot_leaves(cache) -> List[Tuple[str, object]]:
    """``(path, leaf)`` of every per-slot leaf of a cache tree (none for a
    model of attention layers alone)."""
    return [(_path_str(path), leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(cache)[0]
            if _declared_per_slot(path)]


def window_leaves(cache) -> List[Tuple[str, object, int]]:
    """``(path, leaf, window)`` of every window leaf of a cache tree (none
    for a model whose layers all attend their whole context)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if _declared(path, _WINDOW_KEY):
            window = int(str(path[-1].key).split(":")[1])
            out.append((_path_str(path), leaf, window))
    return out


def _sharding(mesh, kind: Optional[str]):
    """Heads are the outer factor of a payload's merged dimension, so a
    shard over 'model' holds whole heads (the dense decode cache's split
    under TP); scale tables, per-slot leaves and anything else replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_example_tpu.parallel.mesh import MODEL_AXIS
    return NamedSharding(
        mesh, P(None, None, MODEL_AXIS) if kind == PAYLOAD else P())


def shard(cache, mesh, num_blocks: int, block_size: int):
    """The cache tree placed on ``mesh``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.device_put(leaf, _sharding(
            mesh, _kind(path, leaf, num_blocks, block_size))), cache)


def extract(cache, block_ids, num_blocks: int, block_size: int,
            slot: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Blocks ``block_ids`` of every block-resident leaf as host arrays
    in the leaf's STORAGE dtype (a handoff moves the low-bit bytes), keyed
    by path; with ``slot``, that slot's row ``[1, ...]`` of every per-slot
    leaf as well, so that a request's state travels with its blocks.
    ``np.array``, not ``np.asarray``: an owned, writable copy that does
    not pin the gather's buffer across the transport."""
    ids = jnp.asarray(np.ascontiguousarray(block_ids))
    out = {path: np.array(leaf[ids])
           for path, leaf, _ in block_leaves(cache, num_blocks, block_size)}
    if slot is not None:
        out.update((path, np.array(leaf[slot:slot + 1]))
                   for path, leaf in slot_leaves(cache))
    return out


@functools.lru_cache(maxsize=8)
def _fused_block_scatter(shapes):
    """ONE jitted scatter for every leaf of a handoff payload, cached per
    geometry: blocks at ``idx`` of the block leaves and, where the tree has
    per-slot leaves, row ``slot`` of those.  Pad lanes are out of range and
    drop.  The leaves are DONATED: an admission writes a few blocks in
    place."""
    del shapes                        # cache key only; shapes ride args

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(leaves, idx, rows, slot, state_rows):
        n = len(rows)
        return tuple(l.at[idx].set(r, mode="drop")
                     for l, r in zip(leaves[:n], rows)) \
            + tuple(l.at[slot].set(r, mode="drop")
                    for l, r in zip(leaves[n:], state_rows))

    return scatter


def insert(cache, block_ids: Sequence[int], payload: Dict[str, np.ndarray],
           num_blocks: int, block_size: int, pad_to: int, mesh=None,
           slot: Optional[int] = None):
    """The cache tree with ``payload`` (:func:`extract`'s) written at
    ``block_ids`` and, where the tree has per-slot leaves, at row ``slot``
    of each (a payload that lacks one, or no ``slot``, is refused: the
    request would go on from another request's state).  The leaves written
    are donated: the caller rebinds its cache from the result.  Indices and
    rows are padded to ``pad_to`` blocks, so one compiled scatter serves
    every handoff size.  A payload that does not match leaf for leaf in
    shape and storage dtype is refused.  With ``mesh`` the written leaves
    go back onto their shardings."""
    n = len(block_ids)
    pad = max(pad_to, n)
    idx = np.full((pad,), num_blocks, np.int32)
    idx[:n] = block_ids
    found = block_leaves(cache, num_blocks, block_size)
    rows_in = []
    for key, leaf, _ in found:
        if key not in payload:
            raise ValueError(
                f"handoff payload missing arena leaf {key!r} — "
                "prefill/decode geometry or kv_quant mismatch")
        rows = payload[key]
        if rows.shape != (n,) + leaf.shape[1:] \
                or str(rows.dtype) != str(leaf.dtype):
            raise ValueError(
                f"handoff payload {key!r} {rows.dtype}{tuple(rows.shape)} "
                f"does not fit arena {leaf.dtype}{tuple(leaf.shape)} ({n} "
                "blocks) — the transport is shape- and storage-dtype-exact")
        padded = np.zeros((pad,) + tuple(rows.shape[1:]), dtype=rows.dtype)
        padded[:n] = rows
        rows_in.append(jnp.asarray(padded))
    states = slot_leaves(cache)
    state_rows = []
    for key, leaf in states:
        rows = payload.get(key)
        if rows is None or slot is None:
            raise ValueError(
                f"handoff payload missing per-slot leaf {key!r} — the "
                "sender's cache tree has no such state, or it was "
                "extracted without its slot")
        if rows.shape != (1,) + leaf.shape[1:] \
                or str(rows.dtype) != str(leaf.dtype):
            raise ValueError(
                f"handoff payload {key!r} {rows.dtype}{tuple(rows.shape)} "
                f"does not fit one slot's row of {leaf.dtype}"
                f"{tuple(leaf.shape)}")
        state_rows.append(jnp.asarray(rows))
    arena = tuple(leaf for _, leaf, _ in found) \
        + tuple(leaf for _, leaf in states)
    # ``slot`` None and no rows for a tree of block leaves alone
    new = _fused_block_scatter(tuple(a.shape for a in arena))(
        arena, jnp.asarray(idx), tuple(rows_in),
        jnp.asarray([slot], jnp.int32) if states else None,
        tuple(state_rows))
    new, new_states = new[:len(found)], new[len(found):]
    if mesh is not None:
        new = [jax.device_put(leaf, _sharding(mesh, kind))
               for leaf, (_, _, kind) in zip(new, found)]
        new_states = [jax.device_put(leaf, _sharding(mesh, PER_SLOT))
                      for leaf in new_states]
    written = dict(zip((key for key, _, _ in found), new))
    written.update(zip((key for key, _ in states), new_states))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: written.get(_path_str(path), leaf), cache)
