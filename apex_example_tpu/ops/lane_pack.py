"""Lane packing: a serve tick's live lanes as dense rows.

A tick's tokens arrive as ``tok [SLOTS, C]`` with ``n_new [SLOTS]`` live
lanes a slot: ``C`` for a slot inside its prompt, 1 for a decoding one, so
at a chat load about a tenth of the ``SLOTS * C`` lanes carry a token.
What is token-wise in a model (embedding, norms, projections, MLP, the
head's pick) needs no slot beside another and runs on ``[R, ...]`` rows,
``R = rows(SLOTS, C)`` static and far under ``SLOTS * C``; only what reads
a slot's lanes side by side (a scan, attention over the slot's cache) sees
``[SLOTS, C, ...]``, through ``LaneMap.unpack`` and back through ``pack``.

**The layout** keeps every move a slice or a whole slab, never a gather by
row (XLA's row gather on the TPU runs far under bandwidth):

    rows [0, SLOTS)                 slot ``s``'s lane 0, live where
                                    ``n_new[s] == 1`` (decode, or the one
                                    token a prompt has left)
    rows [SLOTS + g C, .. + C)      group ``g``: all ``C`` lanes of the
                                    ``g``-th slot (in slot order) with
                                    ``n_new > 1``, lanes past its ``n_new``
                                    dead; ``groups(SLOTS, C)`` groups

so a decode-only tick moves nothing but its first ``SLOTS`` rows and a
prefill chunk moves as one ``[C, ...]`` slab.  The maps are worked out in
the program from ``n_new`` alone (a cumulative sum gives each multi-lane
slot its group, the group's slot is the inverse): the host puts no array
for them.  A dead row holds zeros from the embedding on (every token-wise
operation of the models here maps a zero row to a zero row) and is never
unpacked: ``unpack`` and ``pack`` select, they do not multiply, so whatever
a dead row or a dead lane holds, a NaN included, reaches nothing live.

**The engine's side of the contract** (``serve/engine.py``): at most
``groups(SLOTS, C)`` slots a tick may feed more than one lane.  The engine
reads that budget from here for a model that declares ``packed_lanes``,
grants prefill chunks whole, oldest admission first, and leaves the rest at
``n_new = 0`` for the tick.  ``R`` is no knob: it follows from the tick's
geometry, a quarter of its lanes (at least every slot's lane 0 and one
chunk).  With ``C == 1`` (a decode-role engine) there is nothing to pack:
``R = SLOTS``, no group, and every move is the identity.

Every move runs under the device span ``lane_pack`` (as the paged cache's
run under ``kv_*``), whichever model calls it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _spanned(fn):
    @functools.wraps(fn)
    def inner(*a, **k):
        with jax.named_scope("lane_pack"):
            return fn(*a, **k)
    return inner


def groups(slots: int, chunk: int) -> int:
    """How many slots a tick may feed more than one lane: the whole
    ``[chunk]`` slabs that fit a quarter of the tick's lanes behind every
    slot's lane 0, at least one (a prompt must be able to advance)."""
    if chunk == 1:
        return 0
    return max(1, (slots * chunk // 4 - slots) // chunk)


def rows(slots: int, chunk: int) -> int:
    """``R``: the dense rows of a ``[slots, chunk]`` tick."""
    return slots + groups(slots, chunk) * chunk


class LaneMap:
    """The maps of one tick between lanes ``[S, C]`` and rows ``[R]``,
    from ``n_new [S]`` (traced) and the static chunk width."""

    @_spanned
    def __init__(self, n_new, chunk: int):
        S = n_new.shape[0]
        G = groups(S, chunk)
        self.slots, self.chunk, self.groups = S, chunk, G
        self.rows = S + G * chunk
        self.n_new = n_new
        many = n_new > 1
        upto = jnp.cumsum(many)                      # groups used up to s
        # slot -> its group (G: none); group -> its slot (S: none)
        self.group_of = jnp.where(many, upto - 1, G)
        self.slot_of = jnp.sum(upto[None, :] <= jnp.arange(G)[:, None], -1)
        lanes = jnp.arange(chunk)
        self.one = n_new == 1                                       # [S]
        self.live = lanes[None, :] < n_new[:, None]                 # [S, C]
        taken = jnp.concatenate([n_new, jnp.zeros((1,), n_new.dtype)])[
            self.slot_of]
        self.group_live = lanes[None, :] < taken[:, None]           # [G, C]
        self.row_live = jnp.concatenate(
            [self.one, self.group_live.reshape(-1)])                # [R]

    @staticmethod
    def _where(mask, x, fill):
        return jnp.where(mask.reshape(mask.shape + (1,) * (
            x.ndim - mask.ndim)), x, jnp.asarray(fill, x.dtype))

    @_spanned
    def pack(self, x, fill=0):
        """``x [S, C, ...]`` -> ``[R, ...]``; dead rows hold ``fill``."""
        S, C, G = self.slots, self.chunk, self.groups
        first = self._where(self.one, x[:, 0], fill)
        if not G:
            return first
        slabs = jnp.take(x, self.slot_of, axis=0, mode="fill",
                         fill_value=fill)                    # [G, C, ...]
        slabs = self._where(self.group_live, slabs, fill)
        return jnp.concatenate(
            [first, slabs.reshape((G * C,) + x.shape[2:])])

    @_spanned
    def unpack(self, rows):
        """``rows [R, ...]`` -> ``[S, C, ...]``, zeros on dead lanes."""
        S, C, G = self.slots, self.chunk, self.groups
        tail = rows.shape[1:]
        if not G:
            return self._where(self.live, rows[:, None], 0)
        slabs = jnp.take(rows[S:].reshape((G, C) + tail), self.group_of,
                         axis=0, mode="fill", fill_value=0)  # [S, C, ...]
        first = jnp.arange(C)[None, :] == 0
        out = jnp.where((self.one[:, None] & first).reshape(
            (S, C) + (1,) * len(tail)), rows[:S, None], slabs)
        return self._where(self.live, out, 0)

    @_spanned
    def last(self, rows):
        """``rows [R, ...]`` -> ``[S, ...]``: each slot's last live lane
        (the lane sampled from), zeros for a slot with none."""
        S, C = self.slots, self.chunk
        at = jnp.where(self.n_new > 1,
                       S + self.group_of * C + self.n_new - 1,
                       jnp.arange(S))
        return self._where(self.n_new > 0, rows[at], 0)
