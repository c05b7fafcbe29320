"""Lane packing: a serve tick's live lanes as dense rows.

A tick's tokens arrive as ``tok [SLOTS, C]`` with ``n_new [SLOTS]`` live
lanes a slot: ``C`` for a slot inside its prompt, 1 for a decoding one (2
where the model drafts a token for itself), so at a chat load about a tenth
of the ``SLOTS * C`` lanes carry a token.  What is token-wise in a model
(embedding, norms, projections, MLP, experts, the head's pick) needs no
slot beside another and runs on ``[R, ...]`` rows, ``R = rows(SLOTS, C,
head)`` static and far under ``SLOTS * C``; only what reads a slot's lanes
side by side (a scan, attention over the slot's cache) sees ``[SLOTS, C,
...]``, through ``LaneMap.unpack`` and back through ``pack``.

**The layout** keeps every move a slice or a whole slab, never a gather by
row (XLA's row gather on the TPU runs far under bandwidth).  ``head = k``
is how many lanes a slot may feed without taking a group: 1, or 1 + the
drafts of a model that drafts for itself.

    rows [j SLOTS, (j + 1) SLOTS)   the head, ``j < k``: slot ``s``'s lane
                                    ``j``, live where ``j < n_new[s] <= k``
                                    (decode with its drafts, or the ``k``
                                    tokens or fewer a prompt has left)
    rows [k SLOTS + g C, .. + C)    group ``g``: all ``C`` lanes of the
                                    ``g``-th slot (in slot order) with
                                    ``n_new > k``, lanes past its ``n_new``
                                    dead; ``groups(SLOTS, C, k)`` groups

so a decode-only tick moves nothing but its first ``k SLOTS`` rows, as ``k``
slices ``x[:, j]``, and a prefill chunk moves as one ``[C, ...]`` slab.  At
``k = 1`` (the default) every array and every traced operation is what it
was before the head had a width.  The maps are worked out in
the program from ``n_new`` alone (a cumulative sum gives each multi-lane
slot its group, the group's slot is the inverse): the host puts no array
for them.  A dead row holds zeros from the embedding on (every token-wise
operation of the models here maps a zero row to a zero row) and is never
unpacked: ``unpack`` and ``pack`` select, they do not multiply, so whatever
a dead row or a dead lane holds, a NaN included, reaches nothing live.

**The engine's side of the contract** (``serve/engine.py``): at most
``groups(SLOTS, C, k)`` slots a tick may feed more than ``k`` lanes.  The
engine reads that budget from here for a model that declares
``packed_lanes`` (and ``k`` from its ``lane_head``, 1 where it names none),
grants longer prefill chunks whole, oldest admission first, and leaves the
rest at ``n_new = 0`` for the tick; a slot that asks for ``k`` lanes or
fewer is never left waiting.  ``R`` is no knob: it follows from the tick's
geometry, a quarter of its lanes (at least every slot's head and one chunk:
256 of ``64 x 16`` at ``k = 1``, 12 groups, and at ``k = 2``, 8).  With ``C
<= k`` (a decode-role engine's ``C == 1``) there is nothing to pack: ``R =
SLOTS C``, no group, and every move is the identity.

Every move runs under the device span ``lane_pack`` (as the paged cache's
run under ``kv_*``), whichever model calls it: ``models/granite_hybrid.py``
(PR 35), ``models/xing4.py`` and ``models/pangu_moe.py`` (PR 44, the latter
with ``k = 2``).
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


def groups(slots: int, chunk: int, head: int = 1) -> int:
    """How many slots a tick may feed more than ``head`` lanes: the whole
    ``[chunk]`` slabs that fit a quarter of the tick's lanes behind every
    slot's ``head`` first lanes, at least one (a prompt must be able to
    advance); none where the head holds a whole chunk."""
    if chunk <= head:
        return 0
    return max(1, (slots * chunk // 4 - slots * head) // chunk)


def rows(slots: int, chunk: int, head: int = 1) -> int:
    """``R``: the dense rows of a ``[slots, chunk]`` tick."""
    return slots * min(head, chunk) + groups(slots, chunk, head) * chunk


class LaneMap:
    """The maps of one tick between lanes ``[S, C]`` and rows ``[R]``,
    from ``n_new [S]`` (traced), the static chunk width and the static
    ``head``: how many lanes a slot may feed without taking a group."""

    @_spanned
    def __init__(self, n_new, chunk: int, head: int = 1):
        S = n_new.shape[0]
        G = groups(S, chunk, head)
        k = min(head, chunk)
        self.slots, self.chunk, self.groups, self.head = S, chunk, G, k
        self.rows = S * k + G * chunk
        self.n_new = n_new
        many = n_new > k
        upto = jnp.cumsum(many)                      # groups used up to s
        # slot -> its group (G: none); group -> its slot (S: none)
        self.group_of = jnp.where(many, upto - 1, G)
        self.slot_of = jnp.sum(upto[None, :] <= jnp.arange(G)[:, None], -1)
        lanes = jnp.arange(chunk)
        # head lane j is a row of its slot where j < n_new <= k (the last
        # head lane: where n_new is k exactly)
        self.head_live = [(n_new > j) & ~many for j in range(k - 1)] \
            + [n_new == k]                                          # k [S]
        self.live = lanes[None, :] < n_new[:, None]                 # [S, C]
        taken = jnp.concatenate([n_new, jnp.zeros((1,), n_new.dtype)])[
            self.slot_of]
        self.group_live = lanes[None, :] < taken[:, None]           # [G, C]
        self.row_live = jnp.concatenate(
            [*self.head_live, self.group_live.reshape(-1)])         # [R]

    @staticmethod
    def _where(mask, x, fill):
        return jnp.where(mask.reshape(mask.shape + (1,) * (
            x.ndim - mask.ndim)), x, jnp.asarray(fill, x.dtype))

    @_spanned
    def pack(self, x, fill=0):
        """``x [S, C, ...]`` -> ``[R, ...]``; dead rows hold ``fill``."""
        C, G = self.chunk, self.groups
        first = [self._where(live, x[:, j], fill)
                 for j, live in enumerate(self.head_live)]
        if not G:
            return first[0] if len(first) == 1 else jnp.concatenate(first)
        slabs = jnp.take(x, self.slot_of, axis=0, mode="fill",
                         fill_value=fill)                    # [G, C, ...]
        slabs = self._where(self.group_live, slabs, fill)
        return jnp.concatenate(
            [*first, slabs.reshape((G * C,) + x.shape[2:])])

    @_spanned
    def unpack(self, rows):
        """``rows [R, ...]`` -> ``[S, C, ...]``, zeros on dead lanes."""
        S, C, G, k = self.slots, self.chunk, self.groups, self.head
        tail = rows.shape[1:]
        if not G:
            out = rows[:S * k].reshape((k, S) + tail)
            return self._where(self.live, jnp.moveaxis(out, 0, 1), 0)
        out = jnp.take(rows[S * k:].reshape((G, C) + tail), self.group_of,
                       axis=0, mode="fill", fill_value=0)    # [S, C, ...]
        for j, live in enumerate(self.head_live):
            lane = jnp.arange(C)[None, :] == j
            out = jnp.where((live[:, None] & lane).reshape(
                (S, C) + (1,) * len(tail)), rows[j * S:(j + 1) * S, None],
                out)
        return self._where(self.live, out, 0)

    def _head_row(self, lane):
        """The head row of lane ``lane [S, ...]`` of each slot: lane ``j``'s
        rows start at ``j S``."""
        S, k = self.slots, self.head
        slot = jnp.arange(S).reshape((S,) + (1,) * (lane.ndim - 1))
        if k > 1:
            slot = slot + S * jnp.clip(lane, 0, k - 1)
        return slot

    @_spanned
    def row_of(self, lane):
        """``lane [S, K]`` -> ``[S, K]``: the row that holds each of the
        given lanes of each slot (of lanes the slot feeds this tick)."""
        S, C, k = self.slots, self.chunk, self.head
        return jnp.where((self.n_new > k)[:, None],
                         S * k + self.group_of[:, None] * C + lane,
                         self._head_row(lane))

    @_spanned
    def last(self, rows):
        """``rows [R, ...]`` -> ``[S, ...]``: each slot's last live lane
        (the lane sampled from), zeros for a slot with none."""
        S, C, k = self.slots, self.chunk, self.head
        at = jnp.where(self.n_new > k,
                       S * k + self.group_of * C + self.n_new - 1,
                       self._head_row(self.n_new - 1))
        return self._where(self.n_new > 0, rows[at], 0)
