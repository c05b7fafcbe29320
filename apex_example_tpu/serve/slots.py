"""Block-paged KV cache, the host's side: free-list allocator, per-slot
block tables, admission and reservation.

The dense layout this replaces pinned a [SLOTS, max_len, H, D] page per
slot, so HBM cost scaled with ``max_len`` regardless of request length
(PR 6's gauges measured ~92% ``kv_waste_pct`` on the smoke workload).
Here a request maps only the arena blocks its sequence actually touches,
through a per-slot block table (``[SLOTS, max_blocks]`` int32) that the
attention layers read inside the one compiled decode step.  The arena's
layout, the tick's operations on it and which leaves of a cache tree are
block-resident are ops/paged_cache.py's; this module holds policy and
knows no leaf by name or shape.  Table CONTENTS are data: one compile.

Host-side policy (this module, no jax in the allocator):

- **Free-list allocation** with per-block refcounts.  Admission
  reserves a request's worst-case block count up front
  (``ceil((prompt + max_new) / block_size)`` minus fully-shared
  blocks), so a decoding slot can never hit out-of-blocks mid-flight —
  OOM resolves deterministically at admission (queueing/shed in the
  engine), never as a stuck slot.
- **Prefix sharing** (copy-on-write): full blocks are registered in a
  chain-keyed index (each key hashes the block's tokens AND its whole
  prefix — KV content depends on every preceding token, so per-block
  content alone can never key it).  A new request maps the longest
  indexed chain covering its prompt, including a partial overlap into
  the last matched block; blocks mapped by several slots (or cached in
  the index) are immutable, and the first write into one triggers a
  block copy inside the compiled step (``cow_*`` pairs).  Zero-ref
  indexed blocks linger as a reusable cache (LRU-evicted under
  pressure), so a recurring system prompt keeps its KV across
  non-overlapping requests.
- **Chunked prefill**: the engine feeds up to ``block_size`` prompt
  tokens per tick through the same compiled step (serve/engine.py);
  this module's ``stage_writes``/``commit_writes`` bracket each tick's
  span with allocation/COW before and full-block registration after.

Shared prefixes always stop one token short of the full prompt: the
first generated token is sampled from the logits AFTER the last prompt
token, and sharing that position's KV would skip the forward pass that
produces those logits.

**Two lifetimes in one pool.**  A model whose cache tree declares *window
leaves* (ops/paged_cache.py: layers that attend a sliding window of ``W``
positions) gets a second arena, a second :class:`BlockAllocator` and a
second table beside the first: ``num_slots * ring_blocks`` blocks,
``ring_table [SLOTS, ring_blocks]`` with logical block ``j`` in column ``j
mod ring_blocks``.  A request then holds its whole sequence in the full
arena and its last ``W`` tokens (and the tick's span) in the window arena:
``stage_writes`` allocates in both, ``commit_writes`` hands back every
window block the slot's fill has passed by a window — while the request
still runs — and ``evict`` frees both.  Admission reserves ``min(blocks,
ring_blocks)`` there, so a running slot can never want a window block and
find none.  ``num_blocks``, ``blocks_needed``, ``fits`` and the prefix index
stay the full arena's; a pool with window leaves shares no prefix (the
window blocks a match would need may be handed back already), hands no
request to another pool and takes no low-bit cache or draft lanes: each is
refused with its reason, here or in serve/engine.py.
"""

from __future__ import annotations

import math
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_example_tpu.ops import paged_cache
from apex_example_tpu.serve.queue import Request


@dataclass
class BlockNode:
    """One indexed (full, immutable) block: its chain key encodes the
    block's tokens and, through ``parent``, every token before it."""

    bid: int
    key: Tuple
    parent: Optional[Tuple]
    tokens: Tuple[int, ...]


class BlockAllocator:
    """Free-list + refcount + prefix-index bookkeeping for one arena.

    Pure host code (no jax): the engine calls it between compiled
    steps.  Determinism contract: allocation order, LRU eviction order
    and prefix-match tie-breaks depend only on the call sequence, so a
    rerun of the same request stream allocates identically.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.refcount = [0] * num_blocks
        self._immutable = [False] * num_blocks
        self._free: List[int] = list(range(num_blocks))[::-1]  # pop()=0 first
        # Zero-ref indexed blocks, LRU order (oldest first): reusable
        # prefix cache, evicted only when the free list runs dry.
        self._reusable: "OrderedDict[int, BlockNode]" = OrderedDict()
        self._index: Dict[Tuple, BlockNode] = {}
        self._children: Dict[Optional[Tuple], List[BlockNode]] = {}
        self._nodes: Dict[int, BlockNode] = {}   # bid -> node (indexed only)

    # ------------------------------------------------------------ state

    @property
    def blocks_in_use(self) -> int:
        """Blocks currently mapped by at least one slot."""
        return self.num_blocks - len(self._free) - len(self._reusable)

    def available(self, revive: Tuple[int, ...] = ()) -> int:
        """Blocks an admission could still draw from: the free list plus
        the evictable reusable cache, minus any of ``revive`` that sit
        in that cache (mapping a cached shared block removes it from the
        evictable pool, so it must not be double-counted)."""
        revived = sum(1 for b in set(revive) if b in self._reusable)
        return len(self._free) + len(self._reusable) - revived

    def immutable(self, bid: int) -> bool:
        return self._immutable[bid]

    # -------------------------------------------------------- lifecycle

    def alloc(self) -> int:
        """One fresh mutable block (refcount 1).  Draws the free list
        first, then evicts the least-recently-freed reusable block
        (deregistering its index entry).  Raising here means the
        caller's reservation accounting is broken — admission must have
        checked ``available()``."""
        if self._free:
            bid = self._free.pop()
        elif self._reusable:
            bid, node = self._reusable.popitem(last=False)
            self._deregister(node)
        else:
            raise RuntimeError(
                "out of KV blocks — admission reserves worst-case block "
                "budgets, so this is an allocator accounting bug")
        self.refcount[bid] = 1
        self._immutable[bid] = False
        return bid

    def ref(self, bid: int) -> None:
        """Map an already-cached block into one more slot (prefix
        sharing); revives it out of the reusable pool if parked there."""
        self.refcount[bid] += 1
        self._reusable.pop(bid, None)

    def unref(self, bid: int) -> None:
        """Drop one mapping.  At zero refs an indexed block parks in the
        reusable cache (its KV stays valid for future prefix hits); an
        unindexed one returns to the free list."""
        if self.refcount[bid] < 1:
            raise RuntimeError(f"unref of free block {bid}")
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            node = self._nodes.get(bid)
            if node is not None:
                self._reusable[bid] = node
            else:
                self._free.append(bid)

    def _deregister(self, node: BlockNode) -> None:
        del self._index[node.key]
        self._children[node.parent].remove(node)
        if not self._children[node.parent]:
            del self._children[node.parent]
        del self._nodes[node.bid]

    # ----------------------------------------------------- prefix index

    def register_full(self, parent: Optional[Tuple],
                      tokens: Tuple[int, ...], bid: int) -> Tuple:
        """Index a block that just filled (immutable from here on: any
        later write COWs).  A duplicate chain — two slots computed the
        same content in parallel — keeps the first index entry; the
        duplicate block stays unindexed and frees normally."""
        if len(tokens) != self.block_size:
            raise ValueError(f"register_full wants exactly "
                             f"{self.block_size} tokens, got {len(tokens)}")
        key = (parent, tokens)
        self._immutable[bid] = True
        if key not in self._index:
            node = BlockNode(bid, key, parent, tokens)
            self._index[key] = node
            self._children.setdefault(parent, []).append(node)
            self._nodes[bid] = node
        return key

    def match_prefix(self, prompt) -> Tuple[int, List[int], List[Tuple]]:
        """Longest cached prefix of ``prompt``: ``(shared_len, block
        ids, chain keys)``.  Walks exact full-block chain matches, then
        tries a partial overlap into one more indexed block (the COW
        case: the block is mapped read-only for its first few positions
        and copied at the first divergent write).  Read-only — the
        caller refs the returned blocks on admission.  Always capped at
        ``len(prompt) - 1`` so the last prompt token is re-fed (its
        forward pass produces the first sampled token's logits)."""
        BS = self.block_size
        bids: List[int] = []
        keys: List[Tuple] = []
        parent: Optional[Tuple] = None
        shared = 0
        for b in range(len(prompt) // BS):
            key = (parent, tuple(prompt[b * BS:(b + 1) * BS]))
            node = self._index.get(key)
            if node is None:
                break
            bids.append(node.bid)
            keys.append(key)
            parent = key
            shared += BS
        # Partial overlap into one more child block: first registered
        # child with the longest common prefix wins (deterministic).
        rest = tuple(prompt[shared:shared + BS])
        best, best_j = None, 0
        for node in self._children.get(parent, []):
            j = 0
            while j < len(rest) and node.tokens[j] == rest[j]:
                j += 1
            if j > best_j:
                best, best_j = node, j
        if best is not None:
            bids.append(best.bid)
            keys.append(best.key)
            shared += best_j
        shared = min(shared, len(prompt) - 1)
        n_mapped = math.ceil(shared / BS) if shared else 0
        return shared, bids[:n_mapped], keys[:n_mapped]

    def hot_prefixes(self, top_n: int) -> List[Tuple[int, ...]]:
        """The hottest indexed blocks' CUMULATIVE token prefixes,
        hottest first (ISSUE 19): rank every indexed block by live
        refcount (ties to lower bid — allocation order, deterministic)
        and unwind each chain key back to the full token prefix it
        covers.  Zero-ref blocks parked in the reusable cache rank
        last but still advertise — their KV is warm and a prefix hit
        revives them."""
        if top_n < 1:
            return []
        ranked = sorted(self._nodes.values(),
                        key=lambda n: (-self.refcount[n.bid], n.bid))
        out: List[Tuple[int, ...]] = []
        for node in ranked[:top_n]:
            parts: List[Tuple[int, ...]] = []
            key: Optional[Tuple] = node.key
            while key is not None:
                parent, toks = key
                parts.append(toks)
                key = parent
            out.append(tuple(t for toks in reversed(parts)
                             for t in toks))
        return out


@dataclass
class Slot:
    """Host-side state of one live request in a slot.

    ``tokens`` is the full sequence (prompt + generated so far);
    ``cursor`` counts tokens whose KV is in the arena — fed through the
    model OR covered by a shared prefix.  During decode
    ``len(tokens) == cursor + 1`` (the newest element is the next token
    to feed); during prefill ``cursor < n_prompt``.

    ``block_keys`` parallels the slot's mapped blocks: the chain key
    for registered (full, immutable) blocks, None for a mutable block
    still filling (registered by ``commit_writes`` when it fills).

    ``draft`` is what a self-drafting model's module proposed for the
    token after the newest (fed beside it next tick), ``drafts`` every
    draft verified so far as ``(output index it claimed, token,
    accepted)`` (serve/engine.py).

    ``ring_lo``/``ring_hi``/``ring_reserved``: what the slot holds of the
    window arena, where the model has window leaves.
    """

    request: Request
    admitted_step: int
    t_admitted: float
    tokens: List[int] = field(default_factory=list)
    cursor: int = 0
    n_generated: int = 0
    t_first_token: Optional[float] = None
    shared_len: int = 0
    n_mapped: int = 0
    reserved: int = 0
    block_keys: List[Optional[Tuple]] = field(default_factory=list)
    draft: Optional[int] = None
    drafts: List[Tuple[int, int, bool]] = field(default_factory=list)
    # window leaves: the logical blocks [ring_lo, ring_hi) stand in the
    # window arena; ring_reserved more may be drawn at once
    ring_lo: int = 0
    ring_hi: int = 0
    ring_reserved: int = 0

    @property
    def n_prompt(self) -> int:
        return len(self.request.prompt)

    @property
    def prefilling(self) -> bool:
        return self.cursor < self.n_prompt


class BlockPool:
    """``num_slots`` request slots over one block-paged KV arena (and,
    for a model with window leaves, the window arena beside it).

    ``model`` is the plain (training) module; the pool derives the
    paged slot-decode clone and allocates the per-layer arenas via an
    abstract init trace (no real forward runs), exactly like
    models/gpt.generate.  Handoff, migration and the byte accounting
    carry whatever block-resident leaves the model allocates
    (``paged_cache.block_leaves``).  ``num_blocks`` defaults to the dense
    layout's capacity (``num_slots * ceil(max_len / block_size)``), so
    the default arena reserves the same HBM the old [SLOTS, max_len]
    pages did — the win is that admission now shares and packs it.
    """

    def __init__(self, model, num_slots: int, max_len: int,
                 block_size: int = 8, num_blocks: Optional[int] = None,
                 kv_quant: bool = False, spec_slack: int = 0,
                 rows_read_next_token: bool = False):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if spec_slack < 0:
            raise ValueError(f"spec_slack must be >= 0, got {spec_slack}")
        if model.max_position < max_len:
            raise ValueError(f"max_len {max_len} exceeds the model's "
                             f"position table ({model.max_position})")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.max_blocks = math.ceil(max_len / block_size)
        if num_blocks is None:
            num_blocks = num_slots * self.max_blocks
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size
        self.num_blocks = num_blocks
        # kv_quant (ISSUE 13): int8 arenas + bf16 per-token block
        # scales, quantize-on-scatter / dequant-in-gather inside the
        # same ONE compiled step (models/bert.py).  Allocation, COW
        # pairs and refcounts in this module are dtype-blind — only
        # the byte accounting below changes.
        self.kv_quant = bool(kv_quant)
        # spec_slack (ISSUE 18): with speculation armed, a slot's staged
        # write span can run up to K draft tokens ahead of its committed
        # cursor within a tick, so the worst-case reservation must cover
        # those in-flight positions or _alloc_for would fault mid-tick.
        self.spec_slack = int(spec_slack)
        # A self-drafting model's module caches, at position i, a row
        # computed from token i + 1 as well (models/pangu_moe.py): the
        # last position of a shared prefix would hold the row of the
        # OTHER request's next token, so such a pool shares one token
        # less and the sharer writes that position itself (_match_prefix).
        self.rows_read_next_token = bool(rows_read_next_token)
        self.dec = model.clone(decode=True, slot_decode=True,
                               fused_attention=False,
                               kv_num_blocks=num_blocks,
                               kv_block_size=block_size,
                               kv_quant=self.kv_quant)
        shapes = jax.eval_shape(
            self.dec.init, jax.random.PRNGKey(0),
            jnp.zeros((num_slots, max_len), jnp.int32))["cache"]
        self.cache = jax.tree_util.tree_map(
            lambda t: jnp.zeros(t.shape, t.dtype), shapes)
        # what the byte gauges need, fixed with the geometry (scale tables
        # counted: the quantized layout's true footprint)
        leaves = paged_cache.block_leaves(shapes, num_blocks, block_size)
        payload = [leaf for _, leaf, kind in leaves
                   if kind == paged_cache.PAYLOAD]
        if self.kv_quant and len(payload) == len(leaves):
            raise ValueError(
                "kv_quant: the model allocated no per-token scale table "
                "beside its arena leaves — quantized paged KV is not built "
                "for this cache layout")
        self._kv_reserved = sum(leaf.size * leaf.dtype.itemsize
                                for _, leaf, _ in leaves)
        self._kv_elems = sum(leaf.size for leaf in payload)
        self._kv_dtype = str(payload[0].dtype) if payload else "none"
        # The second kind of cache (ops/paged_cache.py): state a recurrent
        # layer keeps per SLOT.  A property of the tree, not a model's
        # name: such a tree cannot share prefixes (the state at a prefix
        # boundary is held nowhere, so a request started at fill > 0 on
        # another's blocks would compute from the wrong state) and cannot
        # roll rejected draft lanes back.  Counted apart from K/V.
        states = paged_cache.slot_leaves(shapes)
        self.per_slot_state = bool(states)
        self._state_per_slot = sum(
            leaf.size // num_slots * leaf.dtype.itemsize
            for _, leaf in states)
        if self.per_slot_state and self.spec_slack:
            raise ValueError(
                "speculate: this model keeps a recurrent state per slot, "
                "and a state advanced over rejected draft lanes cannot be "
                "rolled back without a snapshot (ROADMAP M4)")
        # The third kind: window leaves, in an arena, an allocator and a
        # table of their own (module docstring).  ``window`` None and a
        # table zero wide for every model without them.
        windows = paged_cache.window_leaves(shapes)
        self.window: Optional[int] = None
        self.ring_blocks = 0
        self.ring_alloc: Optional[BlockAllocator] = None
        win_bytes = sum(leaf.size * leaf.dtype.itemsize
                        for _, leaf, _ in windows)
        if windows:
            widths = sorted({w for _, _, w in windows})
            if len(widths) != 1:
                raise ValueError("window leaves of more than one window "
                                 f"({widths}): one ring table serves one")
            self.window = widths[0]
            self.ring_blocks = paged_cache.ring_blocks(self.window,
                                                       block_size)
            ring_total = num_slots * self.ring_blocks
            for path, leaf, _ in windows:
                if leaf.shape[:2] != (ring_total, block_size):
                    raise ValueError(
                        f"window leaf {path!r} {tuple(leaf.shape)}: wanted "
                        f"[{ring_total}, {block_size}, ..] ({num_slots} "
                        f"slots x {self.ring_blocks} ring blocks)")
            if self.spec_slack:
                raise ValueError(
                    "speculate: rejected draft lanes would have turned the "
                    "ring over blocks the accepted prefix still needs; "
                    "drafting over a ring is not built (ROADMAP M3)")
            self.ring_alloc = BlockAllocator(ring_total, block_size)
            self._kv_reserved += win_bytes
        self._win_per_token = win_bytes // (
            num_slots * self.ring_blocks * block_size) if windows else 0
        self._full_per_token = (self._kv_reserved - win_bytes) \
            // (num_blocks * block_size)
        self.ring_table = np.zeros((num_slots, self.ring_blocks), np.int32)
        self._ring_reserved_total = 0
        self.window_blocks_released = 0
        self.alloc = BlockAllocator(num_blocks, block_size)
        self.table = np.zeros((num_slots, self.max_blocks), np.int32)
        self.slots: List[Optional[Slot]] = [None] * num_slots
        self._free: List[int] = list(range(num_slots))[::-1]  # pop()=slot 0
        self._reserved_total = 0
        self.cow_copies = 0
        self._shared_tokens = 0
        self._prompt_tokens = 0
        self._mesh = None                    # set by shard(mesh)

    # --------------------------------------------------------- sharding

    def shard(self, mesh) -> None:
        """TP-shard the arenas over the mesh's ``model`` axis
        (``paged_cache.shard``).  Tables, free list and admission stay
        host-side and replicated: a placement of the SAME geometry, so
        policy is untouched and the step lowers once with GSPMD
        shardings."""
        from apex_example_tpu.parallel.mesh import MODEL_AXIS
        if mesh.shape.get(MODEL_AXIS, 1) > 1 \
                and not self.dec.tensor_parallel:
            raise ValueError(
                f"mesh has '{MODEL_AXIS}' size {mesh.shape[MODEL_AXIS]} "
                "but the model was built without tensor_parallel=True: "
                "its arena leaves have no head axis to shard")
        self._mesh = mesh
        self.cache = paged_cache.shard(self.cache, mesh, self.num_blocks,
                                       self.block_size)

    # ------------------------------------------------------------ state

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def any_live(self) -> bool:
        return len(self._free) < self.num_slots

    # ---------------------------------------------------- block budgets

    def max_new_for(self, request: Request) -> int:
        """Effective output budget: the request's ask, clamped so the
        total sequence fits a slot's logical capacity."""
        return min(request.max_new_tokens,
                   self.max_len - len(request.prompt))

    def blocks_needed(self, request: Request,
                      shared_len: int = 0) -> int:
        """Worst-case blocks this request will ALLOCATE over its
        lifetime: blocks covering the clamped total sequence, minus
        fully-shared blocks (never written — a partially-overlapped
        shared block still costs its COW copy, so it is not
        subtracted).  With speculation armed, ``spec_slack`` extra
        in-flight tokens are budgeted: draft lanes stage KV writes up
        to K positions past the cursor before the accept decision."""
        total = len(request.prompt) + self.max_new_for(request) \
            + self.spec_slack
        return math.ceil(total / self.block_size) \
            - shared_len // self.block_size

    def fits(self, request: Request) -> bool:
        """Could this request EVER be admitted?  (Worst case, no
        sharing.)  False means admission must reject it outright —
        queueing would deadlock."""
        return self.max_new_for(request) >= 1 \
            and self.blocks_needed(request) <= self.num_blocks

    def can_admit(self, request: Request) -> bool:
        """Slot free AND the worst-case block budget (after prefix
        sharing) is coverable by unreserved blocks right now."""
        if not self._free:
            return False
        shared, bids, _ = self._match_prefix(request.prompt)
        need = self.blocks_needed(request, shared)
        if self.ring_alloc is not None and self.ring_alloc.available() \
                - self._ring_reserved_total < self._ring_needed(request):
            return False
        return self.alloc.available(tuple(bids)) \
            - self._reserved_total >= need

    def _ring_needed(self, request: Request) -> int:
        """Window blocks a request can hold at once: its sequence's, or a
        ring's if that is less."""
        return min(self.blocks_needed(request), self.ring_blocks)

    def _match_prefix(self, prompt) -> Tuple[int, List[int], List[Tuple]]:
        """``alloc.match_prefix``; no match at all for a cache tree with
        per-slot state or window leaves (the window blocks a match needs
        may be handed back: ROADMAP M3, prefix sharing per leaf kind), one
        token less where rows read the next token."""
        if self.per_slot_state or self.window is not None:
            return 0, [], []
        shared, bids, keys = self.alloc.match_prefix(prompt)
        if self.rows_read_next_token and shared:
            shared -= 1
            n_mapped = math.ceil(shared / self.block_size)
            bids, keys = bids[:n_mapped], keys[:n_mapped]
        return shared, bids, keys

    # -------------------------------------------------------- lifecycle

    def admit(self, request: Request, step: int) -> int:
        """Insert ``request`` into a free slot: map its shared prefix
        blocks (refcounted), reserve its worst-case allocation budget
        and seed the host state.  Returns the slot id.  The engine must
        gate on ``fits``/``can_admit`` first."""
        if not self._free:
            raise RuntimeError("no free slot (admission must check "
                               "free_count first)")
        n_prompt = len(request.prompt)
        if n_prompt >= self.max_len:
            raise ValueError(
                f"{request.uid}: prompt length {n_prompt} must be < "
                f"cache max_len {self.max_len} (admission should have "
                "rejected this request)")
        shared, bids, keys = self._match_prefix(request.prompt)
        need = self.blocks_needed(request, shared)
        idx = self._free.pop()
        for b in bids:
            self.alloc.ref(b)
        self.table[idx, :] = 0
        self.table[idx, :len(bids)] = bids
        self.slots[idx] = Slot(request=request, admitted_step=step,
                               t_admitted=time.perf_counter(),
                               tokens=[int(t) for t in request.prompt],
                               cursor=shared, shared_len=shared,
                               n_mapped=len(bids), reserved=need,
                               block_keys=list(keys))
        self._reserved_total += need
        if self.ring_alloc is not None:
            self.ring_table[idx, :] = 0
            self.slots[idx].ring_reserved = self._ring_needed(request)
            self._ring_reserved_total += self.slots[idx].ring_reserved
        self._shared_tokens += shared
        self._prompt_tokens += n_prompt
        return idx

    def evict(self, idx: int) -> None:
        """Free a slot (finished, failed or cancelled): unref its
        mapped blocks (full indexed ones park in the reusable prefix
        cache) and release the unspent reservation."""
        slot = self.slots[idx]
        if slot is None:
            raise RuntimeError(f"slot {idx} is already free")
        for b in range(slot.n_mapped):
            self.alloc.unref(int(self.table[idx, b]))
        self._reserved_total -= slot.reserved
        self.table[idx, :] = 0
        if self.ring_alloc is not None:
            for b in range(slot.ring_lo, slot.ring_hi):
                self.ring_alloc.unref(
                    int(self.ring_table[idx, b % self.ring_blocks]))
            self._ring_reserved_total -= slot.ring_reserved
            self.ring_table[idx, :] = 0
        self.slots[idx] = None
        self._free.append(idx)

    # ------------------------------------------------------- KV handoff

    def extract_blocks(self, idx: int, n_blocks: Optional[int] = None
                       ) -> Tuple[int, int, Dict[str, "np.ndarray"]]:
        """Gather slot ``idx``'s mapped arena blocks (its first
        ``n_blocks``; all by default) for a KV handoff:
        ``(fill, n_blocks, payload)`` where payload maps each arena
        leaf's path string to a host ``[n_blocks, BS, ...]`` array in
        the leaf's STORAGE dtype (int8 payload + bf16 scales under
        kv_quant — the handoff moves low-bit bytes, never dequantizes),
        and each per-slot leaf's to the slot's own ``[1, ...]`` row.

        The copy is deep by construction (``np.asarray`` of a device
        gather): a payload built from COW-shared prefix blocks shares
        nothing with the arena, so the receiver can never alias a
        block another request still maps."""
        slot = self.slots[idx]
        if slot is None:
            raise RuntimeError(f"slot {idx} is free — nothing to hand off")
        self._refuse_ring_handoff()
        n = slot.n_mapped if n_blocks is None else n_blocks
        payload = paged_cache.extract(self.cache, self.table[idx, :n],
                                      self.num_blocks, self.block_size,
                                      slot=idx)
        return slot.cursor, n, payload

    def _refuse_ring_handoff(self) -> None:
        if self.window is not None:
            raise ValueError(
                "this pool has window leaves: a payload of blocks carries "
                "no ring (which columns stand for which positions), so a "
                "request is not handed to or taken from another pool "
                "(ROADMAP M3: hand-off of a ring between roles)")

    def blocks_needed_prefilled(self, request: Request) -> int:
        """Worst-case blocks a handed-off request needs on the RECEIVING
        side: the full clamped sequence, no prefix sharing (the payload
        blocks are scattered fresh)."""
        return self.blocks_needed(request)

    def can_admit_prefilled(self, request: Request) -> bool:
        """Slot free AND the handed-off request's whole worst-case
        block budget is coverable right now.  The deterministic-requeue
        contract: a False here must leave NO state behind — the caller
        retries the same handoff later."""
        if not self._free:
            return False
        return self.alloc.available() - self._reserved_total \
            >= self.blocks_needed_prefilled(request)

    def admit_prefilled(self, request: Request, step: int, fill: int,
                        payload: Dict[str, "np.ndarray"],
                        tokens: List[int]) -> int:
        """Admit a request whose first ``fill`` tokens of KV arrive as a
        handoff payload: allocate the payload's blocks, scatter the
        rows into this pool's own arenas (dtype-checked — an int8
        payload must land in an int8 arena), seed the slot at
        ``cursor == fill`` and reserve the rest of the worst-case
        budget.  ``tokens`` is the full token list so far (prompt plus
        the prefill worker's first sampled token).  The caller gates on
        ``can_admit_prefilled`` first."""
        if not self._free:
            raise RuntimeError("no free slot (handoff admission must "
                               "check can_admit_prefilled first)")
        self._refuse_ring_handoff()
        BS = self.block_size
        n_pay = math.ceil(fill / BS)
        total = self.blocks_needed_prefilled(request)
        if n_pay > total:
            raise ValueError(
                f"{request.uid}: payload covers {n_pay} blocks but the "
                f"clamped sequence only needs {total}")
        bids = [self.alloc.alloc() for _ in range(n_pay)]
        # one jitted scatter whatever the handoff's size: admission sits
        # inside the decode worker's TPOT window.  The leaves are donated.
        self.cache = paged_cache.insert(
            self.cache, bids, payload, self.num_blocks, BS,
            pad_to=self.max_blocks, mesh=self._mesh, slot=self._free[-1])
        idx = self._free.pop()
        self.table[idx, :] = 0
        self.table[idx, :n_pay] = bids
        self.slots[idx] = Slot(request=request, admitted_step=step,
                               t_admitted=time.perf_counter(),
                               tokens=[int(t) for t in tokens],
                               cursor=fill, shared_len=0,
                               n_mapped=n_pay,
                               reserved=total - n_pay,
                               block_keys=[None] * n_pay)
        self._reserved_total += total - n_pay
        self._prompt_tokens += len(request.prompt)
        return idx

    def _alloc_for(self, slot: Slot) -> int:
        if slot.reserved < 1:
            raise RuntimeError(
                f"{slot.request.uid}: write past the reserved block "
                "budget — blocks_needed accounting bug")
        bid = self.alloc.alloc()
        slot.reserved -= 1
        self._reserved_total -= 1
        return bid

    def stage_writes(self, idx: int, n_new: int) -> Tuple[int, int]:
        """Pre-step: make every block covering write positions
        ``[cursor, cursor + n_new)`` mapped and mutable for slot
        ``idx``.  Returns the tick's COW pair ``(src, dst)`` —
        ``(-1, -1)`` when no shared block is written this tick.  At
        most one COW per slot per tick: only the first written block
        can be shared (later blocks in the span are freshly
        allocated)."""
        slot = self.slots[idx]
        cow = (-1, -1)
        start, end = slot.cursor, slot.cursor + n_new
        BS = self.block_size
        for b in range(start // BS, (end - 1) // BS + 1):
            if b < slot.n_mapped:
                bid = int(self.table[idx, b])
                if self.alloc.immutable(bid):
                    # First divergent write into a shared/cached block:
                    # copy-on-write inside the compiled step.
                    new = self._alloc_for(slot)
                    cow = (bid, new)
                    self.alloc.unref(bid)
                    self.table[idx, b] = new
                    slot.block_keys[b] = None     # content diverges
                    self.cow_copies += 1
            else:
                if b != slot.n_mapped:
                    raise RuntimeError("non-contiguous block mapping")
                self.table[idx, b] = self._alloc_for(slot)
                slot.block_keys.append(None)
                slot.n_mapped += 1
        if self.ring_alloc is not None:
            # the same span in the window arena, through the ring: logical
            # block b in column b mod ring_blocks (never shared: no COW)
            R = self.ring_blocks
            for b in range(max(slot.ring_hi, start // BS),
                           (end - 1) // BS + 1):
                if slot.ring_reserved < 1 or b - slot.ring_lo >= R:
                    raise RuntimeError(
                        f"{slot.request.uid}: the ring is full at block "
                        f"{b} (holds {slot.ring_lo}..{slot.ring_hi}) — "
                        "ring_blocks accounting bug")
                self.ring_table[idx, b % R] = self.ring_alloc.alloc()
                slot.ring_reserved -= 1
                self._ring_reserved_total -= 1
                slot.ring_hi = b + 1
        return cow

    def commit_writes(self, idx: int, n_new: int) -> None:
        """Post-step: advance the slot's fill cursor and register every
        block that just became full in the prefix index (it turns
        immutable; its chain key hashes the whole token prefix)."""
        slot = self.slots[idx]
        slot.cursor += n_new
        BS = self.block_size
        if self.ring_alloc is not None:
            # Hand back every window block the fill has passed by a window:
            # the next lane, at position ``cursor``, sees positions >
            # cursor - window, so a block whose last position lies at or
            # before that is never read again.
            R = self.ring_blocks
            while slot.ring_lo < slot.ring_hi and \
                    (slot.ring_lo + 1) * BS - 1 <= slot.cursor - self.window:
                self.ring_alloc.unref(
                    int(self.ring_table[idx, slot.ring_lo % R]))
                slot.ring_lo += 1
                slot.ring_reserved += 1
                self._ring_reserved_total += 1
                self.window_blocks_released += 1
        if self.per_slot_state or self.window is not None:
            return                 # nothing is shared, so nothing is indexed
        for b in range(slot.n_mapped):
            if slot.block_keys[b] is None and (b + 1) * BS <= slot.cursor:
                parent = slot.block_keys[b - 1] if b else None
                toks = tuple(slot.tokens[b * BS:(b + 1) * BS])
                slot.block_keys[b] = self.alloc.register_full(
                    parent, toks, int(self.table[idx, b]))

    # ---------------------------------------------------- KV accounting

    def kv_bytes_reserved(self) -> int:
        """HBM bytes the arenas pin for the engine's lifetime: every
        page leaf is a full [num_blocks, block_size, W] allocation
        (scale tables counted with them), every window leaf a
        [num_slots * ring_blocks, block_size, W] one.  The default
        ``num_blocks`` makes this equal to the dense layout's
        reservation — the paged win shows up in the per-tick committed/
        live gauges, not here."""
        return self._kv_reserved

    def kv_bytes_per_token(self) -> int:
        """Bytes one cached token occupies across every layer's arena
        leaves, each arena's bytes over its own room in tokens (the full
        arena's ``num_blocks * block_size``; the window arena's) —
        dtype-accurate: int8 payload plus the bf16 block scales under
        kv_quant, the full-precision payload otherwise."""
        return self._full_per_token + self._win_per_token

    def kv_bytes_per_token_bf16(self) -> int:
        """What one cached token WOULD cost in a bf16 dense-payload
        arena of this geometry (2 bytes per K/V element, no scales) —
        the bf16-equivalent baseline the quant compression ratio and
        the ci_gate ``--quant-stream`` floor are computed against."""
        return self._kv_elems * 2 // (self.num_blocks * self.block_size)

    @property
    def kv_dtype(self) -> str:
        """The arena payload dtype name ("int8" under kv_quant)."""
        return self._kv_dtype

    def kv_bytes_live(self) -> int:
        """Bytes of KV the live slots logically hold (per-slot fill
        level times the per-token cost; a shared block's tokens count
        once per sharer — this is the demand gauge, ``blocks_in_use``
        the physical one).  A window leaf's tokens count while their
        slot lives, handed back or not: ``window_tokens_held`` says what
        still stands in the window arena."""
        per_token = self.kv_bytes_per_token()
        return sum(s.cursor for s in self.slots if s is not None) \
            * per_token

    def window_tokens_held(self) -> List[int]:
        """Per slot, the tokens whose rows stand in the window arena (a
        free slot's 0): the fill less what has been handed back.  The
        slot's fill itself where nothing was, and for a pool without
        window leaves."""
        BS = self.block_size
        return [0 if s is None else s.cursor - s.ring_lo * BS
                for s in self.slots]

    def state_bytes_reserved(self) -> int:
        """HBM bytes the per-slot leaves pin (a recurrent layer's state and
        convolution rows, every slot's, every layer's): 0 for a model of
        attention layers alone.  Apart from ``kv_bytes_*``, which keep
        meaning paged K/V."""
        return self._state_per_slot * self.num_slots

    def state_bytes_live(self) -> int:
        """Per-slot state bytes of the slots that hold a request."""
        return self._state_per_slot * (self.num_slots - len(self._free))

    def blocks_live(self) -> int:
        """Blocks of the full arena physically held by live slots right
        now."""
        return self.alloc.blocks_in_use

    def window_blocks_live(self) -> int:
        """Blocks of the window arena held right now (0 without one)."""
        return self.ring_alloc.blocks_in_use if self.ring_alloc else 0

    def blocks_committed(self) -> int:
        """Blocks of the full arena admission has committed: physically
        held plus reserved-but-unallocated worst-case budget."""
        return self.alloc.blocks_in_use + self._reserved_total

    def kv_bytes_committed(self) -> int:
        """``blocks_committed`` in bytes, the window arena's held and
        reserved blocks with it."""
        BS = self.block_size
        return self.blocks_committed() * BS * self._full_per_token \
            + (self.window_blocks_live() + self._ring_reserved_total) \
            * BS * self._win_per_token

    def prefix_hit_rate(self) -> float:
        """Shared prompt tokens / total prompt tokens over every
        admission so far (0.0 before any admission)."""
        if not self._prompt_tokens:
            return 0.0
        return self._shared_tokens / self._prompt_tokens

    def prefix_counters(self) -> Tuple[int, int]:
        """Raw ``(shared_tokens, prompt_tokens)`` behind the hit rate —
        what replicas advertise so the router can compute the EXACT
        fleet-level ratio (a mean of per-replica ratios would weight a
        one-request replica like a thousand-request one)."""
        return self._shared_tokens, self._prompt_tokens

    def hot_prefix_hashes(self, top_n: int) -> List[str]:
        """sched/prefix.py digests of the hottest indexed cumulative
        prefixes (ISSUE 19) — the replica_state advertisement the
        ``prefix_affinity`` router policy scores against."""
        from ..sched.prefix import hash_prefix
        return [hash_prefix(toks)
                for toks in self.alloc.hot_prefixes(top_n)]
