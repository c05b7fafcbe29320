"""Block-paged KV cache (serve/slots.py; ISSUE 8).

- BlockAllocator unit coverage: deterministic alloc/free order,
  refcounts, the chain-keyed prefix index (full-block walk + partial
  overlap), immutability/COW bookkeeping, LRU reuse of zero-ref cached
  blocks, deterministic out-of-blocks.
- BlockPool budgets: worst-case reservation at admission, can_admit
  gating while a slot is free but blocks are not, eviction returning
  both blocks and reservation (no compiled step involved — the pool's
  construction is an abstract init trace).
- Engine-level acceptance: shared-prefix and chunked-prefill greedy
  outputs token-identical to one-shot generate(), COW actually firing
  with refcounted sharing, the zero-output-budget rejection satellite,
  and block-budget head-of-line queueing keeping FIFO order.

Engine tests ride the session's SLOTS=4 / MAX_LEN=32 / block-size-8
geometry, so the ONE paged decode program test_serve.py already
compiles serves here too (suite-budget constraint: no new compiles).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu.models.gpt import generate, gpt_tiny
from apex_example_tpu.serve import (BlockAllocator, BlockPool, Request,
                                    ServeEngine, synthetic_requests)

pytestmark = pytest.mark.serve

SLOTS, MAX_LEN, BS = 4, 32, 8


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _run(model, params, requests, rng_seed=0, **kw):
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(rng_seed), **kw)
    eng.queue.submit_all(requests)
    eng.queue.close()
    eng.run(max_steps=2000)
    return eng


def _ref_tokens(model, params, prompt, n):
    P = len(prompt)
    ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_len=MAX_LEN)
    return np.asarray(ref)[0, P:P + n].tolist()


# ============================ allocator =============================

def test_allocator_alloc_free_deterministic():
    a = BlockAllocator(4, 8)
    assert a.available() == 4 and a.blocks_in_use == 0
    got = [a.alloc() for _ in range(4)]
    assert got == [0, 1, 2, 3]               # deterministic pop order
    assert a.available() == 0 and a.blocks_in_use == 4
    with pytest.raises(RuntimeError, match="out of KV blocks"):
        a.alloc()
    a.unref(2)
    assert a.available() == 1
    assert a.alloc() == 2                    # unindexed free: LIFO reuse
    with pytest.raises(RuntimeError, match="unref of free"):
        a.unref(2)
        a.unref(2)
    with pytest.raises(ValueError, match="num_blocks"):
        BlockAllocator(0, 8)
    with pytest.raises(ValueError, match="block_size"):
        BlockAllocator(4, 0)


def test_allocator_refcount_sharing():
    a = BlockAllocator(4, 4)
    b0 = a.alloc()
    assert a.refcount[b0] == 1 and not a.immutable(b0)
    key = a.register_full(None, (1, 2, 3, 4), b0)
    assert a.immutable(b0)
    a.ref(b0)                                # second slot maps it
    assert a.refcount[b0] == 2
    a.unref(b0)
    a.unref(b0)
    # zero refs + indexed: parks in the reusable cache, still matchable
    assert a.available() == 4
    shared, bids, keys = a.match_prefix([1, 2, 3, 4, 9])
    assert shared == 4 and bids == [b0] and keys == [key]


def test_allocator_prefix_chain_and_partial_overlap():
    a = BlockAllocator(8, 4)
    # chain: block A = tokens 0..3, block B = 4..7 (child of A)
    ba, bb = a.alloc(), a.alloc()
    ka = a.register_full(None, (10, 11, 12, 13), ba)
    a.register_full(ka, (14, 15, 16, 17), bb)
    # exact 2-block walk, capped one short of the full prompt
    shared, bids, _ = a.match_prefix([10, 11, 12, 13, 14, 15, 16, 17])
    assert shared == 7 and bids == [ba, bb]
    # full chain + divergent tail: only the matching prefix is shared
    shared, bids, _ = a.match_prefix([10, 11, 12, 13, 99, 15])
    assert shared == 4 and bids == [ba]
    # partial overlap INTO an indexed block (the COW case): 2 tokens of
    # B match, so B is mapped read-only for positions 4-5
    shared, bids, _ = a.match_prefix([10, 11, 12, 13, 14, 15, 99])
    assert shared == 6 and bids == [ba, bb]
    # chain keys encode the whole prefix: same content under a
    # different parent must NOT match
    bc = a.alloc()
    a.register_full(None, (14, 15, 16, 17), bc)
    shared, bids, _ = a.match_prefix([14, 15, 16, 17, 1])
    assert shared == 4 and bids == [bc]      # root chain, not A's child
    # no match at all
    assert a.match_prefix([1, 2, 3])[0] == 0


def test_allocator_lru_reuse_eviction():
    a = BlockAllocator(2, 2)
    b0, b1 = a.alloc(), a.alloc()
    k0 = a.register_full(None, (1, 2), b0)
    a.register_full(None, (3, 4), b1)
    a.unref(b0)                              # parked first -> LRU oldest
    a.unref(b1)
    assert a.available() == 2
    # allocation under pressure evicts the LRU reusable block (b0) and
    # deregisters its index entry; b1's stays matchable
    got = a.alloc()
    assert got == b0
    assert a.match_prefix([1, 2, 9])[0] == 0          # k0 evicted
    assert a.match_prefix([3, 4, 9])[0] == 2          # b1 still cached
    assert k0 not in a._index


def test_allocator_duplicate_chain_keeps_first():
    a = BlockAllocator(4, 2)
    b0, b1 = a.alloc(), a.alloc()
    a.register_full(None, (5, 6), b0)
    a.register_full(None, (5, 6), b1)        # same chain, parallel slot
    shared, bids, _ = a.match_prefix([5, 6, 7])
    assert bids == [b0]                      # first registration wins
    assert a.immutable(b1)                   # duplicate still immutable
    a.unref(b1)
    assert a.available() == 3                # unindexed: plain free


# ============================ pool budgets ==========================

def test_pool_reservation_and_can_admit(model_and_params):
    """Worst-case block budgets gate admission even with a slot free,
    and eviction returns blocks + unspent reservation."""
    model, _ = model_and_params
    pool = BlockPool(model, num_slots=2, max_len=16, block_size=8,
                     num_blocks=2)
    # r1 needs ceil((3+13)/8) = 2 blocks -> the whole arena
    r1 = Request(prompt=[1, 2, 3], max_new_tokens=16)
    r2 = Request(prompt=[4, 5, 6], max_new_tokens=16)
    assert pool.blocks_needed(r1) == 2 and pool.fits(r1)
    assert pool.can_admit(r1)
    idx = pool.admit(r1, step=0)
    assert pool.free_count == 1              # a slot IS free...
    assert not pool.can_admit(r2)            # ...but no block budget
    assert pool.blocks_committed() == 2
    pool.evict(idx)
    assert pool.can_admit(r2)                # budget released
    assert pool.blocks_committed() == 0
    # a request that can NEVER fit is rejected up front, not queued
    huge = Request(prompt=[1] * 15, max_new_tokens=1)   # 2 blocks, fits
    assert pool.fits(huge)
    pool2 = BlockPool(model, num_slots=1, max_len=16, block_size=8,
                      num_blocks=1)
    assert not pool2.fits(huge)              # needs 2 > arena's 1
    full = Request(prompt=[1] * 16, max_new_tokens=4)
    assert pool.max_new_for(full) == 0 and not pool.fits(full)


def test_pool_stage_commit_cow(model_and_params):
    """stage_writes maps/COWs exactly the tick's span; commit_writes
    registers blocks as they fill; a second slot sharing the chain
    triggers COW at its first divergent write."""
    model, _ = model_and_params
    pool = BlockPool(model, num_slots=2, max_len=16, block_size=8)
    ra = Request(prompt=list(range(100, 110)), max_new_tokens=6)  # 10+6
    ia = pool.admit(ra, step=0)
    assert pool.slots[ia].reserved == 2
    assert pool.stage_writes(ia, 8) == (-1, -1)        # fresh block 0
    pool.commit_writes(ia, 8)                          # block 0 full
    assert pool.slots[ia].block_keys[0] is not None    # registered
    assert pool.alloc.immutable(int(pool.table[ia, 0]))
    assert pool.stage_writes(ia, 2) == (-1, -1)        # fresh block 1
    pool.commit_writes(ia, 2)
    assert pool.slots[ia].reserved == 0
    # rb shares ra's full block 0 (8 of its 10 prompt tokens)...
    rb = Request(prompt=list(range(100, 110)), max_new_tokens=6)
    ib = pool.admit(rb, step=1)
    slot_b = pool.slots[ib]
    assert slot_b.shared_len == 8 and slot_b.cursor == 8
    assert int(pool.table[ib, 0]) == int(pool.table[ia, 0])
    assert pool.alloc.refcount[int(pool.table[ia, 0])] == 2
    assert pool.prefix_hit_rate() == 8 / 20
    # ...and rb's first write lands in a FRESH block 1, no COW (ra's
    # block 1 is mutable/private, not indexed, so it never matched)
    src, dst = pool.stage_writes(ib, 2)
    assert (src, dst) == (-1, -1)
    assert int(pool.table[ib, 1]) != int(pool.table[ia, 1])
    pool.commit_writes(ib, 2)
    # now force the COW case: evict ra (its block 1 stays mutable ->
    # freed; block 0 parks reusable), fill a slot whose prompt overlaps
    # partway into a REGISTERED block
    pool.evict(ia)
    pool.evict(ib)
    rc = Request(prompt=list(range(100, 112)), max_new_tokens=2)  # 12+2
    ic = pool.admit(rc, step=2)
    slot_c = pool.slots[ic]
    assert slot_c.shared_len == 8            # full block 0 only
    cows_before = pool.cow_copies
    src, dst = pool.stage_writes(ic, 4)
    assert (src, dst) == (-1, -1) and pool.cow_copies == cows_before
    pool.commit_writes(ic, 4)                # block 1 (12 tokens) not full
    pool.evict(ic)
    # rd overlaps 4 tokens into rc's... rc's block 1 never filled, so
    # build the COW against a filled chain: re-admit rc's twin and run
    # it to fill block 1, then share partially into it
    re_ = Request(prompt=list(range(100, 112)), max_new_tokens=6)  # 12+6
    ie = pool.admit(re_, step=3)
    assert pool.slots[ie].cursor == 8        # rode block 0 again
    pool.stage_writes(ie, 4)                 # remaining prompt chunk
    pool.commit_writes(ie, 4)                # cursor 12
    for g in range(4):                       # decode through 16, engine
        pool.slots[ie].tokens.append(200 + g)  # order: append after
        pool.stage_writes(ie, 1)               # the PREVIOUS commit
        pool.commit_writes(ie, 1)
    assert pool.slots[ie].cursor == 16
    assert pool.slots[ie].block_keys[1] is not None  # block 1 full
    rf = Request(prompt=list(range(100, 111)), max_new_tokens=4)  # 11+4
    if_ = pool.admit(rf, step=4)
    assert pool.slots[if_].shared_len == 10  # 8 + 2-token overlap
    assert pool.alloc.refcount[int(pool.table[ie, 1])] == 2
    src, dst = pool.stage_writes(if_, 1)     # first divergent write
    assert src == int(pool.table[ie, 1]) and dst >= 0
    assert pool.cow_copies == cows_before + 1
    assert int(pool.table[if_, 1]) == dst    # remapped to the copy
    assert pool.alloc.refcount[src] == 1     # back to ie alone


# ====================== engine-level acceptance =====================

def test_shared_prefix_token_identity_and_cow(model_and_params):
    """The gold standard under prefix sharing: a --shared-prefix-style
    workload (20-token common system prompt: two full shared blocks
    PLUS a 4-token overlap into the third) stays token-identical to
    one-shot generate() per request, while the pool actually shares
    (hit rate > 0, refcounted blocks) and copy-on-writes at the first
    divergent token inside the partially-shared block."""
    model, params = model_and_params
    reqs = synthetic_requests(6, vocab_size=model.vocab_size, seed=7,
                              prompt_len=(3, 6), max_new=(4, 8),
                              stagger=3, shared_prefix=20)
    assert all(r.prompt[:20] == reqs[0].prompt[:20] for r in reqs)
    eng = _run(model, params, reqs)
    assert eng.counts["ok"] == 6
    for c in eng.completions:
        assert c.tokens == _ref_tokens(model, params,
                                       list(c.request.prompt),
                                       len(c.tokens)), c.request.uid
    # the 20-token prefix rides 2 full shared blocks per later arrival
    assert eng.pool.prefix_hit_rate() > 0.4
    assert eng.pool.cow_copies >= 1          # divergence inside block 2
    s = eng.summary_record()
    assert s["prefix_hit_rate"] == round(eng.pool.prefix_hit_rate(), 4)
    assert s["cow_copies"] == eng.pool.cow_copies
    # sharing packs the arena: waste stays under the acceptance bar
    # even with every request carrying a 16-token system prompt
    assert s["kv_waste_pct"] <= 40.0


def test_chunked_prefill_token_identity_and_speed(model_and_params):
    """A prompt spanning multiple blocks prefills at up to block_size
    tokens per tick through the same compiled step: outputs stay
    token-identical to generate(), and TTFT-in-ticks collapses from
    n_prompt to ceil(n_prompt / block_size)."""
    model, params = model_and_params
    prompt = [int(t) for t in
              np.random.RandomState(11).randint(0, model.vocab_size, 20)]
    req = Request(prompt=prompt, max_new_tokens=8)
    eng = _run(model, params, [req])
    comp = eng.completions[0]
    assert comp.status == "ok" and len(comp.tokens) == 8
    assert comp.tokens == _ref_tokens(model, params, prompt, 8)
    # 3 prefill ticks (8+8+4 tokens; the first token arrives with the
    # prompt-crossing chunk) + 7 more decode ticks
    assert eng.step_count == 10
    # mixed with short requests: chunked prefill must not perturb a
    # concurrently decoding slot's stream
    short = Request(prompt=[5, 9, 13], max_new_tokens=10)
    long_ = Request(prompt=prompt, max_new_tokens=6, arrival_step=2)
    eng2 = _run(model, params, [short, long_])
    assert eng2.counts["ok"] == 2
    for c in eng2.completions:
        assert c.tokens == _ref_tokens(model, params,
                                       list(c.request.prompt),
                                       len(c.tokens)), c.request.uid


def test_admission_rejects_zero_output_budget(model_and_params,
                                              tmp_path):
    """The ISSUE 8 satellite bugfix: a request whose prompt fills the
    cache (max_new_for == 0) used to occupy a slot and 'complete' with
    zero tokens; now it terminates at admission with first-class
    status 'rejected' (request_failed record, summary count,
    availability debit) and never touches a slot."""
    from apex_example_tpu import obs
    from apex_example_tpu.obs import schema as obs_schema
    model, params = model_and_params
    path = str(tmp_path / "rej.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={}, arch="gpt_tiny")
    full = Request(prompt=list(range(MAX_LEN)), max_new_tokens=4)
    okr = Request(prompt=[1, 2, 3], max_new_tokens=4)
    eng = _run(model, params, [full, okr], sink=sink,
               run_id=emitter.run_id)
    sink.write(eng.summary_record())
    sink.close()
    assert eng.counts["rejected"] == 1 and eng.counts["ok"] == 1
    comp = next(c for c in eng.completions if c.request is full)
    assert comp.status == "rejected" and comp.slot == -1
    assert comp.tokens == [] and comp.ttft_s is None
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    failed = next(r for r in recs if r["record"] == "request_failed")
    assert failed["status"] == "rejected"
    assert failed["request_id"] == full.uid
    summary = recs[-1]
    assert summary["rejected"] == 1 and summary["completed"] == 1
    assert summary["availability"] == 0.5


def test_block_budget_queueing_is_fifo(model_and_params):
    """Out-of-blocks at admission resolves as deterministic
    head-of-line queueing: with a 12-block arena, three hogs book the
    whole arena (4 blocks each) while a SLOT still sits free — the
    tiny head request waits at the queue front (the later arrival does
    not jump it), admits as soon as an eviction frees its budget, and
    every request completes token-identically.  (The default arena is
    dense-capacity sized, where a free slot always implies free
    blocks; shrinking it is the only way to exercise this path — the
    one extra decode-step compile in the suite, ~tiny-GPT sized.)"""
    model, params = model_and_params
    hogs = [Request(prompt=[i + 1] * 8, max_new_tokens=24)
            for i in range(3)]                    # 4 blocks each -> 12
    tiny = Request(prompt=[60, 61], max_new_tokens=2)     # 1 block
    late = Request(prompt=[70, 71, 72], max_new_tokens=2,
                   arrival_step=1)                # behind tiny in FIFO
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      num_blocks=12, rng=jax.random.PRNGKey(0))
    eng.queue.submit_all(hogs + [tiny, late])
    eng.queue.close()
    eng.step()
    # hogs admitted and fully booked; tiny is BLOCK-gated though a
    # slot is free, and holds the line for late (FIFO preserved)
    assert sorted(c.request.uid for c in eng.completions) == []
    assert len(eng.pool.live) == 3 and eng.pool.free_count == 1
    assert eng.pool.blocks_committed() == 12
    assert eng.queue.pending() == 2
    comps = eng.run(max_steps=2000)
    assert eng.counts["ok"] == 5
    by = {c.request.uid: c for c in comps}
    first_evict = min(by[h.uid].finished_step for h in hogs)
    assert by[tiny.uid].admitted_step >= first_evict
    assert by[late.uid].admitted_step >= by[tiny.uid].admitted_step
    for c in comps:
        assert c.tokens == _ref_tokens(model, params,
                                       list(c.request.prompt),
                                       len(c.tokens)), c.request.uid


def test_loadgen_shared_prefix():
    reqs = synthetic_requests(4, vocab_size=100, seed=3, stagger=2,
                              shared_prefix=6, prompt_len=(2, 4))
    head = reqs[0].prompt[:6]
    assert len(head) == 6
    for r in reqs:
        assert list(r.prompt[:6]) == list(head)
        assert 8 <= len(r.prompt) <= 10          # 6 + sampled 2..4
    # deterministic under the seed, including the prefix draw
    again = synthetic_requests(4, vocab_size=100, seed=3, stagger=2,
                               shared_prefix=6, prompt_len=(2, 4))
    assert [r.prompt for r in reqs] == [r.prompt for r in again]
    with pytest.raises(ValueError, match="shared_prefix"):
        synthetic_requests(2, vocab_size=100, shared_prefix=-1)


def test_queue_push_front_preserves_fifo():
    from apex_example_tpu.serve import RequestQueue
    q = RequestQueue()
    a = Request(prompt=[1], max_new_tokens=1)
    b = Request(prompt=[2], max_new_tokens=1)
    q.submit_all([a, b])
    q.close()                                # engine hand-back still works
    got = q.pop(0)
    assert got is a
    q.push_front(got)
    assert q.pop(0) is a and q.pop(0) is b


# =================== in-place arena updates (ISSUE 25) ==============
#
# The tick donates the cache and the arena leaf is [NB, BS, H*D]: COW,
# write and gather address one layout, so the program updates the
# arena in place.  What that could break is tested here: a caller
# still holding the old leaves, a write clobbering the block a COW
# copies from in the same tick, the served tokens, and the handoff
# payload's row shape.  (tests/test_arena_inplace.py reads the same
# property off the compiled program.)

_ARENA_MODES = {"float32": {}, "kv_quant": {"kv_quant": True},
                "speculative": {"speculate": 3}}


def _arena_leaves(pool):
    """Leaves by the path string handoff payloads are keyed by."""
    from apex_example_tpu.ops import paged_cache
    return {path: leaf for path, leaf, _ in paged_cache.block_leaves(
        pool.cache, pool.num_blocks, pool.block_size)}


@pytest.mark.parametrize("mode", sorted(_ARENA_MODES) + ["cost_model"])
def test_tick_deletes_the_leaves_passed_in_and_rebinds_the_cache(
        model_and_params, mode):
    """``cost_model``: obs/costmodel.instrument runs the step through
    ``lower().compile()``; the compiled executable must keep the
    donation the jitted function declares."""
    from apex_example_tpu import obs
    from apex_example_tpu.obs import costmodel
    model, params = model_and_params
    cm = obs.CostModel() if mode == "cost_model" else None
    costmodel.set_default(cm)
    try:
        eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                          rng=jax.random.PRNGKey(0),
                          **_ARENA_MODES.get(mode, {}))
    finally:
        costmodel.set_default(None)
    eng.queue.submit_all([Request(prompt=list(range(3, 15)),
                                  max_new_tokens=4)])
    eng.queue.close()
    for _ in range(3):                       # prefill, prefill, decode
        old = jax.tree_util.tree_leaves(eng.pool.cache)
        assert eng.step()
        assert all(leaf.is_deleted() for leaf in old)
        new = jax.tree_util.tree_leaves(eng.pool.cache)
        assert not any(leaf.is_deleted() for leaf in new)
        assert [l.shape for l in new] == [l.shape for l in old]
    payload = [l for l in new if l.ndim == 3]
    assert payload and all(
        l.shape == (eng.pool.num_blocks, BS, model.hidden_size)
        for l in payload)
    assert any(np.asarray(l).any() for l in payload)   # really written
    eng.run(max_steps=100)
    assert eng.counts["ok"] == 1
    if cm is not None:
        assert cm.compile_counts == {"serve_decode_step": 1}


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["float32", "kv_quant"])
def test_cow_then_write_in_one_tick_leaves_the_source_block_bit_identical(
        model_and_params, kv_quant):
    """One tick copies block ``src`` to ``dst`` AND writes a token into
    ``dst`` (the first divergent write after a shared prefix).  In
    place, the write must land in the copy and nowhere else: ``src``
    stays bit-identical, ``dst`` is ``src`` except the written row,
    every other block keeps its bytes."""
    from apex_example_tpu.serve.engine import TickArgs, _slot_step
    model, params = model_and_params
    pool = BlockPool(model, num_slots=SLOTS, max_len=MAX_LEN,
                     block_size=BS, kv_quant=kv_quant)
    layout = TickArgs(BS, pool.max_blocks)
    step = _slot_step(pool.dec, layout)
    src, dst, row = 3, 5, 4
    rng = jax.random.PRNGKey(0)
    none = np.full((SLOTS,), -1, np.int32)
    table = np.zeros((SLOTS, pool.max_blocks), np.int32)

    def tick(tok, table, fill, n_new, cow_src, cow_dst):
        packed, f = layout.blank(SLOTS)
        for name, value in dict(tok=tok, block_table=table, fill=fill,
                                n_new=n_new, cow_src=cow_src,
                                cow_dst=cow_dst).items():
            f[name][...] = value
        pool.cache, nxt, finite = step(params, pool.cache,
                                       jnp.asarray(packed), rng)
        assert bool(np.asarray(finite)[0])
        return {k: np.asarray(v) for k, v in _arena_leaves(pool).items()}

    # tick 1: slot 0 fills block ``src`` with a whole chunk
    tok = np.zeros((SLOTS, BS), np.int32)
    tok[0] = np.arange(10, 10 + BS)
    table[0, 0] = src
    before = tick(tok, table, [0] * SLOTS, [BS, 0, 0, 0], none, none)
    assert all(v[src].any() for v in before.values())
    assert not any(v[dst].any() for v in before.values())
    # tick 2: slot 1 shares the first ``row`` tokens, COWs src -> dst
    # and writes its divergent token at row ``row`` of dst
    tok = np.zeros((SLOTS, BS), np.int32)
    tok[1, 0] = 99
    table[1, 0] = dst
    after = tick(tok, table, [0, row, 0, 0], [0, 1, 0, 0],
                 [-1, src, -1, -1], [-1, dst, -1, -1])
    assert sorted(after) == sorted(before)
    for name, a in after.items():
        b = before[name]
        np.testing.assert_array_equal(a[src], b[src], err_msg=name)
        keep = np.arange(BS) != row
        np.testing.assert_array_equal(a[dst][keep], b[src][keep],
                                      err_msg=name)
        assert not np.array_equal(a[dst][row], b[src][row]), name
        others = [i for i in range(pool.num_blocks) if i not in (src, dst)]
        np.testing.assert_array_equal(a[others], b[others], err_msg=name)


@pytest.mark.parametrize("mode", sorted(_ARENA_MODES))
def test_mixed_prefill_decode_run_serves_the_reference_tokens(
        model_and_params, mode):
    """Long prompts prefilling in chunks beside slots that decode, with
    a shared prefix so COWs fire: float32 and speculative decoding
    serve generate()'s tokens exactly; the int8 arena serves what each
    request gets when it runs alone (its own quantised reference)."""
    model, params = model_and_params
    rs = np.random.RandomState(25)
    shared = [int(t) for t in rs.randint(0, model.vocab_size, 12)]
    reqs = []
    for i, (extra, new) in enumerate([(9, 6), (1, 8), (12, 5), (3, 7),
                                      (6, 6), (2, 8)]):
        tail = [int(t) for t in rs.randint(0, model.vocab_size, extra)]
        reqs.append(Request(prompt=shared + tail, max_new_tokens=new,
                            arrival_step=2 * i))
    eng = _run(model, params, reqs, **_ARENA_MODES[mode])
    assert eng.counts["ok"] == len(reqs)
    assert eng.pool.cow_copies >= 1 and eng.pool.prefix_hit_rate() > 0
    for c in eng.completions:
        prompt = list(c.request.prompt)
        if mode == "kv_quant":
            solo = _run(model, params,
                        [Request(prompt=prompt,
                                 max_new_tokens=c.request.max_new_tokens)],
                        kv_quant=True)
            want = solo.completions[0].tokens
        else:
            want = _ref_tokens(model, params, prompt, len(c.tokens))
        assert c.tokens == want, (mode, c.request.uid)


@pytest.mark.parametrize("kv_quant", [False, True],
                         ids=["float32", "kv_quant"])
def test_handoff_export_import_round_trips_bit_exactly(model_and_params,
                                                       kv_quant):
    """extract_blocks -> admit_prefilled -> extract_blocks gives the
    same bytes back, rows shaped like the arena leaf's ([BS, H*D]
    payload, [BS] scales); the import writes in place (its leaves are
    donated) and touches no block but the ones it allocated."""
    model, params = model_and_params
    src = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0), kv_quant=kv_quant)
    req = Request(prompt=list(range(40, 53)), max_new_tokens=6)
    src.queue.submit_all([req])
    src.queue.close()
    for _ in range(4):                       # 13-token prompt + 2 decodes
        src.step()
    idx = src.pool.live[0]
    fill, n, payload = src.pool.extract_blocks(idx)
    assert fill == 15 and n == 2
    assert len(payload) == (4 if kv_quant else 2) * model.num_layers
    for key, rows in payload.items():
        want = (n, BS) if key.endswith("_scale") \
            else (n, BS, model.hidden_size)
        assert rows.shape == want, key
        assert rows.any(), key

    dst = BlockPool(model, num_slots=SLOTS, max_len=MAX_LEN, block_size=BS,
                    kv_quant=kv_quant)
    dst.alloc.alloc()                        # so the import lands off 0
    old = jax.tree_util.tree_leaves(dst.cache)
    assert dst.can_admit_prefilled(req)
    j = dst.admit_prefilled(req, step=0, fill=fill, payload=payload,
                            tokens=list(src.pool.slots[idx].tokens))
    assert all(leaf.is_deleted() for leaf in old)
    fill2, n2, back = dst.extract_blocks(j)
    assert (fill2, n2) == (fill, n) and sorted(back) == sorted(payload)
    for key in payload:
        assert back[key].dtype == payload[key].dtype, key
        np.testing.assert_array_equal(back[key], payload[key], err_msg=key)
    mapped = set(int(b) for b in dst.table[j, :n])
    assert 0 not in mapped
    for key, leaf in _arena_leaves(dst).items():
        rest = [i for i in range(dst.num_blocks) if i not in mapped]
        assert not np.asarray(leaf)[rest].any(), key
