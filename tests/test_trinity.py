"""models/trinity.py, its window leaves and the paged GQA kernel (CPU,
float32, the tiny size: a window of 8 over blocks of 4, so a request of 40
tokens crosses the window, wraps the ring and frees blocks).

Tolerances.  Logits lie within +-4 (unit-scale hidden state, a head at
1/sqrt(d)).  The model's float32 forward reads 5e-6 from the plain
reference (orders of summation), the engine's chunked path with the
interpreted kernel (an online softmax a tile at a time) the same: ``TOL``
1e-4 is twenty times that.  The faults of the last test read 3e-3 to 1 and
more: a window off by one, a rotation on the full layer, a missing gate,
bfloat16 norm statistics."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_example_tpu.models import trinity as tr  # noqa: E402
from apex_example_tpu.ops import _config as ops_config  # noqa: E402
from apex_example_tpu.ops import attention, paged_cache  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve import engine as engine_lib  # noqa: E402
from apex_example_tpu.serve.engine import TickArgs  # noqa: E402
from benchmarks import harness  # noqa: E402

pytestmark = pytest.mark.serve

REF, _ = harness.load_reference("benchmarks/reference/trinity.py:trinity")
W, F = tr.WINDOW, tr.FULL
RCFG = dict(vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, route_scale=2.826,
            sliding_window=8, layer_types=[W, W, W, W, F],
            num_dense_layers=1, rms_norm_eps=1e-5, rope_theta=10000.0,
            block=8)
SLOTS, MAX_LEN, BS = 3, 64, 4
RING = 4                       # ceil((8 + 4) / 4) + 1
TOL = 1e-4


@pytest.fixture(scope="module")
def model():
    m = tr.trinity_tiny()
    assert list(m.layer_kinds()) == RCFG["layer_types"]
    return m


@pytest.fixture(scope="module")
def params():
    return REF.trinity_weights(jax.random.PRNGKey(0), RCFG,
                               jnp.float32)["params"]


def _ref_logits(params, cfg=RCFG):
    fn = jax.jit(lambda ids: REF.trinity_logits(params, ids, cfg))

    def of(seq):
        ids = np.zeros((1, MAX_LEN), np.int32)         # one shape, one compile
        ids[0, :len(seq)] = seq
        return np.asarray(fn(jnp.asarray(ids)))[0, :len(seq)]
    return of


@pytest.fixture(scope="module")
def ref_logits(params):
    return _ref_logits(params)


def _engine(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    return ServeEngine(model, params, max_len=MAX_LEN, block_size=BS, **kw)


def _record_logits(eng):
    """Put a step of the test's own in the engine's place that is the
    engine's program (the same module clone, the same arguments, greedy)
    and also hands out the logits: ``seen[uid][position] = logits row`` for
    every lane the engine sampled or could have."""
    seen = {}
    dec = eng.pool.dec

    @jax.jit
    def step(params, cache, packed):
        said = eng.tick_args.fields(packed)
        logits, mut = dec.apply(
            {"params": params, "cache": cache}, said["tok"], train=False,
            paged=engine_lib._paged(said), mutable=["cache", "counters"])
        return mut["cache"], logits[:, 0], mut["counters"]

    def recording(*a):
        cache, last, counters = step(*a[:3])
        said = eng.tick_args.fields(np.asarray(a[2]))
        fill, n_new = said["fill"], said["n_new"]
        for i, slot in enumerate(eng.pool.slots):
            if slot is not None and n_new[i]:
                seen.setdefault(slot.request.uid, {})[
                    int(fill[i] + n_new[i] - 1)] = np.asarray(last[i])
        return (cache, jnp.argmax(last, -1).astype(jnp.int32),
                jnp.all(jnp.isfinite(last), -1), counters)

    eng._step_fn = recording
    return seen


def _requests(lens, new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=k, uid=f"r{i}")
            for i, (n, k) in enumerate(zip(lens, new))]


def _run(eng, reqs, on_tick=None):
    for r in reqs:
        eng.submit(r)
    eng.queue.close()
    done = eng.run(max_steps=2000, on_tick=on_tick)
    return {c.request.uid: c for c in done if c.status == "ok"}


def _worst(done, seen, ref_logits):
    """Widest distance of a recorded logits row from the reference's full
    forward over the finished sequence, every request, every position."""
    worst = 0.0
    for uid, c in done.items():
        want = ref_logits(list(c.request.prompt) + list(c.tokens))
        for at, row in seen[uid].items():
            worst = max(worst, float(np.max(np.abs(row - want[at]))))
    return worst


def _at_rest(pool):
    """Both allocators back at their first counts, nothing reserved."""
    return (pool.alloc.available() == pool.num_blocks
            and pool.ring_alloc.available() == pool.num_slots * RING
            and pool._reserved_total == 0 and pool._ring_reserved_total == 0
            and not pool.ring_table.any() and not pool.table.any())


# ------------------------------------------------------------- the model

def test_seeded_layout_is_the_models_own(model, params):
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
    assert shape(init) == shape(params)


def test_plain_forward_matches_the_reference(model, params):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)))
    got = model.apply({"params": params}, ids)
    want = REF.trinity_logits(params, ids, RCFG)
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 256)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert 0.5 < float(jnp.std(want)) < 2.0


def test_the_published_pattern_follows_from_the_period():
    big = tr.TrinityForCausalLM()
    assert big.layer_kinds() == (W, W, W, F) * 8
    assert tr.trinity_mini_cut().layer_kinds() == (W, W, W, W, F)
    with pytest.raises(ValueError, match="layer_types"):
        tr.TrinityForCausalLM(num_layers=3, layer_types=(W, F)).layer_kinds()


def test_parameters_of_the_configuration_file_are_the_models_own():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "trinity_mini.json"))
    m = harness.resolve(cfg["model"]["builder"])(**cfg["model"]["kwargs"])
    assert m == tr.trinity_mini_cut()
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    p = cfg["parameters"]
    assert count(shapes["layer_0"]["attn"]) == p["attention"]
    assert count(shapes["layer_0"]) == p["dense_layer"]
    assert count(shapes["layer_1"]) == p["expert_layer_whole"]
    assert count(shapes) == p["held"] == 4241534720


# --------------------------------------------- through the engine's pool

def test_chunked_prefill_then_decode_gives_the_references_logits(
        model, params, ref_logits):
    """Requests of 3 to 6 windows (24 to 56 tokens against a window of 8),
    short ones between them in one queue of 3 slots, one cancelled in the
    middle of its decode and asked again (a preemption): every logits row
    the engine sampled from is the reference's full pass's, through the
    interpreted kernel, a ring that wrapped and blocks handed back."""
    assert ops_config.INTERPRET and not ops_config.FORCE_XLA
    eng = _engine(model, params)
    pool = eng.pool
    assert (pool.window, pool.ring_blocks) == (8, RING)
    seen = _record_logits(eng)
    reqs = _requests([30, 5, 41, 9, 17, 26], [12, 6, 15, 30, 7, 5])
    wrapped, again = [], []

    def on_tick(e):
        wrapped.extend(s.ring_hi for s in e.pool.slots if s is not None)
        for s in e.pool.slots:
            if s is not None and s.request.uid == "r2" and not again \
                    and s.n_generated == 4:
                # preempt: evicted mid-flight with both arenas' blocks,
                # and asked again from the start
                held = pool.ring_alloc.blocks_in_use
                assert e.cancel("r2") and held > pool.ring_alloc.blocks_in_use
                again.append(Request(prompt=list(reqs[2].prompt),
                                     max_new_tokens=15, uid="r2again"))
                e.queue._closed = False
                e.submit(again[0])
                e.queue.close()

    done = _run(eng, reqs, on_tick)
    assert set(done) == {"r0", "r1", "r3", "r4", "r5", "r2again"} and again
    assert [len(done[u].tokens) for u in ("r0", "r1", "r3", "r2again")] \
        == [12, 6, 30, 15]
    assert _worst(done, seen, ref_logits) < TOL
    # the preempted request's second life gave the first's tokens
    cancelled = next(c for c in eng.completions if c.request.uid == "r2")
    assert cancelled.status == "cancelled" and len(cancelled.tokens) == 4 \
        and done["r2again"].tokens[:4] == cancelled.tokens
    # the ring wrapped (logical blocks past its 4 columns) and gave back
    assert max(wrapped) > 2 * RING and pool.window_blocks_released > 30
    assert _at_rest(pool)


def test_the_xla_form_serves_the_same_tokens(model, params):
    reqs = lambda: _requests([30, 5, 41], [12, 6, 15])
    kernel = _run(_engine(model, params), reqs())
    with ops_config.force_xla():
        xla = _run(_engine(model, params), reqs())
    assert {u: c.tokens for u, c in kernel.items()} \
        == {u: c.tokens for u, c in xla.items()} and len(xla) == 3


def test_a_reused_slot_and_ring_give_the_logits_of_a_fresh_engine(
        model, params, ref_logits):
    """One slot, three requests one after another: the second and third
    find the ring's columns and the window arena's blocks as the first
    left them (stale rows, mapped to other positions)."""
    eng = _engine(model, params, num_slots=1)
    seen = _record_logits(eng)
    done = _run(eng, _requests([33, 9, 21], [9, 20, 4], seed=3))
    assert len(done) == 3 and _worst(done, seen, ref_logits) < TOL
    assert _at_rest(eng.pool)


# ------------------------------------------------ the allocator's invariants

def test_the_window_arena_is_a_ring_a_slot_not_a_page_a_slot(model, params):
    eng = _engine(model, params)
    pool = eng.pool
    row = 2 * 16 * 4                       # K or V of one token, float32
    leaves = paged_cache.window_leaves(pool.cache)
    assert len(leaves) == 8 and {w for _, _, w in leaves} == {8}
    for _, leaf, _ in leaves:
        assert leaf.shape == (SLOTS * RING, BS, 32)
    full = paged_cache.block_leaves(pool.cache, pool.num_blocks, BS)
    assert len(full) == 2 and pool.num_blocks == SLOTS * MAX_LEN // BS
    assert pool.kv_bytes_reserved() \
        == 8 * SLOTS * RING * BS * row + 2 * SLOTS * MAX_LEN * row
    assert pool.kv_bytes_per_token() == 10 * row
    assert pool.ring_table.shape == (SLOTS, RING)
    assert eng.tick_args == TickArgs(BS, MAX_LEN // BS, False, RING)


def test_blocks_are_reserved_drawn_and_handed_back_while_a_request_runs(
        model, params):
    eng = _engine(model, params)
    pool = eng.pool
    short, long = _requests([6, 30], [3, 12], seed=5)
    assert pool.blocks_needed(short) == 3 and pool._ring_needed(short) == 3
    assert pool.blocks_needed(long) == 11 and pool._ring_needed(long) == RING
    eng.submit(long)
    eng.queue.close()
    held, counted = [], []
    while not eng.work_drained() or pool.any_live():
        eng.step()
        s = pool.slots[0]
        if s is None:
            continue
        # reserved + held is what admission promised, at every tick
        assert s.ring_reserved + (s.ring_hi - s.ring_lo) == RING
        assert pool._ring_reserved_total == s.ring_reserved
        assert pool.window_blocks_live() == s.ring_hi - s.ring_lo <= RING
        # a block is held iff a lane to come may still see it
        assert s.ring_lo == max(0, s.cursor - 8 + 1) // BS
        assert pool.window_tokens_held()[0] == s.cursor - s.ring_lo * BS
        held.append(s.ring_hi - s.ring_lo)
        counted.append(eng.counter_log[-1][1])
    # 41 positions written: the blocks whose last position lies 8 or more
    # behind the fill, 0..7 of 0..10, came back before the request ended
    assert max(held) == RING - 1 and pool.window_blocks_released == 8
    # every tick's record carries the gauges, 0 included
    for tree in counted:
        assert {"window_blocks_released", "window_tokens_held",
                "full_tokens_held", "attn_positions_walked",
                "lanes_live"} <= set(tree)
    assert sum(int(t["window_blocks_released"].sum())
               for _, t in eng.counter_log) == 8
    last = counted[-1]
    assert int(last["full_tokens_held"].sum()) \
        > int(last["window_tokens_held"].sum()) > 0
    assert _at_rest(pool)


def test_a_failed_request_frees_both_arenas(model, params):
    from apex_example_tpu.resilience.faults import SERVE_KINDS, FaultPlan
    eng = _engine(model, params,
                  fault=FaultPlan("slot_fail", 9, kinds=SERVE_KINDS))
    done = _run(eng, _requests([30, 22], [12, 10], seed=7))
    assert eng.counts["failed"] == 1 and len(done) == 1
    assert _at_rest(eng.pool)


def test_a_pool_with_window_leaves_shares_no_prefix(model, params):
    eng = _engine(model, params)
    same = np.random.default_rng(1).integers(0, 256, 24).tolist()
    reqs = [Request(prompt=list(same), max_new_tokens=6, uid=f"p{i}")
            for i in range(3)]
    done = _run(eng, reqs)
    assert eng.pool.prefix_hit_rate() == 0.0 and eng.pool.cow_copies == 0
    assert done["p0"].tokens == done["p1"].tokens == done["p2"].tokens
    assert _at_rest(eng.pool)


def test_what_a_window_leaf_cannot_do_is_refused_with_the_reason(
        model, params):
    with pytest.raises(ValueError, match="kv_quant.*window leaf"):
        _engine(model, params, kv_quant=True)
    with pytest.raises(ValueError, match="speculate.*ring"):
        _engine(model, params, speculate=2)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="role.*window leaves"):
            _engine(model, params, role=role, handoff_sink=lambda h: None)
    with pytest.raises(ValueError, match="tensor_parallel"):
        _engine(model.clone(tensor_parallel=True), params)
    # a request is not migrated out of a ring either
    eng = _engine(model, params)
    eng.submit(_requests([12], [8])[0])
    for _ in range(4):
        eng.step()
    with pytest.raises(ValueError, match="window leaves.*ring"):
        eng.extract_live("r0")
    with pytest.raises(ValueError, match="window leaves"):
        eng.pool.admit_prefilled(_requests([4], [2])[0], 0, 4, {}, [1] * 5)


# -------------------------------------------------------------- the kernel

def _kernel_case(seed, window, S=6, C=4, Hq=4, Hk=2, hd=16, max_len=64):
    rng = np.random.default_rng(seed)
    R = paged_cache.ring_blocks(window, BS) if window else None
    cols = R or max_len // BS
    NB = S * cols
    arena = lambda: jnp.asarray(rng.normal(size=(NB, BS, Hk * hd)),
                                jnp.float32)
    fill = rng.integers(0, max_len - C, S)
    n_new = rng.integers(0, C + 1, S)
    n_new[0], fill[1], n_new[1] = 0, 0, C        # a dead slot, a first chunk
    perm = rng.permutation(NB)
    table = np.full((S, cols), -1, np.int32)     # unmapped entries: anything
    for s in range(S):
        last = (fill[s] + n_new[s] - 1) // BS if fill[s] + n_new[s] else -1
        for b in range(max(0, last - R + 1) if R else 0, last + 1):
            table[s, b % cols] = perm[s * cols + b % cols]
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)), jnp.float32)
    args = (q, arena(), arena(), jnp.asarray(table),
            jnp.asarray(fill, jnp.int32), jnp.asarray(n_new, jnp.int32))
    walked = [0 if not n_new[s] else
              ((fill[s] + n_new[s] + BS - 1) // BS
               - (max(0, fill[s] - window + 1) // BS if window else 0)) * BS
              for s in range(S)]
    return args, R, walked, n_new


@pytest.mark.parametrize("row_tile", [None, 8])
def test_kernel_takes_a_decoding_slots_rows_in_one_small_tile(row_tile):
    """Groups of 4 over 4 lanes: 16 rows a head, of which a decoding slot
    has 4 live (one small tile of 8), a prefilling one up to all: in one
    row tile of 16, or in two of 8 with no smaller one (``row_tile`` and
    ``pages`` are the kernel's for this: the cell's 128 rows are one
    tile, and a CPU test cannot afford two of those)."""
    args, ring, walked, n_new = _kernel_case(4, 8, Hq=8, Hk=2)
    assert 1 in n_new and n_new.max() > 2
    want, _ = attention.paged_gqa_attention_reference(*args, 0.25, 8, ring)
    got, read = attention._paged_gqa_pallas(*args, 0.25, 8, ring, True,
                                            pages=2, row_tile=row_tile)
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5
    assert list(np.asarray(read)) == walked


@pytest.mark.parametrize("window", [None, 8, 5])
@pytest.mark.parametrize("pages", [None, 1, 3])
def test_kernel_matches_its_reference(window, pages):
    """Random fills, lanes, dead slots; a full table, and rings of two
    windows (5: not a whole number of blocks) that have wrapped; one page a
    tile, three (tiles that end inside the walk) and the whole walk."""
    for seed in range(3):
        args, ring, walked, n_new = _kernel_case(seed, window)
        want, all_of_it = attention.paged_gqa_attention_reference(
            *args, 0.25, window, ring)
        got, read = attention._paged_gqa_pallas(
            *args, 0.25, window, ring, True, pages=pages)
        assert float(jnp.max(jnp.abs(want - got))) < 1e-5
        assert list(np.asarray(read)) == walked
        assert int(all_of_it[0]) == args[3].shape[1] * BS
        dead = np.arange(got.shape[1])[None, :] >= n_new[:, None]
        assert not np.asarray(got)[dead].any()


def _poisoned(args):
    """``args`` with every arena row no slot's walk may see made NaN: the
    tail of a slot's last block, blocks no table names."""
    q, k, v, table, fill, n_new = args
    live = np.zeros(k.shape[:2], bool)
    for s, total in enumerate(np.asarray(fill + n_new)):
        if n_new[s]:
            for b in range(-(-int(total) // BS)):
                live[int(table[s, b]), :min(BS, int(total) - b * BS)] = True
    nan = lambda a: jnp.where(jnp.asarray(live)[..., None], a, jnp.nan)
    return (q, nan(k), nan(v), table, fill, n_new)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("Hq,Hk", [(8, 2), (4, 4)])
def test_kernel_takes_a_pair_of_64_wide_heads_a_lane_tile(Hq, Hk, dtype):
    """ISSUE 41: at ``hd = 64`` two adjacent key/value heads are one head
    of 128 to the kernel (groups of 4 as `granite4h`'s, of 1 as GPT-1's;
    bfloat16 and float32 arenas): random fills and lanes, a dead slot, a
    first chunk, tiles that end inside the walk, stale rows NaN; against
    the XLA form on the clean arenas, ``walked`` by hand."""
    args, _, walked, n_new = _kernel_case(5, None, Hq=Hq, Hk=Hk, hd=64)
    args = tuple(a.astype(dtype) if a.dtype == jnp.float32 else a
                 for a in args)
    want, _ = attention.paged_gqa_attention_reference(*args, 0.125)
    got, read = attention._paged_gqa_pallas(
        *_poisoned(args), 0.125, None, None, True, pages=3)
    assert got.dtype == dtype and got.shape == args[0].shape
    # one rounding of a result of magnitude < 4, or float32's summation
    bound = 2.0 ** -6 if dtype == jnp.bfloat16 else 1e-5
    err = jnp.abs(want.astype(jnp.float32) - got.astype(jnp.float32))
    assert float(jnp.max(err)) <= bound
    assert list(np.asarray(read)) == walked
    dead = np.arange(got.shape[1])[None, :] >= n_new[:, None]
    assert not np.asarray(got.astype(jnp.float32))[dead].any()


@pytest.mark.parametrize("hd,Hk,ok", [(64, 8, True), (64, 12, True),
                                      (64, 3, False), (128, 4, True),
                                      (96, 4, False)])
def test_mosaic_takes_a_head_or_a_pair_of_heads_a_whole_lane_tile(
        hd, Hk, ok, monkeypatch):
    """The rule the chip's dispatch reads, read here without a chip (the
    interpreter takes any shape): ``hd % 128 == 0``, or ``hd == 64`` with
    an even number of key/value heads."""
    monkeypatch.setattr(ops_config, "INTERPRET", False)
    monkeypatch.setattr(ops_config, "use_pallas", lambda: True)
    sds = jax.ShapeDtypeStruct
    for dtype in (jnp.bfloat16, jnp.float32):
        assert attention._paged_gqa_ok(
            sds((4, 16, 2 * Hk, hd), dtype),
            sds((8, 16, Hk * hd), dtype)) is ok


def test_at_128_wide_heads_the_launcher_hands_the_kernel_what_it_did():
    """`trinity_mini`'s path: at ``hd = 128`` nothing is paired, the
    kernel's operands are the parent's (PR 40), shape for shape."""
    S, C, cols = 4, 16, 8
    i32, bf = jnp.int32, jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *a: attention._paged_gqa_pallas(
        *a, 0.1, None, None, True))(
            sds((S, C, 32, 128), bf), sds((S * cols, 16, 512), bf),
            sds((S * cols, 16, 512), bf), sds((S, cols), i32),
            sds((S,), i32), sds((S,), i32))
    inner = jaxpr.jaxpr.eqns[0].params["jaxpr"].jaxpr
    calls = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert [(v.aval.shape, str(v.aval.dtype)) for v in calls[0].invars] == [
        ((S * cols,), "int32"), ((S,), "int32"), ((S,), "int32"),
        ((S, 4, C * 8, 128), "bfloat16"), ((S * cols, 16, 512), "bfloat16"),
        ((S * cols, 16, 512), "bfloat16")]
    assert [v.aval.shape for v in calls[0].outvars] == [
        (S, 4, C * 8, 128), (S,)]
    assert not any(e.primitive.name in ("select_n", "concatenate")
                   for e in inner.eqns)


def test_the_op_takes_the_kernel_here_and_the_xla_form_when_forced():
    args, ring, walked, _ = _kernel_case(0, 8)
    _, read = attention.paged_gqa_attention(*args, scale=0.25, window=8,
                                            ring=ring)
    assert list(np.asarray(read)) == walked
    with ops_config.force_xla():
        _, read = attention.paged_gqa_attention(*args, scale=0.25, window=8,
                                                ring=ring)
    assert set(np.asarray(read)) == {ring * BS}
    with pytest.raises(ValueError, match="ring of 3"):
        attention.paged_gqa_attention(*args, scale=0.25, window=8, ring=3)


# ------------------------------------- models without window leaves: as were

def _other(name):
    from apex_example_tpu.models import gpt, granite_hybrid, pangu_moe, xing4
    return {"gpt1": gpt.gpt_tiny, "xing4": xing4.xing4_tiny,
            "granite": granite_hybrid.granite_hybrid_tiny,
            "pangu": pangu_moe.pangu_moe_tiny}[name]()


def test_tick_args_without_a_ring_are_what_they_were():
    plain = TickArgs(8, 4)
    assert plain == TickArgs(8, 4, False, 0) and plain.width == 8 + 4 + 6
    packed, f = plain.blank(2)
    assert "ring_table" not in f and packed.shape == (2, 18)
    assert [f[k].base is packed or f[k].base is not None for k in f]
    ringed = TickArgs(8, 4, False, 3)
    packed, f = ringed.blank(2)
    assert ringed.width == 21 and f["ring_table"].shape == (2, 3)
    f["ring_table"][:] = 7
    f["fill"][:] = 5
    assert (packed[:, 12:15] == 7).all() and (packed[:, 15] == 5).all()
    assert (packed[:, :12] == 0).all()


@pytest.mark.parametrize("name", ["gpt1", "xing4", "granite", "pangu"])
def test_the_other_models_pools_and_tick_layouts_are_untouched(name):
    m = _other(name)
    p = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServeEngine(m, p, num_slots=2, max_len=32, block_size=8)
    pool = eng.pool
    assert pool.window is None and pool.ring_alloc is None
    assert pool.ring_table.shape == (2, 0) and pool.ring_blocks == 0
    assert eng.tick_args == TickArgs(eng.chunk, pool.max_blocks,
                                     eng.self_draft)
    _, f = eng.tick_args.blank(2)
    assert set(engine_lib._paged(f)) == {"block_table", "fill", "n_new",
                                         "cow_src", "cow_dst"}
    # one arena's arithmetic, as before the second
    assert pool.kv_bytes_per_token() == pool.kv_bytes_reserved() \
        // (pool.num_blocks * pool.block_size)
    assert pool.kv_bytes_committed() == 0 and pool.window_blocks_live() == 0
    if name != "gpt1":
        return                      # one tick of one of them is enough
    eng.submit(Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=3, uid="a"))
    eng.step()
    assert pool.kv_bytes_committed() == pool.blocks_committed() \
        * pool.block_size * pool.kv_bytes_per_token() > 0
    assert sum(pool.window_tokens_held()) * pool.kv_bytes_per_token() \
        == pool.kv_bytes_live()
    if eng.counter_log:
        assert not {"window_tokens_held", "window_blocks_released"} \
            & set(eng.counter_log[-1][1])


# ----------------------------------------------------------- the tolerance

def _bf16_statistics(x, scale, eps):
    y = x.astype(jnp.bfloat16)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + jnp.bfloat16(eps))
    return (y * scale.astype(jnp.bfloat16)).astype(x.dtype)


@pytest.mark.parametrize("fault", ["none", "window_off_by_one",
                                   "rotation_on_the_full_layer",
                                   "missing_gate", "bfloat16_statistics"])
def test_the_tolerance_fails_each_fault(fault, model, params, monkeypatch):
    """The same comparison as the engine test's (the XLA form: quicker),
    with one thing wrong in the program or in what it is compared with."""
    served, cfg = params, RCFG
    if fault == "window_off_by_one":
        cfg = dict(RCFG, sliding_window=9)
    elif fault == "rotation_on_the_full_layer":
        plain = REF._attention
        monkeypatch.setattr(REF, "_attention", lambda x, p, c, kind, prec: (
            plain(x, p, dict(c, sliding_window=10 ** 9), W, prec)
            if kind == F else plain(x, p, c, kind, prec)))
    elif fault == "missing_gate":
        # a zero W_g gates everything by one half, which the norm after
        # attention takes out again: no gate at all
        served = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x)
            if path[-1].key == "wg" else x, params)
    elif fault == "bfloat16_statistics":
        monkeypatch.setattr(tr, "rms_norm", _bf16_statistics)
    with ops_config.force_xla():
        eng = _engine(model, served)
        seen = _record_logits(eng)
        done = _run(eng, _requests([30, 5, 26], [10, 6, 5], seed=2))
    worst = _worst(done, seen, _ref_logits(params, cfg))
    assert len(done) == 3
    if fault == "none":
        assert worst < TOL
    else:
        assert worst > 20 * TOL, worst


# ------------------------------------------------------------------ the CLI

def test_serve_cli_serves_the_tiny_arch_end_to_end(capsys):
    import serve
    assert serve.main(["--arch", "trinity_tiny", "--requests", "4",
                       "--prompt-len", "20:40", "--max-new", "6:10",
                       "--max-len", "64", "--block-size", "4",
                       "--slots", "2"]) == 0
    said = capsys.readouterr().out
    assert "arch=trinity_tiny" in said
