"""models/lfm2.py through the engine and the pool (CPU, float32, the tiny
size: heads of 64, so the paged kernel pairs them under the interpreter as
at the published widths; chunks of 16 lanes against 3 taps).

Tolerances.  Logits lie within +-4 (unit-scale hidden state, the tied table
at 1/sqrt(d)).  The model's float32 forward reads 8e-6 from the plain
reference (orders of summation: grouped against one-expert-at-a-time
products, the program's 1e-20 against the reference's 1e-6 under the gates'
sum), the engine's chunked path with the interpreted kernel (an online
softmax a tile at a time) the same: ``TOL`` 1e-4 is twelve times that.  The
faults of the tolerance test read 2e-3 to 1 and more: bfloat16 activations
over the float32 weights, bfloat16 norm statistics, kept rows dropped
between ticks, a slot's rows not zeroed at its next request, no rotation."""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_example_tpu.models import lfm2  # noqa: E402
from apex_example_tpu.ops import _config as ops_config  # noqa: E402
from apex_example_tpu.ops import paged_cache, ssd  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve import engine as engine_lib  # noqa: E402
from apex_example_tpu.serve.slots import BlockPool  # noqa: E402
from benchmarks import harness  # noqa: E402

pytestmark = pytest.mark.serve

REF, _ = harness.load_reference("benchmarks/reference/lfm2.py:lfm2")
C, F = lfm2.CONV, lfm2.FULL
RCFG = dict(vocab_size=256, hidden_size=128, num_heads=4, num_kv_heads=2,
            head_dim=64, intermediate_size=256, moe_intermediate_size=128,
            num_experts=8, num_experts_per_tok=4, routed_scaling_factor=1.0,
            conv_L_cache=3, layer_types=[C, F, C, C, C], num_dense_layers=1,
            norm_eps=1e-5, rope_theta=1e6, block=8)
SLOTS, MAX_LEN, BS = 3, 64, 16
TOL = 1e-4
# shorter than the taps, and across a chunk's edge
PROMPTS = (1, 2, 3, 15, 16, 17, 33)


@pytest.fixture(scope="module")
def model():
    m = lfm2.lfm2_tiny()
    assert list(m.layer_kinds()) == RCFG["layer_types"]
    return m


@pytest.fixture(scope="module")
def params():
    return REF.lfm2_weights(jax.random.PRNGKey(0), RCFG,
                            jnp.float32)["params"]


def _ref_logits(params, cfg=RCFG):
    fn = jax.jit(lambda ids: REF.lfm2_logits(params, ids, cfg))

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


def _record_logits(eng, between=None):
    """Put a step of the test's own in the engine's place that is the
    engine's program (the same module clone, the same arguments, greedy)
    and also hands out the logits: ``seen[uid][position] = logits row`` for
    every lane the engine sampled or could have.  ``between``: done to the
    cache after every tick (a fault to show the tolerance by)."""
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
        if between is not None:
            cache = between(cache)
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


def _rows(cache):
    """The conv layers' per-slot leaves, by layer."""
    return {path: np.asarray(leaf)
            for path, leaf in paged_cache.slot_leaves(cache)}


# ------------------------------------------------------------- the model

def test_seeded_layout_is_the_models_own(model, params):
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
    assert shape(init) == shape(params)
    assert "shared" not in init["layer_1"]["moe"]       # no shared expert
    assert "head" not in init                           # the table is tied


def test_plain_forward_matches_the_reference(model, params):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)))
    got = model.apply({"params": params}, ids)
    want = jax.jit(lambda x: REF.lfm2_logits(params, x, RCFG))(ids)
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 256)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert 0.5 < float(jnp.std(want)) < 2.0


def test_the_published_pattern_and_the_cut():
    big = lfm2.Lfm2ForCausalLM()
    kinds = big.layer_kinds()
    assert len(kinds) == 24 and kinds.count(F) == 6
    assert [i for i, k in enumerate(kinds) if k == F] \
        == [2, 6, 10, 14, 18, 21]
    cut = lfm2.lfm2_8b_a1b_cut()
    assert cut.layer_kinds() == (kinds[0],) + kinds[2:14] \
        and cut.num_dense_layers == 1
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2ForCausalLM(num_layers=3, layer_types=(C, F)).layer_kinds()
    with pytest.raises(ValueError, match="slot path only"):
        lfm2.lfm2_tiny(decode=True).init(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 4), jnp.int32))


def test_parameters_of_the_configuration_file_are_the_models_own():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "lfm2_8b_a1b.json"))
    m = harness.resolve(cfg["model"]["builder"])(**cfg["model"]["kwargs"])
    assert m == lfm2.lfm2_8b_a1b_cut()
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    p = cfg["parameters"]
    assert count(shapes["layer_0"]["conv"]) == p["conv_mixer"]
    assert count(shapes["layer_1"]["attn"]) == p["attention_mixer"]
    assert count(shapes["layer_1"]["moe"]) == p["expert_ffn"]
    assert count(shapes["layer_0"]) == p["dense_layer_conv"]
    assert count(shapes["layer_1"]) == p["expert_layer_attention"]
    assert count(shapes["layer_2"]) == p["expert_layer_conv"]
    assert count(shapes) == p["held"] == 4606249728
    # the grouped kernel's blocks: whole lane tiles, inside its budget
    from apex_example_tpu.ops import grouped_matmul
    w = shapes["layer_1"]["moe"]["w_gate"]
    assert w.shape == (32, 2048, 1792) and 1792 % 128 == 0
    assert 2048 * 1792 * 2 <= grouped_matmul._WEIGHT_BLOCK_BYTES


# --------------------------------------------- through the engine's pool

@pytest.fixture(scope="module")
def served(model, params):
    """One queue of 3 slots: a prompt of every length of ``PROMPTS``, each
    then decoding through the cache, through the interpreted kernels."""
    assert ops_config.INTERPRET and not ops_config.FORCE_XLA
    eng = _engine(model, params)
    assert eng.pool.per_slot_state and eng.chunk == BS
    seen = _record_logits(eng)
    done = _run(eng, _requests(PROMPTS, [5, 4, 6, 3, 4, 5, 6]))
    return eng, seen, done


@pytest.mark.parametrize("at", range(len(PROMPTS)),
                         ids=[f"prompt{n}" for n in PROMPTS])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        served, ref_logits, at):
    _, seen, done = served
    uid = f"r{at}"
    c = done[uid]
    P = PROMPTS[at]
    assert len(c.request.prompt) == P and len(c.tokens) >= 3
    # the last lane of every chunk of the prompt, then every decoded token
    edges = [min(e, P) - 1 for e in range(BS, P + BS, BS)]
    assert sorted(seen[uid]) == sorted(
        set(edges) | set(range(P - 1, P - 1 + len(c.tokens))))
    assert _worst({uid: c}, seen, ref_logits) < TOL


def test_the_tick_counts_what_its_layers_did(served):
    eng, _, done = served
    assert len(done) == len(PROMPTS)
    trees = [tree for _, tree in eng.counter_log]
    assert trees and all(
        set(t) == {"expert_load", "expert_weight_visits",
                   "attn_positions_walked", "conv_slots_advanced",
                   "lanes_live"} for t in trees)
    t = jax.tree_util.tree_map(np.asarray, trees[1])
    assert t["expert_load"].shape == t["expert_weight_visits"].shape \
        == (4, 8)
    assert t["attn_positions_walked"].shape == (1, SLOTS)
    assert t["conv_slots_advanced"].shape == (4, SLOTS)
    for t in (jax.tree_util.tree_map(np.asarray, t) for t in trees):
        live = t["lanes_live"][0]
        # a conv layer's rows move exactly where a slot has a live lane;
        # 4 experts a live lane
        assert (t["conv_slots_advanced"] == (live > 0)[None, :]).all()
        assert (t["expert_load"].sum(-1) == 4 * live.sum()).all()
    pool = eng.pool
    assert pool.alloc.available() == pool.num_blocks \
        and pool._reserved_total == 0


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(model, params):
    """One slot, two requests one after the other: the second reads what a
    fresh engine reads, bit for bit (the first one's rows are zeroed away
    inside the tick, not on the host)."""
    first, second = _requests([21, 13], [5, 6], seed=3)
    eng = _engine(model, params, num_slots=1)
    seen = _record_logits(eng)
    _run(eng, [first, second])
    assert any(r.any() for r in _rows(eng.pool.cache).values())
    fresh = _engine(model, params, num_slots=1)
    alone = _record_logits(fresh)
    _run(fresh, [Request(prompt=list(second.prompt), max_new_tokens=6,
                         uid="r1")])
    assert sorted(seen["r1"]) == sorted(alone["r1"])
    for pos, row in alone["r1"].items():
        assert row.tobytes() == seen["r1"][pos].tobytes()


def test_a_slot_with_no_new_lane_keeps_its_rows_bit_for_bit(model, params):
    """A tick in which slot 1 has ``n_new == 0`` beside two advancing
    slots: its kept rows and its blocks of K and V come out as they went
    in, bit for bit; the others' rows moved."""
    eng = _engine(model, params)
    taken = []
    real = eng._step_fn
    eng._step_fn = lambda *a: (taken.append(a), real(*a))[1]
    for r in _requests([20, 9, 30], [8, 8, 8], seed=4):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    args = taken[-1]
    cache = jax.tree_util.tree_map(jnp.copy, eng.pool.cache)
    before = _rows(cache)
    packed = np.array(args[2])
    said = eng.tick_args.fields(packed)
    assert (said["n_new"] > 0).all()
    said["n_new"][1] = 0
    step = engine_lib._slot_step(eng.pool.dec, eng.tick_args)
    kv_before = [np.asarray(leaf) for _, leaf, _ in paged_cache.block_leaves(
        cache, eng.pool.num_blocks, BS)]
    out = step(args[0], cache, jnp.asarray(packed), args[3])
    after = _rows(out[0])
    assert len(before) == 4
    for path, rows in before.items():
        assert rows.shape == (SLOTS, 2 * 128)
        assert after[path][1].tobytes() == rows[1].tobytes()
        assert rows[1].any()
        for s in (0, 2):
            assert after[path][s].tobytes() != rows[s].tobytes()
    mine = [b for b in eng.pool.table[1] if b >= 0]
    for was, (_, leaf, _) in zip(kv_before, paged_cache.block_leaves(
            out[0], eng.pool.num_blocks, BS)):
        assert np.asarray(leaf)[mine].tobytes() == was[mine].tobytes()


def test_a_preempted_request_asked_again_gives_the_same_tokens(
        model, params, ref_logits):
    """A request cancelled in the middle of its decode (evicted with its
    blocks; its rows stay behind in the slot) and asked again from the
    start, while others run: the second life's logits are the reference's
    and its first tokens the first life's."""
    eng = _engine(model, params)
    seen = _record_logits(eng)
    reqs = _requests([30, 5, 41, 9], [12, 6, 15, 20], seed=6)
    again = []

    def on_tick(e):
        for s in e.pool.slots:
            if s is not None and s.request.uid == "r2" and not again \
                    and s.n_generated == 4:
                assert e.cancel("r2")
                again.append(Request(prompt=list(reqs[2].prompt),
                                     max_new_tokens=15, uid="r2again"))
                e.queue._closed = False
                e.submit(again[0])
                e.queue.close()

    done = _run(eng, reqs, on_tick)
    assert set(done) == {"r0", "r1", "r3", "r2again"} and again
    assert _worst(done, seen, ref_logits) < TOL
    cancelled = next(c for c in eng.completions if c.request.uid == "r2")
    assert cancelled.status == "cancelled" \
        and done["r2again"].tokens[:4] == cancelled.tokens


def test_a_slot_moved_with_its_rows_goes_on_with_the_references_logits(
        model, params, ref_logits):
    """Migration mid-decode: the payload carries the kept rows of every
    conv layer beside the blocks of K and V."""
    reqs = _requests([19, 11, 26], [8, 9, 7], seed=11)
    src, dst = _engine(model, params), _engine(model, params)
    seen = _record_logits(dst)
    for r in reqs:
        src.submit(r)
    src.queue.close()

    def mid_decode():
        s = next((s for s in src.pool.slots
                  if s is not None and s.request.uid == "r0"), None)
        return s is not None and not s.prefilling and s.n_generated >= 2
    for _ in range(500):
        if mid_decode():
            break
        src.step()
    h = src.extract_live("r0")
    assert h is not None and h.kind == "migration"
    kinds = {}
    for k, v in h.payload.items():
        kinds.setdefault(k.rsplit("/", 1)[1], []).append(v.shape)
    assert kinds["slot:conv_rows"] == [(1, 2 * 128)] * 4
    assert len(kinds["cached_key"]) == len(kinds["cached_value"]) == 1
    comps = src.run(max_steps=2000)
    assert dst.admit_migrated(h) is True
    dst.queue.close()
    done = {c.request.uid: c for c in comps + dst.run(max_steps=2000)
            if c.status == "ok"}
    assert sorted(done) == ["r0", "r1", "r2"] and len(seen["r0"]) >= 5
    assert _worst({"r0": done["r0"]}, seen, ref_logits) < TOL
    bare = {k: v for k, v in h.payload.items() if "slot:" not in k}
    with pytest.raises(ValueError, match="missing per-slot leaf"):
        paged_cache.insert(dst.pool.cache, [0],
                           {k: v[:1] for k, v in bare.items()},
                           dst.pool.num_blocks, BS, pad_to=8, slot=0)


def test_the_xla_forms_serve_the_same_tokens(model, params, served,
                                             step_traced_with):
    _, _, kernel = served
    with step_traced_with(xla=True):
        xla = _run(_engine(model, params),
                   _requests(PROMPTS, [5, 4, 6, 3, 4, 5, 6]))
    assert {u: c.tokens for u, c in kernel.items()} \
        == {u: c.tokens for u, c in xla.items()} and len(xla) == len(PROMPTS)


def test_what_per_slot_rows_cannot_do_is_refused_with_the_reason(
        model, params):
    with pytest.raises(ValueError, match="cannot be rolled back"):
        _engine(model, params, speculate=2)
    with pytest.raises(ValueError, match="kv_quant.*kept rows"):
        BlockPool(model, SLOTS, MAX_LEN, block_size=BS, kv_quant=True)
    with pytest.raises(ValueError, match="tensor_parallel.*no sharding"):
        BlockPool(model.clone(tensor_parallel=True), SLOTS, MAX_LEN,
                  block_size=BS)
    pool = BlockPool(model, SLOTS, MAX_LEN, block_size=BS)
    assert pool.per_slot_state and pool.window is None
    # counted apart from K/V: 4 conv layers of two float32 rows (this
    # preset's dtype); one attention layer's K and V
    per_slot = 4 * 2 * 128 * 4
    assert pool.state_bytes_reserved() == SLOTS * per_slot
    assert pool.kv_bytes_per_token() == 2 * 2 * 64 * 4
    # no prefix is shared: the rows at a prefix's edge are held nowhere
    eng = _engine(model, params)
    same = np.random.default_rng(1).integers(0, 256, 24).tolist()
    done = _run(eng, [Request(prompt=list(same), max_new_tokens=4,
                              uid=f"p{i}") for i in range(3)])
    assert eng.pool.prefix_hit_rate() == 0.0 and eng.pool.cow_copies == 0
    assert done["p0"].tokens == done["p1"].tokens == done["p2"].tokens


# ----------------------------------------------------------- the tolerance

def _bf16_statistics(x, scale, eps):
    y = x.astype(jnp.bfloat16)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + jnp.bfloat16(eps))
    return (y * scale.astype(jnp.bfloat16)).astype(x.dtype)


@pytest.mark.parametrize("fault", ["none", "bfloat16_activations",
                                   "bfloat16_statistics", "dropped_rows",
                                   "rows_not_zeroed", "no_rotation"])
def test_the_tolerance_fails_each_fault(fault, model, params, monkeypatch,
                                        step_traced_with):
    """The same comparison as the engine test's (the XLA forms: quicker),
    with one thing wrong in the program or in what it is compared with."""
    served, between, slots = model, None, SLOTS
    if fault == "bfloat16_activations":
        # a bfloat16 run of the float32 tiny model
        served = model.clone(dtype=jnp.bfloat16)
    elif fault == "bfloat16_statistics":
        monkeypatch.setattr(lfm2, "rms_norm", _bf16_statistics)
    elif fault == "dropped_rows":
        between = lambda cache: jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf)
            if path[-1].key == "slot:conv_rows" else leaf, cache)
    elif fault == "rows_not_zeroed":
        slots = 1                       # one slot, one request after another
        plain = ssd.causal_conv
        monkeypatch.setattr(ssd, "causal_conv", lambda rows, x, w, b, n_new,
                            reset=None: plain(rows, x, w, b, n_new, None))
    elif fault == "no_rotation":
        monkeypatch.setattr(lfm2, "rotate_half", lambda x, pos, theta: x)
    with step_traced_with(xla=True):
        eng = _engine(served, params, num_slots=slots)
        seen = _record_logits(eng, between)
        done = _run(eng, _requests([30, 5, 26], [10, 6, 5], seed=2))
    worst = _worst(done, seen, _ref_logits(params))
    assert len(done) == 3
    if fault == "none":
        assert worst < TOL
    else:
        assert worst > 20 * TOL, worst


# ------------------------ the code this model shares: its other callers

# sha256 of the parameter tree's paths, shapes and dtypes (16 digits) and of
# the tick's lowered text (4 slots x 64, blocks of 8, under the tests'
# interpreter), read on the parent of PR 43 (5f5d2e5): ``RoutedExperts``
# with its shared expert on (``n_shared = 1``, the default) and
# ``ssd.causal_conv`` with a bias are what they were.  "xing4" and "granite"
# are also ``tests/test_pangu_moe.py``'s ``TICK_SINCE_PR39`` lines.  PR 44
# replaced the ticks of "xing4" and "pangu" on purpose (their token-wise
# sublayers on ``ops/lane_pack.py``'s packed rows); their trees are the same.
SHARED_CODE_BEFORE_PR43 = {
    "xing4": ("ba01ff959f52bcc9", "b816f09c208380fbc92edd265b4e75ae24b2d91c"
                                  "178523a48a950be55dbceece"),
    "pangu": ("0fbebc4380dcdb26", "d812400a5d0edf5b04a0315a98023b7b5bcf17fc"
                                  "d3ec0349867d09417d2abfc8"),
    "trinity": ("169d2b945e80e893", "7872dc93d8cd9046802bfe8f26265e52e1f7ff3"
                                    "4b1c7a26e67043013e092cf82"),
    "granite": ("f8cd4135ab977b14", "0670df7e1cda5c43df44cc7c5f5cc8bf92accef"
                                    "d8c93eaa9f25bce2b88a615bf"),
}


def _other(name):
    from apex_example_tpu.models import (granite_hybrid, pangu_moe, trinity,
                                         xing4)
    return {"xing4": lambda: xing4.xing4_tiny(num_layers=2),
            "pangu": pangu_moe.pangu_moe_tiny,
            "trinity": trinity.trinity_tiny,
            "granite": granite_hybrid.granite_hybrid_tiny}[name]()


@pytest.mark.parametrize("name", sorted(SHARED_CODE_BEFORE_PR43))
def test_the_other_callers_trees_and_ticks_are_the_parents(name):
    model = _other(name)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    tree = str(jax.tree_util.tree_map(lambda t: (t.shape, str(t.dtype)),
                                      shapes))
    params = jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), shapes)
    eng = ServeEngine(model, params, num_slots=4, max_len=64, block_size=8)
    build = engine_lib._draft_step if eng.self_draft \
        else engine_lib._slot_step
    text = build(eng.pool.dec, eng.tick_args).lower(
        params, eng.pool.cache,
        jnp.zeros((4, eng.tick_args.width), jnp.int32),
        jax.random.PRNGKey(0)).as_text()
    assert (hashlib.sha256(tree.encode()).hexdigest()[:16],
            hashlib.sha256(text.encode()).hexdigest()) \
        == SHARED_CODE_BEFORE_PR43[name]


def test_routed_experts_without_a_shared_expert_is_the_routed_sum():
    from apex_example_tpu.models.layers import RoutedExperts
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 32))
    kw = dict(hidden_size=32, width=16, n_experts=4, top_k=2, scale=1.0,
              experts_held=(0, 4), dtype=jnp.float32,
              param_dtype=jnp.float32)
    with_shared, none = RoutedExperts(**kw), RoutedExperts(**kw, n_shared=0)
    p = with_shared.init(jax.random.PRNGKey(0), x)["params"]
    bare = {k: v for k, v in p.items() if k != "shared"}
    assert set(none.init(jax.random.PRNGKey(0), x)["params"]) == set(bare)
    y1, load1, _ = with_shared.apply({"params": p}, x)
    y0, load0, _ = none.apply({"params": bare}, x)
    g = jax.nn.silu(x @ p["shared"]["w_gate"]) * (x @ p["shared"]["w_up"])
    assert float(jnp.max(jnp.abs(y1 - y0 - g @ p["shared"]["w_down"]))) \
        < 1e-5
    assert (np.asarray(load0) == np.asarray(load1)).all()
    # two shared experts are one SwiGLU of twice the width
    two = RoutedExperts(**kw, n_shared=2).init(jax.random.PRNGKey(0),
                                               x)["params"]
    assert two["shared"]["w_gate"].shape == (32, 32)


def test_causal_conv_without_a_bias_is_the_one_with_a_zero_bias():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(3, 2, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 5, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    n_new = jnp.asarray([5, 0, 1], jnp.int32)
    reset = jnp.asarray([True, False, False])
    a, ra = ssd.causal_conv(rows, x, w, None, n_new, reset)
    b, rb = ssd.causal_conv(rows, x, w, jnp.zeros((8,)), n_new, reset)
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.asarray(ra).tobytes() == np.asarray(rb).tobytes()
    # by hand: slot 0 starts from zero rows; slot 1 keeps its rows; slot 2
    # keeps its last old row and its one new one
    assert float(jnp.max(jnp.abs(
        a[0, 0] - w[2] * x[0, 0]))) < 1e-6
    assert np.asarray(ra[1]).tobytes() == np.asarray(rows[1]).tobytes()
    assert np.allclose(ra[2], jnp.stack([rows[2, 1], x[2, 0]]))
    assert np.allclose(ra[0], x[0, 3:5])


# ------------------------------------------------------------------ the CLI

def test_serve_cli_serves_the_tiny_arch_end_to_end(capsys):
    import serve
    assert serve.main(["--arch", "lfm2_tiny", "--requests", "4",
                       "--prompt-len", "3:40", "--max-new", "3:8",
                       "--max-len", "64", "--slots", "2",
                       "--shared-prefix", "10"]) == 0
    said = capsys.readouterr().out
    assert "arch=lfm2_tiny" in said and "done: 4/4 completed" in said
    assert "lfm2_8b_a1b_cut" in serve.build_parser().format_help()
    with pytest.raises(ValueError, match="cannot be rolled back"):
        serve.main(["--arch", "lfm2_tiny", "--requests", "2",
                    "--speculate", "2"])
