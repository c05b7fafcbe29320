"""The openPangu-Ultra-MoE-style decoder (models/pangu_moe.py) and the
engine's self-drafting path (serve/engine.draft_tick) against the plain
reference (benchmarks/reference/pangu_moe.py), at tiny widths on the CPU,
float32 unless said: the plain forward and the module's; chunked prefill
then drafted decode through ServeEngine (logits, not tokens); served
tokens with drafting on and off; every draft the reference module's first
place; rollback of a rejected lane in every leaf; slot reuse; a shared
prefix under a module whose rows read the next token; the share test and
the vocabulary slice of the model-configs guide; the configuration file's
parameter count; what is refused; serve.py's --arch entry; and that the
other served models' engines and tick programs are what they were.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_example_tpu.models import layers, pangu_moe, xing4  # noqa: E402
from apex_example_tpu.ops import grouped_matmul, paged_cache  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve import engine as engine_lib  # noqa: E402
from apex_example_tpu.serve.slots import BlockPool  # noqa: E402
from apex_example_tpu.transformer import expert_parallel as ep  # noqa: E402
from benchmarks import harness  # noqa: E402

pytestmark = pytest.mark.serve

REF, _ = harness.load_reference("benchmarks/reference/pangu_moe.py:pangu")
TINY = dict(vocab_size=256, hidden_size=64, num_layers=3, first_k_dense=1,
            num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2)
RCFG = dict(TINY, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
            rope_theta=25600000.0)
SLOTS, MAX_LEN, BS = 4, 64, 8
# float32 against float32 over the same weights: the two differ by the order
# of their sums (absorbed against expanded attention, grouped against
# one-expert-at-a-time products, chunked against whole-sequence softmax);
# the plain forward reads 4e-6 on logits of spread 1, the paged path 1e-5.
# A router or a softmax in bfloat16 reads 1e-2 and more (shown below).
TOL = 5e-5


def _weights(cfg=RCFG, seed=0, dtype=jnp.float32):
    return REF.pangu_weights(jax.random.PRNGKey(seed), cfg, dtype)["params"]


@pytest.fixture(scope="module")
def model():
    return pangu_moe.pangu_moe_tiny()


@pytest.fixture(scope="module")
def params():
    return _weights()


def _engine(model, params, **kw):
    kw = dict(dict(num_slots=SLOTS, max_len=MAX_LEN, block_size=BS), **kw)
    return ServeEngine(model, params, **kw)


def _requests(n, seed=0, lo=5, hi=40, new=(4, 12), vocab=256):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab, int(rng.integers(lo, hi))
                                        ).tolist(),
                    max_new_tokens=int(rng.integers(*new)), uid=f"r{i}")
            for i in range(n)]


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.queue.close()
    return {c.request.uid: c for c in eng.run(max_steps=2000)}


def _sequences(done, max_len=MAX_LEN):
    ids = np.zeros((len(done), max_len), np.int32)   # one shape, one compile
    for r, c in enumerate(done.values()):
        seq = list(c.request.prompt) + list(c.tokens)
        ids[r, :len(seq)] = seq
    return jnp.asarray(ids)


def _record_logits(eng):
    """Put a step of the test's own in the engine's place that is the
    engine's tick (``engine.draft_tick`` over the same module clone and
    arguments) and also hands out what it read: ``seen[uid][position]`` the
    head's logits row at every verify lane, ``drafted[uid][position]`` the
    module's at the lane the next draft was read from."""
    seen, drafted = {}, {}
    tick = jax.jit(lambda *a: engine_lib.draft_tick(
        eng.pool.dec, eng.tick_args, *a))

    def recording(*a):
        cache, picked, finite, counters, logits, draft_logits = tick(*a)
        said = eng.tick_args.fields(np.asarray(a[2]))
        fill, n_new, aux, tok = (said[k] for k in ("fill", "n_new", "aux",
                                                   "tok"))
        picked_h = np.asarray(picked)
        for i, slot in enumerate(eng.pool.slots):
            if slot is None or not n_new[i]:
                continue
            at = int(fill[i] + n_new[i] - 1 - aux[i, 0])
            rows = seen.setdefault(slot.request.uid, {})
            rows[at] = np.asarray(logits[i, 0])
            took = bool(aux[i, 0]) and picked_h[i, 0] == int(tok[i, 1])
            if took:
                rows[at + 1] = np.asarray(logits[i, 1])
            drafted.setdefault(slot.request.uid, {})[at + took] = \
                np.asarray(draft_logits[i])
        return cache, picked, finite, counters

    eng._step_fn = recording
    return seen, drafted


# ------------------------------------------------------- the mathematics

def test_seeded_layout_is_the_models_own(model, params):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    sig = lambda tree: jax.tree_util.tree_map(
        lambda t: (tuple(t.shape), str(t.dtype)), tree)
    assert sig(shapes) == sig(params)
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "block",
                                  "norm"}
    assert set(params["layer_1"]) == {"attn_norm", "attn_post_norm",
                                      "ffn_norm", "ffn_post_norm", "attn",
                                      "moe"}


def test_plain_forward_and_the_modules_match_the_reference(model, params):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    got, got_mtp = model.apply({"params": params}, ids, mtp=True)
    assert got.shape == got_mtp.shape == (2, 24, 256)
    assert got.dtype == got_mtp.dtype == jnp.float32
    np.testing.assert_allclose(got, REF.pangu_logits(params, ids, RCFG),
                               atol=TOL)
    # position i of the module: from h_i and token i + 1 (the last
    # position's next token is the pad, in both)
    np.testing.assert_allclose(got_mtp,
                               REF.pangu_mtp_logits(params, ids, RCFG),
                               atol=TOL)
    alone = model.apply({"params": params}, ids)
    assert np.array_equal(np.asarray(alone), np.asarray(got))


def test_sandwich_norms_are_where_the_configuration_says(params):
    """A layer by hand: the sublayer's OUTPUT is normed before it is added
    (scales of 2 and 3 on the post-norms show up as such)."""
    p = jax.tree_util.tree_map(lambda t: t, params["layer_0"])
    p["attn_post_norm"] = 2.0 * p["attn_post_norm"]
    p["ffn_post_norm"] = 3.0 * p["ffn_post_norm"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64))
    rms = lambda t: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                      + 1e-5)
    u = x + 2.0 * rms(REF._attention(rms(x), p["attn"], RCFG, "highest"))
    want = u + 3.0 * rms(REF._swiglu(rms(u), p["mlp"], "highest"))
    np.testing.assert_allclose(REF._layer(x, p, RCFG, "highest"), want,
                               atol=1e-5)


# --------------------------------------------- through the engine, drafting

@pytest.fixture(scope="module")
def served(model, params):
    eng = _engine(model, params)
    seen, drafted = _record_logits(eng)
    done = _run(eng, _requests(6, seed=2))
    ids = _sequences(done)
    return (eng, done, seen, drafted,
            np.asarray(REF.pangu_logits(params, ids, RCFG)),
            np.asarray(REF.pangu_mtp_logits(params, ids, RCFG)))


def test_chunked_prefill_then_drafted_decode_match_the_references_pass(
        served):
    """Every logits row the tick read — each prompt chunk's last lane, each
    decode tick's verified lane and an accepted draft's — equals the
    reference's one full pass over the finished sequence (expanded
    attention, no cache), within ``TOL``; so does the module's row at the
    lane every draft was read from."""
    eng, done, seen, drafted, ref, ref_mtp = served
    assert eng.self_draft and len(done) == 6
    worst = worst_mtp = 0.0
    for r, (uid, c) in enumerate(done.items()):
        P, n = len(c.request.prompt), len(c.tokens)
        assert c.status == "ok" and n == c.request.max_new_tokens
        # every position that delivered a token was read
        assert set(range(P - 1, P + n - 1)) <= set(seen[uid])
        for pos, row in seen[uid].items():
            worst = max(worst, float(np.abs(row - ref[r, pos]).max()))
        for pos, row in drafted[uid].items():
            if pos + 1 < P + n:         # the module's next token was served
                worst_mtp = max(worst_mtp,
                                float(np.abs(row - ref_mtp[r, pos]).max()))
    assert 0 < worst < TOL and 0 < worst_mtp < TOL


@pytest.mark.parametrize("where", ["router", "softmax"])
def test_a_lower_precision_in_one_place_fails_the_tolerance(
        model, params, where, monkeypatch):
    """The tolerance is tight enough: the router's scores, or attention's
    probabilities, rounded to bfloat16 read far above it."""
    bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    if where == "router":
        real = ep.dropless_route
        monkeypatch.setattr(
            layers, "dropless_route",
            lambda x, w, *a, **k: real(bf16(x), bf16(w), *a, **k))
    else:
        real = jax.nn.softmax
        monkeypatch.setattr(jax.nn, "softmax",
                            lambda *a, **k: bf16(real(*a, **k)))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    got = model.apply({"params": params}, ids)
    monkeypatch.undo()
    assert np.abs(got - REF.pangu_logits(params, ids, RCFG)).max() > 20 * TOL


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_served_tokens_are_the_same_with_drafting_on_and_off(
        form, step_traced_with):
    """At a vocabulary of 8 the run holds accepted and rejected drafts;
    greedy verification changes nothing that is delivered.  On both forms
    of paged latent attention."""
    cfg = dict(RCFG, vocab_size=8)
    model = pangu_moe.pangu_moe_tiny(vocab_size=8)
    params = _weights(cfg)

    def requests():
        reqs = _requests(7, seed=2, vocab=8)
        for i, r in enumerate(reqs):
            r.prompt[0] = i             # no prefix in common
        return reqs

    with step_traced_with(xla=form == "xla"):
        on = _engine(model, params)
        got = _run(on, requests())
        off = _engine(model, params, speculate=0)
        want = _run(off, requests())
    assert on.self_draft and not off.self_draft and off.speculate == 0
    assert {u: c.tokens for u, c in got.items()} \
        == {u: c.tokens for u, c in want.items()}
    assert 0 < on.tokens_accepted < on.tokens_drafted
    verdicts = [ok for c in got.values() for _, _, ok in c.drafts]
    assert sum(verdicts) == on.tokens_accepted
    assert len(verdicts) == on.tokens_drafted
    assert all(not c.drafts for c in want.values())
    # a tick may yield two tokens: a tick less in its slot an accepted draft
    # (and a tick more a chunk that waited for rows: ops/lane_pack.py)
    ticks = lambda eng, done: sum(
        c.finished_step - c.admitted_step
        for c in done.values()) - eng.prefill_chunks_deferred
    assert ticks(on, got) == ticks(off, want) - on.tokens_accepted
    summary = on.summary_record()
    assert (summary["speculate_k"], summary["draft_kind"]) == (1, "mtp")
    assert summary["tokens_drafted"] == on.tokens_drafted
    assert summary["output_tokens"] \
        == summary["tokens_accepted"] + summary["tokens_sampled"]
    assert "speculate_k" not in off.summary_record()
    # hand-offs to the runtime a tick: the key, the one packed put (`aux`
    # its last two columns), the step's call, 2 fetches (the picked tokens
    # in one); the same 5 with drafting off, two columns narrower
    assert summary["runtime_handoffs_per_tick"] == 5
    assert off.runtime_handoffs == 5 * off.compute_steps
    assert on.tick_args.width == off.tick_args.width + 2
    # the counters, in every tick's tree, 0 included
    log = [jax.tree_util.tree_map(np.asarray, t) for _, t in on.counter_log]
    assert sum(int(t["drafts_verified"].sum()) for t in log) \
        == on.tokens_drafted
    assert sum(int(t["drafts_accepted"].sum()) for t in log) \
        == on.tokens_accepted
    for t in log:
        assert t["drafts_verified"].shape == (1, 1)
        assert t["expert_load"].shape == t["expert_load_held"].shape == (3, 8)
        assert t["attn_positions_walked"].shape == (4, SLOTS)


def test_every_recorded_draft_is_the_reference_modules_first_place(served):
    eng, done, _, _, ref, ref_mtp = served
    n = 0
    for r, c in enumerate(done.values()):
        P = len(c.request.prompt)
        # one a decode tick but the last, whose one token no draft follows
        assert [at for at, _, _ in c.drafts] \
            == list(range(1, len(c.tokens) - 1))
        for at, token, ok in c.drafts:
            row = ref_mtp[r, P + at - 2]           # from h there and t after
            assert row[token] >= row.max() - 1e-4
            assert ok == (c.tokens[at] == token)
            n += 1
    assert n == eng.tokens_drafted > 20


def _leaf_rows(eng, slot, upto):
    """Rows ``< upto`` of slot's sequence in each latent leaf."""
    table = eng.pool.table[slot]
    rows = [int(table[p // BS]) * BS + p % BS for p in range(upto)]
    return [np.asarray(leaf).reshape(-1, leaf.shape[-1])[rows]
            for _, leaf, _ in paged_cache.block_leaves(
                eng.pool.cache, eng.pool.num_blocks, BS)]


def test_a_rejected_lane_leaves_every_leaf_as_an_undrafted_engine_has_it(
        model, params):
    """Serve one request a few ticks with drafts being rejected, then hand
    a fresh engine the same tokens as a PROMPT: its chunked prefill feeds no
    draft lane.  Up to the cursor every leaf (the tiny model's three layers'
    and the module's; six at the served depth) holds the same rows: what a
    rejected lane wrote lay past the cursor and was overwritten."""
    req = _requests(1, seed=7, lo=11, hi=12, new=(12, 13))[0]
    eng = _engine(model, params)
    eng.submit(req)
    for _ in range(2 + 6):                  # two chunks, six decode ticks
        eng.step()
    slot = eng.pool.slots[0]
    assert slot.cursor == 11 + 6 and eng.tokens_drafted == 6
    assert eng.tokens_accepted == 0         # every draft lane rolled back
    fresh = _engine(model, params)
    fresh.submit(Request(prompt=list(slot.tokens), max_new_tokens=2,
                         uid="again"))
    for _ in range(3):
        fresh.step()
    assert fresh.pool.slots[0].cursor == len(slot.tokens) == 18
    mine, theirs = _leaf_rows(eng, 0, 17), _leaf_rows(fresh, 0, 17)
    assert len(mine) == len(theirs) == 4
    for a, b in zip(mine, theirs):
        assert np.abs(a).max() > 0.1
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(model, params):
    first, second = _requests(2, seed=3, lo=13, hi=22, new=(5, 7))
    eng = _engine(model, params, num_slots=1)
    seen, drafted = _record_logits(eng)
    _run(eng, [first, second])
    fresh = _engine(model, params, num_slots=1)
    alone, alone_drafted = _record_logits(fresh)
    _run(fresh, [Request(prompt=list(second.prompt),
                         max_new_tokens=second.max_new_tokens, uid="r1")])
    assert sorted(seen["r1"]) == sorted(alone["r1"])
    for got, want in ((seen, alone), (drafted, alone_drafted)):
        for pos, row in want["r1"].items():
            assert row.tobytes() == got["r1"][pos].tobytes()


def test_a_shared_prefix_is_shared_one_token_short_of_the_modules_row(
        model, params):
    """The module's row at position i reads token i + 1, so the last
    position of a shared prefix holds the row of the OTHER request's next
    token: a drafting pool shares one token less and the sharer writes that
    position itself (copy-on-write).  Tokens and drafts equal a solo run's."""
    rng = np.random.default_rng(5)
    common = rng.integers(0, 256, 2 * BS).tolist()
    a = Request(prompt=common + [7, 8, 9], max_new_tokens=6, uid="a")
    b = Request(prompt=common + [200, 8, 9, 3], max_new_tokens=6, uid="b")
    eng = _engine(model, params)
    eng.submit(a)
    for _ in range(4):
        eng.step()                          # a's two full blocks are indexed
    shared, bids, _ = eng.pool._match_prefix(b.prompt)
    assert shared == 2 * BS - 1 and len(bids) == 2
    eng.submit(b)
    eng.queue.close()
    done = {c.request.uid: c for c in eng.run(max_steps=200)}
    assert eng.pool.prefix_hit_rate() > 0 and eng.pool.cow_copies == 1
    plain = BlockPool(model, SLOTS, MAX_LEN, block_size=BS)
    assert not plain.rows_read_next_token
    solo = _engine(model, params)
    alone = _run(solo, [Request(prompt=list(b.prompt), max_new_tokens=6,
                                uid="b")])
    assert done["b"].tokens == alone["b"].tokens
    assert done["b"].drafts == alone["b"].drafts


def test_a_sampled_temperature_slot_keeps_one_lane(model, params):
    eng = _engine(model, params)
    hot = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=6, temperature=0.8,
                  top_k=5, uid="hot")
    cold = Request(prompt=[5, 4, 3, 2, 1], max_new_tokens=6, uid="cold")
    done = _run(eng, [hot, cold])
    assert not done["hot"].drafts and len(done["hot"].tokens) == 6
    assert len(done["cold"].drafts) == eng.tokens_drafted == 4


# ------------------------------------------ bfloat16 against the control

def _emulate_mxu(monkeypatch):
    """The CPU has no bfloat16 x bfloat16 -> float32 product; the MXU
    multiplies bfloat16 operands exactly and adds in float32, which an
    upcast of both operands is (tests/test_xing4.py)."""
    up = lambda t: t.astype(jnp.float32)
    for mod in (layers, pangu_moe):
        monkeypatch.setattr(mod, "matmul_f32",
                            lambda a, b: jnp.matmul(up(a), up(b)))
    monkeypatch.setattr(layers, "einsum_f32",
                        lambda s, a, b: jnp.einsum(s, up(a), up(b)))
    monkeypatch.setattr(grouped_matmul, "_dot_f32",
                        lambda a, b: jnp.matmul(up(a), up(b)))
    monkeypatch.setattr(grouped_matmul, "ragged_dot_f32",
                        lambda a, w, sizes: jax.lax.ragged_dot(
                            up(a), up(w), sizes))


def test_bfloat16_is_inside_a_tolerance_that_fp8_fails(monkeypatch):
    """As served: bfloat16 weights and activations against the float32
    reference over the same (bfloat16) weights, the model's logits and the
    module's.  The numbers are those the benchmark judges: how far the
    first place of the run in question lies below the reference's best (its
    mean) and the share of positions where it is not the reference's first.
    Tolerance: a mean gap of 0.05 and every sixth position; bfloat16 reads
    about 0.01 and every tenth, the reference at fp8 (the benchmark's
    control) above 0.1 and every third."""
    _emulate_mxu(monkeypatch)
    wide = dict(TINY, vocab_size=2048)
    rcfg = dict(RCFG, **wide)
    weights = _weights(rcfg, seed=3, dtype=jnp.bfloat16)
    model = pangu_moe.PanguMoEForCausalLM(**wide, max_position=4096)
    assert model.dtype == jnp.bfloat16
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, 2048)
    refs = (np.asarray(REF.pangu_logits(weights, ids, rcfg)),
            np.asarray(REF.pangu_mtp_logits(weights, ids, rcfg)))
    served = model.apply({"params": weights}, ids, mtp=True)
    control = (REF.pangu_logits(weights, ids, rcfg, "fp8"),
               REF.pangu_mtp_logits(weights, ids, rcfg, "fp8"))
    for ref, got, low in zip(refs, served, control):
        def gap(logits):
            first = np.asarray(logits).argmax(-1)
            return ref.max(-1) - np.take_along_axis(
                ref, first[..., None], -1)[..., 0]
        assert gap(got).mean() < 0.05 < gap(low).mean()
        assert np.mean(gap(got) > 0) < 1 / 6 < np.mean(gap(low) > 0)


# ---------------------------------------- the chip's share, the vocabulary

def test_expert_shares_add_up_to_the_whole_layer(params):
    """The share test of the model-configs guide: the routed parts that 4
    shares of 2 of the 8 experts give, with the shared expert (what every
    chip computes alike) counted once, add up to what the uncut reference
    gives for the whole layer; a share alone is the reference given the
    same share."""
    p = dict(params["layer_1"]["moe"])
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(9), (8,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    whole = REF.pangu_moe(x, p, RCFG)
    no_shared = jax.tree_util.tree_map(jnp.zeros_like, p["shared"])

    def share(first, count):
        layer = layers.RoutedExperts(64, 32, 8, 2, 2.5, (first, count),
                                    jnp.float32, jnp.float32)
        held = {n: p[n][first:first + count]
                for n in ("w_gate", "w_up", "w_down")}
        y, load, _ = layer.apply(
            {"params": dict(p, **held, shared=no_shared)}, x)
        assert int(np.asarray(load).sum()) == 40 * 2    # router keeps 8
        np.testing.assert_allclose(y, REF.pangu_moe(
            x, dict(p, **held), dict(RCFG, experts_held=(first, count)),
            shared=False), atol=1e-5)
        return y

    total = sum(share(first, 2) for first in (0, 2, 4, 6)) \
        + REF._swiglu(x, p["shared"], "highest")
    np.testing.assert_allclose(total, whole, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(REF.pangu_route(x, p, RCFG)[1]).sum(-1), 2.5, rtol=1e-5)


def test_a_share_of_the_model_is_the_reference_given_the_same_share():
    """The whole model over a share: experts 2-3 of 8 held, the router's 8
    outputs kept, through the plain forward and the module."""
    cfg = dict(RCFG, experts_held=(2, 2))
    params = _weights(cfg, seed=5)
    assert params["layer_1"]["moe"]["w_gate"].shape == (2, 64, 32)
    assert params["layer_1"]["moe"]["router"].shape == (64, 8)
    model = pangu_moe.pangu_moe_tiny(experts_held=[2, 2])
    assert model.experts_held == (2, 2)         # a file's list, hashable
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, 20), 0, 256)
    got, got_mtp = model.apply({"params": params}, ids, mtp=True,
                               mutable=["counters"])[0]
    np.testing.assert_allclose(got, REF.pangu_logits(params, ids, cfg),
                               atol=TOL)
    np.testing.assert_allclose(got_mtp,
                               REF.pangu_mtp_logits(params, ids, cfg),
                               atol=TOL)
    counted = model.apply({"params": params}, ids, mtp=True,
                          mutable=["counters"])[1]["counters"]
    load = np.asarray(counted["expert_load"])
    assert load.shape == (3, 8) and (load.sum(-1) == 2 * 20 * 2).all()
    assert np.array_equal(np.asarray(counted["expert_load_held"]),
                          load[:, 2:4])


def test_logits_over_a_vocabulary_slice_are_the_uncut_logits_rows(params):
    """A sliced vocabulary is a smaller vocabulary: 32 of the 256 rows of
    the embedding and of the head, ids drawn from the slice."""
    sliced = dict(params, embed=params["embed"][:32],
                  head=params["head"][:, :32])
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, 32)
    got, got_mtp = pangu_moe.pangu_moe_tiny(vocab_size=32).apply(
        {"params": sliced}, ids, mtp=True)
    whole, whole_mtp = pangu_moe.pangu_moe_tiny().apply(
        {"params": params}, ids, mtp=True)
    np.testing.assert_allclose(got, whole[..., :32], atol=1e-6)
    np.testing.assert_allclose(got_mtp, whole_mtp[..., :32], atol=1e-6)
    np.testing.assert_allclose(
        got, REF.pangu_logits(sliced, ids, dict(RCFG, vocab_size=32)),
        atol=TOL)


def test_the_configuration_files_parameters_are_the_models_own_count():
    cfg = harness.load_json(os.path.join(
        harness.HERE, "configs", "openpangu_ultra_moe_718b.json"))
    served = harness.resolve(cfg["model"]["builder"])(
        **cfg["model"]["kwargs"])
    cut = pangu_moe.openpangu_ultra_moe_718b_cut()
    assert served == cut and cut.dtype == cut.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        cut.clone(decode=True, slot_decode=True, kv_num_blocks=4,
                  kv_block_size=16).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    p, want = shapes["params"], cfg["parameters"]
    assert count(p) == want["held"] == 6037863680
    assert want["held"] == want["matrices_held"] \
        + want["norm_scales_and_router_biases"]
    assert want["matrices_held"] == 6037635072      # ISSUE 36's table
    matrices = lambda tree: sum(
        x.size for x in jax.tree_util.tree_leaves(tree) if x.ndim > 1)
    assert matrices(p["layer_0"]) == want["dense_layer"]
    assert matrices(p["layer_1"]) == want["expert_layer_held"]
    assert matrices(p["mtp"]) == want["mtp_module"]
    assert matrices(p["layer_1"]["attn"]) == want["latent_attention"]
    assert p["embed"].size + p["head"].size == want["embedding_and_head"]
    assert want["expert_layer_whole"] - want["expert_layer_held"] \
        == 240 * want["one_expert"]
    assert cfg["serving_bytes"]["weight_bytes"] \
        == 2 * (want["held"] - p["embed"].size)
    # six latent leaves, 576 values a token stored 640 wide
    cache = jax.tree_util.tree_leaves(shapes["cache"])
    assert [(c.shape, str(c.dtype)) for c in cache] \
        == [((4, 16, 640), "bfloat16")] * 6
    assert cfg["serving_bytes"]["kv_bytes_per_token"] == 6 * 576 * 2


# ------------------------------------------------------- what is refused

def test_kv_quant_is_refused_at_construction(model, params):
    with pytest.raises(ValueError, match="head-less"):
        _engine(model, params, kv_quant=True)


def test_tensor_parallel_is_refused_at_construction(model, params):
    with pytest.raises(ValueError, match="head-less"):
        _engine(model.clone(tensor_parallel=True), params)


def test_more_drafts_than_the_model_has_modules_is_refused(model, params):
    with pytest.raises(ValueError, match="next-token module"):
        _engine(model, params, speculate=2)
    from apex_example_tpu.spec import NgramProposer
    with pytest.raises(ValueError, match="drafts for itself"):
        _engine(model, params, proposer=NgramProposer())
    assert _engine(model, params, speculate=1).self_draft


def test_serve_cli_serves_the_tiny_arch_drafting_with_no_flag(capsys):
    import serve
    argv = ["--arch", "pangu_moe_tiny", "--requests", "6", "--slots", "4",
            "--max-len", "48", "--prompt-len", "3:20", "--max-new", "3:8",
            "--stagger", "2"]
    assert serve.main(argv) == 0
    out = capsys.readouterr().out
    assert "arch=pangu_moe_tiny" in out and "done: 6/6 completed" in out
    assert "spec: K=1 draft=mtp" in out
    assert serve.main(argv + ["--speculate", "0"]) == 0
    assert "spec:" not in capsys.readouterr().out
    assert "openpangu_ultra_moe_718b_cut" in serve.build_parser() \
        .format_help()
    for flag, err, match in (
            (["--kv-quant"], ValueError, "head-less"),
            (["--speculate", "2"], ValueError, "next-token module"),
            (["--weight-quant", "int8"], SystemExit, "no leaf it quantizes")):
        with pytest.raises(err, match=match):
            serve.main(["--arch", "pangu_moe_tiny", "--requests", "2"]
                       + flag)


# ------------------------------- the other served models are what they were

def _other(name):
    if name == "gpt1":
        from apex_example_tpu.models.gpt import gpt_tiny
        return gpt_tiny()
    if name == "xing4":
        return xing4.xing4_tiny(num_layers=2)
    if name == "trinity":
        from apex_example_tpu.models.trinity import trinity_tiny
        return trinity_tiny()
    if name == "lfm2":
        from apex_example_tpu.models.lfm2 import lfm2_tiny
        return lfm2_tiny()
    from apex_example_tpu.models.granite_hybrid import granite_hybrid_tiny
    return granite_hybrid_tiny()


# sha256 of the tick's lowered text (``jit(...).lower(...).as_text()``, 4
# slots x 64, blocks of 8, under the tests' interpreter).  A PR that changes
# one of these models' tick on purpose replaces its line: PR 39 did, for all
# three (the step takes one packed array and a key, ``engine.TickArgs``, where
# it took nine arrays; what it computes from them is what PR 36 pinned), and
# PR 41 for "gpt1" and "granite" (their paged attention became
# ``ops.attention.paged_gqa_attention``), and PR 44 for "xing4" (its
# token-wise sublayers run on ``ops/lane_pack.py``'s packed rows; "granite",
# the map's first caller, "trinity" and "lfm2", which share ``RoutedExperts``
# and ``SwiGLU`` with it, and "gpt1" are what PR 44's parent a09fe8f lowered).
# A PR that only adds a model or an engine path beside them must not.
TICK_SINCE_PR39 = {
    "gpt1": "d30b7b180b9438e05e4eb0e2f39eb2e238c162146865677faf5a30a0e77e0c64",
    "xing4": "b816f09c208380fbc92edd265b4e75ae24b2d91c178523a48a950be55dbceece",
    "granite": "0670df7e1cda5c43df44cc7c5f5cc8bf92accefd8c93eaa9f25bce2b88a615bf",
    "trinity": "7872dc93d8cd9046802bfe8f26265e52e1f7ff34b1c7a26e67043013e092cf82",
    "lfm2": "50966f410f0a3251750340dfee9c1c25e6d3effdc3f8ad8c56be2e7707f6f73b",
}


@pytest.mark.parametrize("name", sorted(TICK_SINCE_PR39))
def test_the_other_models_engines_and_tick_programs_are_untouched(name):
    model = _other(name)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), shapes["params"])
    eng = _engine(model, params)
    assert not eng.self_draft and eng.speculate == 0 and eng.proposer is None
    assert not eng.pool.rows_read_next_token and eng.pool.spec_slack == 0
    assert eng.tick_args == engine_lib.TickArgs(
        BS, MAX_LEN // BS, ring=eng.tick_args.ring)
    # a row budget only where the model declares packed rows, of head 1
    assert (eng._chunk_budget, eng._lane_head) == (
        (1 if name in ("xing4", "granite") else None), 1)
    step = engine_lib._slot_step(eng.pool.dec, eng.tick_args)
    text = step.lower(
        params, eng.pool.cache,
        jnp.zeros((SLOTS, eng.tick_args.width), jnp.int32),
        jax.random.PRNGKey(0)).as_text()
    assert "draft_verify" not in text and "mtp" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == TICK_SINCE_PR39[name]
