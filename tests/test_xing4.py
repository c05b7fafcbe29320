"""The Xing4.0-style decoder (models/xing4.py) against its plain reference
(benchmarks/reference/xing4.py), at tiny widths on the CPU, float32 unless
said: the plain forward, chunked prefill and decode through
ServeEngine/BlockPool (logits, not tokens), absorbed against expanded
latent attention, bfloat16 inside a tolerance that fp8 fails, Sinkhorn,
dropless routing, the share test of the model-configs guide, YaRN by hand,
migration and handoff of a latent slot, what the engine and the pool
refuse, and serve.py's --arch entry end to end.
"""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_example_tpu.models import layers, xing4  # noqa: E402
from apex_example_tpu.models.gpt import gpt_tiny  # noqa: E402
from apex_example_tpu.ops import grouped_matmul, paged_cache  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve.slots import BlockPool  # noqa: E402
from apex_example_tpu.transformer import expert_parallel as ep  # noqa: E402
from benchmarks import harness  # noqa: E402

pytestmark = pytest.mark.serve

REF, _ = harness.load_reference("benchmarks/reference/xing4.py:xing4")
TINY = dict(vocab_size=256, hidden_size=64, num_layers=2, first_k_dense=1,
            num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2)
RCFG = dict(TINY, routed_scaling_factor=2.0, rms_norm_eps=1e-6, hc_mult=4,
            hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp_min=-30,
            hc_clamp_max=30,
            rope=dict(theta=10000, factor=64,
                      original_max_position_embeddings=4096, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1))
SLOTS, MAX_LEN, BS = 4, 64, 8


@pytest.fixture(scope="module")
def model():
    return xing4.xing4_tiny(num_layers=2)


@pytest.fixture(scope="module")
def params():
    return REF.xing4_weights(jax.random.PRNGKey(0), RCFG,
                             jnp.float32)["params"]


def _engine(model, params, **kw):
    return ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                       block_size=BS, **kw)


def _requests(n, seed=0, lo=5, hi=40, new=(4, 12)):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, 256, int(rng.integers(lo, hi))
                                        ).tolist(),
                    max_new_tokens=int(rng.integers(*new)), uid=f"r{i}")
            for i in range(n)]


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.queue.close()
    return {c.request.uid: c for c in eng.run(max_steps=2000)}


# ------------------------------------------------------- the mathematics

def test_seeded_layout_is_the_models_own(model, params):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    sig = lambda tree: jax.tree_util.tree_map(
        lambda t: (tuple(t.shape), str(t.dtype)), tree)
    assert sig(shapes) == sig(params)


def test_plain_forward_matches_the_reference(model, params):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    got = model.apply({"params": params}, ids)
    want = REF.xing4_logits(params, ids, RCFG)
    assert got.shape == (2, 24, 256) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_paged_prefill_and_decode_match_the_references_one_pass(model,
                                                                params):
    """Chunked prefill then decode through BlockPool and the paged branch
    (absorbed attention over the latent arena): at every sampled lane the
    logits equal the reference's one full pass over the finished sequence
    (expanded attention), and so the plain forward's."""
    pool = BlockPool(model, SLOTS, MAX_LEN, block_size=BS)
    seq = np.random.default_rng(5).integers(0, 256, 41).tolist()
    P = 29                              # three full chunks and a part
    want = np.asarray(REF.xing4_logits(params, jnp.asarray([seq]), RCFG))[0]
    plain = np.asarray(model.apply({"params": params},
                                   jnp.asarray([seq])))[0]
    np.testing.assert_allclose(plain, want, atol=2e-5)
    idx = pool.admit(Request(prompt=seq[:P], max_new_tokens=len(seq) - P,
                             uid="a"), 0)
    step = jax.jit(lambda cache, tok, paged: pool.dec.apply(
        {"params": params, "cache": cache}, tok, train=False, paged=paged,
        mutable=["cache", "counters"]))
    cursor, seen = 0, []
    while cursor < len(seq):
        n = min(BS, P - cursor) if cursor < P else 1
        tok = np.zeros((SLOTS, BS), np.int32)
        tok[idx, :n] = seq[cursor:cursor + n]
        fill = np.zeros((SLOTS,), np.int32)
        n_new = np.zeros((SLOTS,), np.int32)
        fill[idx], n_new[idx] = cursor, n
        cow = np.full((SLOTS,), -1, np.int32)
        cow_src, cow_dst = cow.copy(), cow.copy()
        cow_src[idx], cow_dst[idx] = pool.stage_writes(idx, n)
        logits, mut = step(pool.cache, jnp.asarray(tok), {
            "block_table": jnp.asarray(pool.table),
            "fill": jnp.asarray(fill), "n_new": jnp.asarray(n_new),
            "cow_src": jnp.asarray(cow_src), "cow_dst": jnp.asarray(cow_dst)})
        pool.cache = mut["cache"]
        pool.slots[idx].tokens = seq[:cursor + n + 1]
        pool.commit_writes(idx, n)
        cursor += n
        assert logits.shape == (SLOTS, 1, 256)       # the sampled lane only
        seen.append((cursor - 1, np.asarray(logits[idx, 0])))
        load = np.asarray(mut["counters"]["expert_load"])
        assert load.shape == (1, 8) and load.sum() == 2 * n   # live lanes
    assert len(seen) == 4 + (len(seq) - P)
    for pos, row in seen:
        np.testing.assert_allclose(row, want[pos], atol=5e-5)


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_served_tokens_are_the_references_first_places(model, params, form,
                                                       step_traced_with):
    """On both forms of paged latent attention: the Pallas kernel (the
    interpreter here, Mosaic on the TPU) and the XLA gather form."""
    with step_traced_with(xla=form == "xla"):
        eng = _engine(model, params)
        done = _run(eng, _requests(7, seed=2))
    # the XLA form scores every position of every slot, the kernel the
    # live blocks alone
    walked = np.stack([np.asarray(tree["attn_positions_walked"])
                       for _, tree in eng.counter_log])
    assert walked.shape[1:] == (2, SLOTS)
    if form == "xla":
        assert (walked == MAX_LEN).all()
    else:
        assert 0 < walked.max() <= MAX_LEN and (walked % BS == 0).all()
        assert walked.mean() < MAX_LEN / 2
    assert len(done) == 7 and all(c.status == "ok" for c in done.values())
    ids = np.zeros((7, MAX_LEN), np.int32)          # one shape, one compile
    for r, c in enumerate(done.values()):
        seq = list(c.request.prompt) + list(c.tokens)
        ids[r, :len(seq)] = seq
    ref = np.asarray(REF.xing4_logits(params, jnp.asarray(ids), RCFG))
    for r, c in enumerate(done.values()):
        P = len(c.request.prompt)
        assert len(c.tokens) == c.request.max_new_tokens
        for j, t in enumerate(c.tokens):
            assert ref[r, P - 1 + j, t] >= ref[r, P - 1 + j].max() - 1e-4


def _emulate_mxu(monkeypatch):
    """The CPU has no bfloat16 x bfloat16 -> float32 product; the MXU
    multiplies bfloat16 operands exactly and adds in float32, which an
    upcast of both operands is."""
    up = lambda t: t.astype(jnp.float32)
    # the one home, where the shared layers read them, and the model's
    # own binding (its head)
    for mod in (layers, xing4):
        monkeypatch.setattr(mod, "matmul_f32",
                            lambda a, b: jnp.matmul(up(a), up(b)))
    monkeypatch.setattr(layers, "einsum_f32",
                        lambda s, a, b: jnp.einsum(s, up(a), up(b)))
    # the grouped products: the kernel's dot (the interpreter runs it
    # here) and the XLA form
    monkeypatch.setattr(grouped_matmul, "_dot_f32",
                        lambda a, b: jnp.matmul(up(a), up(b)))
    monkeypatch.setattr(grouped_matmul, "ragged_dot_f32",
                        lambda a, w, sizes: jax.lax.ragged_dot(
                            up(a), up(w), sizes))


def test_bfloat16_is_inside_a_tolerance_that_fp8_fails(monkeypatch):
    """As served: bfloat16 weights and activations against the float32
    reference over the same (bfloat16) weights.  The numbers are those the
    benchmark judges: how far the first place of the run in question lies
    below the reference's best (here its mean: at these widths a single
    flipped expert moves a token's every logit by more than the widest gap
    can tell apart) and the share of positions where it is not the
    reference's first.  Tolerance: a mean gap of 0.05, a twentieth of the
    logits' spread of 1, and every sixth position; bfloat16 reads 0.015
    and every tenth, the reference at fp8 (the benchmark's control) 0.2
    and every second."""
    _emulate_mxu(monkeypatch)
    wide = dict(TINY, vocab_size=2048, num_layers=3)
    rcfg = dict(RCFG, **wide)
    weights = REF.xing4_weights(jax.random.PRNGKey(3), rcfg)["params"]
    model = xing4.Xing4ForCausalLM(**wide, max_position=4096)
    assert model.dtype == jnp.bfloat16
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, 2048)
    ref = np.asarray(REF.xing4_logits(weights, ids, rcfg))
    best = ref.max(-1)

    def gap(logits):
        first = np.asarray(logits).argmax(-1)
        return best - np.take_along_axis(ref, first[..., None], -1)[..., 0]

    served = gap(model.apply({"params": weights}, ids))
    control = gap(REF.xing4_logits(weights, ids, rcfg, "fp8"))
    assert served.mean() < 0.05 < control.mean()
    assert np.mean(served > 0) < 1 / 6 < np.mean(control > 0)


def test_sinkhorn_gives_doubly_stochastic_matrices_and_identity_at_seed():
    noise = jax.random.normal(jax.random.PRNGKey(0), (7, 4, 4))
    # the seeded bias plus a data-dependent part of the size alpha gives it
    m = xing4.sinkhorn(8.0 * jnp.eye(4) + 0.01 * noise, 20, 1e-6, -30.0,
                       30.0)
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-4)
    # far from the seed, 20 iterations leave the columns exact (they come
    # last) and the rows near
    wild = xing4.sinkhorn(3.0 * noise, 20, 1e-6, -30.0, 30.0)
    np.testing.assert_allclose(wild.sum(-2), 1.0, atol=1e-4)
    np.testing.assert_allclose(wild.sum(-1), 1.0, atol=2e-2)
    assert np.all(np.asarray(wild) >= 0)
    seeded = xing4.sinkhorn(8.0 * jnp.eye(4), 20, 1e-6, -30.0, 30.0)
    np.testing.assert_allclose(seeded, np.eye(4), atol=2e-3)
    np.testing.assert_allclose(
        seeded, REF._sinkhorn(8.0 * jnp.eye(4), RCFG), atol=1e-7)
    # at the seeded biases a unit starts as a plain residual: H_pre = 1/n
    # reads the streams' mean, H_post = 1 adds the sublayer's output once
    hc = xing4.HyperConnection(4, 16, 1e-6, 20, 1e-6, (-30.0, 30.0),
                               jnp.float32)
    X = jnp.zeros((2, 3, 4, 16))
    v = hc.init(jax.random.PRNGKey(0), X, method="mix_in")
    u, (h_res, h_post) = hc.apply(v, X + 1.0, method="mix_in")
    np.testing.assert_allclose(u, 1.0, atol=2e-2)
    np.testing.assert_allclose(h_post, 1.0, atol=2e-2)
    np.testing.assert_allclose(h_res, np.broadcast_to(np.eye(4), h_res.shape),
                               atol=5e-3)


def test_yarn_frequencies_by_hand():
    """64 rotary dimensions, theta 10000, factor 64 over 4096 positions,
    beta 32 and 1: the ramp runs over dimensions 10 (32 rotations fit:
    floor(10.47)) to 23 (one fits: ceil(22.51))."""
    f = np.asarray(layers.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    assert f.shape == (32,)
    np.testing.assert_allclose(f[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(f[10], 10000.0 ** (-20 / 64), rtol=1e-5)
    np.testing.assert_allclose(f[23], 10000.0 ** (-46 / 64) / 64, rtol=1e-5)
    np.testing.assert_allclose(f[31], 10000.0 ** (-62 / 64) / 64, rtol=1e-5)
    mid = 16                                       # inside the ramp: a blend
    t = (mid - 10) / 13
    want = 10000.0 ** (-32 / 64) * ((1 - t) + t / 64)
    np.testing.assert_allclose(f[mid], want, rtol=1e-5)
    np.testing.assert_allclose(
        f, REF.xing4_yarn_inv_freq(dict(RCFG, qk_rope_head_dim=64)),
        rtol=1e-6)
    assert layers.yarn_mscale(64.0, 1.0) == pytest.approx(1.4159, abs=1e-4)
    assert REF.xing4_softmax_scale(dict(
        RCFG, qk_nope_head_dim=128, qk_rope_head_dim=64)) \
        == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)


# ------------------------------------------------------ the expert layer

def _moe_parts(key, d=16, f=8, E=8):
    ks = jax.random.split(key, 5)
    n = lambda k, s, fan: jax.random.normal(k, s) / math.sqrt(fan)
    return dict(router=n(ks[0], (d, E), d), router_bias=jnp.zeros((E,)),
                w_gate=n(ks[1], (E, d, f), d), w_up=n(ks[2], (E, d, f), d),
                w_down=n(ks[3], (E, f, d), f),
                shared=dict(w_gate=n(ks[4], (d, f), d),
                            w_up=n(ks[4], (d, f), d) * 0.5,
                            w_down=n(ks[4], (f, d), f)))


def test_dropless_keeps_every_token_where_gshard_drops():
    """All tokens forced to one expert: the capacity layer keeps
    ceil(T / E * 1.25) of them and drops the rest, the dropless layer
    computes every one."""
    p = _moe_parts(jax.random.PRNGKey(0))
    T, d, E = 32, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (T, d))
    bias = jnp.zeros((E,)).at[3].set(100.0)          # selection only
    idx, gates = ep.dropless_route(x, p["router"], bias, 1, 2.0)
    assert np.all(np.asarray(idx) == 3)
    np.testing.assert_allclose(gates, 2.0, rtol=1e-6)   # a lone gate is 1
    y, visits = ep.dropless_experts(x, idx, gates, p["w_gate"], p["w_up"],
                                    p["w_down"], (0, E))
    assert np.asarray(visits).tolist() == [0, 0, 0, 1, 0, 0, 0, 0]
    want = 2.0 * (jax.nn.silu(x @ p["w_gate"][3]) * (x @ p["w_up"][3])) \
        @ p["w_down"][3]
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)     # none dropped
    assert np.asarray(ep.expert_load(idx, E)).tolist() \
        == [0, 0, 0, T, 0, 0, 0, 0]
    # the trainers' capacity layer on the same routing drops tokens
    logits = jnp.zeros((T, E)).at[:, 3].set(100.0)
    capacity = math.ceil(T / E * 1.25)
    dispatch, _, _ = ep._dispatch_masks(logits, capacity)
    assert int(np.asarray(dispatch).sum()) == capacity < T


def test_dead_lanes_belong_to_no_group():
    """The padding of a static serving batch is routed like any lane but
    computed by no expert: live lanes get the reference's result, dead
    lanes the shared expert's alone, and the load counts live lanes."""
    d, f, E, k = 16, 8, 8, 2
    p = _moe_parts(jax.random.PRNGKey(5), d, f, E)
    cfg = dict(n_routed_experts=E, num_experts_per_tok=k,
               routed_scaling_factor=2.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 6, d))
    live = jnp.arange(6)[None, :] < jnp.asarray([6, 1, 0, 3])[:, None]
    layer = layers.RoutedExperts(d, f, E, k, 2.0, (0, E), jnp.float32,
                                jnp.float32)
    y, load, _ = layer.apply({"params": p}, x, live)
    whole = REF.xing4_moe(x, p, cfg)
    shared = REF._swiglu(x, p["shared"], "highest")
    np.testing.assert_allclose(y[live], whole[live], atol=1e-5)
    np.testing.assert_allclose(y[~live], shared[~live], atol=1e-5)
    assert int(np.asarray(load).sum()) == int(live.sum()) * k


def test_expert_shares_add_up_to_the_whole_layer():
    """The share test of the model-configs guide: the parts of the result
    that the two halves of the experts give, with the shared expert (what
    every chip computes alike) counted once, add up to what the uncut
    reference gives for the whole layer."""
    d, f, E, k = 16, 8, 8, 3
    p = _moe_parts(jax.random.PRNGKey(2), d, f, E)
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(9), (E,)) * 0.1
    cfg = dict(n_routed_experts=E, num_experts_per_tok=k,
               routed_scaling_factor=2.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, d))
    whole = REF.xing4_moe(x, p, cfg)

    def share(first, count):
        layer = layers.RoutedExperts(d, f, E, k, 2.0, (first, count),
                                    jnp.float32, jnp.float32)
        held = {n: p[n][first:first + count]
                for n in ("w_gate", "w_up", "w_down")}
        y, load, visits = layer.apply({"params": dict(
            p, **held, shared=jax.tree_util.tree_map(jnp.zeros_like,
                                                     p["shared"]))}, x)
        assert int(np.asarray(load).sum()) == 40 * k    # router keeps E
        # the kernel visits the experts held here that got a token, and
        # none of the others
        seen = np.asarray(visits) > 0
        assert seen.shape == (E,) and not seen[:first].any() \
            and not seen[first + count:].any()
        assert (seen == (np.asarray(load) > 0))[first:first + count].all()
        return y

    shared_once = REF._swiglu(x, p["shared"], "highest")
    np.testing.assert_allclose(share(0, 4) + share(4, 4) + shared_once,
                               whole, atol=1e-5)
    # a share alone is the reference given the same share
    np.testing.assert_allclose(
        share(4, 4), REF.xing4_moe(x, p, cfg, experts_held=(4, 4),
                                   shared=False), atol=1e-5)
    # and the routing is the reference's, gates included
    idx, g = ep.dropless_route(x, p["router"], p["router_bias"], k, 2.0)
    ridx, rg = REF.xing4_route(x, p, cfg)
    assert np.array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(g, rg, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.0, rtol=1e-5)


# --------------------------------------------------- the pool, the engine

def test_latent_arena_is_one_headless_leaf_a_layer(model):
    pool = BlockPool(model, SLOTS, MAX_LEN, block_size=BS, num_blocks=40)
    leaves = paged_cache.block_leaves(pool.cache, 40, BS)
    assert len(leaves) == 2 and all(kind == paged_cache.PAYLOAD
                                    for _, _, kind in leaves)
    # kv_lora_rank + qk_rope_head_dim = 40 values, stored in whole
    # 128-lane tiles
    assert {tuple(leaf.shape) for _, leaf, _ in leaves} == {(40, BS, 128)}
    assert pool.kv_bytes_per_token() == 2 * 128 * 4
    assert pool.kv_dtype == "float32"
    served = xing4.xing4_29b_a4b_cut()
    assert (served.num_layers, served.first_k_dense) == (6, 1)
    assert served.dtype == served.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        served.clone(decode=True, slot_decode=True, kv_num_blocks=4,
                     kv_block_size=16).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    cache = jax.tree_util.tree_leaves(shapes["cache"])
    assert [(c.shape, str(c.dtype)) for c in cache] \
        == [((4, 16, 640), "bfloat16")] * 6
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert abs(n - 4.79e9) < 0.01e9              # every expert, whole vocab


def test_what_a_latent_leaf_cannot_do_is_refused_at_construction(model,
                                                                 params):
    with pytest.raises(ValueError, match="head-less"):
        BlockPool(model, SLOTS, MAX_LEN, block_size=BS, kv_quant=True)
    with pytest.raises(ValueError, match="sampled lane"):
        _engine(model, params, speculate=2)
    from jax.sharding import Mesh
    if len(jax.devices()) >= 2:
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    ("data", "model"))
        pool = BlockPool(model, SLOTS, MAX_LEN, block_size=BS)
        with pytest.raises(ValueError, match="no head axis"):
            pool.shard(mesh)
    with pytest.raises(ValueError, match="head-less"):
        BlockPool(model.clone(tensor_parallel=True), SLOTS, MAX_LEN,
                  block_size=BS)


def test_engine_keeps_the_expert_load_of_live_lanes(model, params):
    eng = _engine(model, params)
    done = _run(eng, _requests(5, seed=4))
    assert len(done) == 5
    log = [(t, jax.tree_util.tree_map(np.asarray, tree))
           for t, tree in eng.counter_log]
    assert len(log) == eng.compute_steps > 0
    t = [at for at, _ in log]
    assert t == sorted(t)
    for _, tree in log:
        load = tree["expert_load"]
        assert load.shape == (1, 8) and load.dtype == np.int32
        # every live lane chose num_experts_per_tok experts
        assert load.sum() > 0 and load.sum() % 2 == 0
    lanes = sum(len(c.request.prompt) + len(c.tokens) - 1
                for c in done.values())
    assert sum(int(tree["expert_load"][0].sum()) for _, tree in log) \
        == 2 * lanes
    # a model that counts nothing keeps the three-output step
    g = gpt_tiny()
    gp = g.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"]
    geng = ServeEngine(g, gp, num_slots=2, max_len=32, block_size=8)
    _run(geng, [Request(prompt=[1, 2, 3], max_new_tokens=3, uid="g")])
    assert not geng.counter_log


@pytest.mark.parametrize("how", ["migration", "handoff"])
def test_a_latent_slot_moved_between_engines_resumes_token_identically(
        model, params, how):
    reqs = _requests(4, seed=11, new=(6, 14))
    want = {u: list(c.tokens) for u, c in
            _run(_engine(model, params), _requests(4, seed=11,
                                                   new=(6, 14))).items()}
    if how == "migration":
        src, dst = _engine(model, params), _engine(model, params)
        for r in reqs:
            src.submit(r)
        src.queue.close()
        uid = reqs[0].uid

        def mid_decode():
            s = next((s for s in src.pool.slots
                      if s is not None and s.request.uid == uid), None)
            return s is not None and not s.prefilling and s.n_generated >= 2
        for _ in range(500):
            if mid_decode():
                break
            src.step()
        h = src.extract_live(uid)
        assert h is not None and h.kind == "migration"
        assert all(v.shape[1:] == (BS, 128) for v in h.payload.values())
        assert len(h.payload) == 2                   # one leaf a layer
        comps = src.run(max_steps=2000)
        assert dst.admit_migrated(h) is True
        dst.queue.close()
        comps = comps + dst.run(max_steps=2000)
    else:
        shipped = []
        src = _engine(model, params, role="prefill",
                      handoff_sink=shipped.append)
        dst = _engine(model, params, role="decode")
        comps = list(_run(src, reqs).values())
        assert len(shipped) == 4
        for h in shipped:
            assert dst.admit_handoff(h) is True
        dst.queue.close()
        comps = [c for c in comps if c.status == "ok"] \
            + dst.run(max_steps=2000)
    got = {c.request.uid: list(c.tokens) for c in comps if c.status == "ok"}
    assert got == want


def test_gpt_payload_is_what_the_pool_always_shipped():
    """The older decoder's handoff payload, now found by shape: the same
    keys (the cache paths), dtypes and bytes as the named K and V leaves
    gathered at the slot's blocks."""
    g = gpt_tiny()
    gp = g.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"]
    for kv_quant in (False, True):
        eng = ServeEngine(g, gp, num_slots=2, max_len=32, block_size=8,
                          kv_quant=kv_quant)
        eng.submit(Request(prompt=list(range(1, 20)), max_new_tokens=6,
                           uid="a"))
        for _ in range(4):
            eng.step()
        fill, n, payload = eng.pool.extract_blocks(0)
        names = ["cached_key", "cached_value"] + (
            ["cached_key_scale", "cached_value_scale"] if kv_quant else [])
        assert sorted(payload) == sorted(
            f"layer_{i}/attention/{name}" for i in range(2)
            for name in names)
        bids = np.asarray(eng.pool.table[0, :n])
        for key, rows in payload.items():
            layer, _, name = key.split("/")
            leaf = np.asarray(eng.pool.cache[layer]["attention"][name])
            assert rows.dtype == leaf.dtype and rows.flags.writeable
            assert rows.tobytes() == leaf[bids].tobytes()
        assert fill == eng.pool.slots[0].cursor and n == math.ceil(fill / 8)
        assert eng.pool.kv_bytes_per_token() == (264 if kv_quant else 1024)


def test_serve_cli_serves_the_tiny_arch_end_to_end(capsys):
    import serve
    assert serve.main(["--arch", "xing4_tiny", "--requests", "6",
                       "--slots", "4", "--max-len", "48", "--prompt-len",
                       "3:20", "--max-new", "3:8", "--stagger", "2"]) == 0
    out = capsys.readouterr().out
    assert "arch=xing4_tiny" in out and "done: 6/6 completed" in out
    # refused where the rule lives: the model (head-less leaf), the engine
    # (sampled-lane head), the entry point (nothing quantized)
    for flag, err, match in (
            (["--kv-quant"], ValueError, "head-less"),
            (["--speculate", "2"], ValueError, "sampled lane only"),
            (["--weight-quant", "int8"], SystemExit, "no leaf it quantizes")):
        with pytest.raises(err, match=match):
            serve.main(["--arch", "xing4_tiny", "--requests", "2"] + flag)
