"""The Granite 4.0-H style hybrid decoder (models/granite_hybrid.py: Mamba-2
layers with a per-slot recurrent state beside the paged K/V of a GQA
attention layer) against its plain reference (benchmarks/reference/
granite_hybrid.py: float32, the recurrence token by token), at tiny widths
on the CPU, float32 unless said: the plain forward; chunked prefill then
decode through ServeEngine, logits not tokens; a reused slot; two requests
of one prompt; migration and handoff of a slot with its state; what is
refused; the parameter count at the published sizes; serve.py's entry.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_example_tpu.models import granite_hybrid as gh  # noqa: E402
from apex_example_tpu.ops import paged_cache  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve.slots import BlockPool  # noqa: E402
from benchmarks import harness  # noqa: E402

pytestmark = pytest.mark.serve

REF, _ = harness.load_reference(
    "benchmarks/reference/granite_hybrid.py:granite")
RCFG = dict(vocab_size=256, hidden_size=64,
            layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
            num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
            mamba_d_conv=4, embedding_multiplier=12.0,
            attention_multiplier=0.015625, residual_multiplier=0.22,
            logits_scaling=8.0, rms_norm_eps=1e-5)
SLOTS, MAX_LEN, BS = 3, 64, 8
# logits lie within +-0.1 (the tied table is seeded small: no echo); the
# engine's float32 path reads 5e-8 from the reference, a state rounded to
# bfloat16 between ticks 1.0e-5 (the tiny state weighs little in a logit:
# 16 columns, steps of 1e-3 to 1e-1), three dropped convolution rows 0.053
# (the last test but two)
TOL = 5e-7


@pytest.fixture(scope="module")
def model():
    m = gh.granite_hybrid_tiny()
    assert list(m.layer_kinds()) == RCFG["layer_types"]
    return m


@pytest.fixture(scope="module")
def params():
    return REF.granite_weights(jax.random.PRNGKey(0), RCFG,
                               jnp.float32)["params"]


@pytest.fixture(scope="module")
def ref_logits(params):
    fn = jax.jit(lambda ids: REF.granite_logits(params, ids, RCFG))

    def of(seq):
        ids = np.zeros((1, MAX_LEN), np.int32)         # one shape, one compile
        ids[0, :len(seq)] = seq
        return np.asarray(fn(jnp.asarray(ids)))[0, :len(seq)]
    return of


def _engine(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    return ServeEngine(model, params, max_len=MAX_LEN, block_size=BS, **kw)


def _record_logits(eng, between=None):
    """Put a step of the test's own in the engine's place that is the
    engine's program (the same module clone, the same arguments, greedy)
    and also hands out the logits: ``seen[uid][position] = logits row``
    for every lane the engine sampled or could have.  ``between``: done to
    the cache after every tick (a fault to show the tolerance by)."""
    seen = {}
    dec = eng.pool.dec

    @jax.jit
    def step(params, cache, packed):
        said = eng.tick_args.fields(packed)
        logits, mut = dec.apply(
            {"params": params, "cache": cache}, said["tok"], train=False,
            paged={k: said[k] for k in ("block_table", "fill", "n_new",
                                        "cow_src", "cow_dst")},
            mutable=["cache", "counters"])
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


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.queue.close()
    return {c.request.uid: c for c in eng.run(max_steps=2000)}


def _worst(done, seen, ref_logits):
    """Widest distance of a recorded logits row from the reference's full
    forward over the finished sequence, every request, every position."""
    worst = 0.0
    for uid, c in done.items():
        want = ref_logits(list(c.request.prompt) + list(c.tokens))
        for pos, row in seen[uid].items():
            worst = max(worst, float(np.abs(row - want[pos]).max()))
    return worst


def test_seeded_layout_is_the_models_own(model, params):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    sig = lambda tree: jax.tree_util.tree_map(
        lambda t: (tuple(t.shape), str(t.dtype)), tree)
    assert sig(shapes) == sig(params)


def test_plain_forward_matches_the_reference(model, params):
    """37 positions: four chunks of the model's 8 and a part, against the
    reference's token-by-token scan."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 256)
    got = jax.jit(lambda ids: model.apply({"params": params}, ids))(ids)
    want = REF.granite_logits(params, ids, RCFG)
    assert got.shape == (2, 37, 256) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_chunked_prefill_then_decode_gives_the_references_logits(
        model, params, ref_logits):
    """Prompts that are no multiple of the chunk (8), more requests than
    slots (so a slot is reused and must start from a zero state inside
    the tick), two requests of one prompt (nothing may be shared: the
    state at a prefix boundary is held nowhere)."""
    eng = _engine(model, params)
    seen = _record_logits(eng)
    reqs = _requests([29, 5, 17, 20, 9], [6, 9, 4, 7, 5])
    twin = Request(prompt=list(reqs[0].prompt), max_new_tokens=6, uid="twin")
    done = _run(eng, reqs + [twin])
    assert len(done) == 6 and all(c.status == "ok" for c in done.values())
    # every chunk's last lane and every decode lane was recorded
    assert len(seen["r0"]) == 4 + 6 - 1 and len(seen["r1"]) == 1 + 9 - 1
    assert _worst(done, seen, ref_logits) < TOL
    assert list(done["twin"].tokens) == list(done["r0"].tokens)
    assert eng.pool.prefix_hit_rate() == 0.0 and eng.pool.cow_copies == 0
    assert eng.summary_record()["prefix_hit_rate"] == 0.0
    # the counters: every live lane, and every Mamba layer's slots moved
    lanes = sum(len(c.request.prompt) + len(c.tokens) - 1
                for c in done.values())
    log = [jax.tree_util.tree_map(np.asarray, t) for _, t in eng.counter_log]
    assert sum(int(t["lanes_live"].sum()) for t in log) == lanes
    for t in log:
        assert t["lanes_live"].shape == (1, SLOTS)
        assert t["ssm_slots_advanced"].shape == (4, SLOTS)
        assert (t["ssm_slots_advanced"] == (t["lanes_live"] > 0)).all()


def test_both_forms_of_paged_attention_serve_the_same_tokens(
        model, params, step_traced_with):
    """ISSUE 41: the GQA layer reads its arenas through
    ``ops.attention.paged_gqa_attention``.  Its kernel (the interpreter
    here) walks each slot's live blocks and nothing of a slot without a
    live lane; its XLA form every row of the table; both serve the same
    tokens.  ``attn_positions_walked [attention layers, slots]`` says which
    ran and how far."""
    tokens, walked, lanes = {}, {}, {}
    for form in ("kernel", "xla"):
        with step_traced_with(xla=form == "xla"):
            eng = _engine(model, params)
            done = _run(eng, _requests([29, 5, 17, 20], [6, 9, 4, 7]))
        tokens[form] = {u: list(c.tokens) for u, c in done.items()}
        log = [jax.tree_util.tree_map(np.asarray, t)
               for _, t in eng.counter_log]
        walked[form] = np.stack([t["attn_positions_walked"] for t in log])
        lanes[form] = np.stack([t["lanes_live"] for t in log])
    assert tokens["kernel"] == tokens["xla"] and len(tokens["xla"]) == 4
    assert walked["xla"].shape[1:] == (1, SLOTS)       # one GQA layer
    assert (walked["xla"] == MAX_LEN).all()
    k, live = walked["kernel"], lanes["kernel"] > 0
    assert (k % BS == 0).all() and ((k > 0) == live).all()
    assert BS <= k[live].mean() < MAX_LEN / 2


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(model, params):
    """One slot, two requests one after the other: the second reads what a
    fresh engine reads, bit for bit (the first one's state and rows are
    zeroed away inside the tick, not on the host)."""
    first, second = _requests([21, 13], [5, 6], seed=3)
    eng = _engine(model, params, num_slots=1)
    seen = _record_logits(eng)
    _run(eng, [first, second])
    fresh = _engine(model, params, num_slots=1)
    alone = _record_logits(fresh)
    _run(fresh, [Request(prompt=list(second.prompt), max_new_tokens=6,
                         uid="r1")])
    assert sorted(seen["r1"]) == sorted(alone["r1"])
    for pos, row in alone["r1"].items():
        assert row.tobytes() == seen["r1"][pos].tobytes()


@pytest.mark.parametrize("how", ["migration", "handoff"])
def test_a_slot_moved_with_its_state_goes_on_with_the_references_logits(
        model, params, ref_logits, how):
    reqs = _requests([19, 11, 26], [8, 9, 7], seed=11)
    if how == "migration":
        src, dst = _engine(model, params), _engine(model, params)
        seen = _record_logits(dst)
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
        kinds = {k.rsplit("/", 1)[1]: v.shape for k, v in h.payload.items()}
        assert kinds["slot:ssm_state"] == (1, 4, 16, 16)
        assert kinds["slot:conv_rows"] == (1, 3 * (64 + 32))
        assert kinds["cached_key"][1:] == (BS, 32)
        assert len(h.payload) == 2 * 4 + 2       # per Mamba layer, K and V
        comps = src.run(max_steps=2000)
        assert dst.admit_migrated(h) is True
        dst.queue.close()
        comps = comps + dst.run(max_steps=2000)
        moved = [uid]
    else:
        shipped = []
        src = _engine(model, params, role="prefill",
                      handoff_sink=shipped.append)
        dst = _engine(model, params, role="decode")
        seen = _record_logits(dst)
        comps = list(_run(src, reqs).values())
        assert len(shipped) == 3
        for h in shipped:
            assert dst.admit_handoff(h) is True
        dst.queue.close()
        comps = [c for c in comps if c.status == "ok"] \
            + dst.run(max_steps=2000)
        moved = [r.uid for r in reqs]
    done = {c.request.uid: c for c in comps if c.status == "ok"}
    assert sorted(done) == ["r0", "r1", "r2"]
    assert all(len(seen[u]) >= 5 for u in moved)
    assert _worst({u: done[u] for u in moved}, seen, ref_logits) < TOL
    # a payload without the slot's state is refused, not served from
    # whatever the slot held
    bare = {k: v for k, v in h.payload.items() if "slot:" not in k}
    with pytest.raises(ValueError, match="missing per-slot leaf"):
        paged_cache.insert(dst.pool.cache, [0], {k: v[:1] for k, v
                                                 in bare.items()},
                           dst.pool.num_blocks, BS, pad_to=8, slot=0)


def test_what_a_per_slot_state_cannot_do_is_refused_with_the_reason(
        model, params):
    with pytest.raises(ValueError, match="cannot be rolled back"):
        _engine(model, params, speculate=2)
    with pytest.raises(ValueError, match="kv_quant.*float32 by design"):
        BlockPool(model, SLOTS, MAX_LEN, block_size=BS, kv_quant=True)
    with pytest.raises(ValueError, match="tensor_parallel.*no sharding"):
        BlockPool(model.clone(tensor_parallel=True), SLOTS, MAX_LEN,
                  block_size=BS)
    pool = BlockPool(model, SLOTS, MAX_LEN, block_size=BS)
    assert pool.per_slot_state
    # counted apart from K/V: 4 Mamba layers of float32 state and three
    # float32 rows (this preset's dtype); one attention layer's K and V
    per_slot = 4 * (4 * 16 * 16 + 3 * 96) * 4
    assert pool.state_bytes_reserved() == SLOTS * per_slot
    assert pool.state_bytes_live() == 0
    assert pool.kv_bytes_per_token() == 2 * 32 * 4
    pool.admit(Request(prompt=[1, 2, 3], max_new_tokens=2, uid="a"), 0)
    assert pool.state_bytes_live() == per_slot


@pytest.mark.parametrize("fault,least", [("bfloat16_state", 5e-6),
                                         ("dropped_rows", 1e-2)])
def test_the_tolerance_fails_a_rounded_state_and_a_dropped_carry(
        model, params, ref_logits, fault, least):
    def between(cache):
        def hurt(path, leaf):
            name = path[-1].key
            if fault == "bfloat16_state" and name == "slot:ssm_state":
                return leaf.astype(jnp.bfloat16).astype(leaf.dtype)
            if fault == "dropped_rows" and name == "slot:conv_rows":
                return jnp.zeros_like(leaf)
            return leaf
        return jax.tree_util.tree_map_with_path(hurt, cache)

    eng = _engine(model, params)
    seen = _record_logits(eng, between)
    done = _run(eng, _requests([29, 17], [8, 8], seed=5))
    assert _worst(done, seen, ref_logits) > least > 2 * TOL


def test_parameters_of_the_configuration_file_are_the_models_own():
    cfg = harness.load_json(os.path.join(
        REPO, "benchmarks", "configs", "granite_4_0_h_micro.json"))
    model = harness.resolve(cfg["model"]["builder"])(**cfg["model"]["kwargs"])
    assert list(model.layer_kinds()) == cfg["layer_types"]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    want = cfg["parameters"]
    assert count(shapes["layer_0"]) == want["per_mamba_layer"] == 76182976
    assert count(shapes["layer_5"]) == want["per_attention_layer"] == 60821504
    assert count(shapes["embed"]) == want["embedding_tied"] == 205520896
    assert count(shapes) == want["total"] == 3191396096
    assert cfg["serving_bytes"]["weight_bytes"] == 2 * want["total"]
    # the reference's seeded tree is the same tree
    ref = jax.eval_shape(lambda k: REF.granite_weights(
        k, cfg["reference_cfg"]), jax.random.PRNGKey(0))["params"]
    sig = lambda tree: jax.tree_util.tree_map(
        lambda t: (tuple(t.shape), str(t.dtype)), tree)
    assert sig(ref) == sig(shapes)
    # every seeded value is listed on both sides
    assert sorted(cfg["assumed"]) == sorted(REF.ASSUMED)
    assert cfg["reduced"] == [] and cfg["published"] == {}
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is not None:
        assert {k: cfg[k] for k in row["config"]} == row["config"]


def test_serve_cli_serves_the_tiny_arch_end_to_end(capsys):
    import serve
    assert serve.main(["--arch", "granite_hybrid_tiny", "--requests", "6",
                       "--slots", "4", "--max-len", "48", "--prompt-len",
                       "3:20", "--max-new", "3:8", "--stagger", "2",
                       "--shared-prefix", "10"]) == 0
    out = capsys.readouterr().out
    assert "arch=granite_hybrid_tiny" in out
    assert "done: 6/6 completed" in out
    for flag, err, match in (
            (["--kv-quant"], ValueError, "float32 by design"),
            (["--speculate", "2"], ValueError, "cannot be rolled back"),
            (["--weight-quant", "int8"], SystemExit, "no leaf it quantizes")):
        with pytest.raises(err, match=match):
            serve.main(["--arch", "granite_hybrid_tiny", "--requests", "2"]
                       + flag)


# ------------------------------------------------------------ packed lanes
# The paged program's token-wise sublayers run on the tick's live lanes as
# dense rows (ops/lane_pack.py); the engine grants no more multi-lane
# chunks a tick than the rows hold.  The other side of every comparison is
# the same model with ``packed_lanes = False``: the [SLOTS, C] form, and an
# engine with no budget.

class UnpackedGranite(gh.GraniteHybridForCausalLM):
    packed_lanes = False


def _unpacked(model):
    fields = {f: getattr(model, f) for f in model.__dataclass_fields__
              if f not in ("parent", "name")}
    return UnpackedGranite(**fields)


def test_rows_follow_from_the_ticks_geometry_alone():
    from apex_example_tpu.ops import lane_pack
    # the served cell: every slot's lane 0 and 12 whole chunks, a quarter
    # of the tick's 1024 lanes
    assert lane_pack.groups(64, 16) == 12 and lane_pack.rows(64, 16) == 256
    # never fewer than one chunk (a prompt must advance); C = 1 (a
    # decode-role engine) packs nothing
    assert lane_pack.groups(3, 8) == 1 and lane_pack.rows(3, 8) == 11
    assert lane_pack.groups(8, 16) == 1 and lane_pack.rows(8, 16) == 24
    assert lane_pack.groups(64, 1) == 0 and lane_pack.rows(64, 1) == 64


@pytest.mark.parametrize("n_new", [
    [1, 0, 5, 1, 8, 0, 1, 3],       # decode, idle, chunks whole and part
    [1, 1, 1, 1, 1, 1, 1, 1],       # decode only: the first S rows
    [0, 0, 0, 0, 0, 0, 0, 0],       # nothing live
    [8, 1, 8, 0, 8, 1, 0, 1],       # the budget spent (3 groups of 3)
], ids=["mixed", "decode_only", "idle", "budget_spent"])
def test_lane_map_packs_and_unpacks_every_live_lane_once(
        n_new, monkeypatch):
    from apex_example_tpu.ops import lane_pack
    monkeypatch.setattr(lane_pack, "groups", lambda s, c, head=1: 3)
    S, C = 8, 8
    n = jnp.asarray(n_new, jnp.int32)
    m = lane_pack.LaneMap(n, C)
    assert m.rows == S + 3 * C
    x = np.arange(1, S * C * 2 + 1, dtype=np.float32).reshape(S, C, 2)
    live = np.arange(C)[None, :] < np.asarray(n_new)[:, None]
    rows = np.asarray(m.pack(jnp.asarray(x)))
    # every live lane is exactly one row, a dead row holds zeros
    assert sorted(rows[:, 0][rows[:, 0] > 0]) == sorted(x[live][:, 0])
    assert int(np.asarray(m.row_live).sum()) == int(live.sum())
    assert not rows[~np.asarray(m.row_live)].any()
    assert (np.asarray(m.pack(jnp.asarray(x), fill=-7))[
        ~np.asarray(m.row_live)] == -7).all()
    # there and back; dead lanes read zero whatever the dead rows hold
    dirty = np.where(np.asarray(m.row_live)[:, None], rows, np.nan)
    back = np.asarray(m.unpack(jnp.asarray(dirty)))
    np.testing.assert_array_equal(back, np.where(live[..., None], x, 0))
    last = np.asarray(m.last(jnp.asarray(dirty)))
    for s, k in enumerate(n_new):
        np.testing.assert_array_equal(last[s], x[s, k - 1] if k else 0)


MIXES = {
    # more requests than slots (a reused slot), prompts that are no
    # multiple of the chunk, and with one group a tick two of the three
    # slots sit at n_new = 0 while the oldest prefills
    "chunks_decode_reuse": dict(lens=[29, 5, 17, 20, 9],
                                new=[6, 9, 4, 7, 5], num_slots=3, seed=0),
    "one_slot_reused": dict(lens=[21, 13], new=[5, 6], num_slots=1, seed=3),
    "long_prompts_at_once": dict(lens=[40, 33, 25, 18], new=[3, 4, 5, 6],
                                 num_slots=4, seed=7),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_packed_rows_give_the_unpacked_forms_logits_and_tokens(
        model, params, ref_logits, mix):
    kw = dict(MIXES[mix])
    slots = kw.pop("num_slots")
    runs = []
    for m in (model, _unpacked(model)):
        eng = _engine(m, params, num_slots=slots)
        seen = _record_logits(eng)
        runs.append((eng, seen, _run(eng, _requests(**kw))))
    (packed, seen, done), (plain, seen_plain, done_plain) = runs
    assert packed._chunk_budget == 1 and plain._chunk_budget is None
    assert sorted(done) == sorted(done_plain)
    for uid, c in done.items():
        assert c.status == "ok"
        assert list(c.tokens) == list(done_plain[uid].tokens)
        assert sorted(seen[uid]) == sorted(seen_plain[uid])
        for pos, row in seen[uid].items():
            assert np.abs(row - seen_plain[uid][pos]).max() < TOL
    assert _worst(done, seen, ref_logits) < TOL
    # the products ran on the packed rows, and a slot did sit a tick out
    dense = {int(np.asarray(t["rows_dense"]).sum())
             for _, t in packed.counter_log}
    assert dense == {slots + BS}
    assert {int(np.asarray(t["rows_dense"]).sum())
            for _, t in plain.counter_log} == {slots * BS}
    if slots > 1:
        assert packed.prefill_chunks_deferred > 0
    assert plain.prefill_chunks_deferred == 0


def test_a_nan_in_a_dead_row_reaches_no_live_lane_and_no_finite_check(
        model, params, monkeypatch):
    """Every pack plants NaN in its dead rows (so from the first Mamba
    layer on every dead row of the residual stream is NaN through every
    projection, norm and MLP): the logits are the clean run's bit for bit,
    the program's finite mask holds for every slot, empty and deferred ones
    too, and nothing in the cache turns NaN."""
    from apex_example_tpu.ops import lane_pack
    reqs = dict(lens=[29, 5, 17, 20], new=[4, 6, 3, 5], seed=2)
    clean = _engine(model, params)
    want = _record_logits(clean)
    done = _run(clean, _requests(**reqs))

    pack = lane_pack.LaneMap.pack
    planted = []

    def dirty(self, x, fill=0):
        out = pack(self, x, fill)
        if not jnp.issubdtype(out.dtype, jnp.floating):
            return out
        planted.append(out.shape)
        return jnp.where(self.row_live.reshape(
            (-1,) + (1,) * (out.ndim - 1)), out, jnp.nan)
    monkeypatch.setattr(lane_pack.LaneMap, "pack", dirty)
    eng = _engine(model, params)
    got = _record_logits(eng)
    finite = []
    step = eng._step_fn

    def watching(*a):
        out = step(*a)
        finite.append(np.asarray(out[2]))
        return out
    eng._step_fn = watching
    done_dirty = _run(eng, _requests(**reqs))
    assert len(planted) == 5                 # 4 Mamba layers' y, 1 layer's o
    assert np.concatenate(finite).all()
    for uid, c in done.items():
        assert list(done_dirty[uid].tokens) == list(c.tokens)
        for pos, row in want[uid].items():
            assert got[uid][pos].tobytes() == row.tobytes()
    for leaf in jax.tree_util.tree_leaves(eng.pool.cache):
        assert np.isfinite(np.asarray(leaf)).all()


# -- the engine's budget: 8 slots x 16 lanes, one multi-lane chunk a tick

BUDGET_LENS = [70, 64, 49, 37, 33, 30, 21, 17]


@pytest.fixture(scope="module")
def budgeted(model, params):
    """Eight long prompts at once through the packed engine, with every
    tick's marshal and the cache before and after it kept, and through an
    engine with no budget.  Admission order never defers a slot that has
    begun its prompt, so after its second chunk the oldest request is
    given the youngest stamp: it then waits, mid-prompt, with a state and
    K/V blocks of its own, until the others are through."""
    reqs = lambda: _requests(BUDGET_LENS, [4, 3, 5, 2, 6, 3, 4, 5], seed=13)
    eng = ServeEngine(model, params, num_slots=8, max_len=96, block_size=16)
    assert eng._chunk_budget == 1 and eng.chunk == 16
    seen = _record_logits(eng)
    ticks = []
    step = eng._step_fn

    def keeping(*a):
        slots = eng.pool.slots
        before = jax.tree_util.tree_map(np.asarray, a[1])
        order = sorted((i for i, s in enumerate(slots)
                        if s is not None and s.prefilling
                        and s.n_prompt - s.cursor > 1),
                       key=lambda i: slots[i].t_admitted)
        out = step(*a)
        said = eng.tick_args.fields(np.asarray(a[2]))
        ticks.append(dict(
            tok=said["tok"], table=said["block_table"],
            fill=said["fill"], n_new=said["n_new"], asking=order,
            uid={i: s.request.uid for i, s in enumerate(slots)
                 if s is not None},
            before=before, after=jax.tree_util.tree_map(np.asarray, out[0])))
        if len(ticks) == 2:
            next(s for s in slots if s.request.uid == "r0").t_admitted \
                = float("inf")
        return out
    eng._step_fn = keeping
    done = _run(eng, reqs())
    free = ServeEngine(_unpacked(model), params, num_slots=8, max_len=96,
                       block_size=16)
    assert free._chunk_budget is None
    return dict(eng=eng, ticks=ticks, done=done, seen=seen,
                free=free, done_free=_run(free, reqs()))


def test_budget_every_requests_tokens_equal_an_unthrottled_engines(budgeted):
    done, free = budgeted["done"], budgeted["done_free"]
    assert sorted(done) == sorted(free) and len(done) == 8
    for uid, c in done.items():
        assert c.status == "ok" == free[uid].status
        assert list(c.tokens) == list(free[uid].tokens)
    # the budget only delays: more ticks, the same work
    assert budgeted["eng"].compute_steps > budgeted["free"].compute_steps
    assert budgeted["free"].prefill_chunks_deferred == 0
    assert "prefill_chunks_deferred" not in budgeted["free"].summary_record()


def test_budget_every_prompt_token_is_fed_exactly_once(budgeted):
    fed = {}
    for t in budgeted["ticks"]:
        for i, uid in t["uid"].items():
            for j in range(int(t["n_new"][i])):
                at = int(t["fill"][i]) + j
                assert at not in fed.setdefault(uid, {})
                fed[uid][at] = int(t["tok"][i, j])
    for uid, c in budgeted["done"].items():
        seq = list(c.request.prompt) + list(c.tokens)
        assert [fed[uid][p] for p in range(len(seq) - 1)] == seq[:-1]
    lanes = sum(len(c.request.prompt) + len(c.tokens) - 1
                for c in budgeted["done"].values())
    assert sum(int(t["n_new"].sum()) for t in budgeted["ticks"]) == lanes


def test_budget_grants_whole_chunks_oldest_admission_first(budgeted):
    eng, deferred, deferring = budgeted["eng"], 0, 0
    for t in budgeted["ticks"]:
        many = [i for i in np.flatnonzero(t["n_new"] > 1)]
        assert many == t["asking"][:1]       # one chunk, the oldest asker's
        for i in many:                       # granted whole
            left = BUDGET_LENS[int(t["uid"][i][1:])] - int(t["fill"][i])
            assert int(t["n_new"][i]) == min(16, left)
        for i in t["asking"][1:]:
            assert int(t["n_new"][i]) == 0
        deferred += len(t["asking"][1:])
        deferring += bool(t["asking"][1:])
    assert deferred > 0
    rec = eng.summary_record()
    assert rec["prefill_chunks_deferred"] == deferred \
        == eng.prefill_chunks_deferred
    assert rec["prefill_ticks_deferring"] == deferring
    from apex_example_tpu.obs import schema
    assert not schema.validate_record(dict(rec, run_id="x"))
    counted = sum(int(np.asarray(tree["prefill_chunks_deferred"]).sum())
                  for _, tree in eng.counter_log
                  if "prefill_chunks_deferred" in tree)
    assert counted == deferred


def test_budget_a_deferred_slot_keeps_state_rows_and_blocks_bit_for_bit(
        budgeted):
    checked = 0
    for t in budgeted["ticks"]:
        arenas = [(was, now) for (path, was), (_, now) in zip(
            jax.tree_util.tree_flatten_with_path(t["before"])[0],
            jax.tree_util.tree_flatten_with_path(t["after"])[0])
            if "cached_" in jax.tree_util.keystr(path)]
        assert len(arenas) == 2                   # one layer's K and V
        for i in t["asking"][1:]:
            assert int(t["n_new"][i]) == 0
            for (name, was), (_, now) in zip(
                    paged_cache.slot_leaves(t["before"]),
                    paged_cache.slot_leaves(t["after"])):
                assert was[i].tobytes() == now[i].tobytes(), name
            fill = int(t["fill"][i])
            for b in t["table"][i][:-(-fill // 16)]:
                for was, now in arenas:
                    assert was[b].tobytes() == now[b].tobytes()
            if fill and t["uid"][i] == "r0":
                state = paged_cache.slot_leaves(t["before"])[0][1][i]
                assert np.abs(state).max() > 0 and np.abs(arenas[0][0][
                    t["table"][i][0]]).max() > 0
                checked += 1
    assert checked > 3            # r0 waited mid-prompt, with state to lose
