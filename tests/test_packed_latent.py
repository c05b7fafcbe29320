"""The two latent-attention expert models on packed rows (PR 44): the paged
programs of ``models/xing4.py`` and ``models/pangu_moe.py`` run everything
token-wise on the tick's live lanes as ``ops/lane_pack.py``'s dense rows,
``pangu``'s with a head of two lanes a slot (the fed token and its draft).
At tiny widths on the CPU, float32: ``LaneMap(head=2)`` by hand; each model
through ``ServeEngine`` against the same model with ``packed_lanes = False``
(the ``[SLOTS, C]`` program, an engine with no budget): the same tokens, the
same logits at every lane either read; what the budget defers and what it
never does; NaN in every dead row reaching nothing.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_example_tpu.models import pangu_moe, xing4  # noqa: E402
from apex_example_tpu.ops import lane_pack  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.serve import engine as engine_lib  # noqa: E402

pytestmark = pytest.mark.serve

SLOTS, MAX_LEN, BS = 4, 64, 8
# float32 both sides over the same weights: the packed program differs from
# the [SLOTS, C] one by the shapes its products are tiled to, nothing else
TOL = 2e-5


# ------------------------------------------------------ the map, head of two

def test_rows_of_a_two_lane_head_are_still_a_quarter_of_the_tick():
    # the served cell: every slot's two first lanes and 8 whole chunks
    assert lane_pack.groups(64, 16, 2) == 8
    assert lane_pack.rows(64, 16, 2) == 256 == lane_pack.rows(64, 16)
    # never fewer than one chunk; a chunk no wider than the head packs
    # nothing (a decode-role engine's C = 1 among them)
    assert lane_pack.groups(4, 8, 2) == 1 and lane_pack.rows(4, 8, 2) == 16
    assert lane_pack.groups(64, 2, 2) == 0 and lane_pack.rows(64, 2, 2) == 128
    assert lane_pack.groups(64, 1, 2) == 0 and lane_pack.rows(64, 1, 2) == 64
    # head = 1 is what it was
    assert lane_pack.groups(64, 16, 1) == 12 == lane_pack.groups(64, 16)


@pytest.mark.parametrize("n_new", [
    [1, 2, 0, 5, 2, 8, 1, 0],       # decode with and without a draft, chunks
    [2, 2, 2, 2, 2, 2, 2, 2],       # every slot drafting: the head alone
    [0, 0, 0, 0, 0, 0, 0, 0],       # nothing live
    [8, 1, 3, 0, 2, 1, 0, 2],       # the budget spent (2 groups of 2)
], ids=["mixed", "all_drafting", "idle", "budget_spent"])
def test_lane_map_with_a_head_of_two_holds_every_live_lane_once(
        n_new, monkeypatch):
    monkeypatch.setattr(lane_pack, "groups", lambda s, c, head=1: 2)
    S, C, K = 8, 8, 2
    n = jnp.asarray(n_new, jnp.int32)
    m = lane_pack.LaneMap(n, C, head=K)
    assert m.rows == S * K + 2 * C
    x = np.arange(1, S * C * 2 + 1, dtype=np.float32).reshape(S, C, 2)
    live = np.arange(C)[None, :] < np.asarray(n_new)[:, None]
    row_live = np.asarray(m.row_live)
    rows = np.asarray(m.pack(jnp.asarray(x)))
    # every live lane is exactly one row, a dead row holds zeros (or fill)
    assert sorted(rows[:, 0][rows[:, 0] > 0]) == sorted(x[live][:, 0])
    assert int(row_live.sum()) == int(live.sum())
    assert not rows[~row_live].any()
    assert (np.asarray(m.pack(jnp.asarray(x), fill=-7))[~row_live]
            == -7).all()
    # a slot with at most two lanes sits in the head, whatever it is doing
    for s, k in enumerate(n_new):
        if 0 < k <= K:
            for j in range(k):
                np.testing.assert_array_equal(rows[j * S + s], x[s, j])
    # there and back; dead lanes read zero whatever the dead rows hold
    dirty = np.where(row_live[:, None], rows, np.nan)
    back = np.asarray(m.unpack(jnp.asarray(dirty)))
    np.testing.assert_array_equal(back, np.where(live[..., None], x, 0))
    last = np.asarray(m.last(jnp.asarray(dirty)))
    for s, k in enumerate(n_new):
        np.testing.assert_array_equal(last[s], x[s, k - 1] if k else 0)
    # the row of any lane a slot feeds (the verify lanes of a draft)
    lanes = np.stack([np.maximum(np.asarray(n_new) - 2, 0),
                      np.maximum(np.asarray(n_new) - 1, 0)], 1)
    at = np.asarray(m.row_of(jnp.asarray(lanes)))
    for s, k in enumerate(n_new):
        if k:
            np.testing.assert_array_equal(dirty[at[s]], x[s, lanes[s]])


@pytest.mark.parametrize("chunk,head", [(1, 1), (1, 2), (2, 2)])
def test_a_chunk_no_wider_than_the_head_has_no_group_and_moves_as_it_is(
        chunk, head):
    """A decode-role engine's C = 1, or a drafting one whose chunk is the
    head: every lane is a head row, there and back."""
    S = 4
    for n_new in ([1, 0, 1, 1], [chunk, 0, 1, chunk]):
        m = lane_pack.LaneMap(jnp.asarray(n_new, jnp.int32), chunk, head)
        assert m.groups == 0
        assert m.rows == lane_pack.rows(S, chunk, head) == S * chunk
        x = np.arange(1, S * chunk * 3 + 1, dtype=np.float32).reshape(
            S, chunk, 3)
        live = np.arange(chunk)[None, :] < np.asarray(n_new)[:, None]
        rows = np.asarray(m.pack(jnp.asarray(x)))
        assert rows.shape == (S * chunk, 3)
        dirty = np.where(np.asarray(m.row_live)[:, None], rows, np.nan)
        np.testing.assert_array_equal(
            np.asarray(m.unpack(jnp.asarray(dirty))),
            np.where(live[..., None], x, 0))
        last = np.asarray(m.last(jnp.asarray(dirty)))
        for s, k in enumerate(n_new):
            np.testing.assert_array_equal(last[s], x[s, k - 1] if k else 0)


# --------------------------------------------- the two models, both programs

class UnpackedXing4(xing4.Xing4ForCausalLM):
    packed_lanes = False


class UnpackedPangu(pangu_moe.PanguMoEForCausalLM):
    packed_lanes = False


# (the tiny model, its [SLOTS, C] form, the vocabulary: at 8 tokens a run
# holds accepted and rejected drafts)
MODELS = {"xing4": (xing4.xing4_tiny, UnpackedXing4, 256),
          "pangu": (pangu_moe.pangu_moe_tiny, UnpackedPangu, 8)}
# a one-token tail (17), a two-token tail (18), chunks that wait for rows
# (five requests at once on four slots: a reused slot too), and a slot
# sampled at a temperature (one lane, no draft; top-1, so whichever tick it
# falls in it picks the same token)
LENS, NEW = [17, 18, 21, 5, 12], [6, 5, 7, 6, 4]


def _requests(vocab):
    rng = np.random.default_rng(11)
    return [Request(prompt=rng.integers(0, vocab, n).tolist(),
                    max_new_tokens=new, uid=f"r{i}",
                    **(dict(temperature=0.7, top_k=1) if i == 3 else {}))
            for i, (n, new) in enumerate(zip(LENS, NEW))]


def _record(eng):
    """The engine's own tick (the same module clone, the same arguments)
    as a step of the test's that also hands out what the head read:
    ``seen[uid, position, which]`` a logits row, ``ticks`` each tick's
    ``n_new`` beside what every live slot had left of its prompt."""
    seen, ticks, finite_all = {}, [], []
    dec, args = eng.pool.dec, eng.tick_args
    if eng.self_draft:
        tick = jax.jit(lambda *a: engine_lib.draft_tick(dec, args, *a))
    else:
        @jax.jit
        def tick(params, cache, packed, key):
            said = args.fields(packed)
            logits, mut = dec.apply(
                {"params": params, "cache": cache}, said["tok"], train=False,
                paged=engine_lib._paged(said), mutable=["cache", "counters"])
            nxt = engine_lib.sample_tokens(
                key, logits[:, 0], said["temperature"], said["top_k"])
            return (mut["cache"], nxt, jnp.all(jnp.isfinite(logits), (1, 2)),
                    mut["counters"], logits, None)

    def recording(*a):
        cache, picked, finite, counters, logits, draft_logits = tick(*a)
        said = args.fields(np.asarray(a[2]))
        fill, n_new = said["fill"], said["n_new"]
        left = {}
        for i, slot in enumerate(eng.pool.slots):
            if slot is None:
                continue
            left[i] = slot.n_prompt - slot.cursor
            if not n_new[i]:
                continue
            drafts = int(said["aux"][i, 0]) if "aux" in said else 0
            at = int(fill[i] + n_new[i] - 1 - drafts)
            uid = slot.request.uid
            seen[uid, at, "head"] = np.asarray(logits[i, 0])
            if drafts:
                seen[uid, at + 1, "head"] = np.asarray(logits[i, 1])
            if draft_logits is not None:
                seen[uid, at, "module"] = np.asarray(draft_logits[i])
        ticks.append((n_new.copy(), left))
        finite_all.append(np.asarray(finite))
        return cache, picked, finite, counters

    eng._step_fn = recording
    return seen, ticks, finite_all


def _serve(model, params):
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      block_size=BS)
    seen, ticks, finite = _record(eng)
    for r in _requests(model.vocab_size):
        eng.submit(r)
    eng.queue.close()
    done = {c.request.uid: c for c in eng.run(max_steps=500)}
    return dict(eng=eng, seen=seen, ticks=ticks, finite=finite, done=done)


@pytest.fixture(scope="module", params=sorted(MODELS))
def both(request):
    """One model served by its packed program and by its ``[SLOTS, C]``
    one, two layers deep (a dense and an expert layer; ``pangu``'s module
    besides)."""
    tiny, unpacked, vocab = MODELS[request.param]
    model = tiny(num_layers=2, vocab_size=vocab)
    # the rows' head: the fed token, and the draft of a model that drafts
    head = getattr(model, "lane_head", 1)
    assert head == (2 if request.param == "pangu" else 1)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    fields = {f: getattr(model, f) for f in model.__dataclass_fields__
              if f not in ("parent", "name")}
    return dict(name=request.param, model=model, params=params, head=head,
                packed=_serve(model, params),
                plain=_serve(unpacked(**fields), params))


def test_packed_rows_give_the_unpacked_programs_tokens_and_logits(both):
    packed, plain = both["packed"], both["plain"]
    assert packed["eng"]._chunk_budget == 1
    assert packed["eng"]._lane_head == both["head"]
    assert plain["eng"]._chunk_budget is None
    assert sorted(packed["done"]) == sorted(plain["done"]) \
        == [f"r{i}" for i in range(5)]
    for uid, c in packed["done"].items():
        assert c.status == "ok" and len(c.tokens) == c.request.max_new_tokens
        assert list(c.tokens) == list(plain["done"][uid].tokens)
    # every lane either program read, by request and position
    assert sorted(packed["seen"]) == sorted(plain["seen"])
    worst = max(np.abs(row - plain["seen"][k]).max()
                for k, row in packed["seen"].items())
    assert worst < TOL, worst
    spread = np.mean([row.max() - row.min()
                      for row in packed["seen"].values()])
    assert spread > 1000 * TOL
    # the token-wise products ran on the rows, a quarter of the lanes where
    # the tick is large and here the head and one chunk
    rows = lane_pack.rows(SLOTS, BS, both["head"])
    assert rows == SLOTS * both["head"] + BS
    dense = lambda run: {int(np.asarray(t["rows_dense"]).sum())
                         for _, t in run["eng"].counter_log}
    assert dense(packed) == {rows} and dense(plain) == {SLOTS * BS}
    for _, t in packed["eng"].counter_log:
        assert np.asarray(t["lanes_live"]).shape == (1, SLOTS)
        assert np.asarray(t["rows_dense"]).shape == (1, 1)
    if both["name"] == "pangu":
        # two-lane decode slots were among them, accepted and not
        eng = packed["eng"]
        assert eng.self_draft and 0 < eng.tokens_accepted < eng.tokens_drafted
        assert eng.tokens_drafted == plain["eng"].tokens_drafted
        assert any((n == 2).sum() >= 2 for n, _ in packed["ticks"])


def test_the_budget_defers_chunks_longer_than_the_head_and_nothing_else(
        both):
    packed, plain, head = both["packed"], both["plain"], both["head"]
    deferred = 0
    for n_new, left in packed["ticks"]:
        assert (n_new > head).sum() <= 1          # the one group
        for i, remaining in left.items():
            if not n_new[i]:
                # only a chunk of more lanes than the head ever waits: no
                # decoding slot (its draft with it), no tail of a prompt
                assert remaining > head
                deferred += 1
    assert deferred == packed["eng"].prefill_chunks_deferred > 0
    assert plain["eng"].prefill_chunks_deferred == 0
    assert all(n_new[i] for n_new, left in plain["ticks"] for i in left)
    # tails of one and of two tokens were fed (r0: 17 = 8 + 8 + 1, r1: 18)
    tails = {int(n_new[i]) for n_new, left in packed["ticks"]
             for i, remaining in left.items() if 0 < remaining <= 2}
    assert tails == {1, 2}
    # the same work, later: every prompt token and every decode lane once
    lanes = lambda run: sum(int(n.sum()) for n, _ in run["ticks"])
    assert lanes(packed) == lanes(plain)
    assert packed["eng"].compute_steps > plain["eng"].compute_steps


def test_a_nan_in_every_dead_row_reaches_no_logit_no_mask_and_no_cache(
        both, monkeypatch):
    """Every pack of floats plants NaN in its dead rows (every attention
    output's, so from the first layer on the whole residual stream's dead
    rows are NaN through every product, norm and expert): the logits are
    the clean run's bit for bit, the finite mask holds for every slot,
    empty and deferred ones too, and nothing in the cache turns NaN."""
    pack, planted = lane_pack.LaneMap.pack, []

    def dirty(self, x, fill=0):
        out = pack(self, x, fill)
        if not jnp.issubdtype(out.dtype, jnp.floating):
            return out
        planted.append(out.shape)
        return jnp.where(self.row_live.reshape(
            (-1,) + (1,) * (out.ndim - 1)), out, jnp.nan)
    monkeypatch.setattr(lane_pack.LaneMap, "pack", dirty)

    run = _serve(both["model"], both["params"])
    # one attention output a layer (the module's too)
    assert len(planted) == (3 if both["name"] == "pangu" else 2)
    assert np.concatenate(run["finite"]).all()
    clean = both["packed"]
    assert sorted(run["seen"]) == sorted(clean["seen"])
    for k, row in clean["seen"].items():
        assert run["seen"][k].tobytes() == row.tobytes(), k
    for uid, c in clean["done"].items():
        assert list(run["done"][uid].tokens) == list(c.tokens)
    for leaf in jax.tree_util.tree_leaves(run["eng"].pool.cache):
        assert np.isfinite(np.asarray(leaf)).all()

