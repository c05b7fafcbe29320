"""The protocol between ``ServeEngine._tick`` and its step program (ISSUE 39):
one packed argument array, the key split made ahead, every result requested
at once.

- ``TickArgs``: what the host writes into the one int32 array is what the
  program takes out of it, bit for bit — ``-1`` where nothing is staged,
  temperatures through their bit pattern, a non-zero ``top_k`` — in the
  plain, the speculative (``C = K + 1``) and the self-drafting layout;
- the key chain: the keys the step receives over a run with idle ticks
  between its steps are those of a plain chain of ``jax.random.split``, and
  ``eng.rng`` at the end is that chain's state after exactly
  ``compute_steps`` splits;
- sampled-temperature tokens of a fixed seed are the ones the commit before
  this protocol served (recorded from it, below);
- the split ahead is made after the step's call and before the first
  blocking fetch, and a tick with no live slot makes none.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_example_tpu.models.gpt import gpt_tiny
from apex_example_tpu.models.pangu_moe import pangu_moe_tiny
from apex_example_tpu.serve import Request, ServeEngine
from apex_example_tpu.serve import engine as engine_lib
from apex_example_tpu.serve.engine import TickArgs

SLOTS, MAX_LEN = 4, 32          # the session-shared decode geometry
KINDS = ["plain", "speculative", "self_draft"]


# ------------------------------------------------- pack -> unpack

LAYOUTS = {
    # chunk C, the block table's T columns, self-drafting
    "plain": TickArgs(8, 4),
    "speculative": TickArgs(3, 16),            # C = K + 1 under a K of 2
    "self_draft": TickArgs(16, 5, True),
}


def _filled(layout, slots=5):
    """A tick's arguments as the host holds them, every field different."""
    rs = np.random.RandomState(slots)
    want = {
        "tok": rs.randint(0, 2 ** 31 - 1, (slots, layout.chunk)),
        "block_table": rs.randint(0, 4096, (slots, layout.blocks)),
        "fill": rs.randint(0, 4096, slots),
        "n_new": rs.randint(0, layout.chunk + 1, slots),
        "cow_src": np.array([-1, 7, -1, 0, 4095][:slots]),
        "cow_dst": np.array([-1, 9, -1, 4095, 0][:slots]),
        "top_k": np.array([0, 5, 0, 40, 1][:slots]),
        "temperature": np.array([0.0, 0.7, 1e-3, 1.3, 0.7][:slots],
                                np.float32),
    }
    if layout.self_draft:
        want["aux"] = np.array([[0, -1], [1, -1], [0, 17], [1, 3],
                                [0, -1]][:slots])
    packed, f = layout.blank(slots)
    for name, value in want.items():
        f[name][...] = value
    return packed, want


@pytest.mark.parametrize("kind", KINDS)
def test_what_the_host_packs_is_what_the_program_unpacks(kind):
    layout = LAYOUTS[kind]
    assert layout.width == layout.chunk + layout.blocks + 6 \
        + 2 * layout.self_draft
    packed, want = _filled(layout)
    assert packed.dtype == np.int32 and packed.shape == (5, layout.width)
    # every column is some field's: nothing overlaps, nothing is left over
    cover = np.zeros_like(packed)
    for view in layout.fields(cover).values():
        view.view(np.int32)[...] += 1
    assert (cover == 1).all()
    # on the host, then inside a program
    on_host = layout.fields(packed)
    in_program = jax.jit(layout.fields)(jnp.asarray(packed))
    assert set(on_host) == set(in_program) == set(want)
    for name, value in want.items():
        for got in (on_host[name], np.asarray(in_program[name])):
            assert got.dtype == (np.float32 if name == "temperature"
                                 else np.int32), name
            assert got.shape == value.shape, name
            assert got.tobytes() == value.astype(got.dtype).tobytes(), name
    assert "aux" in want or "aux" not in on_host


@pytest.mark.parametrize("kind", KINDS)
def test_a_blank_tick_stages_nothing(kind):
    layout = LAYOUTS[kind]
    packed, f = layout.blank(SLOTS)
    assert (f["cow_src"] == -1).all() and (f["cow_dst"] == -1).all()
    for name in ("tok", "block_table", "fill", "n_new", "top_k"):
        assert not f[name].any(), name
    assert f["temperature"].tobytes() == bytes(4 * SLOTS)
    if layout.self_draft:
        assert (f["aux"] == (0, -1)).all()
    # the fields are the array: what is written to one is in the other
    f["temperature"][2] = 0.7
    assert packed[2, layout.width - 1 - 2 * layout.self_draft] \
        == np.float32(0.7).view(np.int32)
    assert hash(layout) == hash(TickArgs(layout.chunk, layout.blocks,
                                         layout.self_draft))


# ------------------------------------------------- engines of each kind

@pytest.fixture(scope="module")
def models():
    out = {}
    for name, model in (("gpt", gpt_tiny()), ("pangu", pangu_moe_tiny())):
        out[name] = (model, model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"])
    return out


def _sampled_requests(vocab):
    """Greedy and sampled requests side by side, in three waves with idle
    ticks between them."""
    rs = np.random.RandomState(11)
    mix = [(0.7, 0), (0.0, 0), (1e-3, 5), (0.7, 5), (1.3, 3), (0.0, 0)]
    return [Request(prompt=[int(t) for t in rs.randint(1, vocab, 3 + 2 * i)],
                    max_new_tokens=5 + i, temperature=t, top_k=k,
                    arrival_step=9 * (i // 2), uid=f"s{i}")
            for i, (t, k) in enumerate(mix)]


def _engine(models, kind):
    model, params = models["pangu" if kind == "self_draft" else "gpt"]
    kw = {"speculate": 2} if kind == "speculative" else {}
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(5), **kw)
    assert eng.self_draft == (kind == "self_draft")
    assert eng.tick_args == TickArgs(eng.chunk, eng.pool.max_blocks,
                                     eng.self_draft)
    eng.queue.submit_all(_sampled_requests(model.vocab_size))
    eng.queue.close()
    return eng


@pytest.fixture(scope="module")
def runs(models):
    """One run of each kind of engine, with every call of its step kept:
    the key it was given and what the engine held at that instant."""
    out = {}
    for kind in KINDS:
        eng = _engine(models, kind)
        calls, step = [], eng._step_fn

        def keeping(*a, eng=eng, calls=calls, step=step):
            assert len(a) == 4                  # params, cache, packed, key
            assert a[2].shape == (SLOTS, eng.tick_args.width) \
                and a[2].dtype == jnp.int32
            calls.append({"key": np.asarray(a[3]),
                          "carried": np.asarray(eng.rng),
                          "tick": eng.step_count,
                          "ahead": eng._key_ahead})
            return step(*a)
        eng._step_fn = keeping
        eng.run(max_steps=500)
        for _ in range(3):                      # an idle spin at the end
            assert eng.step() is False
        done = sorted(eng.completions, key=lambda c: c.request.uid)
        out[kind] = {"eng": eng, "calls": calls,
                     "tokens": [list(map(int, c.tokens)) for c in done]}
    return out


# Served by the commit before this protocol (178afdb: nine arrays a tick, the
# split in engine.marshal, two fetches one after the other) for
# ``_sampled_requests`` on these engines, with the state ``eng.rng`` ended in
# and the steps it took.
BEFORE = {
    "plain": {
        "tokens": [[81, 81, 227, 155, 155],
                   [77, 130, 130, 130, 130, 130],
                   [177, 177, 177, 177, 177, 177, 177],
                   [181, 210, 96, 96, 96, 96, 96, 176],
                   [68, 169, 169, 35, 35, 35, 35, 35, 35],
                   [109, 109, 109, 157, 157, 157, 157, 157, 157, 157]],
        "rng": [2030533547, 3853773407], "compute_steps": 26, "idle": 3},
    "speculative": {
        "tokens": [[81, 81, 227, 155, 155],
                   [77, 130, 130, 130, 130, 130],
                   [177, 177, 177, 177, 177, 177, 177],
                   [235, 181, 181, 169, 169, 169, 72, 72],
                   [68, 68, 96, 96, 96, 29, 29, 29, 29],
                   [109, 109, 109, 157, 157, 157, 157, 157, 157, 157]],
        "rng": [1792790541, 2846007282], "compute_steps": 24, "idle": 4},
    # Since PR 44 this model's rows are packed (ops/lane_pack.py) and the
    # engine grants one chunk of more than two lanes a tick at 4 slots x 16:
    # four chunks wait a tick, the run takes three steps more, and the two
    # sampled requests that decode after a wait (s3, s4) draw from later
    # keys of the same chain.  The greedy requests and s0 are 178afdb's
    # tokens; a ``packed_lanes = False`` subclass serves all six of 178afdb's
    # (s3 [5, 76, 206, 215, 123, 206, 126, 50], s4 [23, 39, 3, 23, 42, 112,
    # 43, 255, 80]) in its 26 steps.
    "self_draft": {
        "tokens": [[158, 120, 47, 241, 212],
                   [176, 40, 29, 180, 134, 129],
                   [212, 42, 211, 28, 255, 48, 215],
                   [93, 104, 240, 231, 159, 79, 56, 169],
                   [23, 191, 189, 207, 193, 254, 254, 131, 131],
                   [151, 155, 186, 173, 232, 146, 196, 199, 182, 33]],
        "rng": [232827639, 2877507195], "compute_steps": 29, "idle": 2},
}


@pytest.mark.parametrize("kind", KINDS)
def test_sampled_tokens_are_the_ones_served_before(runs, kind):
    eng, before = runs[kind]["eng"], BEFORE[kind]
    assert runs[kind]["tokens"] == before["tokens"]
    assert eng.compute_steps == before["compute_steps"]
    assert eng.idle_ticks == before["idle"] + 3
    assert np.asarray(eng.rng).tolist() == before["rng"]
    # sampling did happen: the sampled requests left the greedy path
    if kind == "plain":
        assert runs[kind]["tokens"] != runs["speculative"]["tokens"]


@pytest.mark.parametrize("kind", KINDS)
def test_the_steps_keys_are_a_plain_chain_of_splits(runs, kind):
    eng, calls = runs[kind]["eng"], runs[kind]["calls"]
    assert len(calls) == eng.compute_steps > 0
    # idle ticks lie between steps (and three behind the last)
    ticks = [c["tick"] for c in calls]
    assert any(b - a > 1 for a, b in zip(ticks, ticks[1:]))
    assert eng.step_count == ticks[-1] + 1 + 3
    carried = jax.random.PRNGKey(5)
    for call in calls:
        carried, key = jax.random.split(carried)
        assert call["key"].tolist() == np.asarray(key).tolist()
        # committed on use: at the call the engine carries this split's
        # state, and nothing prepared is left over
        assert call["carried"].tolist() == np.asarray(carried).tolist()
        assert call["ahead"] is None
    # ... and after the run the state after exactly compute_steps splits,
    # whatever was prepared for a step that never came
    assert np.asarray(eng.rng).tolist() == np.asarray(carried).tolist()
    source, nxt, key = eng._key_ahead
    assert source is eng.rng
    want = jax.random.split(carried)
    assert np.asarray(nxt).tolist() == np.asarray(want[0]).tolist()
    assert np.asarray(key).tolist() == np.asarray(want[1]).tolist()


def test_a_key_set_from_outside_is_not_overtaken_by_the_prepared_pair(
        models):
    """The pair is kept with the key it was split from: whoever replaces
    ``eng.rng`` between two ticks gets a chain from the new key."""
    eng = _engine(models, "plain")
    seen, step = [], eng._step_fn

    def keeping(*a):
        seen.append(np.asarray(a[3]).tolist())
        return step(*a)
    eng._step_fn = keeping
    assert eng.step() and eng._key_ahead is not None
    eng.rng = fresh = jax.random.PRNGKey(77)
    assert eng.step()
    carried, key = jax.random.split(fresh)
    assert seen[1] == np.asarray(key).tolist()
    assert np.asarray(eng.rng).tolist() == np.asarray(carried).tolist()


# ------------------------------------------------- the order of a tick

@pytest.mark.parametrize("kind", KINDS)
def test_the_split_ahead_lies_between_the_call_and_the_first_fetch(
        models, kind, monkeypatch):
    eng = _engine(models, kind)
    order, step = [], eng._step_fn
    reads = {"plain": 2, "speculative": 4, "self_draft": 2}[kind]

    class Watched:
        """An output of the step as the tick may use it: asked for ahead
        of time, then read."""

        def __init__(self, value):
            self.value, self.nbytes = value, value.nbytes

        def copy_to_host_async(self):
            order.append("request")
            self.value.copy_to_host_async()

        def __array__(self, *a, **k):
            order.append("fetch")
            return np.asarray(self.value)

    def calling(*a):
        order.append("call")
        cache, *outs = step(*a)
        outs[:reads] = [Watched(o) for o in outs[:reads]]
        return (cache, *outs)
    eng._step_fn = calling
    real_split = jax.random.split

    def split(key, *a, **k):
        order.append("split")
        return real_split(key, *a, **k)
    monkeypatch.setattr(engine_lib.jax.random, "split", split)

    assert eng.step() is True
    # the first step has nothing prepared: it splits on the spot, then
    # prepares the next one's
    assert order == ["split", "call"] + ["request"] * reads + ["split"] \
        + ["fetch"] * reads
    del order[:]
    assert eng.step() is True
    assert order == ["call"] + ["request"] * reads + ["split"] \
        + ["fetch"] * reads
    # a tick with no live slot makes none: the idle ticks between the waves
    # and behind the last
    while eng.pool.live:
        eng.step()
    ahead = eng._key_ahead
    del order[:]
    idle = eng.idle_ticks
    assert eng.step() is False and eng.idle_ticks == idle + 1
    assert order == [] and eng._key_ahead is ahead


def test_one_put_whatever_the_engine(models):
    """No per-array form is left beside the packed one: every kind of
    engine hands its step exactly (params, cache, packed, key)."""
    from apex_example_tpu.quant import weights as quant_weights
    model, params = models["gpt"]
    qparams, _ = quant_weights.quantize_params(params, "int8")
    for kw, chunk in (({"role": "decode"}, 1),
                      ({"role": "prefill", "handoff_sink": lambda h: None},
                       8),
                      ({"weight_quant": "int8", "kv_quant": True}, 8)):
        quantized = kw.get("weight_quant") == "int8"
        eng = ServeEngine(model, qparams if quantized else params,
                          num_slots=SLOTS, max_len=MAX_LEN, **kw)
        assert eng.tick_args == TickArgs(chunk, eng.pool.max_blocks)
        seen, step = [], eng._step_fn

        def keeping(*a, seen=seen, step=step):
            seen.append([getattr(x, "shape", None) for x in a[2:]])
            return step(*a)
        eng._step_fn = keeping
        if kw.get("role") == "decode":
            continue            # fed by hand-offs only (tests/test_disagg.py)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        eng.queue.close()
        eng.run(max_steps=50)
        assert seen and all(s == [(SLOTS, eng.tick_args.width), (2,)]
                            for s in seen)
        assert eng.runtime_handoffs == 5 * eng.compute_steps
