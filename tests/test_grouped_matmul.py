"""The grouped-matmul kernel of the dropless experts (ops/grouped_matmul.py)
under the interpreter on the CPU, against ``lax.ragged_dot`` on upcast
operands (what the MXU computes: bfloat16 products exact, float32 sums):
the walk over groups and row tiles case by case, the visits counter by
hand, what falls back to the XLA form, the expert layer over rows the
kernel never writes, an expert-parallel share, and the served model's
tokens with the kernel and without.
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

from apex_example_tpu.models import xing4  # noqa: E402
from apex_example_tpu.ops import _config  # noqa: E402
from apex_example_tpu.ops import grouped_matmul as gm  # noqa: E402
from apex_example_tpu.serve import Request, ServeEngine  # noqa: E402
from apex_example_tpu.transformer import expert_parallel as ep  # noqa: E402

M, K, N, G = 256, 64, 96, 8      # two row tiles of 128; N no whole lane tile
UP = lambda t: t.astype(jnp.float32)

# sizes of the 8 groups -> the row tiles each group has a row in, by hand
# (tiles of 128 rows: rows 0-127 and 128-255)
WALKS = {
    "empty_groups_between_full_ones":
        ([40, 0, 0, 88, 0, 60, 0, 0], [1, 0, 0, 1, 0, 1, 0, 0]),
    "a_group_straddles_a_row_tile":         # group 1 holds rows 100-159
        ([100, 60, 20, 0, 0, 0, 0, 0], [1, 2, 1, 0, 0, 0, 0, 0]),
    "every_row_dead":
        ([0] * 8, [0] * 8),
    "one_expert_holds_every_pair":
        ([0, 0, 0, 256, 0, 0, 0, 0], [0, 0, 0, 2, 0, 0, 0, 0]),
    "a_few_rows_and_the_rest_past_the_groups":
        ([5, 3, 0, 9, 0, 0, 1, 0], [1, 1, 0, 1, 0, 0, 1, 0]),
    "a_group_ends_on_the_tile_boundary":
        ([128, 0, 1, 0, 0, 0, 0, 127], [1, 0, 1, 0, 0, 0, 0, 1]),
}


@pytest.fixture(scope="module")
def operands():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = lambda key, shape, fan: (jax.random.normal(key, shape)
                                  / fan ** 0.5).astype(jnp.bfloat16)
    return (bf(k[0], (M, K), 1), bf(k[1], (G, K, N), K),
            bf(k[2], (G, K, N), K), bf(k[3], (G, N, K), N))


@pytest.mark.parametrize("case", WALKS)
@pytest.mark.parametrize("form", ["matmul", "swiglu"])
def test_kernel_is_the_ragged_dot_on_every_live_row(operands, case, form):
    a, w, w2, _ = operands
    sizes, by_hand = WALKS[case]
    s = jnp.asarray(sizes, jnp.int32)
    if form == "matmul":
        got, visits = gm.grouped_matmul(a, w, s)
        want = jax.lax.ragged_dot(UP(a), UP(w), s)
        assert got.dtype == jnp.float32
    else:
        got, visits = gm.grouped_swiglu(a, w, w2, s)
        want = (jax.nn.silu(jax.lax.ragged_dot(UP(a), UP(w), s))
                * jax.lax.ragged_dot(UP(a), UP(w2), s)).astype(a.dtype)
        assert got.dtype == a.dtype
    live = sum(sizes)
    assert got.shape == (M, N)
    np.testing.assert_allclose(UP(got)[:live], UP(want)[:live],
                               atol=1e-5 if form == "matmul" else 1e-2)
    # the counter: the (group, row tile) pairs that hold a live row
    assert np.asarray(visits).tolist() == by_hand


def test_visit_metadata_walks_groups_and_their_tiles_in_order():
    sizes = jnp.asarray(WALKS["a_group_straddles_a_row_tile"][0], jnp.int32)
    offsets, group, tile, n, visits = gm.visit_metadata(sizes, M, 128)
    assert int(n) == 4 and group.shape == tile.shape == (2 + G - 1,)
    assert np.asarray(offsets).tolist() == [0, 100, 160, 180, 180, 180, 180,
                                            180, 180]
    assert np.asarray(group)[:4].tolist() == [0, 1, 1, 2]
    assert np.asarray(tile)[:4].tolist() == [0, 0, 1, 1]
    # past the last visit the maps stay inside both arrays
    assert 0 <= int(np.asarray(group).min()) \
        and int(np.asarray(group).max()) < G
    assert 0 <= int(np.asarray(tile).min()) and int(np.asarray(tile).max()) < 2
    # nothing live: no visit, every index still legal
    _, group, tile, n, visits = gm.visit_metadata(
        jnp.zeros((G,), jnp.int32), M, 128)
    assert int(n) == 0 and not np.asarray(visits).any()
    assert int(np.asarray(tile).max()) < 2 \
        and int(np.asarray(group).max()) < G


@pytest.mark.parametrize("shape, dtypes, kernel", [
    ((4096, 3584, 1024), ("bfloat16", "bfloat16"), True),   # gate and up
    ((4096, 1024, 3584), ("bfloat16", "bfloat16"), True),   # down
    ((256, 128, 128), ("float32", "float32"), True),
    ((256, 64, 128), ("bfloat16", "bfloat16"), False),      # K no lane tile
    ((256, 128, 96), ("bfloat16", "bfloat16"), False),      # N no lane tile
    ((264, 128, 128), ("bfloat16", "bfloat16"), False),     # rows: 16 a tile
    ((264, 128, 128), ("float32", "float32"), True),        # rows: 8 a tile
    ((256, 128, 128), ("bfloat16", "float32"), False),      # a cast of w
    ((256, 65536, 128), ("bfloat16", "bfloat16"), False),   # no block fits
], ids=["gate_up_widths", "down_widths", "float32", "k_not_whole_tiles",
        "n_not_whole_tiles", "rows_not_whole_bf16_tiles",
        "rows_whole_f32_tiles", "two_dtypes", "k_too_long_for_a_block"])
def test_mosaic_takes_whole_tiles_and_the_rest_falls_back(monkeypatch, shape,
                                                          dtypes, kernel):
    """As on the TPU (no interpreter): eligibility is read off the
    operands; what Mosaic cannot tile is ``lax.ragged_dot`` as before."""
    monkeypatch.setattr(_config, "INTERPRET", False)
    monkeypatch.setattr(_config, "use_pallas",
                        lambda: not _config.FORCE_XLA)
    m, k, n = shape
    args = (jax.ShapeDtypeStruct((m, k), dtypes[0]),
            jax.ShapeDtypeStruct((4, k, n), dtypes[1]),
            jax.ShapeDtypeStruct((4,), jnp.int32))
    for fn, operands in ((gm.grouped_matmul, args),
                         (gm.grouped_swiglu, (args[0], args[1], *args[1:]))):
        text = str(jax.make_jaxpr(fn)(*operands))
        assert ("pallas_call" in text) == kernel
        assert ("ragged_dot" in text) == (not kernel)
        out, visits = jax.eval_shape(fn, *operands)
        assert out.shape == (m, n) and (visits is not None) == kernel
    with _config.force_xla():       # read when traced: a fresh function
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda *a: gm.grouped_matmul(*a))(*args))


def _layer(key, T=48, d=16, f=8, E=8, k=2):
    ks = jax.random.split(key, 6)
    n = lambda kk, shape, fan: jax.random.normal(kk, shape) / fan ** 0.5
    x = jax.random.normal(ks[0], (T, d))
    idx = jnp.argsort(jax.random.uniform(ks[1], (T, E)), -1)[:, :k] \
        .astype(jnp.int32)
    gates = jax.random.uniform(ks[2], (T, k)) + 0.1
    return x, idx, gates, n(ks[3], (E, d, f), d), n(ks[4], (E, d, f), d), \
        n(ks[5], (E, f, d), f)


def _by_hand(x, idx, gates, wg, wu, wd, held, live):
    first, count = held
    y = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(idx[t]), np.asarray(gates[t])):
            if live[t] and first <= e < first + count:
                xe = np.asarray(x[t], np.float64)
                gate = xe @ np.asarray(wg[e - first], np.float64)
                up = xe @ np.asarray(wu[e - first], np.float64)
                y[t] += g * ((gate / (1 + np.exp(-gate)) * up)
                             @ np.asarray(wd[e - first], np.float64))
    return y


def test_rows_the_kernel_never_writes_reach_no_token(monkeypatch):
    """Rows past ``sum(sizes)`` (dead lanes, pairs of experts held
    elsewhere) hold anything: with NaN there, in the golden's place, the
    layer's output is finite and the kernel's and the XLA form's."""
    x, idx, gates, wg, wu, wd = _layer(jax.random.PRNGKey(1))
    live = jnp.arange(48) % 3 != 0
    held = (2, 4)
    share = lambda w: w[2:6]
    args = (x, idx, gates, share(wg), share(wu), share(wd), held, live)

    def poisoned(fn):
        def form(a, *rest):
            out, visits = fn(a, *rest)
            dead = jnp.arange(a.shape[0])[:, None] >= jnp.sum(rest[-1])
            return jnp.where(dead, jnp.nan, out), visits
        return form

    y_kernel, visits = ep.dropless_experts(*args)
    with _config.force_xla():
        y_xla, none = ep.dropless_experts(*args)
    assert none is None and visits.shape == (4,)
    with _config.force_xla():
        monkeypatch.setattr(ep, "grouped_matmul", poisoned(gm.grouped_matmul))
        monkeypatch.setattr(ep, "grouped_swiglu", poisoned(gm.grouped_swiglu))
        y_nan, _ = ep.dropless_experts(*args)
    assert np.isfinite(np.asarray(y_nan)).all()
    want = _by_hand(x, idx, gates, share(wg), share(wu), share(wd), held,
                    np.asarray(live))
    for y in (y_kernel, y_xla, y_nan):
        np.testing.assert_allclose(y, want, atol=1e-5)
    assert not np.asarray(y_kernel)[~np.asarray(live)].any()


def test_an_expert_parallel_share_is_the_same_call_with_fewer_groups():
    """``experts_held = (16, 32)`` of 64: the kernel walks the 32 matrices
    held here, strangers' pairs sort past every group."""
    E, held = 64, (16, 32)
    x, idx, gates, wg, wu, wd = _layer(jax.random.PRNGKey(2), T=64, E=E, k=4)
    share = lambda w: w[16:48]
    args = (x, idx, gates, share(wg), share(wu), share(wd), held)
    y, visits = ep.dropless_experts(*args)
    with _config.force_xla():
        y_xla, _ = ep.dropless_experts(*args)
    want = _by_hand(x, idx, gates, share(wg), share(wu), share(wd), held,
                    np.ones(64, bool))
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(y_xla, want, atol=1e-5)
    load = np.asarray(ep.expert_load(idx, E))[16:48]
    # 256 rows are two tiles: an expert is visited once, or twice if its
    # rows lie across row 128
    assert visits.shape == (32,)
    assert ((np.asarray(visits) > 0) == (load > 0)).all()
    assert int(load.sum()) > 128 and 0 < int((np.asarray(visits) == 2).sum()) <= 1


def test_served_tokens_are_the_same_with_the_kernel_and_without(
        step_traced_with):
    """The paged tick of the tiny model end to end: the grouped kernel
    (the interpreter here) and ``force_xla`` serve the same tokens; the
    kernel's tick counts its visits, the XLA form's counts none."""
    model = xing4.xing4_tiny(num_layers=2)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, int(rng.integers(5, 30))).tolist()
               for _ in range(5)]
    served = {}
    for form in ("kernel", "xla"):
        with step_traced_with(xla=form == "xla"):
            eng = ServeEngine(model, params, num_slots=4, max_len=64,
                              block_size=8)
            for i, p in enumerate(prompts):
                eng.submit(Request(prompt=p, max_new_tokens=6, uid=f"r{i}"))
            eng.queue.close()
            served[form] = {c.request.uid: list(c.tokens)
                            for c in eng.run(max_steps=500)}
            counted = [tree for _, tree in eng.counter_log]
        assert all("expert_load" in tree for tree in counted)
        assert all(("expert_weight_visits" in tree) == (form == "kernel")
                   for tree in counted)
        if form == "kernel":
            for tree in counted:
                load = np.asarray(tree["expert_load"])
                visits = np.asarray(tree["expert_weight_visits"])
                assert visits.shape == load.shape == (1, 8)
                # 4 slots x 8 lanes x 2 experts = 64 rows: one tile
                assert (visits == (load > 0)).all()
    assert len(served["kernel"]) == 5 \
        and all(len(t) == 6 for t in served["kernel"].values())
    assert served["kernel"] == served["xla"]
