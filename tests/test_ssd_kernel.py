"""ops/ssd.py's Pallas form of one chunk (``ssd_chunk``, ISSUE 37) against
its XLA form, under the interpreter at small shapes the kernel takes (heads
of 8 channels, 128 state columns): the same numbers, a sequence with no
live lane neither read nor written, ``reset`` inside the kernel, which form
runs where, and the tiny engine through both forms."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu.models import granite_hybrid as gh
from apex_example_tpu.ops import _config as cfg
from apex_example_tpu.ops import ssd
from apex_example_tpu.serve import Request, ServeEngine
from apex_example_tpu.serve import engine as engine_lib

S, H, P, N, CHUNK = 5, 16, 8, 128, 16
# the live lanes of each sequence: a full one, none, a ragged one, none, one
N_NEW = {16: [16, 0, 9, 0, 1], 1: [1, 0, 1, 0, 1]}
TOL = 1e-5                                  # tests/test_ssd.py's


def _inputs(L, seed=0, n_new=None, H=H, P=P, N=N):
    r = np.random.default_rng(seed)
    n_new = np.asarray(N_NEW[L] if n_new is None else n_new)
    S = len(n_new)
    f = lambda *shape: r.standard_normal(shape).astype(np.float32)
    return dict(
        state=f(S, H, P, N), x=f(S, L, H, P),
        dt=np.log1p(np.exp(f(S, L, H) - 2.0)),
        a_log=np.log(r.uniform(1, 16, H)).astype(np.float32),
        B=f(S, L, N), C=f(S, L, N), D=f(H),
        live=np.arange(L)[None, :] < n_new[:, None])


def _scan(a, xla=False, **kw):
    """``(y, state, visits)`` as numpy through one form or the other."""
    with cfg.force_xla(xla):
        out = ssd.ssd_scan_counted(chunk=CHUNK, **{**a, **kw})
    return [None if t is None else np.asarray(t) for t in out]


def _close(got, want):
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("L", [1, 16])
def test_kernel_is_the_xla_chunk(L):
    a = _inputs(L)
    y, new, visits = _scan(a)
    want_y, want_new, none = _scan(a, xla=True)
    assert none is None
    assert visits.tolist() == [int(n > 0) for n in N_NEW[L]]
    _close(y[a["live"]], want_y[a["live"]])
    _close(new, want_new)


@pytest.mark.parametrize("L", [1, 16])
def test_a_sequence_with_no_live_lane_is_neither_read_nor_written(L):
    """Its state keeps every bit, a planted NaN, negative zeros and
    denormals among them, and nothing of it reaches another sequence."""
    a = _inputs(L, 1)
    a["state"][1] = np.nan
    a["state"][3, 0, 0, :4] = [-0.0, 0.0, 1e-42, -1e-42]
    y, new, _ = _scan(a)
    for s in (1, 3):
        assert new[s].tobytes() == a["state"][s].tobytes()
    moved = [0, 2, 4]
    assert np.isfinite(y[moved]).all() and np.isfinite(new[moved]).all()
    clean = dict(a, state=np.where(np.isnan(a["state"]), 0.0, a["state"]))
    y0, new0, _ = _scan(clean)
    assert y[moved].tobytes() == y0[moved].tobytes()
    assert new[moved].tobytes() == new0[moved].tobytes()


@pytest.mark.parametrize("L", [1, 16])
def test_reset_reads_the_fetched_state_as_zero_nan_or_not(L):
    a = _inputs(L, 2)
    reset = np.asarray([True, False, True, False, False])
    poisoned = a["state"].copy()
    poisoned[0], poisoned[2, 1] = np.nan, np.inf
    y, new, _ = _scan(dict(a, state=poisoned), reset=jnp.asarray(reset))
    zeroed = a["state"].copy()
    zeroed[reset] = 0.0
    want_y, want_new, _ = _scan(dict(a, state=zeroed), xla=True)
    _close(y[a["live"]], want_y[a["live"]])
    _close(new, want_new)


@pytest.mark.parametrize("L", [1, 16])
def test_y_of_a_sequence_with_no_live_lane_is_finite(L):
    """``LaneMap.pack`` carries every slot's lane 0 into the dense rows:
    zeros, whatever the state and the output buffer hold."""
    a = _inputs(L, 3)
    a["state"][1] = np.nan
    a["D"][:] = 0.0
    y, _, _ = _scan(a)
    assert not y[1].any() and not y[3].any()
    a["live"][:] = False                    # the grid is empty
    y, new, visits = _scan(a)
    assert not y.any() and not visits.any()
    assert new.tobytes() == a["state"].tobytes()


@pytest.mark.parametrize("cut", [1, 7, 15])
def test_a_call_split_in_two_is_one_call(cut):
    a = _inputs(16, 4)
    a["live"][:] = True
    a["live"][1] = False
    y, whole, _ = _scan(a)
    lanes = ("x", "dt", "B", "C", "live")
    first = {k: v[:, :cut] if k in lanes else v for k, v in a.items()}
    y0, mid, _ = _scan(first)
    rest = {k: v[:, cut:] if k in lanes else v for k, v in a.items()}
    y1, end, _ = _scan(dict(rest, state=mid))
    _close(end, whole)
    _close(np.concatenate([y0, y1], 1), y)
    assert end[1].tobytes() == a["state"][1].tobytes()


def _shaped(L=16, **shape):
    return _inputs(L, 5, n_new=[L, L], **shape)


@pytest.mark.parametrize("case,kernel", [
    ("one chunk of whole tiles", True),
    ("heads of 128 channels", True),
    ("FORCE_XLA", False),
    ("longer than a chunk", False),
    ("16 state columns", False),
    ("heads of 4 channels", False),
    ("heads of 24 channels", False),
    ("a bfloat16 state", False),
])
def test_which_form_runs(case, kernel):
    """The kernel where its shapes hold, the XLA form everywhere else:
    told by what is visited (None from the XLA form), and the same numbers
    either way."""
    a = {"one chunk of whole tiles": _shaped(),
         "heads of 128 channels": _shaped(H=2, P=128),
         "FORCE_XLA": _shaped(),
         "longer than a chunk": _shaped(L=CHUNK + 1),
         "16 state columns": _shaped(N=16),
         "heads of 4 channels": _shaped(H=32, P=4),
         "heads of 24 channels": _shaped(P=24),
         "a bfloat16 state": _shaped()}[case]
    if case == "a bfloat16 state":
        a["state"] = a["state"].astype(jnp.bfloat16)
    y, new, visits = _scan(a, xla=case == "FORCE_XLA")
    assert (visits is not None) == kernel
    if kernel:
        want_y, want_new, _ = _scan(a, xla=True)
        _close(y, want_y)
        _close(new, want_new)


# ------------------------------------------- the tiny engine, through both

SLOTS, MAX_LEN, BS = 3, 64, 8


def _served(xla):
    """Tokens and the tick-by-tick counters of the tiny model at a state
    the kernel takes (4 heads of 32 channels, 128 columns; ticks of 8
    lanes in one chunk), prompts over several chunks and slots reused."""
    model = gh.granite_hybrid_tiny(mamba_d_head=32, mamba_d_state=128)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    # the form is chosen as the tick is traced, and the engine keeps one
    # traced tick a model
    engine_lib._slot_step.cache_clear()
    with cfg.force_xla(xla):
        eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                          block_size=BS)
        for i, (n, k) in enumerate(zip((3, 19, 9, 12, 5), (6, 4, 7, 3, 5))):
            eng.submit(Request(prompt=rng.integers(0, 256, n).tolist(),
                               max_new_tokens=k, uid=f"r{i}"))
        eng.queue.close()
        done = {c.request.uid: list(c.tokens)
                for c in eng.run(max_steps=2000)}
    engine_lib._slot_step.cache_clear()
    return done, [jax.device_get(c) for _, c in eng.counter_log]


@pytest.fixture(scope="module")
def served():
    return {form: _served(form == "xla") for form in ("kernel", "xla")}


def test_engine_tokens_are_the_same_through_both_forms(served):
    (tokens, ticks), (want, _) = served["kernel"], served["xla"]
    assert len(ticks) > 8 and len(want) == 5
    assert tokens == want


def test_engine_counts_a_visit_for_every_state_that_advanced(served):
    _, ticks = served["kernel"]
    for tick in ticks:
        assert tick["ssm_state_visits"].shape == (4, SLOTS)
        assert np.array_equal(tick["ssm_state_visits"],
                              tick["ssm_slots_advanced"])
    advanced = sum(int(t["ssm_slots_advanced"].sum()) for t in ticks)
    assert 0 < advanced < 4 * SLOTS * len(ticks)     # some slots sat idle


def test_engine_counts_no_visit_through_the_xla_form(served):
    _, ticks = served["xla"]
    assert ticks and all("ssm_state_visits" not in t
                         and "ssm_slots_advanced" in t for t in ticks)
