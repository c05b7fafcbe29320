"""ops/ssd.py: the chunked (SSD) form against the recurrence it stands
for, token by token (ISSUE 34)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu.ops import ssd

S, L, H, P, N, CH, K = 3, 37, 4, 8, 16, 24, 4


def _inputs(seed=0, L=L):
    r = np.random.default_rng(seed)
    f = lambda *shape: r.standard_normal(shape).astype(np.float32)
    return dict(
        state=f(S, H, P, N), x=f(S, L, H, P),
        dt=np.log1p(np.exp(f(S, L, H) - 2.0)), a_log=np.log(
            r.uniform(1, 16, H)).astype(np.float32),
        B=f(S, L, N), C=f(S, L, N), D=f(H))


def _by_token(state, x, dt, a_log, B, C, D, live):
    """float64, one lane at a time; dead lanes are skipped outright."""
    state = state.astype(np.float64).copy()
    A = -np.exp(a_log.astype(np.float64))
    y = np.zeros(x.shape, np.float64)
    for s in range(x.shape[0]):
        for t in range(x.shape[1]):
            if live[s, t]:
                a = np.exp(dt[s, t] * A)                       # [H]
                state[s] = a[:, None, None] * state[s] + np.einsum(
                    "h,hp,n->hpn", dt[s, t], x[s, t], B[s, t])
            y[s, t] = state[s] @ C[s, t] + D[:, None] * x[s, t]
    return y, state


def _live(kind, L=L):
    live = np.ones((S, L), bool)
    if kind == "middle":
        live[0, 5:9] = False
        live[1, 0] = False
        live[2, ::3] = False
    elif kind == "end":
        live[0, 20:] = False
        live[1, 1:] = False
        live[2, :] = False
    return live


def _close(got, want, tol=1e-5):
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got) - want).max() <= tol * scale


@pytest.mark.parametrize("chunk", [1, 16, 256])
@pytest.mark.parametrize("dead", ["none", "middle", "end"])
def test_chunked_form_is_the_recurrence(chunk, dead):
    a, live = _inputs(), _live(dead)
    y, new = jax.jit(lambda **kw: ssd.ssd_scan(chunk=chunk, **kw))(
        live=jnp.asarray(live), **a)
    want_y, want_state = _by_token(live=live, **a)
    _close(y[live], want_y[live])
    _close(new, want_state)


def test_no_live_lane_keeps_state_and_rows_bit_for_bit():
    a = _inputs(1)
    a["state"][0, 0, 0, :4] = [-0.0, 0.0, 1e-42, -1e-42]    # zeros, denormals
    live = _live("end")                                      # slot 2: none
    _, new = ssd.ssd_scan(live=jnp.asarray(live), chunk=16, **a)
    live[:] = False
    _, kept = ssd.ssd_scan(live=jnp.asarray(live), chunk=16, **a)
    assert np.asarray(new)[2].tobytes() == a["state"][2].tobytes()
    assert np.asarray(kept).tobytes() == a["state"].tobytes()
    r = np.random.default_rng(2)
    rows = r.standard_normal((S, K - 1, CH)).astype(jnp.bfloat16)
    x = r.standard_normal((S, L, CH)).astype(jnp.bfloat16)
    w, b = (r.standard_normal(s).astype(np.float32) for s in ((K, CH), (CH,)))
    _, after = ssd.causal_conv(jnp.asarray(rows), jnp.asarray(x), w, b,
                               jnp.asarray([0, 5, 0]))
    after = np.asarray(after)
    assert after[0].tobytes() == rows[0].tobytes()
    assert after[2].tobytes() == rows[2].tobytes()
    assert after[1].tobytes() == x[1, 2:5].tobytes()


@pytest.mark.parametrize("cut", [1, 7, 16, 36])
def test_a_tick_split_at_any_lane_gives_the_same_state(cut):
    a = _inputs(3)
    live = jnp.ones((S, L), bool)
    y, whole = ssd.ssd_scan(live=live, chunk=256, **a)
    lanes = {k: a[k] for k in ("x", "dt", "B", "C")}
    rest = {k: a[k] for k in ("a_log", "D")}
    y0, mid = ssd.ssd_scan(a["state"], live=live[:, :cut], chunk=256, **rest,
                           **{k: v[:, :cut] for k, v in lanes.items()})
    y1, end = ssd.ssd_scan(mid, live=live[:, cut:], chunk=256, **rest,
                           **{k: v[:, cut:] for k, v in lanes.items()})
    _close(end, np.asarray(whole, np.float64))
    _close(np.concatenate([y0, y1], 1), np.asarray(y, np.float64))


def test_reset_starts_from_zero_whatever_the_state_holds():
    a = _inputs(4)
    live = jnp.ones((S, L), bool)
    poisoned = a["state"].copy()
    poisoned[1] = np.nan
    reset = jnp.asarray([False, True, False])
    y, new = ssd.ssd_scan(live=live, chunk=16, reset=reset,
                          **dict(a, state=poisoned))
    zeroed = a["state"].copy()
    zeroed[1] = 0.0
    want_y, want = _by_token(live=np.ones((S, L), bool),
                             **dict(a, state=zeroed))
    _close(new, want)
    _close(y, want_y)


def test_conv_over_kept_rows_is_the_conv_over_the_whole_sequence():
    r = np.random.default_rng(5)
    x = r.standard_normal((S, L, CH)).astype(np.float32)
    w, b = (r.standard_normal(s).astype(np.float32) for s in ((K, CH), (CH,)))
    zeros = jnp.zeros((S, K - 1, CH), jnp.float32)
    whole, _ = ssd.causal_conv(zeros, x, w, b, jnp.full((S,), L))
    padded = np.concatenate([np.zeros((S, K - 1, CH), np.float32), x], 1)
    want = b + sum(w[k] * padded[:, k:k + L] for k in range(K))
    _close(whole, want)
    # in two ticks, the second 16 lanes wide with 9 live; a stale row
    # state is reset away
    stale = jnp.full((S, K - 1, CH), 7.0)
    first, rows = ssd.causal_conv(stale, x[:, :20], w, b, jnp.full((S,), 20),
                                  reset=jnp.ones((S,), bool))
    lanes = np.zeros((S, 16, CH), np.float32)
    lanes[:, :9] = x[:, 20:29]
    second, rows = ssd.causal_conv(rows, lanes, w, b, jnp.full((S,), 9))
    _close(first, want[:, :20])
    _close(second[:, :9], want[:, 20:29])
    assert np.array_equal(np.asarray(rows), x[:, 26:29])
