"""Runner for serving cells whose model drafts for itself.

A model served with its own next-token module (multi-token prediction)
delivers the same tokens whether the module is sound, broken or absent:
greedy verification lets nothing through that the model would not have
said.  So ``runners/serve_blocked.py``'s comparison cannot see the module,
and this runner is that one with the module added to the same check, for
the same sampled requests: every draft the engine verified (``Completion.
drafts``: the output position it claimed, the token, the verdict) is
judged like a served token — how far its logit lies below the best of the
reference's module at its position, given the same prefix
(``<prefix>_mtp_hidden`` beside ``<prefix>_hidden`` and ``<prefix>_head``).
The limits file names ``draft_logit_gap_mean`` and ``draft_off_first_share``
beside the served ones.  A run that verified no draft reads NaN, which
fails.  Load, window, drain, sampling of requests, the judged metrics and
the host's care are ``runners/serve_blocked.py``'s (``run``, by import, over
this module's ``Cell`` and ``served_gaps``).

``break_step="perturb_module"`` (the tests') scrambles the module's
projection under the engine: served tokens stay, drafts go wrong.

``python3 benchmarks/runners/serve_selfdraft.py --workload <cell> --seeds
1,2,3`` is the control, as ``serve_blocked``'s: the reference at the
configuration's ``control.precision`` in the program's place, for the served
tokens and for the drafts alike (its module's own first places at that
precision), judged by the same limits; it prints the checks and ``correct``.
"""

from __future__ import annotations

import gc
import os
import sys
from typing import Dict, Optional
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.runners import serve_blocked as blocked


class Cell(blocked.Cell):
    """``serve_blocked.Cell`` whose reference pass also runs the module."""

    def __init__(self, cfg: Dict, trf: Dict, devices):
        super().__init__(cfg, trf, devices)
        module = getattr(self.ref, self.prefix + "_mtp_hidden")
        self._module = jax.jit(
            lambda p, x, h, prec: module(p, x, self.rcfg, prec, h),
            static_argnums=3)
        self.drafts_wanted = None       # [n, L] the drafts judged, -1 none
        self.draft_gaps = None          # [n, L] their gaps

    def engine(self, key, break_step: Optional[str] = None,
               control: bool = False):
        eng = super().engine(
            key, None if break_step == "perturb_module" else break_step,
            control)
        if break_step == "perturb_module":
            mtp = dict(eng.params["mtp"])
            mtp["eh_proj"] = mtp["eh_proj"][:, ::-1]
            eng.params = dict(eng.params, mtp=mtp)
        return eng

    def gaps(self, key, ids: np.ndarray,
             served_by: Optional[str] = None) -> np.ndarray:
        """``serve_blocked.Cell.gaps``, and in the same pass over each
        request ``draft_gaps``: at module position ``i`` (its logits are of
        token ``i + 2``) the gap of ``drafts_wanted[r, i]``, or of the
        module's own first place at ``served_by``, below the float32
        module's best."""
        gc.collect()                   # the engine's weights and arena go
        params = self.weights(key)
        n, L = ids.shape
        blk = int(self.cfg.get("reference_block", L))
        out = np.zeros((2, n, L), np.float32)
        nxt = np.concatenate([ids[:, 1:], np.zeros((n, 1), ids.dtype)], 1)
        judged = (nxt, np.maximum(self.drafts_wanted, 0))
        for r in range(n):
            x = jnp.asarray(ids[r:r + 1])
            precs = ("highest", served_by) if served_by else ("highest",)
            hs = [self._hidden(params, x, prec) for prec in precs]
            zs = [self._module(params, x, h, prec)
                  for h, prec in zip(hs, precs)]
            for which, states in enumerate((hs, zs)):
                for b0 in range(0, L, blk):
                    out[which, r, b0:b0 + blk] = np.asarray(self._block(
                        params, [s[0, b0:b0 + blk] for s in states],
                        jnp.asarray(judged[which][r, b0:b0 + blk]),
                        served_by))
        self.draft_gaps = out[1]
        return out[0, :, :-1]


def served_gaps(sut: Cell, key, sample,
                served_by: Optional[str] = None) -> Dict[str, float]:
    """``serve_blocked.served_gaps`` and, of the drafts the engine verified
    for the same requests, the mean gap below the reference module's best
    and the share that are not its first place."""
    L = sut.trf["engine"]["max_len"]
    sut.drafts_wanted = np.full((len(sample), L), -1, np.int32)
    for r, c in enumerate(sample):
        for at, token, _ in c.drafts:
            # output index ``at`` is token P + at, judged at position
            # P + at - 2: from h there and the token after it
            sut.drafts_wanted[r, len(c.request.prompt) + at - 2] = token
    got = _served_gaps(sut, key, sample, served_by)
    gaps = sut.draft_gaps[sut.drafts_wanted >= 0]
    accepted = sum(ok for c in sample for _, _, ok in c.drafts)
    blocked.harness.note(f"drafts judged: {gaps.size}, {accepted} of them "
                         "accepted by the engine")
    nan = float("nan")
    return dict(got, drafts_checked=int(gaps.size),
                draft_logit_gap_mean=float(gaps.mean()) if gaps.size
                else nan,
                draft_off_first_share=float(np.mean(gaps > 0)) if gaps.size
                else nan)


_served_gaps, _run = blocked.served_gaps, blocked.run


def run(cell, cfg, trf, limits, args, devices, t_process, spans,
        compiles, break_step=None, served_by: Optional[str] = None):
    """``serve_blocked.run`` over this module's ``Cell`` and
    ``served_gaps``."""
    with mock.patch.object(blocked, "Cell", Cell), \
            mock.patch.object(blocked, "served_gaps", served_gaps):
        return _run(cell, cfg, trf, limits, args, devices, t_process, spans,
                    compiles, break_step=break_step, served_by=served_by)


def main(argv=None):
    with mock.patch.object(blocked, "run", run):
        return blocked.main(argv)


if __name__ == "__main__":
    sys.exit(main())
