"""Serve engine: drafts accepted over drafts verified in the window's
ticks, in %, from the counters the drafting tick logs in every tick's tree
(`drafts_verified`, `drafts_accepted`: serve/engine._draft_step; the
runner's `counted` keeps a counter's mean over the ticks in which it was
not 0, and nothing for one that was 0 throughout: no draft accepted).
A program without the counters gives nothing."""


def compute(run):
    counted = run.facts.get("counted") or {}
    verified = counted.get("drafts_verified")
    if not verified:
        return None
    total = lambda got: got["routed"] * got["ticks"] if got else 0.0
    return 100.0 * total(counted.get("drafts_accepted")) / total(verified)
