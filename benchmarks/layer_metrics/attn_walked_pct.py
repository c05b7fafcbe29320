"""Kernels: cache positions paged attention read a layer and tick (the
model's `attn_positions_walked` counter, `[layers, slots]`: a slot's live
blocks x block size from the kernel that walks them, every position of the
slot's table row from a form that gathers it whole), over the tokens the
pool has room for, mean over the window's ticks and the layers, in %.  A
little over `kv_pool_live_pct` (block rounding) when attention follows the
load; 100 when it skips nothing.  A program without the counter gives
nothing."""


def compute(run):
    got = (run.facts.get("counted") or {}).get("attn_positions_walked")
    room = run.facts.get("pool_tokens")
    return 100.0 * got["routed"] / room if got and room else None
