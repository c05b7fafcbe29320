"""ops/paged_cache.py: the tick's three device operations against a plain
numpy model of a block table, one case per kind of leaf the tree has, and
leaf discovery against what the served models declare.

The numpy model is the semantics and nothing else: a dict of blocks, a
copy, a loop over lanes, a concatenation.  That the compiled tick does all
this in place is tests/test_arena_inplace.py's; that a parent's handoff
payload imports is tests/test_paged_kv.py's and tests/test_disagg.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_example_tpu.models.gpt import gpt_tiny
from apex_example_tpu.models.xing4 import xing4_tiny
from apex_example_tpu.ops import paged_cache
from apex_example_tpu.quant import kv as kv_quant
from apex_example_tpu.serve.slots import BlockPool

pytestmark = pytest.mark.serve

NB, BS, SLOTS, MAX_BLOCKS, LANES = 12, 4, 3, 3, 4

# kind of leaf -> (dtype, stored width or None for a scale table, the
# width of the rows a model hands to write)
LEAVES = {
    "float32_kv_768": (jnp.float32, 768, 768),
    "int8_payload": (jnp.int8, 768, 768),
    "bf16_scale_table": (kv_quant.KV_SCALE_DTYPE, None, None),
    "latent_576_stored_640": (jnp.bfloat16, 640, 576),
}


def _values(rng, shape, dtype):
    if dtype == jnp.int8:
        return rng.integers(-127, 128, shape).astype(np.int8)
    return np.asarray(jnp.asarray(rng.standard_normal(shape), dtype))


def _case(name):
    """A leaf full of distinct values, this tick's rows and the tick: slot
    0 decodes one token into its own block 7; slot 1's next write lands in
    shared block 2, so block 2 is copied onto 9 and lanes 0-1 written into
    the copy; slot 2 is dead (no COW, no live lane)."""
    dtype, width, row_width = LEAVES[name]
    rng = np.random.default_rng(sorted(LEAVES).index(name))
    tail = () if width is None else (width,)
    leaf = _values(rng, (NB, BS) + tail, dtype)
    rows = _values(rng, (SLOTS, LANES) + (() if width is None
                                          else (row_width,)), dtype)
    if row_width != width:         # the model pads its rows to whole tiles
        assert width == paged_cache.lane_tiles(row_width)
        rows = np.concatenate(
            [rows, np.zeros((SLOTS, LANES, width - row_width), rows.dtype)],
            -1)
    table = np.array([[5, 7, 0], [1, 9, 0], [0, 0, 0]], np.int32)
    fill = np.array([6, 5, 0], np.int32)
    n_new = np.array([1, 2, 0], np.int32)
    cow_src = np.array([-1, 2, -1], np.int32)
    cow_dst = np.array([-1, 9, -1], np.int32)
    return leaf, rows, table, fill, n_new, cow_src, cow_dst


def _numpy_tick(leaf, rows, table, fill, n_new, cow_src, cow_dst):
    """The same tick on ``{block id: block}``; returns the leaf after it
    and every slot's logical view."""
    blocks = {b: leaf[b].copy() for b in range(NB)}
    for src, dst in zip(cow_src, cow_dst):
        if dst >= 0:
            blocks[int(dst)] = leaf[int(src)].copy()
    for s in range(SLOTS):
        for j in range(int(n_new[s])):
            pos = int(fill[s]) + j
            blocks[int(table[s, pos // BS])][pos % BS] = rows[s, j]
    after = np.stack([blocks[b] for b in range(NB)])
    view = np.stack([np.concatenate([blocks[int(b)] for b in table[s]])
                     for s in range(SLOTS)])
    return after, view


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_cow_write_gather_match_a_numpy_block_table(name):
    leaf, rows, table, fill, n_new, cow_src, cow_dst = _case(name)
    want_leaf, want_view = _numpy_tick(leaf, rows, table, fill, n_new,
                                       cow_src, cow_dst)
    pos = fill[:, None] + np.arange(LANES)[None, :]

    @jax.jit
    def tick(leaf):
        leaf = paged_cache.cow(leaf, cow_src, cow_dst)
        flat = paged_cache.write_rows(jnp.asarray(table), jnp.asarray(pos),
                                      jnp.asarray(n_new), NB, BS)
        leaf = paged_cache.write(leaf, flat, jnp.asarray(rows))
        return leaf, paged_cache.gather(leaf, jnp.asarray(table)), flat

    got_leaf, got_view, flat = tick(jnp.asarray(leaf))
    assert got_leaf.dtype == leaf.dtype and got_leaf.shape == leaf.shape
    np.testing.assert_array_equal(np.asarray(got_leaf), want_leaf)
    assert got_view.shape == (SLOTS, MAX_BLOCKS * BS) + leaf.shape[2:]
    np.testing.assert_array_equal(np.asarray(got_view), want_view)
    # dead lanes index one row past the arena and were dropped; the write
    # after the COW landed in the copy and the source block kept its bytes
    flat = np.asarray(flat).reshape(SLOTS, LANES)
    assert flat[0, 0] == 7 * BS + 2 and flat[1, :2].tolist() == [37, 38]
    assert (flat[0, 1:] == NB * BS).all() and (flat[1, 2:] == NB * BS).all()
    assert (flat[2] == NB * BS).all()
    np.testing.assert_array_equal(want_leaf[2], leaf[2])
    np.testing.assert_array_equal(want_leaf[9, [0, 3]], leaf[2, [0, 3]])
    changed = [b for b in range(NB) if not np.array_equal(want_leaf[b],
                                                          leaf[b])]
    assert changed == [7, 9]


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_a_tick_with_no_cow_and_no_live_lane_changes_nothing(name):
    leaf, rows, table, fill, _, _, _ = _case(name)
    none = jnp.full((SLOTS,), -1, jnp.int32)
    pos = fill[:, None] + np.arange(LANES)[None, :]
    out = paged_cache.cow(jnp.asarray(leaf), none, none)
    flat = paged_cache.write_rows(jnp.asarray(table), jnp.asarray(pos),
                                  jnp.zeros((SLOTS,), jnp.int32), NB, BS)
    out = paged_cache.write(out, flat, jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(out), leaf)


def test_a_pytree_of_leaves_is_one_leaf_at_a_time_and_heads_split_the_view():
    """A layer's K and V go through each operation as one tree (the
    indices are computed once); the result is what each leaf gives alone,
    and ``heads`` only reshapes the gathered view."""
    k, rows, table, fill, n_new, cow_src, cow_dst = _case("float32_kv_768")
    v = k[::-1].copy()
    pos = jnp.asarray(fill[:, None] + np.arange(LANES)[None, :])
    flat = paged_cache.write_rows(jnp.asarray(table), pos,
                                  jnp.asarray(n_new), NB, BS)
    pinned = []

    def pin(t):
        pinned.append(t.shape)
        return t

    def tick(leaves, new, constrain=None):
        leaves = paged_cache.cow(leaves, cow_src, cow_dst, constrain)
        leaves = paged_cache.write(leaves, flat, new, constrain)
        return leaves, paged_cache.gather(leaves, jnp.asarray(table),
                                          heads=12)

    (k2, v2), (kv, vv) = tick((jnp.asarray(k), jnp.asarray(v)),
                              (jnp.asarray(rows), jnp.asarray(-rows)), pin)
    assert pinned == [k.shape] * 4           # K and V, after COW and write
    for one, new, got, view in ((k, rows, k2, kv), (v, -rows, v2, vv)):
        alone, alone_view = tick(jnp.asarray(one), jnp.asarray(new))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        assert view.shape == (SLOTS, MAX_BLOCKS * BS, 12, 64)
        np.testing.assert_array_equal(
            np.asarray(view),
            np.asarray(paged_cache.gather(alone, jnp.asarray(table))
                       ).reshape(view.shape))
        np.testing.assert_array_equal(np.asarray(view), np.asarray(alone_view))


def test_lane_tiles_and_geometry_are_checked_where_the_layout_lives():
    assert [paged_cache.lane_tiles(w) for w in (1, 128, 576, 640, 768)] \
        == [128, 128, 640, 640, 768]
    with pytest.raises(ValueError, match="kv_num_blocks/kv_block_size"):
        gpt_tiny().clone(decode=True, slot_decode=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------------------- leaf discovery

def _gpt_paths(model, names):
    return sorted(f"layer_{i}/attention/{n}"
                  for i in range(model.num_layers) for n in names)


@pytest.mark.parametrize("kw", [{}, {"kv_quant": True}],
                         ids=["gpt", "gpt_kv_quant"])
def test_block_leaves_finds_what_the_gpt_attention_declares(kw):
    model = gpt_tiny()
    pool = BlockPool(model, num_slots=2, max_len=16, block_size=BS,
                     num_blocks=NB, **kw)
    found = paged_cache.block_leaves(pool.cache, NB, BS)
    kinds = {path: kind for path, _, kind in found}
    names = ("cached_key", "cached_value") + (
        ("cached_key_scale", "cached_value_scale") if kw else ())
    assert sorted(kinds) == _gpt_paths(model, names)
    assert len(found) == len(jax.tree_util.tree_leaves(pool.cache))
    d = model.hidden_size
    for path, leaf, kind in found:
        scale = path.endswith("_scale")
        assert kind == (paged_cache.SCALE if scale else paged_cache.PAYLOAD)
        assert leaf.shape == ((NB, BS) if scale else (NB, BS, d)), path
        assert str(leaf.dtype) == ("bfloat16" if scale
                                   else "int8" if kw else "float32")
    # the same paths key a handoff payload
    pool.alloc.alloc()
    assert sorted(paged_cache.extract(pool.cache, [0], NB, BS)) \
        == sorted(kinds)


def test_block_leaves_finds_the_latent_leaf_and_nothing_else():
    model = xing4_tiny()
    pool = BlockPool(model, num_slots=2, max_len=16, block_size=BS,
                     num_blocks=NB)
    found = paged_cache.block_leaves(pool.cache, NB, BS)
    assert [path for path, _, _ in found] == [
        f"layer_{i}/attn/cached_latent" for i in range(model.num_layers)]
    assert all(kind == paged_cache.PAYLOAD and leaf.shape == (NB, BS, 128)
               for _, leaf, kind in found)


def test_block_leaves_passes_over_what_is_not_block_resident():
    """The dense decode cache of generate() (``[B, max_len, H, D]`` pages
    and a running index) holds no arena leaf, whatever geometry is asked
    for; nor does a leaf of the geometry's first two dimensions and a
    fourth."""
    dec = gpt_tiny().clone(decode=True)
    cache = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                           jnp.zeros((NB, BS), jnp.int32))["cache"]
    assert jax.tree_util.tree_leaves(cache)
    assert paged_cache.block_leaves(cache, NB, BS) == []
    odd = {"a": jnp.zeros((NB, BS, 2, 2)), "b": jnp.zeros((NB,)),
           "c": jnp.zeros((NB, BS + 1, 8))}
    assert paged_cache.block_leaves(odd, NB, BS) == []


@pytest.mark.parametrize("fault", ["missing", "shape", "dtype"])
def test_insert_refuses_a_payload_that_does_not_match_leaf_for_leaf(fault):
    cache = {"layer_0": {"k": jnp.zeros((NB, BS, 8), jnp.float32),
                         "k_scale": jnp.zeros((NB, BS), jnp.bfloat16)}}
    payload = paged_cache.extract(cache, [1, 2], NB, BS)
    assert sorted(payload) == ["layer_0/k", "layer_0/k_scale"]
    if fault == "missing":
        del payload["layer_0/k_scale"]
    elif fault == "shape":
        payload["layer_0/k"] = payload["layer_0/k"][:, :, :4]
    else:
        payload["layer_0/k"] = payload["layer_0/k"].astype(np.float16)
    with pytest.raises(ValueError, match="missing arena leaf"
                       if fault == "missing" else "does not fit arena"):
        paged_cache.insert(cache, [3, 4], payload, NB, BS, pad_to=4)


# ------------------------------------------------- the per-slot kind

def _hybrid_pool(slots, num_blocks):
    from apex_example_tpu.models.granite_hybrid import granite_hybrid_tiny
    return BlockPool(granite_hybrid_tiny(), num_slots=slots, max_len=16,
                     block_size=BS, num_blocks=num_blocks)


@pytest.mark.parametrize("slots,num_blocks", [(3, NB), (NB, NB), (BS, BS)],
                         ids=["apart", "slots_eq_blocks",
                              "slots_eq_blocks_eq_block_size"])
def test_per_slot_leaves_are_found_by_declaration_never_by_shape(slots,
                                                                 num_blocks):
    """Four Mamba layers' state and convolution rows are per-slot, one
    attention layer's K and V block-resident, whatever the geometry: with
    ``num_slots == num_blocks`` (== ``block_size``) a per-slot leaf's
    leading dimensions are a block leaf's."""
    pool = _hybrid_pool(slots, num_blocks)
    per_slot = paged_cache.slot_leaves(pool.cache)
    assert [path for path, _ in per_slot] == [
        f"layer_{i}/mixer/slot:{name}" for i in (0, 1, 3, 4)
        for name in ("conv_rows", "ssm_state")]
    assert all(leaf.shape[0] == slots for _, leaf in per_slot)
    assert [(path, kind) for path, _, kind in paged_cache.block_leaves(
        pool.cache, num_blocks, BS)] == [
        (f"layer_2/mixer/cached_{name}", paged_cache.PAYLOAD)
        for name in ("key", "value")]
    # a model of attention layers alone has none, and shares prefixes
    gpt = BlockPool(gpt_tiny(), num_slots=NB, max_len=16, block_size=BS,
                    num_blocks=NB)
    assert paged_cache.slot_leaves(gpt.cache) == []
    assert not gpt.per_slot_state and pool.per_slot_state
    assert gpt.state_bytes_reserved() == 0


def test_a_block_leaf_may_not_take_a_per_slot_key():
    import flax.linen as nn

    class Wrong(nn.Module):
        @nn.compact
        def __call__(self, x):
            paged_cache.variable(self, "slot:k", NB, BS, jnp.float32, 8)
            return x

    with pytest.raises(ValueError, match="per-slot"):
        Wrong().init(jax.random.PRNGKey(0), jnp.zeros((1,)))


def _hybrid_cache(slots=NB):
    """A tree with both kinds at ``num_slots == num_blocks``, every leaf
    full of distinct values."""
    rng = np.random.default_rng(7)
    fill = lambda shape, dtype: jnp.asarray(
        rng.standard_normal(shape), dtype)
    return {"layer_0": {"mixer": {
                "slot:ssm_state": fill((slots, 2, 4, 4), jnp.float32),
                "slot:conv_rows": fill((slots, BS, 8), jnp.bfloat16)}},
            "layer_1": {"mixer": {
                "cached_key": fill((NB, BS, 8), jnp.bfloat16)}}}


def test_extract_and_insert_carry_a_slots_rows_with_its_blocks():
    src, dst = _hybrid_cache(), jax.tree_util.tree_map(
        jnp.zeros_like, _hybrid_cache())
    want = jax.tree_util.tree_map(np.asarray, src)
    payload = paged_cache.extract(src, [5, 2], NB, BS, slot=7)
    assert {k: v.shape for k, v in payload.items()} == {
        "layer_0/mixer/slot:ssm_state": (1, 2, 4, 4),
        "layer_0/mixer/slot:conv_rows": (1, BS, 8),
        "layer_1/mixer/cached_key": (2, BS, 8)}
    assert all(v.flags.writeable for v in payload.values())
    # without a slot only the blocks travel
    assert sorted(paged_cache.extract(src, [5, 2], NB, BS)) \
        == ["layer_1/mixer/cached_key"]
    got = paged_cache.insert(dst, [9, 0], payload, NB, BS, pad_to=4, slot=3)
    got = jax.tree_util.tree_map(np.asarray, got)
    for name in ("slot:ssm_state", "slot:conv_rows"):
        leaf = got["layer_0"]["mixer"][name]
        assert leaf[3].tobytes() \
            == want["layer_0"]["mixer"][name][7].tobytes()
        assert not np.delete(leaf, 3, axis=0).any()   # slot 3's row alone
    keys = got["layer_1"]["mixer"]["cached_key"]
    assert keys[[9, 0]].tobytes() \
        == want["layer_1"]["mixer"]["cached_key"][[5, 2]].tobytes()
    assert not np.delete(keys, [9, 0], axis=0).any()


@pytest.mark.parametrize("fault", ["missing", "no_slot", "shape", "dtype"])
def test_insert_refuses_a_payload_without_the_slots_state(fault):
    cache = _hybrid_cache()
    payload = paged_cache.extract(cache, [1], NB, BS, slot=0)
    slot, key = 2, "layer_0/mixer/slot:ssm_state"
    if fault == "missing":
        del payload[key]
    elif fault == "no_slot":
        slot = None
    elif fault == "shape":
        payload[key] = payload[key][:, :1]
    else:
        payload[key] = payload[key].astype(np.float16)
    with pytest.raises(ValueError, match="does not fit one slot's row"
                       if fault in ("shape", "dtype")
                       else "missing per-slot leaf"):
        paged_cache.insert(cache, [3], payload, NB, BS, pad_to=4, slot=slot)


def test_shard_replicates_a_per_slot_leaf_and_splits_a_payload():
    from jax.sharding import Mesh, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    placed = paged_cache.shard(_hybrid_cache(), mesh, NB, BS)
    mixer = placed["layer_0"]["mixer"]
    # [NB, BS, 8] bfloat16 conv rows look like a payload; they are not one
    assert mixer["slot:conv_rows"].sharding.spec == P()
    assert mixer["slot:ssm_state"].sharding.spec == P()
    assert placed["layer_1"]["mixer"]["cached_key"].sharding.spec \
        == P(None, None, "model")
