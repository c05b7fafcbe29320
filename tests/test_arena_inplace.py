"""The serve tick updates the paged K/V arena in place (ISSUE 25).

Two halves of one property, read off the compiled program:

(a) compiled for a described TPU (``v5e:2x2``, no chip attached) the
    tick holds no ``copy`` as large as one arena leaf: the COW block
    copy, the per-token write and the block gather all address the
    leading dimension of ONE tiled layout, so XLA has nothing to
    convert between them (with ``[NB, BS, H, D]`` leaves it converted
    the whole arena between every pair, six passes a tick);
(b) every arena byte is aliased from argument to result
    (``donate_argnums`` on the cache).  This half also holds on the
    CPU backend and needs no topology.

Geometry: the real head shape (12 x 64), 2 layers, 8 slots x 128,
block 16, and a pool of 96 blocks (dense capacity would be 64): the
gathered per-slot view ``[8, 128, 12, 64]`` is then SMALLER than an
arena leaf, so "at least one arena's element count" cannot be met by
the view's own relayout, which is work of attention and not of the
arena's update.  The vocabulary is cut to 512 (it is not the arena's
business and is most of the compile time).

The TPU half compiles in this process (never in a child: libtpu
belongs to one process), inside fixtures (never at import).
"""

import contextlib
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from apex_example_tpu.models.gpt import GPTForCausalLM
from apex_example_tpu.serve import engine as engine_lib
from apex_example_tpu.serve.slots import BlockPool

pytestmark = pytest.mark.serve

LAYERS, SLOTS, MAX_LEN, BS, NB = 2, 8, 128, 16, 96
HIDDEN, HEADS = 768, 12
SPEC_K = 3                         # lanes = max(BS, K + 1) = BS here
LATENT_WIDTH = 640                 # 512 + 64 in whole 128-lane tiles


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # libtpu cannot describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _model():
    return GPTForCausalLM(vocab_size=512, hidden_size=HIDDEN,
                          num_layers=LAYERS, num_heads=HEADS,
                          intermediate_size=4 * HIDDEN,
                          max_position=MAX_LEN)


def _latent_model():
    """models/xing4.py at the published latent widths (rank 512 + 64
    rotary, 32 heads), everything else small."""
    from apex_example_tpu.models.xing4 import Xing4ForCausalLM
    return Xing4ForCausalLM(vocab_size=512, hidden_size=512, num_layers=2,
                            first_k_dense=1, intermediate_size=512,
                            moe_intermediate_size=256, n_routed_experts=8)


def _lowered(speculative: bool, kv_quant: bool, sharding, model=None,
             slots=SLOTS):
    """The tick's program lowered from shapes alone (no array is made):
    the model clone ``BlockPool`` builds, the step ``ServeEngine``
    calls, every argument a ShapeDtypeStruct on ``sharding``."""
    width = HIDDEN if model is None else LATENT_WIDTH
    dec = (model or _model()).clone(decode=True, slot_decode=True,
                         fused_attention=False, kv_num_blocks=NB,
                         kv_block_size=BS, kv_quant=kv_quant)
    shapes = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                            jnp.zeros((slots, MAX_LEN), jnp.int32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def tree(t):
        return jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), t)

    lanes = max(BS, SPEC_K + 1) if speculative else BS
    layout = engine_lib.TickArgs(lanes, MAX_LEN // BS)
    args = (tree(shapes["params"]), tree(shapes["cache"]),
            sds((slots, layout.width), jnp.int32), sds((2,), jnp.uint32))
    step = engine_lib._slot_step(dec, layout, lanes=speculative)
    leaves = jax.tree_util.tree_leaves(shapes["cache"])
    arena_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    arena_elems = max(l.size for l in leaves)
    assert arena_elems == NB * BS * width
    return step.lower(*args), arena_bytes, arena_elems


_RESULT = re.compile(r"= (\w+)\[([\d,]*)\]\S* copy\(")


def arena_sized_copies(hlo_text: str, arena_elems: int):
    """Every ``copy`` instruction of the optimised HLO (top level or
    inside a fusion) whose result holds at least one arena leaf's
    element count, as ``(dtype, dims)``."""
    found = []
    for dtype, dims in _RESULT.findall(hlo_text):
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n >= arena_elems:
            found.append((dtype, dims))
    return found


def test_copy_counter_sees_the_copies_it_is_for():
    text = ("%copy.54 = f32[96,16,12,64]{3,1,0,2:T(8,128)S(1)} copy(%f)\n"
            "ROOT %copy.9 = bf16[1536,12,64]{2,0,1:T(8,128)(2,1)} copy(%p)\n"
            "%copy.3 = bf16[8,128,12,64]{3,1,2,0} copy(%g)\n"
            "%fusion.1 = f32[96,16,768]{2,1,0} fusion(%a), kind=kLoop\n")
    assert arena_sized_copies(text, NB * BS * HIDDEN) == [
        ("f32", "96,16,12,64"), ("bf16", "1536,12,64")]


@pytest.fixture(scope="module", params=["kernel", "xla"])
def gpt_tick(request, one_chip):
    """The GPT tick at GPT-1's published attention widths (12 heads of 64,
    float32 arenas ``[NB, 16, 768]``) compiled as the chip compiles it, on
    either form of ``ops.attention.paged_gqa_attention``."""
    with compiled_as_the_chip_does(request.param):
        lowered, arena_bytes, arena_elems = _lowered(False, False, one_chip)
    return request.param, lowered.compile(), arena_bytes, arena_elems


def _per_head_calls(text):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "paged_gqa_attention" in line]


def test_tpu_tick_has_no_arena_sized_copy_and_aliases_the_arena(gpt_tick):
    _, compiled, arena_bytes, arena_elems = gpt_tick
    assert arena_sized_copies(compiled.as_text(), arena_elems) == []
    assert compiled.memory_analysis().alias_size_in_bytes == arena_bytes


def test_tpu_speculative_tick_has_no_arena_sized_copy_either(one_chip):
    with compiled_as_the_chip_does("kernel"):
        lowered, arena_bytes, arena_elems = _lowered(True, False, one_chip)
    compiled = lowered.compile()
    assert arena_sized_copies(compiled.as_text(), arena_elems) == []
    assert compiled.memory_analysis().alias_size_in_bytes == arena_bytes
    assert len(_per_head_calls(compiled.as_text())) == LAYERS


def test_tpu_tick_walks_gpt1s_float32_arenas_in_a_kernel(gpt_tick):
    """ISSUE 41: at 12 heads of 64 over float32 arenas the tick compiles
    for the chip with the paged GQA kernel once a layer, two heads a lane
    tile (six pairs of 128 lanes, query rows ``16 lanes x 2``), and holds
    no gathered ``[S, L, 768]`` view of either dtype, no ``[S, L, 12, 64]``
    relayout of one and no ``[S, 12, C, L]`` scores; the XLA form of the
    same op holds them and no such call."""
    form, compiled, _, _ = gpt_tick
    text = compiled.as_text()
    calls = _per_head_calls(text)
    views = [f"{dt}[{SLOTS},{MAX_LEN},{tail}]" for dt in ("f32", "bf16")
             for tail in (HIDDEN, f"{HEADS},64")]
    scores = f"f32[{SLOTS},{HEADS},1,{BS},{MAX_LEN}]"
    if form == "kernel":
        assert len(calls) == LAYERS
        assert all(f"f32[{SLOTS},6,{2 * BS},128]" in c
                   and f"f32[{NB},{BS},{HIDDEN}]" in c for c in calls)
        assert not any(t in text for t in views + [scores])
    else:
        assert not calls and scores in text \
            and any(t in text for t in views)


def test_tpu_tick_updates_the_latent_arena_in_place(one_chip):
    """The head-less latent leaf (ISSUE 27) is stored 640 wide, not 576:
    at 576 XLA gives the arena one layout coming in and another going out
    and copies it twice a layer; at whole tiles it updates it in place."""
    lowered, arena_bytes, arena_elems = _lowered(False, False, one_chip,
                                                 _latent_model())
    compiled = lowered.compile()
    # this model's attention weights (512 x 32 x 128) outsize the small
    # pool's leaf, so only copies of exactly a leaf's element count count
    copies = [(dtype, dims) for dtype, dims
              in arena_sized_copies(compiled.as_text(), arena_elems)
              if math.prod(int(d) for d in dims.split(",")) == arena_elems]
    assert copies == []
    assert compiled.memory_analysis().alias_size_in_bytes == arena_bytes


@contextlib.contextmanager
def compiled_as_the_chip_does(form):
    """The ops' dispatch keys on the live backend (the CPU here) and on the
    test rig's interpreter switch, so a test of the chip's program steers
    both for the length of the lowering, onto the ``"kernel"`` or the
    ``"xla"`` form; the engine's cached step is dropped on either side."""
    from apex_example_tpu.ops import _config
    saved = _config.INTERPRET, _config.use_pallas
    _config.INTERPRET = False
    _config.use_pallas = lambda: not _config.FORCE_XLA
    engine_lib._slot_step.cache_clear()
    try:
        with _config.force_xla(form == "xla"):
            yield
    finally:
        _config.INTERPRET, _config.use_pallas = saved
        engine_lib._slot_step.cache_clear()


@pytest.fixture(scope="module", params=["kernel", "xla"])
def latent_tick(request, one_chip):
    """The latent tick compiled as the chip compiles it, on either form of
    paged latent attention (ops/attention.py)."""
    with compiled_as_the_chip_does(request.param):
        lowered, arena_bytes, _ = _lowered(False, False, one_chip,
                                           _latent_model())
    return request.param, lowered.compile(), arena_bytes


def test_tpu_tick_walks_the_latent_arena_in_a_kernel(latent_tick):
    """ISSUE 28: with the kernel the tick holds no [S, H, C, L] float32
    score tensor and no gathered [S, L, W] view, calls the kernel once a
    layer and still updates the arena in place; the XLA form of the same
    op holds both tensors and no such call."""
    form, compiled, arena_bytes = latent_tick
    text = compiled.as_text()
    scores = f"f32[{SLOTS},32,{BS},{MAX_LEN}]"
    view = f"bf16[{SLOTS},{MAX_LEN},{LATENT_WIDTH}]"
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "paged_latent_attention" in line]
    if form == "kernel":
        assert scores not in text and view not in text
        assert len(calls) == 2                       # one a layer
    else:
        assert scores in text and view in text and not calls
    assert compiled.memory_analysis().alias_size_in_bytes == arena_bytes


EXPERTS, EXPERT_IN, EXPERT_WIDTH, EXPERT_SLOTS = 64, 3584, 1024, 64


@pytest.fixture(scope="module", params=["kernel", "xla"])
def expert_tick(request, one_chip):
    """The latent tick with one expert layer at the published widths (64
    experts of 3584 x 1024, 4 a token) and the cell's 64 slots of 16
    lanes, so the grouped products see the served ``[4096, 3584]`` rows;
    everything that is not the expert layer small.  Steered as
    ``latent_tick`` is."""
    from apex_example_tpu.models.xing4 import Xing4ForCausalLM
    model = Xing4ForCausalLM(
        vocab_size=512, hidden_size=EXPERT_IN, num_layers=2, first_k_dense=1,
        intermediate_size=512, moe_intermediate_size=EXPERT_WIDTH,
        n_routed_experts=EXPERTS)
    with compiled_as_the_chip_does(request.param):
        lowered, _, _ = _lowered(False, False, one_chip, model,
                                 slots=EXPERT_SLOTS)
    return request.param, lowered.compile().as_text()


def test_tpu_tick_multiplies_the_experts_in_a_kernel(expert_tick):
    """ISSUE 33: at the published expert widths the tick's grouped
    products are the two named Pallas calls (gate, up and the activation
    in one; down), bfloat16 in and float32 out of the second, with no
    ``ragged-dot`` left and no copy or concatenation as large as a weight
    stack; the XLA form of the same ops holds XLA's ragged-dot kernels
    and no such call."""
    form, text = expert_tick
    # four pairs a row of the tick's packed rows (ops/lane_pack.py: since
    # PR 44 the experts see the live lanes' rows, not all SLOTS x BS lanes)
    from apex_example_tpu.ops import lane_pack
    rows = lane_pack.rows(EXPERT_SLOTS, BS) * 4
    stack = EXPERTS * EXPERT_IN * EXPERT_WIDTH
    calls = {name: [line for line in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line
                    and f"%{name}." in line.split("=")[0]]
             for name in ("grouped_swiglu", "grouped_matmul")}
    moved = [(op, dims) for dims, op in re.findall(
        r"= \w+\[([\d,]*)\]\S* (copy|concatenate)\(", text)
        if math.prod(int(d) for d in dims.split(",") if d) >= stack]
    assert moved == []
    if form == "kernel":
        assert "ragged-dot" not in text
        assert [len(c) for c in calls.values()] == [1, 1]  # one expert layer
        assert f"= bf16[{rows},{EXPERT_WIDTH}]" in calls["grouped_swiglu"][0]
        assert f"= f32[{rows},{EXPERT_IN}]" in calls["grouped_matmul"][0]
        # the weight stacks go in as they are held: bfloat16 parameters
        assert f"bf16[{EXPERTS},{EXPERT_IN},{EXPERT_WIDTH}]" in text \
            and f"f32[{EXPERTS},{EXPERT_IN},{EXPERT_WIDTH}]" not in text
    else:
        assert "ragged-dot" in text and not any(calls.values())


def _hybrid_lowered(sharding):
    """The tick of models/granite_hybrid.py lowered from shapes: two Mamba
    layers at the published state widths (64 heads of 64, 128 columns:
    ``[slots, 64, 64, 128]`` float32 a layer) round one GQA layer (8 K/V
    heads of 64: ``[NB, BS, 512]`` leaves), everything else small; 16
    slots so that no leaf is padded."""
    from apex_example_tpu.models.granite_hybrid import \
        GraniteHybridForCausalLM
    slots = 16
    dec = GraniteHybridForCausalLM(
        vocab_size=512, hidden_size=512, num_layers=3, attention_period=3,
        attention_offset=1, intermediate_size=512).clone(
            decode=True, slot_decode=True, fused_attention=False,
            kv_num_blocks=NB, kv_block_size=BS)
    shapes = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                            jnp.zeros((slots, MAX_LEN), jnp.int32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def tree(t):
        return jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), t)

    layout = engine_lib.TickArgs(BS, MAX_LEN // BS)
    args = (tree(shapes["params"]), tree(shapes["cache"]),
            sds((slots, layout.width), jnp.int32), sds((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(shapes["cache"])
    assert {l.shape for l in leaves} == {
        (slots, 3 * 4352), (slots, 64, 64, 128), (NB, BS, 512)}
    return (engine_lib._slot_step(dec, layout).lower(*args),
            sum(l.size * l.dtype.itemsize for l in leaves),
            {l.size for l in leaves})


@pytest.fixture(scope="module", params=["kernel", "xla"])
def hybrid_tick(request, one_chip):
    """The hybrid tick compiled for the chip with the scan's Pallas form
    (``ops/ssd.py``'s ``ssd_chunk``, what the TPU runs) and with its XLA
    form.  Steered as ``latent_tick`` is."""
    with compiled_as_the_chip_does(request.param):
        lowered, cache_bytes, sizes = _hybrid_lowered(one_chip)
    return request.param, lowered.compile(), cache_bytes, sizes


def test_tpu_tick_updates_both_kinds_of_cache_in_place(hybrid_tick):
    """ISSUE 34: a Mamba layer's per-slot state is read and written where
    it lies, like the arena beside it: the tick compiled for the chip
    holds no copy of a state leaf's or an arena leaf's size, nothing of
    ``[slots, lanes, H, P, N]`` (the lane-by-lane form's carry), and
    aliases every cache byte from argument to result.  (The three
    convolution rows a slot are kept flat, ``[slots, 3 * 4352]``, so that
    no tile is padded, and are laid out as ``[slots, 3, 4352]`` for the
    convolution and back: a copy of that leaf's 1/300 of the state's
    bytes, let be.)  ISSUE 37: with the kernel the scan is one named
    Pallas call a Mamba layer, its state operand aliased to its state
    result, and no ``select`` passes over a whole state leaf; the XLA form
    holds that select and no such call."""
    form, compiled, cache_bytes, sizes = hybrid_tick
    sizes = sizes - {16 * 3 * 4352}
    text = compiled.as_text()
    copies = [(dtype, dims) for dtype, dims
              in arena_sized_copies(text, min(sizes))
              if math.prod(int(d) for d in dims.split(",")) in sizes]
    assert copies == []
    assert f"[16,{BS},64,64,128]" not in text
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%ssd_chunk." in line.split("=")[0]]
    selects = re.findall(r"= f32\[16,64,64,128\]\S* select\(", text)
    if form == "kernel":
        assert len(calls) == 2 and not selects         # one a Mamba layer
        assert all("output_to_operand_aliasing" in c
                   and "f32[16,64,64,128]" in c for c in calls)
    else:
        assert not calls and selects


def test_tpu_tick_walks_the_hybrids_arenas_in_a_kernel(hybrid_tick):
    """ISSUE 41: at `granite4h`'s published attention widths (32 query
    heads over 8 key/value heads of 64, bfloat16) the GQA layer is the
    paged kernel, two heads a lane tile (four pairs of 128 lanes, query
    rows ``16 lanes x 8``: `trinity_mini`'s operand shape), with no
    gathered ``[S, L, 8, 64]`` view and no ``[S, 8, 4, C, L]`` scores in
    the program; the XLA form of the same op holds the scores and no such
    call."""
    form, compiled, _, _ = hybrid_tick
    text = compiled.as_text()
    calls = _per_head_calls(text)
    views = [f"bf16[16,{MAX_LEN},{tail}]" for tail in ("512", "8,64")]
    scores = f"f32[16,8,4,{BS},{MAX_LEN}]"
    if form == "kernel":
        assert len(calls) == 1                       # the one GQA layer
        assert f"bf16[16,4,{8 * BS},128]" in calls[0] \
            and f"bf16[{NB},{BS},512]" in calls[0]
        assert not any(t in text for t in views + [scores])
    else:
        assert not calls and scores in text


def test_cpu_tick_aliases_both_kinds_of_cache():
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    lowered, cache_bytes, _ = _hybrid_lowered(cpu)
    assert lowered.compile().memory_analysis().alias_size_in_bytes \
        == cache_bytes


def test_tpu_tick_aliases_the_int8_arena(one_chip):
    lowered, arena_bytes, _ = _lowered(False, True, one_chip)
    mem = lowered.compile().memory_analysis()
    # >=: the four [NB, BS] bfloat16 scale tables are padded to whole
    # tiles on the TPU (3072 -> 4096 bytes each at this geometry)
    assert arena_bytes <= mem.alias_size_in_bytes < arena_bytes + 2 ** 16


@pytest.mark.parametrize("speculative,kv_quant",
                         [(False, False), (True, False), (False, True)],
                         ids=["plain", "speculative", "kv_quant"])
def test_cpu_tick_aliases_the_arena(speculative, kv_quant):
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    lowered, arena_bytes, _ = _lowered(speculative, kv_quant, cpu)
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes == arena_bytes


def test_arena_leaf_is_blocks_by_rows_by_merged_heads():
    pool = BlockPool(_model(), num_slots=2, max_len=32, block_size=BS)
    shapes = {l.shape for l in jax.tree_util.tree_leaves(pool.cache)}
    assert shapes == {(4, BS, HIDDEN)}
    # layout-blind accounting: K and V, float32, every layer
    assert pool.kv_bytes_per_token() == 2 * LAYERS * HIDDEN * 4
    assert pool.kv_bytes_per_token_bf16() == 2 * LAYERS * HIDDEN * 2


def test_tpu_latent_kernel_compiles_at_128_heads(one_chip):
    """ISSUE 36: at 16 lanes x 128 heads (models/pangu_moe.py at the
    published widths) one grid step of the paged latent kernel holds 16.25
    MiB, over Mosaic's default scoped-VMEM limit, and the v5e's compiler
    refuses it; the limit the kernel asks for is worked out from the
    shapes, and at 32 heads (models/xing4.py) it asks for none."""
    from apex_example_tpu.ops import attention
    assert attention._paged_vmem_limit(512, 640, 512, 512, 2) is None
    assert attention._paged_vmem_limit(2048, 640, 512, 512, 2) \
        == 2 * int(16.25 * 2 ** 20)
    S, C, H, W, BS, MB = 64, 16, 128, LATENT_WIDTH, 16, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    compiled = jax.jit(
        lambda q, arena, table, fill, n_new: attention._paged_latent_pallas(
            q, arena, table, fill, n_new, scale=0.07, kr=512,
            interpret=False)).lower(
        sds((S, C, H, W), jnp.bfloat16), sds((S * MB, BS, W), jnp.bfloat16),
        sds((S, MB), i32), sds((S,), i32), sds((S,), i32)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


WINDOW_SLOTS, WINDOW_MAX_LEN, WINDOW = 16, 4096, 2048


@pytest.fixture(scope="module", params=["kernel", "xla"])
def window_tick(request, one_chip):
    """The tick of models/trinity.py lowered from shapes: one window layer
    and one full layer at the published head shape (32 query heads over 4
    key/value heads of 128, window 2048, blocks of 16: a ring of 130 blocks
    a slot beside 256 a slot in the full arena), everything else small.
    Steered as ``latent_tick`` is."""
    from apex_example_tpu.models.trinity import (FULL, WINDOW as W,
                                                 TrinityForCausalLM)
    from apex_example_tpu.ops import paged_cache
    slots, nb = WINDOW_SLOTS, WINDOW_SLOTS * WINDOW_MAX_LEN // BS
    dec = TrinityForCausalLM(
        vocab_size=512, hidden_size=512, num_layers=2, num_dense_layers=1,
        intermediate_size=512, moe_intermediate_size=256, num_experts=8,
        layer_types=(W, FULL)).clone(
            decode=True, slot_decode=True, fused_attention=False,
            kv_num_blocks=nb, kv_block_size=BS)
    shapes = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                            jnp.zeros((slots, WINDOW_MAX_LEN), jnp.int32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def tree(t):
        return jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), t)

    ring = paged_cache.ring_blocks(WINDOW, BS)
    layout = engine_lib.TickArgs(BS, WINDOW_MAX_LEN // BS, False, ring)
    args = (tree(shapes["params"]), tree(shapes["cache"]),
            sds((slots, layout.width), jnp.int32), sds((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(shapes["cache"])
    assert sorted({l.shape for l in leaves}) == [
        (slots * ring, BS, 512), (nb, BS, 512)] and ring == 130
    with compiled_as_the_chip_does(request.param):
        lowered = engine_lib._slot_step(dec, layout).lower(*args)
    return (request.param, lowered.compile(),
            sum(l.size * l.dtype.itemsize for l in leaves),
            {l.size for l in leaves})


def test_tpu_tick_walks_both_arenas_in_a_kernel_and_in_place(window_tick):
    """ISSUE 40: at the published head shape the tick compiles for the
    chip with the paged GQA kernel once a layer (a table of 256 columns
    and a ring of 130 in scalar memory), holds no gathered ``[S, L, 4,
    128]`` view and no ``[S, 4, 8, C, L]`` score tensor of either table,
    no copy of either arena's size, and aliases every cache byte; the XLA
    form of the same op holds the views and no such call."""
    form, compiled, cache_bytes, sizes = window_tick
    text = compiled.as_text()
    copies = [(dtype, dims) for dtype, dims
              in arena_sized_copies(text, min(sizes))
              if math.prod(int(d) for d in dims.split(",")) in sizes]
    # (the XLA form's gathered views are as large as the arenas here, at
    # dense capacity, and are relaid for the scores: attention's work)
    assert copies == [] or form == "xla"
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "paged_gqa_attention" in line]
    views = [f"bf16[{WINDOW_SLOTS},{L},4,128]"
             for L in (WINDOW_MAX_LEN, 130 * BS)]
    scores = [f"f32[{WINDOW_SLOTS},4,8,{BS},{L}]"
              for L in (WINDOW_MAX_LEN, 130 * BS)]
    if form == "kernel":
        assert len(calls) == 2                       # one a layer
        assert not any(t in text for t in views + scores)
    else:
        assert not calls and all(t in text for t in scores)
