"""The masked-LM head and loss over the labelled rows only (PR 46).

``workloads.mlm_loss.over_rows`` beside ``BertForMaskedLM.encode`` /
``head``, joined in ``engine.make_train_step``: the same loss and the same
gradients as the head of every row followed by ``mlm_loss``, for any count
of labelled rows; no array of rows x vocabulary in the lowered step; the
counter ``head_rows``; and everything that shares the changed code but not
the mechanism (ResNet-50's step, the six serve ticks, the model's
``__call__``) lowering to the text it had before.
"""

import ast
import hashlib
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_example_tpu import amp, workloads
from apex_example_tpu import models as models_pkg
from apex_example_tpu.engine import (create_train_state,
                                     make_sharded_train_step,
                                     make_train_step)
from apex_example_tpu.models.bert import BertForMaskedLM, bert_tiny

R = 16                      # block rows in these tests
B, S = 8, 16                # 128 rows: 8 blocks, 4 a microbatch of 2


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(workloads, "MLM_BLOCK_ROWS", R)


def all_rows_loss(logits, target):
    """``mlm_loss`` as a loss that declares no form over rows: the step
    forms logits of every row, as every step did before PR 46."""
    return workloads.mlm_loss(logits, target)


def _batch(count, seed=0):
    """(ids, (labels, weights)) with ``count`` labelled positions, scattered
    (so that the two microbatches of ``grad_accum`` 2 hold unequal shares)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    ids = jax.random.randint(k[0], (B, S), 0, 256)
    labels = jax.random.randint(k[1], (B, S), 0, 256)
    where = jax.random.permutation(k[2], B * S)[:count]
    weights = jnp.zeros(B * S).at[where].set(1.0).reshape(B, S)
    return ids, (labels, weights)


@pytest.fixture(scope="module")
def rig():
    """The model, its state, and one jitted step per (loss, grad_accum):
    plain SGD at rate 1, so parameters before - after is the gradient."""
    model = bert_tiny(num_layers=1)
    policy, scaler = amp.initialize("O0")
    state = create_train_state(jax.random.PRNGKey(0), model, optax.sgd(1.0),
                               _batch(0)[0], policy, scaler)
    steps = {}

    def run(loss_fn, grad_accum, batch):
        key = (loss_fn, grad_accum)
        if key not in steps:
            steps[key] = jax.jit(make_train_step(
                model, optax.sgd(1.0), policy, loss_fn=loss_fn,
                compute_accuracy=False, grad_accum=grad_accum))
        new, metrics = steps[key](state, batch)
        grads = jax.tree_util.tree_map(lambda a, b: a - b, state.params,
                                       new.params)
        return metrics, grads
    return run


def _blocks(weights):
    return math.ceil(int((np.asarray(weights) > 0).sum()) / R)


@pytest.mark.parametrize("grad_accum", [1, 2])
@pytest.mark.parametrize("count", [0, 1, R - 1, R, R + 1, B * S])
def test_loss_and_every_gradient_equal_the_all_rows_steps(rig, count,
                                                          grad_accum):
    batch = _batch(count)
    m_rows, g_rows = rig(workloads.mlm_loss, grad_accum, batch)
    m_all, g_all = rig(all_rows_loss, grad_accum, batch)
    assert "head_rows" not in m_all
    np.testing.assert_allclose(m_rows["loss"], m_all["loss"], rtol=2e-6,
                               atol=0)
    np.testing.assert_allclose(m_rows["grad_norm"], m_all["grad_norm"],
                               rtol=2e-6, atol=0)
    flat = jax.tree_util.tree_leaves_with_path
    # a leaf against its own largest entry; the key biases' gradient is 0
    # but for rounding, so also a millionth of the largest of any leaf
    floor = 1e-6 * max(float(jnp.max(jnp.abs(b))) for _, b in flat(g_all))
    for (path, a), (_, b) in zip(flat(g_rows), flat(g_all)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-5 * float(jnp.max(jnp.abs(b))) + floor, \
            jax.tree_util.keystr(path)
    if count == 0:
        assert float(m_rows["loss"]) == 0.0
        assert all(not np.any(np.asarray(g)) for _, g in flat(g_rows))
    # the counter: blocks run x block rows, summed over the microbatches
    weights = np.asarray(batch[1][1])
    want = sum(_blocks(w) for w in np.split(weights, grad_accum)) * R
    assert float(m_rows["head_rows"]) == want


def test_the_all_rows_form_of_the_loss_is_what_it_was():
    ids, (labels, weights) = _batch(40)
    logits = jax.random.normal(jax.random.PRNGKey(5), (B, S, 256))
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(
        workloads.mlm_loss(logits, (labels, weights)),
        (ce * weights).sum() / weights.sum(), rtol=1e-6)


def test_under_ddp_shards_of_unequal_counts_give_the_all_rows_update(
        devices8):
    """shard_map over 4 devices, 2 rows of the batch a shard: 21, 1, 9 and
    0 labelled positions, so the shards' loops run 2, 1, 1 and 0 blocks and
    the parameters' gradients are summed once, after them."""
    from apex_example_tpu.optim import FusedAdam
    from apex_example_tpu.parallel.mesh import make_data_mesh
    mesh = make_data_mesh(devices=devices8[:4])
    model = bert_tiny(num_layers=1)
    policy, scaler = amp.initialize("O0")
    ids, (labels, _) = _batch(0)
    weights = jnp.zeros((B, S)).at[0, :].set(1.0).at[1, :5].set(1.0) \
        .at[2, 3].set(1.0).at[5, :9].set(1.0)
    out = []
    for loss_fn in (workloads.mlm_loss, all_rows_loss):
        opt = FusedAdam(lr=1e-3)
        state = create_train_state(jax.random.PRNGKey(0), model, opt, ids,
                                   policy, scaler)
        step = make_sharded_train_step(mesh, model, opt, policy,
                                       loss_fn=loss_fn, donate=False,
                                       compute_accuracy=False)
        out.append(step(state, (ids, (labels, weights))))
    (s_rows, m_rows), (s_all, m_all) = out
    np.testing.assert_allclose(m_rows["loss"], m_all["loss"], rtol=2e-6)
    mu = lambda s: jax.tree_util.tree_leaves(s.opt_state.mu)
    floor = 1e-6 * max(float(jnp.max(jnp.abs(b))) for b in mu(s_all))
    for a, b in zip(mu(s_rows), mu(s_all)):
        assert float(jnp.max(jnp.abs(a - b))) \
            <= 1e-5 * float(jnp.max(jnp.abs(b))) + floor
    # a replica's mean, as ``loss`` is
    assert float(m_rows["head_rows"]) == (2 + 1 + 1 + 0) * R / 4


@pytest.mark.parametrize("kw", [{"tensor_parallel": True},
                                {"moe_experts": 4},
                                {"context_parallel": True}])
def test_models_that_cannot_offer_the_head_apart_say_so(kw):
    assert bert_tiny().head_apart and not bert_tiny(**kw).head_apart


# ------------------------------------------ the step at the cell's shape

def _cell_step(loss_fn):
    """bert_base.lamb_s128's step lowered from shapes (its widths, batch and
    vocabulary; one layer, since the head is what is looked at)."""
    from apex_example_tpu.optim import FusedLAMB
    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    model = BertForMaskedLM(num_layers=1, dtype=md.compute,
                            param_dtype=md.param, ln_dtype=md.ln_io,
                            softmax_dtype=md.softmax)
    opt = FusedLAMB(lr=1e-3)
    ids = jax.ShapeDtypeStruct((256, 128), jnp.int32)
    f32 = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), model, opt,
                                   jnp.zeros((1, 128), jnp.int32), policy,
                                   scaler))
    step = make_train_step(model, opt, policy, loss_fn=loss_fn,
                           compute_accuracy=False)
    return jax.jit(step).lower(state, (ids, (ids, f32))).as_text()


def _largest_arrays(text, vocab):
    """Elements of the largest array of the lowered text, and of the
    largest with a dimension of ``vocab``."""
    shapes = [[int(d) for d in dims.split("x") if d] for dims in
              re.findall(r"tensor<((?:\d+x)+)[a-z]", text)]
    return (max(math.prod(s) for s in shapes),
            max(math.prod(s) for s in shapes if vocab in s))


@pytest.mark.parametrize("form", ["rows", "all_rows"])
def test_the_cells_step_holds_no_array_of_rows_by_vocabulary(
        form, monkeypatch):
    monkeypatch.setattr(workloads, "MLM_BLOCK_ROWS", 1024)
    rows, vocab = 256 * 128, 30522
    if form == "rows":
        text = _cell_step(workloads.mlm_loss)
        # the layers' (rows, 3072) is the largest array; of the vocabulary's,
        # one block's logits or the embedding table, and nothing larger
        assert _largest_arrays(text, vocab) == (rows * 3072, 1024 * vocab)
        assert "stablehlo.while" in text
    else:
        # the same reading sees the array in the form that holds it
        assert _largest_arrays(_cell_step(all_rows_loss), vocab) \
            == (rows * vocab, rows * vocab)


# --------------------------- what shares the code and not the mechanism

def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


# sha256 of ``BertForMaskedLM``'s parameter tree (paths, shapes, dtypes; 16
# digits) and of ``model.apply``'s lowered text at (4, 16) ids, read on the
# parent of PR 46 (135f574), where ``__call__`` was one compact method.
CALL_BEFORE_PR46 = {
    "float32": ("d8963632d4039f3f", "7ae2211cb89ed9689c53484056bbde8ea58359b451"
                "2a3065fe0af2261e20d670"),
    "bfloat16": ("d8963632d4039f3f", "440d638bfb8c9787add572b34d2a0fd088ed614ca"
                 "e304e41ac81ce5a5af2c7e7"),
    "moe": ("aada13dcea4af10f", "c4d3867d8a03f318170f444d5f5abc82ca4f9b7488f9"
            "a2d730499b6b4f9c8e54"),
}


@pytest.mark.parametrize("name", sorted(CALL_BEFORE_PR46))
def test_the_models_call_and_parameter_tree_are_the_parents(name):
    kw = {"float32": {},
          "bfloat16": dict(dtype=jnp.bfloat16, ln_dtype=jnp.bfloat16),
          "moe": dict(moe_experts=4, moe_axis_name=None)}[name]
    model = bert_tiny(**kw)
    ids = jnp.zeros((4, 16), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    tree = str(jax.tree_util.tree_map(lambda t: (t.shape, str(t.dtype)),
                                      shapes["params"]))
    text = jax.jit(model.apply).lower(shapes, ids).as_text()
    assert (_sha(tree)[:16], _sha(text)) == CALL_BEFORE_PR46[name]


def test_call_is_the_head_of_every_row_of_the_encoders_output():
    model = bert_tiny()
    ids = _batch(0)[0]
    v = model.init(jax.random.PRNGKey(0), ids)
    hidden = model.apply(v, ids, method="encode")
    assert hidden.shape == (B, S, 64)
    whole = model.apply(v, ids)
    np.testing.assert_array_equal(
        whole, model.apply(v, hidden, method="head"))
    # rows of any leading shape
    picked = hidden.reshape(B * S, -1)[jnp.array([3, 77, 5])]
    np.testing.assert_allclose(
        model.apply(v, picked, method="head"),
        whole.reshape(B * S, -1)[jnp.array([3, 77, 5])], rtol=1e-5,
        atol=1e-6)


# sha256 of the lowered text of resnet50.sgd_b256's window step (the
# benchmark's own ``Cell``, 2 images of 32 x 32) on the parent of PR 46:
# the default loss declares no form over rows, so the step is the parent's.
RESNET_STEP_BEFORE_PR46 = ("39591563005e553cfc182f3ade5f8a2320deb6e0ff2b90d4"
                           "a3794a020dfa7667")


def test_resnet50s_step_is_the_parents_text():
    from benchmarks import harness
    from benchmarks.runners import train as runner
    cell = harness.find_cell(harness.benchmark_spec(), "resnet50.sgd_b256")
    cfg, trf = harness.cell_files(cell)
    trf = dict(trf, batch_size=2, image_size=32)
    sut = runner.Cell(cfg, trf, jax.devices()[:1])
    key = jax.random.PRNGKey(0)
    text = sut.step.lower(jax.eval_shape(sut.init, key), key,
                          jnp.zeros((), jnp.int32)).as_text()
    assert _sha(text) == RESNET_STEP_BEFORE_PR46


# sha256 of each served model's tick (``_slot_step`` or, where the model
# drafts for itself, ``_draft_step``; 4 slots x 64, blocks of 8, under the
# tests' interpreter) on the parent of PR 46.  "gpt1" imports
# ``models/bert.py`` for ``BertLayer``; the others run none of it.
TICK_BEFORE_PR46 = {
    "gpt1": "d30b7b180b9438e05e4eb0e2f39eb2e238c162146865677faf5a30a0e77e0c64",
    "xing4": "b816f09c208380fbc92edd265b4e75ae24b2d91c178523a48a950be55dbceece",
    "granite": "0670df7e1cda5c43df44cc7c5f5cc8bf92accefd8c93eaa9f25bce2b88a615bf",
    "pangu": "d812400a5d0edf5b04a0315a98023b7b5bcf17fcd3ec0349867d09417d2abfc8",
    "trinity": "7872dc93d8cd9046802bfe8f26265e52e1f7ff34b1c7a26e67043013e092cf82",
    "lfm2": "50966f410f0a3251750340dfee9c1c25e6d3effdc3f8ad8c56be2e7707f6f73b",
}


def _served(name):
    from apex_example_tpu.models import (gpt, granite_hybrid, lfm2,
                                         pangu_moe, trinity, xing4)
    return {"gpt1": gpt.gpt_tiny,
            "xing4": lambda: xing4.xing4_tiny(num_layers=2),
            "granite": granite_hybrid.granite_hybrid_tiny,
            "pangu": pangu_moe.pangu_moe_tiny,
            "trinity": trinity.trinity_tiny,
            "lfm2": lfm2.lfm2_tiny}[name]()


@pytest.mark.parametrize("name", sorted(TICK_BEFORE_PR46))
def test_the_six_serve_ticks_are_the_parents_text(name):
    from apex_example_tpu.serve import engine as engine_lib
    model = _served(name)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    params = jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), shapes)
    eng = engine_lib.ServeEngine(model, params, num_slots=4, max_len=64,
                                 block_size=8)
    build = engine_lib._draft_step if eng.self_draft \
        else engine_lib._slot_step
    text = build(eng.pool.dec, eng.tick_args).lower(
        params, eng.pool.cache,
        jnp.zeros((4, eng.tick_args.width), jnp.int32),
        jax.random.PRNGKey(0)).as_text()
    assert _sha(text) == TICK_BEFORE_PR46[name]


# What the tick's text does not hold: names.  sha256 (16 digits each) of the
# parameter tree (paths, shapes, dtypes) of ``model.init`` at (1, 4) ids, the
# leaves a checkpoint finds by path, and of the ``cache`` tree of the paged
# clone (``decode=True, slot_decode=True``; 4 slots x 64, 32 blocks of 8),
# the leaves ``serve/slots.BlockPool`` finds by name; read on the parent of
# PR 47 (f0a84cd), where ``models/xing4.py`` held the shared layers.
TREES_BEFORE_PR47 = {
    "xing4": ("ba01ff959f52bcc9", "2819ec28355a03af"),
    "granite": ("f8cd4135ab977b14", "120c7d79d3f5763e"),
    "pangu": ("0fbebc4380dcdb26", "403efb8824e12c68"),
    "trinity": ("169d2b945e80e893", "56d880b035cf7e40"),
    "lfm2": ("e7020ccca5840f4e", "e439eb2fbd89e0e4"),
}


@pytest.mark.parametrize("name", sorted(TREES_BEFORE_PR47))
def test_the_served_models_parameter_and_cache_trees_are_the_parents(name):
    model = _served(name)
    key = jax.random.PRNGKey(0)
    tree = lambda shapes: _sha(str(jax.tree_util.tree_map(
        lambda t: (t.shape, str(t.dtype)), shapes)))[:16]
    params = jax.eval_shape(model.init, key,
                            jnp.zeros((1, 4), jnp.int32))["params"]
    paged = model.clone(decode=True, slot_decode=True, kv_num_blocks=32,
                        kv_block_size=8)
    cache = jax.eval_shape(paged.init, key,
                           jnp.zeros((4, 64), jnp.int32))["cache"]
    assert (tree(params), tree(cache)) == TREES_BEFORE_PR47[name]


# The arrows: serve/ -> models/<one model>.py -> models/layers.py -> ops/.
SERVED_MODEL_FILES = ("xing4", "granite_hybrid", "trinity", "lfm2",
                      "pangu_moe")


def _models_imported(name):
    """The modules of ``apex_example_tpu.models`` that ``models/<name>.py``
    imports, read from its source (nothing runs)."""
    tree = ast.parse((pathlib.Path(models_pkg.__file__).parent
                      / f"{name}.py").read_text())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            dotted = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = ("apex_example_tpu.models" + ("." + node.module
                                                 if node.module else "")
                    if node.level else node.module)
            dotted = [base] + [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for d in dotted:
            parts = d.split(".")
            if parts[:2] == ["apex_example_tpu", "models"] and len(parts) > 2:
                found.add(parts[2])
    return found


@pytest.mark.parametrize("name", SERVED_MODEL_FILES + ("layers",))
def test_no_served_model_imports_another_and_the_layers_import_none(name):
    imported = _models_imported(name)
    if name == "layers":
        assert not imported
    else:
        assert "layers" in imported
        assert not imported & set(SERVED_MODEL_FILES) - {name}
