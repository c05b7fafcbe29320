"""GSPMD-first tensor-parallel layers.

Reference (apex/transformer/tensor_parallel/layers.py, SURVEY.md §3.2):
``ColumnParallelLinear`` (weight split along output dim; Y_i = X·A_i),
``RowParallelLinear`` (weight split along input dim; Y = Σ_i X_i·A_i, the sum
being an all-reduce), ``VocabParallelEmbedding`` (vocab rows sharded; masked
local lookup + all-reduce), and the ``sequence_parallel_enabled`` flag that
turns the row-parallel trailing all-reduce into a reduce-scatter (and the
column-parallel leading identity into an all-gather of the sequence dim).

TPU-native design — *annotate, don't orchestrate*: parameters carry full
logical shapes boxed with flax partitioning metadata
(:func:`flax.linen.with_partitioning`), activations get
``with_sharding_constraint`` at exactly the Megatron f/g points, and GSPMD
materializes the all-gather / reduce-scatter / all-reduce on ICI.  This keeps
every layer a plain function of full-shape arrays — jit-compatible on one
device (constraints are no-ops without a mesh) and parallel under a
``('pipe','data','model')`` mesh with zero code change.  The explicit
shard_map formulation of the same semantics lives in :mod:`.mappings`.

Weight init matches Megatron's "initialize the full weight, then shard"
semantics for free, because the logical weight IS full-shape.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_example_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from apex_example_tpu.transformer import parallel_state

Initializer = Callable[..., Any]

__all__ = ["ColumnParallelLinear", "RowParallelLinear",
           "VocabParallelEmbedding", "batch_axis", "constrain",
           "param_partition_specs"]


def _manual_axes() -> frozenset:
    """Mesh axes the current trace is *manual* over (bound by an enclosing
    shard_map).  Empty outside shard_map.  Constraints must not name these:
    inside the body the arrays are per-shard slices and the axis is already
    consumed by the shard_map's in_specs."""
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Sharding-constrain ``x`` against the current parallel_state mesh.

    No-op when no mesh is registered or every mesh axis is trivial — so the
    same model code runs single-device and under TP without branches.  An
    all-None spec is a real constraint (force replication), not a skip:
    it is how gather_output / the row-parallel reduction point are pinned.
    Axes named in the spec but absent from (or trivial in) the mesh are
    dropped, so layer code can name ``model``/``data`` unconditionally.

    Inside a *partially-manual* shard_map (the TP×PP composition: manual
    over pipe/data, auto over model) the manual axes are likewise dropped
    and the constraint binds to the trace's abstract mesh — the same layer
    code then shards only the still-automatic axes.
    """
    mesh = parallel_state.get_mesh()
    if mesh is None or all(s <= 1 for s in mesh.shape.values()):
        return x
    manual = _manual_axes()

    def live(a):
        return a if a is None or (mesh.shape.get(a, 1) > 1
                                  and a not in manual) else None

    spec = tuple(
        tuple(filter(None, (live(a) for a in e))) or None
        if isinstance(e, tuple) else live(e)
        for e in spec)
    target = jax.sharding.get_abstract_mesh() if manual else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(target,
                                                             P(*spec)))


def batch_axis() -> Optional[str]:
    """The data axis name if the current mesh has a nontrivial one.

    Activations in a mixed DP+TP mesh are batch-sharded over ``data``;
    constraints must say so or they would force an all-gather of the batch.
    None when the data axis is manual (shard_map already split the batch).
    """
    mesh = parallel_state.get_mesh()
    if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1 \
            and DATA_AXIS not in _manual_axes():
        return DATA_AXIS
    return None


def param_partition_specs(variables) -> Any:
    """PartitionSpec pytree for boxed variables (feed to jit shardings /
    jax.device_put).  Thin alias of flax's get_partition_spec, re-exported so
    callers don't reach into flax.linen.spmd."""
    return nn.get_partition_spec(variables)


class ColumnParallelLinear(nn.Module):
    """Linear with the output dim sharded over the ``model`` axis.

    ``gather_output=True`` (reference default) re-replicates the output;
    ``False`` leaves it feature-sharded for a following RowParallelLinear.
    ``sequence_parallel`` marks the input as sequence-sharded (dim 1 of a
    [batch, seq, hidden] activation); the matmul against the sharded kernel
    makes GSPMD emit the sequence all-gather the reference does explicitly.
    """

    features: int
    use_bias: bool = True
    gather_output: bool = True
    sequence_parallel: bool = False
    axis_name: str = MODEL_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()
    bias_init: Initializer = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (None, self.axis_name)),
            (x.shape[-1], self.features), self.param_dtype)
        bias = None
        if self.use_bias:
            bias = self.param(
                "bias", nn.with_partitioning(self.bias_init,
                                             (self.axis_name,)),
                (self.features,), self.param_dtype)

        b = batch_axis()
        if self.sequence_parallel and x.ndim >= 3:
            x = constrain(x, b, self.axis_name, None)
        dtype = self.dtype or x.dtype
        x = x.astype(dtype)
        y = x @ kernel.astype(dtype)
        if bias is not None:
            y = y + bias.astype(dtype)
        if self.gather_output:
            y = constrain(y, b, *([None] * (y.ndim - 1)))
        else:
            y = constrain(y, b, *([None] * (y.ndim - 2)), self.axis_name)
        return y


class RowParallelLinear(nn.Module):
    """Linear with the input dim sharded over the ``model`` axis.

    The partial products Σ over input shards become an all-reduce —
    or, with ``sequence_parallel``, a reduce-scatter onto sequence shards
    (the Megatron-SP optimization) — inserted by GSPMD at the output
    constraint.  Bias is added after the reduction (it must not be summed
    tp-times), exactly like the reference's ``skip_bias_add`` ordering.
    """

    features: int
    use_bias: bool = True
    input_is_parallel: bool = True
    sequence_parallel: bool = False
    axis_name: str = MODEL_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()
    bias_init: Initializer = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (self.axis_name, None)),
            (x.shape[-1], self.features), self.param_dtype)
        bias = None
        if self.use_bias:
            # Replicated: applied after the cross-shard reduction.
            bias = self.param("bias", self.bias_init, (self.features,),
                              self.param_dtype)

        b = batch_axis()
        if self.input_is_parallel:
            x = constrain(x, b, *([None] * (x.ndim - 2)), self.axis_name)
        dtype = self.dtype or x.dtype
        y = x.astype(dtype) @ kernel.astype(dtype)
        if self.sequence_parallel and y.ndim >= 3:
            y = constrain(y, b, self.axis_name, None)
        else:
            y = constrain(y, b, *([None] * (y.ndim - 1)))
        if bias is not None:
            y = y + bias.astype(dtype)
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding with vocab rows sharded over the ``model`` axis.

    The reference masks ids outside the local [first, last) range, looks up
    locally, zeroes the masked rows and all-reduces.  Under GSPMD the same
    dance is the compiler's lowering of a gather from a row-sharded table;
    the output constraint decides whether it lands replicated or
    sequence-sharded (sequence_parallel).
    """

    num_embeddings: int
    features: int
    sequence_parallel: bool = False
    axis_name: str = MODEL_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    # nn.Embed's own default: with the same param name and rng path, a TP
    # model then STARTS from the dense model's weights (same seed => same
    # step-1 loss), not just from an interchangeable checkpoint layout.
    embedding_init: Initializer = nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0)

    def setup(self):
        # setup() (not @nn.compact) so ``attend`` can reuse the table — the
        # tied-decoder pattern nn.Embed supports; param name/shape match
        # nn.Embed, so checkpoints interchange with the non-TP model.
        self.embedding = self.param(
            "embedding",
            nn.with_partitioning(self.embedding_init, (self.axis_name, None)),
            (self.num_embeddings, self.features), self.param_dtype)

    def __call__(self, ids):
        y = jnp.take(self.embedding, ids, axis=0)
        if self.dtype is not None:
            y = y.astype(self.dtype)
        b = batch_axis()
        if self.sequence_parallel and y.ndim >= 3:
            y = constrain(y, b, self.axis_name, None)
        else:
            y = constrain(y, b, *([None] * (y.ndim - 1)))
        return y

    def attend(self, x):
        """Tied decoder: ``x @ table.T`` with the VOCAB dim of the logits
        sharded over the model axis (the table is row-sharded, so each shard
        produces its vocab slice locally — Megatron's parallel LM head).  A
        vocab-sharded-aware loss (XLA cross entropy under GSPMD, or
        :func:`..cross_entropy.vocab_parallel_cross_entropy` under shard_map)
        consumes the logits without re-gathering the (…, V) tensor."""
        table = self.embedding
        y = x @ table.astype(x.dtype).T
        b = batch_axis()
        return constrain(y, b, *([None] * (y.ndim - 2)), self.axis_name)
