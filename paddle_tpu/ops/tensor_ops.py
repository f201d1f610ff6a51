"""Tensor creation / manipulation / elementwise / reduction ops.

TPU-native kernels for the reference op families in
/root/reference/paddle/operators (fill_constant_op.cc, gaussian_random_op.cc,
uniform_random_op.cc, elementwise_*_op.cc, reduce_op.cc, concat_op.cc,
split_op.cc, reshape_op.cc, transpose_op.cc, cast_op.cc, sum_op.cc,
scale_op.cc, clip_op.cc, top_k_op.cc, lookup_table_op.cc, accuracy_op.cc,
fill_constant_batch_size_like_op.cc, increment_op.cc, assign ops).
Each is a pure JAX function; gradients come from jax.vjp in the generic
backward pass unless a custom grad is registered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from ..core.selected_rows import SelectedRows
from ..core.types import to_dtype
from .common import broadcast_to_x, maybe, out, single


# --- creation ---------------------------------------------------------------
@register_op("fill_constant")
def fill_constant(attrs, ins):
    dtype = to_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs.get("shape", ()))
    return out(Out=jnp.full(shape, attrs.get("value", 0.0), dtype=dtype))


def _batch_size_like_shape(attrs, ref):
    """Declared shape with the output batch dim copied from ``ref``'s
    (the *_batch_size_like op family contract)."""
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = ref.shape[
        attrs.get("input_dim_idx", 0)]
    return tuple(shape)


@register_op("fill_constant_batch_size_like")
def fill_constant_batch_size_like(attrs, ins):
    ref = single(ins, "Input")
    dtype = to_dtype(attrs.get("dtype", "float32"))
    return out(Out=jnp.full(_batch_size_like_shape(attrs, ref),
                            attrs.get("value", 0.0), dtype=dtype))


@register_op("gaussian_random", needs_rng=True)
def gaussian_random(attrs, ins, rng):
    dtype = to_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs["shape"])
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    return out(Out=mean + std * jax.random.normal(rng, shape, dtype=dtype))


@register_op("gaussian_random_batch_size_like", needs_rng=True)
def gaussian_random_batch_size_like(attrs, ins, rng):
    """Gaussian noise whose batch dim copies Input's
    (gaussian_random_batch_size_like_op.cc) — the reparameterization-trick
    noise source: an rng LEAF, so grads flow only through mu/sigma."""
    ref = single(ins, "Input")
    dtype = to_dtype(attrs.get("dtype", "float32"))
    noise = jax.random.normal(rng, _batch_size_like_shape(attrs, ref),
                              dtype=dtype)
    return out(Out=attrs.get("mean", 0.0) + attrs.get("std", 1.0) * noise)


@register_op("uniform_random", needs_rng=True)
def uniform_random(attrs, ins, rng):
    dtype = to_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs["shape"])
    lo = attrs.get("min", -1.0)
    hi = attrs.get("max", 1.0)
    return out(Out=jax.random.uniform(rng, shape, dtype=dtype, minval=lo, maxval=hi))


@register_op("truncated_gaussian_random", needs_rng=True)
def truncated_gaussian_random(attrs, ins, rng):
    dtype = to_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs["shape"])
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    x = jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype=dtype)
    return out(Out=mean + std * x)


@register_op("assign")
def assign(attrs, ins):
    return out(Out=single(ins, "X"))


@register_op("assign_value")
def assign_value(attrs, ins):
    dtype = to_dtype(attrs.get("dtype", "float32"))
    vals = np.asarray(attrs["values"], dtype=dtype).reshape(tuple(attrs["shape"]))
    return out(Out=jnp.asarray(vals))


@register_op("cast")
def cast(attrs, ins):
    dtype = to_dtype(attrs.get("out_dtype", attrs.get("dtype", "float32")))
    return out(Out=single(ins, "X").astype(dtype))


@register_op("increment")
def increment(attrs, ins):
    x = single(ins, "X")
    return out(Out=x + jnp.asarray(attrs.get("step", 1.0), dtype=x.dtype))


# --- shape manipulation -----------------------------------------------------
@register_op("reshape")
def reshape(attrs, ins):
    x = single(ins, "X")
    shape = list(attrs["shape"])
    # reference semantics (reshape_op.cc): 0 means copy the input dim.
    shape = [x.shape[i] if d == 0 else d for i, d in enumerate(shape)]
    return out(Out=x.reshape(tuple(shape)))


@register_op("transpose")
def transpose(attrs, ins):
    return out(Out=jnp.transpose(single(ins, "X"), axes=tuple(attrs["axis"])))


@register_op("concat")
def concat(attrs, ins):
    return out(Out=jnp.concatenate(ins["X"], axis=attrs.get("axis", 0)))


@register_op("split")
def split(attrs, ins):
    x = single(ins, "X")
    axis = attrs.get("axis", 0)
    if attrs.get("sections"):
        idx = np.cumsum(attrs["sections"])[:-1]
        parts = jnp.split(x, idx, axis=axis)
    else:
        parts = jnp.split(x, attrs["num"], axis=axis)
    return {"Out": list(parts)}


@register_op("unpack_plane")
def unpack_plane(attrs, ins):
    """Split a packed int32 plane X [rows, W] into its columns, left to
    right: ``widths[i]`` columns become Out[i] [rows, widths[i]], or [rows]
    where the width is 0 (one value a row). A column whose ``dtypes[i]``
    is not int32 carries that type's BITS (the host wrote them with
    ``.view(np.int32)``) and is bitcast back: exact, nothing is rescaled.
    What lets a caller with many small feeds hand the executor one buffer
    (``serving.generation.FeedPlane``)."""
    x = single(ins, "X")
    cols, at = [], 0
    for width, dtype in zip(attrs["widths"], attrs["dtypes"]):
        col = x[:, at:at + width] if width else x[:, at]
        at += max(width, 1)
        if dtype != "int32":
            col = jax.lax.bitcast_convert_type(col, to_dtype(dtype))
        cols.append(col)
    if at != x.shape[1]:
        raise ValueError(f"unpack_plane: the columns cover {at} of the "
                         f"plane's {x.shape[1]}")
    return {"Out": cols}


@register_op("slice")
def slice_op(attrs, ins):
    x = single(ins, "X")
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        idx[ax] = slice(st, en)
    return out(Out=x[tuple(idx)])


@register_op("squeeze")
def squeeze(attrs, ins):
    x = single(ins, "X")
    axes = attrs.get("axes") or [i for i, d in enumerate(x.shape) if d == 1]
    return out(Out=jnp.squeeze(x, axis=tuple(axes)))


@register_op("unsqueeze")
def unsqueeze(attrs, ins):
    return out(Out=jnp.expand_dims(single(ins, "X"), axis=tuple(attrs["axes"])))


@register_op("expand")
def expand(attrs, ins):
    x = single(ins, "X")
    times = attrs["expand_times"]
    return out(Out=jnp.tile(x, tuple(times)))


@register_op("stack")
def stack(attrs, ins):
    return out(Y=jnp.stack(ins["X"], axis=attrs.get("axis", 0)))


@register_op("pad")
def pad(attrs, ins):
    x = single(ins, "X")
    p = attrs["paddings"]  # flat [before0, after0, before1, after1, ...]
    widths = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return out(Out=jnp.pad(x, widths, constant_values=attrs.get("pad_value", 0.0)))


@register_op("crop")
def crop(attrs, ins):
    x = single(ins, "X")
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    # -1 in the target shape = keep the input's full extent on that axis
    # (the dynamic-batch dim in particular)
    idx = tuple(slice(o, None if s == -1 else o + s)
                for o, s in zip(offsets, shape))
    return out(Out=x[idx])


# --- elementwise binary (broadcast semantics per elementwise_op.h) ----------
def _elementwise(op):
    def fn(attrs, ins):
        from ..core.selected_rows import densify

        # a SelectedRows operand (sparse grad flowing into a dense
        # elementwise consumer, e.g. the gradient-accumulation
        # ``acc += grad``) takes its dense view — the row-granular
        # fast path belongs to the sparse_* optimizer ops only
        x = densify(single(ins, "X"))
        y = broadcast_to_x(x, densify(single(ins, "Y")),
                           attrs.get("axis", -1))
        return out(Out=op(x, y))

    return fn


register_op("elementwise_add", _elementwise(jnp.add))
register_op("elementwise_sub", _elementwise(jnp.subtract))
register_op("elementwise_mul", _elementwise(jnp.multiply))
register_op("elementwise_div", _elementwise(jnp.divide))
register_op("elementwise_max", _elementwise(jnp.maximum))
register_op("elementwise_min", _elementwise(jnp.minimum))
register_op("elementwise_pow", _elementwise(jnp.power))


@register_op("sum")
def sum_op(attrs, ins):
    xs = ins["X"]
    # SelectedRows-aware accumulation (grad fan-out of a sparse embedding):
    # sparse+sparse stays sparse (row concat); any dense operand densifies.
    sparse = [x for x in xs if isinstance(x, SelectedRows)]
    dense = [x for x in xs if not isinstance(x, SelectedRows)]
    acc = None
    if sparse:
        acc = sparse[0]
        for x in sparse[1:]:
            acc = acc + x
        if dense:
            acc = acc.to_dense()
    for x in dense:
        acc = x if acc is None else acc + x
    return out(Out=acc)


@register_op("scale")
def scale(attrs, ins):
    x = single(ins, "X")
    if isinstance(x, SelectedRows):
        if attrs.get("bias", 0.0):
            raise ValueError("scale with bias is not defined on SelectedRows")
        return out(Out=x.scale(jnp.asarray(attrs.get("scale", 1.0),
                                           dtype=x.dtype)))
    s = jnp.asarray(attrs.get("scale", 1.0), dtype=x.dtype)
    b = jnp.asarray(attrs.get("bias", 0.0), dtype=x.dtype)
    if attrs.get("bias_after_scale", True):
        return out(Out=x * s + b)
    return out(Out=(x + b) * s)


@register_op("clip")
def clip(attrs, ins):
    x = single(ins, "X")
    if isinstance(x, SelectedRows):
        # merge duplicate rows FIRST: the bound applies to the effective
        # (dense-equivalent) per-row gradient, not each occurrence
        m = x.merged()
        return out(Out=SelectedRows(
            m.rows, jnp.clip(m.values, attrs["min"], attrs["max"]), m.height))
    return out(Out=jnp.clip(x, attrs["min"], attrs["max"]))


def _sq_l2(g):
    """Squared L2 norm of a gradient; SelectedRows are deduplicated first so
    repeated rows contribute their summed (dense-equivalent) value."""
    if isinstance(g, SelectedRows):
        return jnp.sum(jnp.square(g.merged().values.astype(jnp.float32)))
    return jnp.sum(jnp.square(g.astype(jnp.float32)))


def _rescale(g, factor):
    if isinstance(g, SelectedRows):
        return SelectedRows(g.rows,
                            g.values * factor.astype(g.values.dtype),
                            g.height)
    return g * factor.astype(g.dtype)


@register_op("clip_by_norm")
def clip_by_norm(attrs, ins):
    """Rescale X so its L2 norm is at most max_norm (clip_by_norm_op)."""
    x = single(ins, "X")
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.maximum(_sq_l2(x), 1e-12))
    factor = jnp.minimum(1.0, max_norm / norm)
    return out(Out=_rescale(x, factor))


@register_op("clip_by_global_norm")
def clip_by_global_norm(attrs, ins):
    """Jointly rescale every gradient in X so the global L2 norm of the set
    is at most max_norm — one fused kernel over all grads (the TPU-native
    form of the legacy trainer's gradient_clipping_threshold, applied
    per-parameter-update in ParameterConfig.proto)."""
    xs = ins["X"]
    max_norm = attrs["max_norm"]
    gnorm = jnp.sqrt(jnp.maximum(
        sum(_sq_l2(g) for g in xs), 1e-12))
    factor = jnp.minimum(1.0, max_norm / gnorm)
    return {"Out": [_rescale(g, factor) for g in xs]}


@register_op("l1_decay_sign")
def l1_decay_sign(attrs, ins):
    x = single(ins, "X")
    return out(Out=jnp.sign(x) * jnp.asarray(attrs["coeff"], dtype=x.dtype))


# --- reductions -------------------------------------------------------------
@register_op("mean")
def mean(attrs, ins):
    return out(Out=jnp.mean(single(ins, "X")))


def _reduce(op):
    def fn(attrs, ins):
        x = single(ins, "X")
        dim = attrs.get("dim")
        keep = attrs.get("keep_dim", False)
        if attrs.get("reduce_all", dim is None):
            return out(Out=op(x, keepdims=keep))
        axes = tuple(dim) if isinstance(dim, (list, tuple)) else (dim,)
        return out(Out=op(x, axis=axes, keepdims=keep))

    return fn


register_op("reduce_sum", _reduce(jnp.sum))
register_op("reduce_mean", _reduce(jnp.mean))
register_op("reduce_max", _reduce(jnp.max))
register_op("reduce_min", _reduce(jnp.min))
register_op("reduce_prod", _reduce(jnp.prod))


@register_op("argmax")
def argmax(attrs, ins):
    x = single(ins, "X")
    return out(Out=jnp.argmax(x, axis=attrs.get("axis", -1)).astype(jnp.int64))


# --- comparison / logical ---------------------------------------------------
def _compare(op):
    def fn(attrs, ins):
        x = single(ins, "X")
        y = broadcast_to_x(x, single(ins, "Y"), attrs.get("axis", -1))
        return out(Out=op(x, y))

    return fn


register_op("equal", _compare(jnp.equal))
register_op("not_equal", _compare(jnp.not_equal))
register_op("less_than", _compare(jnp.less))
register_op("less_equal", _compare(jnp.less_equal))
register_op("greater_than", _compare(jnp.greater))
register_op("greater_equal", _compare(jnp.greater_equal))
register_op("logical_and", _compare(jnp.logical_and))
register_op("logical_or", _compare(jnp.logical_or))
register_op("logical_xor", _compare(jnp.logical_xor))


@register_op("logical_not")
def logical_not(attrs, ins):
    return out(Out=jnp.logical_not(single(ins, "X")))


# --- indexing ---------------------------------------------------------------
def _lookup_table_grad(attrs, ins, outs, ogs):
    """Embedding gradient, sparse or dense.

    With ``is_sparse`` the gradient is a SelectedRows — (ids, row grads)
    with NO [V, D] buffer — exactly the reference's design
    (lookup_table_op.cc:59 emits SelectedRows; selected_rows.h), consumed
    row-granularly by the optimizer ops. Without it, the dense equivalent
    via scatter-add (fine for small vocabularies).
    """
    w = single(ins, "W")
    ids = single(ins, "Ids").reshape(-1)
    og = ogs["Out"][0].reshape(ids.shape[0], w.shape[-1])
    pad = attrs.get("padding_idx")
    if pad is not None and pad >= 0:
        # the forward zeroes the padding row's output, so its grad is 0:
        # point padding lookups at the out-of-range sentinel so scatters
        # drop them (both paths)
        ids = jnp.where(ids == pad, w.shape[0], ids)
    if attrs.get("is_sparse", False):
        return {"W": [SelectedRows(ids, og.astype(w.dtype), w.shape[0])],
                "Ids": [None]}
    dw = jnp.zeros_like(w).at[ids].add(og.astype(w.dtype), mode="drop")
    return {"W": [dw], "Ids": [None]}


def _vocab_sharded_gather(attrs, w, flat):
    """The shard_map gather when the executor mesh carries the plan's
    vocab axis and the table divides (the vocab_sharded_plan path —
    each device owns a [V/n, D] row block and one psum exchanges the
    looked-up rows); None selects the serial gather — the SAME program
    runs on one device (and under abstract shape inference, where no
    mesh is published)."""
    if not attrs.get("is_sparse", False):
        return None
    from ..parallel.context import current_mesh
    from ..parallel.sharded_embedding import rows_per_shard, vp_lookup

    mesh = current_mesh()
    axis = attrs.get("vocab_axis", "mp")
    if mesh is None or not rows_per_shard(w.shape[0], mesh, axis):
        return None
    return vp_lookup(w, flat, mesh, vocab_axis=axis,
                     data_axis=attrs.get("data_axis", "dp"))


@register_op("lookup_table", grad_fn=_lookup_table_grad)
def lookup_table(attrs, ins):
    w = single(ins, "W")
    ids = single(ins, "Ids")
    squeeze_last = ids.ndim > 1 and ids.shape[-1] == 1
    flat = ids.reshape(-1)
    rows = _vocab_sharded_gather(attrs, w, flat)
    if rows is None:
        rows = w[flat]
    if attrs.get("padding_idx") is not None and attrs.get("padding_idx", -1) >= 0:
        pad_idx = attrs["padding_idx"]
        emb = jnp.where((flat == pad_idx)[:, None], 0.0, rows)
    else:
        emb = rows
    shape = (ids.shape[:-1] if squeeze_last else ids.shape) + (w.shape[-1],)
    return out(Out=emb.reshape(shape))


@register_op("gather")
def gather(attrs, ins):
    x = single(ins, "X")
    idx = single(ins, "Index").reshape(-1)
    return out(Out=jnp.take(x, idx, axis=0))


@register_op("top_k")
def top_k(attrs, ins):
    x = single(ins, "X")
    k = attrs["k"]
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register_op("one_hot")
def one_hot(attrs, ins):
    x = single(ins, "X")
    depth = attrs["depth"]
    flat = x.reshape(x.shape[:-1] if (x.ndim > 1 and x.shape[-1] == 1) else x.shape)
    return out(Out=jax.nn.one_hot(flat, depth, dtype=jnp.float32))


# --- metrics ----------------------------------------------------------------
@register_op("accuracy")
def accuracy(attrs, ins):
    """Inputs: Out (top-k values), Indices (top-k indices), Label [N,1]."""
    idx = single(ins, "Indices")
    label = single(ins, "Label").reshape(-1, 1)
    correct = jnp.sum(jnp.any(idx == label, axis=1))
    total = idx.shape[0]
    acc = correct.astype(jnp.float32) / total
    return {
        "Accuracy": [acc],
        "Correct": [correct.astype(jnp.int32)],
        "Total": [jnp.asarray(total, dtype=jnp.int32)],
    }


# --- IO markers (handled by Executor.run feed/fetch contract) ---------------
@register_op("feed")
def feed(attrs, ins):
    return out(Out=single(ins, "X")) if "X" in ins else None


@register_op("fetch")
def fetch(attrs, ins):
    return out(Out=single(ins, "X"))
