"""Vocab-sharded embedding islands: shard_map gather + row-exchange.

The manual-SPMD half of the Wide&Deep CTR plan
(:func:`paddle_tpu.parallel.vocab_sharded_plan`): the [V, D] table lives
row-sharded over the mesh's vocab axis — each device holds its
contiguous [V/n, D] block, the in-graph form of the reference's sparse
parameter server owning embedding rows by parameter block
(/root/reference/paddle/pserver/ParameterServer2.h:94-100,
/root/reference/paddle/math/SparseRowMatrix.h). Three islands:

- :func:`vp_lookup` — the forward gather. Every shard gathers the rows
  it owns (foreign ids contribute zeros) and one psum over the vocab
  axis exchanges the rows — the "pserver -> trainer" pull as ICI
  all-reduce traffic. Batch stays sharded on the data axis when it
  divides, so dp parallelism survives the island.
- :func:`vp_scatter_add` — the row-granular optimizer write: each shard
  applies only the rows in its block (out-of-range ids — including the
  SelectedRows height sentinel — drop). The "trainer -> pserver" push.
  Two exchange strategies: the legacy ``gather`` path broadcasts the
  whole (rows, values) stream to every shard; the ``a2a`` path (the
  default for deduplicated ``add`` scatters) splits the stream across
  the vocab axis and ships each row ONLY to its owner shard through a
  capacity-bounded ``all_to_all`` — exchange bytes drop ~n_shards-fold.
  A skewed stream that overflows the per-destination capacity falls
  back in-graph (uniform ``lax.cond`` predicate via psum) to the full
  gather, so the result is bitwise identical on every input.
- :func:`vp_rows_pull` — gather a row-subset of sharded per-row state
  (adagrad moments) back to every device for the update formula.

All three are exact: each global row is owned by exactly one shard, so
the psum adds one real value to zeros — bitwise identical to the
unsharded gather/scatter (pinned by the sparse-vs-dense parity tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P



def rows_per_shard(vocab: int, mesh, vocab_axis: str) -> int:
    """Rows per device block, or 0 when the table cannot shard (axis
    absent / size 1 / vocab not divisible) — callers fall back to the
    serial path."""
    if mesh is None or vocab_axis not in mesh.axis_names:
        return 0
    n = mesh.shape[vocab_axis]
    if n <= 1 or vocab % n:
        return 0
    return vocab // n


def _data_spec(n_rows: int, mesh, data_axis):
    """Shard the id/value stream on the data axis when it divides;
    replicated otherwise (shard_map blocks must tile exactly)."""
    if (data_axis and data_axis in mesh.axis_names
            and n_rows % mesh.shape[data_axis] == 0):
        return data_axis
    return None


def vp_lookup(w, flat_ids, mesh, vocab_axis: str = "mp",
              data_axis: str = "dp"):
    """Gather ``w[flat_ids]`` with ``w`` row-sharded over ``vocab_axis``.

    w: [V, D] (annotated P(vocab_axis, None) by the plan); flat_ids: [n]
    int. Returns [n, D] sharded over ``data_axis`` when n divides.
    """
    vl = rows_per_shard(w.shape[0], mesh, vocab_axis)
    if not vl:
        return w[flat_ids]
    da = _data_spec(flat_ids.shape[0], mesh, data_axis)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(vocab_axis, None), P(da)),
                       out_specs=P(da, None))
    def run(wl, ids):
        base = jax.lax.axis_index(vocab_axis) * vl
        local = ids - base
        owned = (local >= 0) & (local < vl)
        rows = jnp.where(owned[:, None],
                         wl[jnp.clip(local, 0, vl - 1)],
                         jnp.zeros((), wl.dtype))
        # the row exchange: each id is owned by exactly ONE shard, so
        # the all-reduce adds its row to zeros — exact, and it IS the
        # ICI traffic replacing the pserver round-trip
        return jax.lax.psum(rows, vocab_axis)

    return run(w, flat_ids)


def vp_scatter_add(p, rows, values, mesh, vocab_axis: str = "mp",
                   mode: str = "add", exchange: str = "auto",
                   capacity_factor: float = 2.0):
    """``p.at[rows].add(values)`` (or ``.set`` with ``mode='set'`` —
    rows must then be deduplicated) with ``p`` row-sharded over
    ``vocab_axis``. rows may carry the SelectedRows height sentinel
    (== p.shape[0]) — it lands outside every shard's block and drops.

    exchange:
      'gather' — rows/values broadcast to all shards (in_specs P());
                 every shard scans the full stream and keeps its rows.
      'a2a'    — the stream splits over ``vocab_axis`` and each row
                 ships only to its owner shard via a capacity-bounded
                 ``all_to_all`` (:func:`_scatter_add_a2a`); requires
                 unique rows (``SelectedRows.merged`` output) so the
                 single add per table row is order-free — bitwise equal
                 to 'gather'.
      'auto'   — 'a2a' when legal (add mode, stream divides the vocab
                 axis), else 'gather'.
    """
    vl = rows_per_shard(p.shape[0], mesh, vocab_axis)
    if not vl:
        upd = p.at[rows]
        return (upd.set(values, mode="drop") if mode == "set"
                else upd.add(values, mode="drop"))
    nmp = mesh.shape[vocab_axis]
    n = rows.shape[0]
    if exchange == "a2a" or (exchange == "auto" and mode == "add"
                             and nmp > 1 and n % nmp == 0):
        return _scatter_add_a2a(p, rows, values, mesh, vocab_axis, vl,
                                capacity_factor)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(vocab_axis, None), P(), P()),
                       out_specs=P(vocab_axis, None))
    def run(pl, rows_g, vals_g):
        base = jax.lax.axis_index(vocab_axis) * vl
        local = rows_g - base
        owned = (local >= 0) & (local < vl)
        # disowned rows point past the block; mode='drop' ignores them
        idx = jnp.where(owned, local, vl)
        upd = pl.at[idx]
        if mode == "set":
            # deduped rows: each local slot is set at most once; foreign
            # rows all alias index vl and drop
            return upd.set(jnp.where(owned[:, None], vals_g,
                                     jnp.zeros((), vals_g.dtype)),
                           mode="drop")
        return upd.add(
            jnp.where(owned[:, None], vals_g,
                      jnp.zeros((), vals_g.dtype)), mode="drop")

    return run(p, rows, values)


def a2a_capacity(n: int, nmp: int, capacity_factor: float = 2.0) -> int:
    """Per-(source, destination) bucket depth of the a2a exchange: the
    stream slice on each shard is n/nmp rows; a uniform owner spread
    puts n/nmp² in each bucket, head-roomed by ``capacity_factor``."""
    import math

    nl = max(1, n // nmp)
    return max(1, min(nl, int(math.ceil(nl / nmp * capacity_factor))))


def exchange_bytes(n: int, nmp: int, width: int,
                   capacity_factor: float = 2.0) -> dict:
    """Modeled interconnect bytes per dp group for one scatter of an
    n-row stream of ``width``-byte rows (id + value lanes) — what the
    PERF.md witness reports. gather replicates the stream to every
    vocab shard; a2a ships each (capacity-padded) row once."""
    cap = a2a_capacity(n, nmp, capacity_factor)
    return {"gather": n * width * nmp,
            "a2a": nmp * cap * width * nmp,  # nmp shards x [nmp, cap]
            "capacity": cap}


def _scatter_add_a2a(p, rows, values, mesh, vocab_axis, vl,
                     capacity_factor):
    """Owner-targeted row exchange: the (rows, values) stream splits
    over ``vocab_axis`` (each shard holds n/nmp rows of it, replicated
    across dp); every row is packed into a per-owner capacity bucket and
    ONE ``all_to_all`` lands it on the shard whose [V/n, D] block owns
    it. Rows must be unique (merged SelectedRows) so each table row
    receives at most one add — arrival order cannot change the sum, and
    the result is bitwise equal to the gather path. A stream skewed
    enough to overflow a bucket flips a psum'd (hence mesh-uniform)
    predicate and the whole scatter falls back to the gather exchange
    in-graph: capacity bounds bytes, never correctness."""
    nmp = mesh.shape[vocab_axis]
    cap = a2a_capacity(rows.shape[0], nmp, capacity_factor)
    height = p.shape[0]

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(vocab_axis, None), P(vocab_axis),
                                 P(vocab_axis, None)),
                       out_specs=P(vocab_axis, None))
    def run(pl, ids, vals):
        base = jax.lax.axis_index(vocab_axis) * vl
        valid = ids < height  # sentinel padding never ships
        owner = jnp.clip(ids // vl, 0, nmp - 1)
        onehot = ((owner[:, None] == jnp.arange(nmp)[None, :])
                  & valid[:, None])
        # position of each row inside its owner's bucket (cumsum trick)
        pos = jnp.sum(jnp.where(
            onehot, jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1, 0),
            axis=1)
        fits = valid & (pos < cap)
        spilled = jax.lax.psum(
            jnp.any(valid & ~fits).astype(jnp.int32), vocab_axis)

        def apply(pl, ids_g, vals_g):
            local = ids_g - base
            owned = (local >= 0) & (local < vl)
            return pl.at[jnp.where(owned, local, vl)].add(
                jnp.where(owned[:, None], vals_g,
                          jnp.zeros((), vals_g.dtype)), mode="drop")

        def a2a_path(_):
            # slot [owner, pos] in the send buffer; non-fitting rows
            # alias the drop column ``cap``
            o = jnp.where(fits, owner, 0)
            s = jnp.where(fits, pos, cap)
            idb = jnp.full((nmp, cap + 1), height, ids.dtype)
            idb = idb.at[o, s].set(jnp.where(fits, ids, height))
            vb = jnp.zeros((nmp, cap + 1) + vals.shape[1:], vals.dtype)
            vb = vb.at[o, s].set(
                jnp.where(fits[:, None], vals,
                          jnp.zeros((), vals.dtype)))
            rid = jax.lax.all_to_all(idb[:, :cap], vocab_axis, 0, 0,
                                     tiled=True)
            rva = jax.lax.all_to_all(vb[:, :cap], vocab_axis, 0, 0,
                                     tiled=True)
            return apply(pl, rid.reshape(-1),
                         rva.reshape((-1,) + vals.shape[1:]))

        def gather_path(_):
            ids_g = jax.lax.all_gather(ids, vocab_axis, tiled=True)
            vals_g = jax.lax.all_gather(vals, vocab_axis, tiled=True)
            return apply(pl, ids_g, vals_g)

        return jax.lax.cond(spilled > 0, gather_path, a2a_path, None)

    return run(p, rows, values)


def vp_rows_pull(state, rows, mesh, vocab_axis: str = "mp"):
    """``state[rows]`` with ``state`` row-sharded over ``vocab_axis``:
    every device gets the full [n, D] row subset (psum-exchange, exactly
    like :func:`vp_lookup` but replicated — optimizer formulas need the
    same values on every shard). Sentinel rows read as zero."""
    vl = rows_per_shard(state.shape[0], mesh, vocab_axis)
    if not vl:
        # mode='fill' semantics by hand: sentinel rows read zero
        n = state.shape[0]
        safe = jnp.clip(rows, 0, n - 1)
        return jnp.where((rows < n)[:, None] if state.ndim > 1
                         else (rows < n), state[safe],
                         jnp.zeros((), state.dtype))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(vocab_axis, None), P()),
                       out_specs=P())
    def run(sl, rows_g):
        base = jax.lax.axis_index(vocab_axis) * vl
        local = rows_g - base
        owned = (local >= 0) & (local < vl)
        vals = jnp.where(owned[:, None] if sl.ndim > 1 else owned,
                         sl[jnp.clip(local, 0, vl - 1)],
                         jnp.zeros((), sl.dtype))
        return jax.lax.psum(vals, vocab_axis)

    return run(state, rows)
