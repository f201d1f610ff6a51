"""DataFeeder: reader minibatches -> executor feed dicts.

Parity with /root/reference/python/paddle/v2/data_feeder.py and the SWIG
DataProviderConverter (/root/reference/paddle/py_paddle/
dataprovider_converter.py): a reader yields rows (tuples ordered like
``feed_order``); the feeder stacks each column into a dense device-ready
array of the declared dtype/shape.

Variable-length (LoD) columns — rows whose entries are sequences of
differing length — are padded to the batch max and returned together with a
``<name>@len`` int32 length vector, the dense+mask TPU replacement for the
reference's sequenceStartPositions (SURVEY.md §5.7).

A dense column whose entries already ARE arrays (every entry an
``ndarray`` of one shape and dtype) is not handed to numpy's generic
sequence conversion: its rows are copied, laid end to end, into one
destination array of the declared dtype: the caller's where it lends one
(``feed(data, out=...)``), a fresh one otherwise. What the column IS picks
the path; there is no switch.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from .core.program import Variable


def _is_ragged(col) -> bool:
    try:
        first = np.asarray(col[0])
    except Exception:
        return True
    if first.ndim == 0:
        return False
    lengths = set()
    for item in col:
        arr = np.asarray(item)
        lengths.add(arr.shape[0] if arr.ndim else 0)
        if len(lengths) > 1:
            return True
    return False


def _dense_rows(col) -> bool:
    """Every entry an ndarray of one shape (a dimension or more) and one
    (non-object) dtype: the column can be copied row by row with no
    conversion in between."""
    first = col[0] if len(col) else None
    if (type(first) is not np.ndarray or first.ndim == 0
            or first.dtype.hasobject):
        return False
    shape, dtype = first.shape, first.dtype
    return all(type(r) is np.ndarray and r.shape == shape
               and r.dtype == dtype for r in col)


def _stack_rows(col, dtype, shape, held) -> np.ndarray:
    """Rows (ndarrays of one shape) -> ``[len(col), *shape]`` of ``dtype``,
    into ``held`` where it fits and a fresh array where it does not.
    Equal, bit for bit, to ``np.asarray(col, dtype)`` and the reshape to
    the declared ``shape`` that follows it in ``feed``: a cast happens in
    the same copy."""
    n, row_shape = len(col), col[0].shape
    if not (shape and row_shape != shape
            and col[0].size == int(np.prod(shape))):
        shape = row_shape
    if (held is None or held.shape != (n,) + shape
            or held.dtype != dtype or not held.flags.c_contiguous):
        held = np.empty((n,) + shape, dtype)
    # rows laid end to end ARE the (C-contiguous) destination: one C call,
    # a third of np.stack's cost on short rows; "unsafe" is what
    # np.asarray(col, dtype=) casts by. One thread: into a buffer that
    # exists the cell's 154 MB take 15 ms of a 100 ms device step
    # (PERF.md section 6, PR 36)
    np.concatenate(col, out=held.reshape((n * row_shape[0],) + row_shape[1:]),
                   casting="unsafe")
    return held


class DataFeeder:
    """``pad_to_multiple`` rounds every ragged column's padded length up
    to the next multiple (serving-engine-style bucket padding): the
    executor compiles one XLA computation per feed-shape signature, so
    padding to the exact batch max means every distinct max length is a
    fresh compile — bucketed padding caps the signature set. Pair with
    ``reader.bucket_by_length(..., pad_to_multiple=m)`` so batches also
    GROUP by the same buckets (occupancy).

    ``feed(data)`` returns arrays of its own, which no later call
    writes to. ``feed(data, out=bufs)`` writes a dense column of arrays
    into ``bufs[name]``, a dict of destination arrays that the CALLER
    owns, where that array has the batch's shape and the declared dtype,
    and leaves a fresh one there where it has not (so a set learns its
    shapes on first use): ``feeds[name] is bufs[name]`` says that the
    column was copied row by row, and whether it was the array that the
    caller lent. The caller decides when a set may be written again
    (``trainer._FeedRing``); the feeder never keeps one. Python lists and
    scalars, ragged / LoD columns and sparse rows are converted as they
    always were, into fresh arrays, either way."""

    def __init__(self, feed_list: Sequence[Variable], place=None,
                 pad_to_multiple: int = None):
        self.feed_vars = list(feed_list)
        self.place = place
        self.pad_to_multiple = (int(pad_to_multiple)
                                if pad_to_multiple else None)

    def feed(self, data: Sequence[Sequence],
             out: Optional[Dict[str, np.ndarray]] = None
             ) -> Dict[str, np.ndarray]:
        """Convert a minibatch (list of rows) into {name: array} feeds."""
        feeds: Dict[str, np.ndarray] = {}
        for i, var in enumerate(self.feed_vars):
            col = [row[i] for row in data]
            dtype = var.dtype
            sval = getattr(var, "sparse_values", None)
            if sval is not None:
                # sparse_float_vector rows: [(index, value), ...] — split
                # into the padded id feed and its companion value feed
                # (reference dataprovider_converter.py SparseFloatScanner).
                ids_col = [[p[0] for p in row[i]] for row in data]
                val_col = [[p[1] for p in row[i]] for row in data]
                feeds.update(self._pad_sequences(var, ids_col))
                vals = self._pad_sequences(sval, val_col)
                feeds[sval.name] = vals[sval.name]
                continue
            shape = tuple(d for d in (var.shape or ()) if d != -1)
            if var.lod_level == 0 and _dense_rows(col):
                held = out.get(var.name) if out is not None else None
                arr = _stack_rows(col, np.dtype(dtype), shape, held)
                if out is not None:
                    out[var.name] = arr
                feeds[var.name] = arr
            elif var.lod_level > 0 or _is_ragged(col):
                feeds.update(self._pad_sequences(var, col))
            else:
                arr = np.asarray(col, dtype=dtype)
                if shape and arr.shape[1:] != shape and arr.size == len(col) * int(np.prod(shape)):
                    arr = arr.reshape((len(col),) + shape)
                feeds[var.name] = arr
        return feeds

    def _pad_sequences(self, var, col) -> Dict[str, np.ndarray]:
        seqs = [np.asarray(item, dtype=var.dtype) for item in col]
        lengths = np.asarray([s.shape[0] for s in seqs], dtype=np.int32)
        max_len = int(lengths.max()) if len(lengths) else 0
        m = self.pad_to_multiple
        if m and m > 1:
            max_len = -(-max_len // m) * m
        tail = seqs[0].shape[1:] if seqs and seqs[0].ndim > 1 else ()
        padded = np.zeros((len(seqs), max_len) + tail, dtype=var.dtype)
        for i, s in enumerate(seqs):
            padded[i, : s.shape[0]] = s
        return {var.name: padded, f"{var.name}@len": lengths}
