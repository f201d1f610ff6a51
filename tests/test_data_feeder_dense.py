"""DataFeeder's dense fast path: a column whose entries are ndarrays of one
shape is copied, rows laid end to end, into ONE destination array (the
caller's ``out`` dict's where it lends one), bit for bit what
``np.asarray(col, dtype)`` + the declared-shape reshape gave; everything
else (Python lists and scalars, ragged / LoD, sparse rows) takes the old
path and gives the old result."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.data_feeder import DataFeeder

IMG = (224, 224, 3)


def _var(name, shape, dtype="float32", lod_level=0):
    with pt.program_guard(pt.Program(), pt.Program()):
        return layers.data(name, shape=list(shape), dtype=dtype,
                           lod_level=lod_level)


def _old_dense(col, dtype, shape):
    """The conversion ``feed`` did before the fast path (the reference)."""
    arr = np.asarray(col, dtype=dtype)
    if shape and arr.shape[1:] != shape and arr.size == len(col) * int(
            np.prod(shape)):
        arr = arr.reshape((len(col),) + shape)
    return arr


def _same_bits(a, b):
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


# rows x (row dtype, declared dtype, row shape, declared shape)
_DENSE_CASES = {
    "float32_rows": (4, "float32", "float32", IMG, IMG),
    "float64_rows_cast": (3, "float64", "float32", IMG, IMG),
    "int32_rows_to_int64": (64, "int32", "int64", (7,), (7,)),
    "flat_rows_reshaped": (3, "float32", "float32", (150528,), IMG),
    "float64_rows_to_int64": (5, "float64", "int64", (2, 3), (6,)),
    "empty_rows": (4, "float32", "float32", (0,), (0,)),
}


@pytest.mark.parametrize("case", sorted(_DENSE_CASES))
@pytest.mark.parametrize("lend", [False, True], ids=["fresh", "lent"])
def test_dense_fast_path_equals_asarray_bit_for_bit(case, lend):
    n, row_dt, dt, row_shape, shape = _DENSE_CASES[case]
    rng = np.random.RandomState(3)
    col = [(rng.standard_normal(row_shape) * 1000).astype(row_dt)
           for _ in range(n)]
    feeder = DataFeeder([_var("x", shape, dt)])
    bufs = {} if lend else None
    got = feeder.feed([(r,) for r in col], out=bufs)["x"]
    assert _same_bits(got, _old_dense(col, dt, shape))
    if lend:
        assert list(bufs) == ["x"] and bufs["x"] is got


@pytest.mark.parametrize("rows", [6, 7], ids=["under_4MiB", "over_4MiB"])
@pytest.mark.parametrize("lend", [False, True], ids=["fresh", "lent"])
def test_a_column_of_megabytes_is_one_copy_like_a_small_one(rows, lend):
    # no size threshold: 6 x 602,112 B is just under 4 MiB, 7 rows just
    # over, and both are written by the same single copy
    rng = np.random.RandomState(5)
    col = [rng.random_sample(IMG).astype("float32") for _ in range(rows)]
    feeder = DataFeeder([_var("x", IMG)])
    bufs = {"x": np.zeros((rows,) + IMG, "float32")} if lend else None
    held = bufs["x"] if lend else None
    got = feeder.feed([(r,) for r in col], out=bufs)["x"]
    assert _same_bits(got, np.asarray(col, dtype="float32"))
    assert (got is held) is lend


def test_lent_buffers_are_reused_and_replaced_when_the_batch_changes():
    rng = np.random.RandomState(7)
    feeder = DataFeeder([_var("x", (5,)), _var("y", (1,), "int64")])
    bufs = {}

    def rows(n):
        return [(rng.random_sample(5).astype("float32"),
                 rng.randint(0, 9, size=(1,))) for _ in range(n)]

    first = feeder.feed(rows(8), out=bufs)
    assert sorted(bufs) == ["x", "y"] and bufs["y"] is first["y"]
    held = dict(bufs)
    batch = rows(8)
    again = feeder.feed(batch, out=bufs)
    assert again["x"] is held["x"] is first["x"]      # written in place
    assert again["y"] is held["y"] and bufs == held
    assert _same_bits(again["x"], np.asarray([r[0] for r in batch]))
    # a last, smaller batch does not fit: a fresh array takes its place
    short = feeder.feed(rows(3), out=bufs)
    assert short["x"].shape == (3, 5)
    assert short["x"] is not held["x"] and bufs["x"] is short["x"]
    # nor does a strided array of the right shape: its reshape is a copy
    strided = bufs["x"] = np.zeros((5, 3), "float32").T
    batch = rows(3)
    got = feeder.feed(batch, out=bufs)["x"]
    assert got is not strided and got.flags.c_contiguous
    assert _same_bits(got, np.asarray([r[0] for r in batch]))


def test_feed_without_buffers_returns_arrays_of_its_own():
    rng = np.random.RandomState(11)
    col = [rng.random_sample(IMG).astype("float32") for _ in range(8)]
    feeder = DataFeeder([_var("x", IMG)])
    a = feeder.feed([(r,) for r in col])["x"]
    kept = a.copy()
    b = feeder.feed([(r[::-1],) for r in col])["x"]
    assert not np.shares_memory(a, b)
    assert all(not np.shares_memory(a, r) for r in col)
    assert _same_bits(a, kept)                  # no later call wrote to it


_OLD_PATH_CASES = {
    "python_lists": ([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], (3,), "float32", 0),
    "python_scalars": ([1, 2, 3], (1,), "int64", 0),
    "zero_d_arrays": ([np.asarray(4), np.asarray(5)], (1,), "int64", 0),
    "mixed_list_and_array": ([np.ones(3, "float32"), [2.0, 2.0, 2.0]],
                             (3,), "float32", 0),
    "mixed_row_dtypes": ([np.ones(3, "float32"), np.ones(3, "float64")],
                         (3,), "float32", 0),
}


@pytest.mark.parametrize("case", sorted(_OLD_PATH_CASES))
def test_lists_and_scalars_take_the_old_path(case):
    col, shape, dt, lod = _OLD_PATH_CASES[case]
    feeder = DataFeeder([_var("x", shape, dt, lod)])
    bufs = {}
    got = feeder.feed([(r,) for r in col], out=bufs)["x"]
    assert _same_bits(got, _old_dense(col, dt, shape))
    assert not bufs


@pytest.mark.parametrize("lod_level", [0, 1], ids=["ragged", "lod"])
def test_ragged_and_lod_columns_are_padded_as_before(lod_level):
    lengths = (2, 5, 3) if lod_level == 0 else (4, 4, 4)
    col = [np.arange(t, dtype="int64") + 1 for t in lengths]
    feeder = DataFeeder([_var("w", (1,), "int64", lod_level)])
    bufs = {}
    got = feeder.feed([(r,) for r in col], out=bufs)
    want = np.zeros((3, max(lengths)), "int64")
    for i, r in enumerate(col):
        want[i, :len(r)] = r
    assert _same_bits(got["w"], want)
    assert got["w@len"].tolist() == list(lengths)
    assert not bufs


def test_sparse_rows_take_the_old_path():
    ids = _var("ids", (1,), "int64", 1)
    ids.sparse_values = _var("ids@val", (1,), "float32", 1)
    feeder = DataFeeder([ids])
    bufs = {}
    got = feeder.feed([([(3, 0.5), (7, 1.5)],), ([(1, 2.0)],)], out=bufs)
    assert got["ids"].tolist() == [[3, 7], [1, 0]]
    assert got["ids@val"].tolist() == [[0.5, 1.5], [2.0, 0.0]]
    assert got["ids@len"].tolist() == [2, 1]
    assert not bufs
