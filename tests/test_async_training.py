"""Overlapped training pipeline: async dispatch, device-resident feeds,
deferred metric fetch.

Pins the tentpole contract: ``SGD.train(async_depth=N)`` is an event-
semantics-compatible, BITWISE-identical pipelined version of the sync
loop (params + per-iteration cost sequence, RNG/dropout included), plus
the satellite contracts — RunHandle deferred resolution, the reader
fill-thread leak fix, bucketed varlen padding, and the scope key-set
memoization.
"""
import gc
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import event, layers, reader as reader_mod
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.reader import decorator
from paddle_tpu.trainer import SGD


def _fresh_programs():
    """Reset the default programs/scope (the conftest fixture body) so one
    test can build two identical trainers from scratch."""
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.core import scope as scope_mod

    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    scope_mod._global_scope = scope_mod.Scope()
    scope_mod._scope_stack[:] = [scope_mod._global_scope]


def _toy_rows(n=48, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.rand(n, 8).astype("float32")
    ys = rng.randint(0, 3, size=(n, 1)).astype("int64")

    def r():
        for i in range(n):
            yield xs[i], ys[i:i + 1]
    return r


def _build_trainer():
    """Model with a dropout layer so the RNG path is part of the parity
    claim, and an accuracy metric so deferred metric fetch is too."""
    x = layers.data("x", shape=[8])
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    h = layers.dropout(h, dropout_prob=0.3)
    logits = layers.fc(h, size=3)
    cost = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    acc = layers.accuracy(logits, y)
    return SGD(cost=cost,
               optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.5),
               feed_list=[x, y], place=pt.CPUPlace(), scope=pt.Scope(),
               metrics={"acc": acc})


def _run_training(async_depth):
    _fresh_programs()
    trainer = _build_trainer()
    events = []
    trainer.train(reader_mod.batch(_toy_rows(), 8), num_passes=2,
                  event_handler=events.append, async_depth=async_depth)
    # positional: the unique-name counter differs between the two builds
    params = [np.asarray(trainer.scope.get(p.name)).copy()
              for p in pt.default_main_program().all_parameters()]
    return events, params


def test_async_depth4_bitwise_parity_with_sync():
    ev_sync, p_sync = _run_training(async_depth=1)
    ev_async, p_async = _run_training(async_depth=4)

    # final parameters bitwise identical (dropout RNG chain included)
    assert len(p_sync) == len(p_async) > 0
    for a, b in zip(p_sync, p_async):
        np.testing.assert_array_equal(a, b)

    def iters(evs):
        return [(e.pass_id, e.batch_id, e.cost, e.metrics)
                for e in evs if isinstance(e, event.EndIteration)]

    # same per-iteration cost AND metric sequence, same order
    assert iters(ev_sync) == iters(ev_async)
    # pass summaries match too
    sync_pass = [e.metrics for e in ev_sync if isinstance(e, event.EndPass)]
    async_pass = [e.metrics for e in ev_async if isinstance(e, event.EndPass)]
    assert sync_pass == async_pass


def test_async_event_ordering_and_drain():
    ev, _ = _run_training(async_depth=3)
    for pass_id in range(2):
        idx_end = [i for i, e in enumerate(ev)
                   if isinstance(e, event.EndIteration)
                   and e.pass_id == pass_id]
        idx_pass = [i for i, e in enumerate(ev)
                    if isinstance(e, event.EndPass) and e.pass_id == pass_id]
        assert len(idx_pass) == 1
        # drain contract: every EndIteration lands before its EndPass
        assert max(idx_end) < idx_pass[0]
        # EndIterations resolve in batch order with batch_size carried
        ends = [e for e in ev if isinstance(e, event.EndIteration)
                and e.pass_id == pass_id]
        assert [e.batch_id for e in ends] == list(range(len(ends)))
        assert all(e.batch_size == 8 for e in ends)
        begins = [e for e in ev if isinstance(e, event.BeginIteration)
                  and e.pass_id == pass_id]
        assert len(begins) == len(ends)


def test_async_emits_dispatch_and_resolve_spans():
    from paddle_tpu import trace

    tracer = trace.get_tracer()
    prev = tracer.level
    trace.enable(level=1)
    tracer.clear()
    try:
        _run_training(async_depth=4)
    finally:
        tracer.configure(level=prev)
    names = [s.name for s in tracer.spans()]
    dispatch = [s for s in tracer.spans() if s.name == "trainer/dispatch"]
    resolve = [s for s in tracer.spans() if s.name == "trainer/resolve"]
    assert dispatch and resolve and "trainer/iteration" not in names
    assert all("queue_depth" in s.attrs for s in dispatch + resolve)
    # the window is bounded: never more than async_depth in flight
    assert max(s.attrs["queue_depth"] for s in dispatch) < 4


# ---------------------------------------------------------------------------
# the feed ring: dense columns stacked into reused host buffers
# ---------------------------------------------------------------------------

_WIDE = 150_000     # 8 rows x 150,000 float32 = 4.8 MB a batch


def _wide_batches(n_batches=7, batch=8, seed=5):
    rng = np.random.RandomState(seed)
    return [[(rng.standard_normal(_WIDE).astype("float32"),
              rng.randint(0, 3, size=(1,)).astype("int64"))
             for _ in range(batch)] for _ in range(n_batches)]


def _train_wide(batches, async_depth):
    _fresh_programs()
    x = layers.data("x", shape=[_WIDE])
    y = layers.data("y", shape=[1], dtype="int64")
    logits = layers.fc(x, size=3)
    cost = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    trainer = SGD(cost=cost,
                  optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.01),
                  feed_list=[x, y], place=pt.CPUPlace(), scope=pt.Scope())
    events = []
    trainer.train(lambda: iter(batches), num_passes=1,
                  event_handler=events.append, async_depth=async_depth)
    return [e.cost for e in events if isinstance(e, event.EndIteration)]


class _AlignedNumpy:
    """numpy, but ``empty`` returns 64-byte-aligned arrays: what the CPU
    client takes zero-copy, so every device array IS its host buffer."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def empty(shape, dtype):
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = np.empty(nbytes + 64, np.uint8)
        off = (-raw.ctypes.data) % 64
        return raw[off:off + nbytes].view(dtype).reshape(shape)


@pytest.mark.parametrize("aligned", [False, True],
                         ids=["copied", "aliased"])
def test_async_feed_ring_recycles_without_changing_a_step(aligned,
                                                          monkeypatch):
    """More distinct batches than the ring holds (2 sets at depth 2), a
    column of megabytes: a buffer written before the
    transfer that reads it completed, or while a device array aliases it
    (a step reads an aliased feed when it RUNS, after the loop has let go
    of the array), changes some step's cost."""
    from paddle_tpu import data_feeder, trace

    if aligned:
        monkeypatch.setattr(data_feeder, "np", _AlignedNumpy())
    batches = _wide_batches()
    assert len(batches) > 2
    sync_costs = _train_wide(batches, async_depth=1)
    tracer = trace.get_tracer()
    prev = tracer.level
    trace.enable(level=1)
    tracer.clear()
    try:
        async_costs = _train_wide(batches, async_depth=2)
    finally:
        tracer.configure(level=prev)
    assert len(sync_costs) == len(batches)
    assert async_costs == sync_costs            # bitwise, step by step
    stacks = [s for s in tracer.spans() if s.name == "trainer/feed_stack"]
    assert len(stacks) == len(batches)
    for s in stacks:
        assert s.attrs["bytes"] == 8 * _WIDE * 4 + 8 * 8
        assert s.attrs["fast_cols"] == s.attrs["cols"] == 2
        assert isinstance(s.attrs["reused"], bool)
    # the first lap of the ring allocates; an aliased set never returns
    assert not stacks[0].attrs["reused"] and not stacks[1].attrs["reused"]
    if aligned:
        assert not any(s.attrs["reused"] for s in stacks)


def _host_array(aligned: bool, n=4096):
    """float32[n] whose data pointer is (not) 64-byte aligned: the CPU
    client takes an aligned host buffer zero-copy."""
    raw = np.zeros(4 * n + 128, np.uint8)
    off = (-raw.ctypes.data) % 64 + (0 if aligned else 16)
    return raw[off:off + 4 * n].view(np.float32)


@pytest.mark.parametrize("aligned", [False, True],
                         ids=["copied", "aliased"])
def test_feed_ring_recycle_rule(aligned):
    import jax

    from paddle_tpu.trainer import _FeedRing

    dev = jax.devices("cpu")[0]
    ring = _FeedRing(1)
    first = ring.take()
    host = first["x"] = _host_array(aligned)
    host[:] = 1.0
    arr = jax.device_put(host, dev)
    arr.block_until_ready()
    assert (arr.unsafe_buffer_pointer() == host.ctypes.data) is aligned
    ring.lend(first, [(host, arr)])
    second = ring.take()
    # a device array that IS the host buffer: the set is forgotten
    assert (second is first) is (not aligned)
    if aligned:
        assert not second
    else:
        host[:] = 2.0       # the transfer completed: the copy is its own
        assert float(arr[0]) == 1.0


@pytest.mark.parametrize("ready", [True, False], ids=["arrived", "in_flight"])
def test_feed_ring_asks_a_transfer_nobody_else_holds_and_never_waits(ready):
    """A device array that was consumed and dropped has not thereby
    arrived: the ring asks the array itself before it lends the set, and
    one still in flight costs the set, not a wait."""
    from paddle_tpu.trainer import _FeedRing

    asked = []

    class Put:
        def is_ready(self):
            asked.append(self)
            return ready

        def unsafe_buffer_pointer(self):
            return 0

    ring = _FeedRing(1)
    first = ring.take()
    host = first["x"] = _host_array(False)
    ring.lend(first, [(host, Put())])           # the ring's is the only ref
    gc.collect()
    again = ring.take()
    assert len(asked) == 1 and (again is first) is ready
    assert ready or not again                   # forgotten: an empty set
    assert ring.take() == {}                    # nothing lent: a new set


def test_feed_ring_holds_three_sets_at_most_whatever_the_depth():
    """``async_depth`` 8 over a dozen batches: the ring's sets, and the
    feeds they keep alive on the device, stay at ``_RING_SETS``."""
    from paddle_tpu import trainer as trainer_mod

    rings = []

    class Ring(trainer_mod._FeedRing):
        def __init__(self, size):
            super().__init__(size)
            rings.append(self)

    real = trainer_mod._FeedRing
    trainer_mod._FeedRing = Ring
    try:
        batches = _wide_batches(n_batches=12, batch=2)
        assert _train_wide(batches, async_depth=8) == _train_wide(
            batches, async_depth=1)
    finally:
        trainer_mod._FeedRing = real
    assert [r._size for r in rings] == [trainer_mod._RING_SETS]
    assert len(rings[0]._lent) == trainer_mod._RING_SETS == 3


# ---------------------------------------------------------------------------
# Executor.run_async / RunHandle
# ---------------------------------------------------------------------------

def _square_program():
    x = layers.data("x", shape=[4])
    w = layers.fc(x, size=4, bias_attr=False)
    out = layers.mean(w)
    return x, out


def test_run_async_matches_run():
    x, out = _square_program()
    scope_a, scope_b = pt.Scope(), pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    feed = {"x": np.arange(8, dtype="float32").reshape(2, 4)}
    exe.run(pt.default_startup_program(), scope=scope_a)
    exe.run(pt.default_startup_program(), scope=scope_b)

    sync = exe.run(pt.default_main_program(), feed=feed, fetch_list=[out],
                   scope=scope_a)
    handle = exe.run_async(pt.default_main_program(), feed=feed,
                           fetch_list=[out], scope=scope_b)
    assert handle.fetch_names == [out.name]
    handle.block()
    assert handle.done()
    res = handle.result()
    np.testing.assert_array_equal(sync[0], res[0])
    # resolution is cached and repeatable
    np.testing.assert_array_equal(res[0], handle.result()[0])
    # non-numpy resolution returns device arrays
    import jax
    assert isinstance(handle.result(return_numpy=False)[0], jax.Array)


def test_run_async_state_writeback_stays_on_device():
    """The scope must hold device arrays (no host materialization) after
    an async dispatch, and chained dispatches must see updated state."""
    import jax

    x = layers.data("x", shape=[8])
    y = layers.data("y", shape=[1], dtype="int64")
    cost = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(x, size=3), y))
    pt.optimizer.SGDOptimizer(learning_rate=0.5).minimize(cost)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program(), scope=scope)
    pname = pt.default_main_program().all_parameters()[0].name
    before = np.asarray(scope.get(pname)).copy()
    feed = {"x": np.random.RandomState(0).rand(8, 8).astype("float32"),
            "y": np.zeros((8, 1), dtype="int64")}
    h1 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[cost], scope=scope)
    assert isinstance(scope.get(pname), jax.Array)
    h2 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[cost], scope=scope)
    c1, c2 = float(h1.result()[0]), float(h2.result()[0])
    assert c2 < c1  # second step trained on step-1's updated params
    assert not np.array_equal(before, np.asarray(scope.get(pname)))


def test_run_async_defers_nan_check_to_resolve():
    x = layers.data("x", shape=[2])
    out = layers.log(x)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace(), check_nan_inf=True)
    feed = {"x": np.array([[-1.0, 1.0]], dtype="float32")}
    handle = exe.run_async(pt.default_main_program(), feed=feed,
                           fetch_list=[out], scope=scope)  # must NOT raise
    try:
        handle.result()
    except FloatingPointError:
        pass
    else:
        raise AssertionError("deferred check_nan_inf did not fire")


def _training_program(extra_feed=None):
    """fc+softmax training block (donated rw state); returns (feeds, cost,
    and an optional extra finite fetch independent of the x path)."""
    x = layers.data("x", shape=[4])
    y = layers.data("y", shape=[1], dtype="int64")
    cost = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(x, size=3), y))
    extra = None
    if extra_feed:
        extra = layers.mean(layers.data(extra_feed, shape=[4]))
    pt.optimizer.SGDOptimizer(learning_rate=0.5).minimize(cost)
    return cost, extra


def test_check_nan_inf_with_overlapped_run_async():
    """check_nan_inf=True + overlapping dispatches: the second dispatch
    DONATES the state the first wrote back (deleted on platforms that
    honor donation — CPU included on this jax), so the first handle's
    deferred check must not touch those arrays when it resolves late."""
    cost, _ = _training_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace(), check_nan_inf=True)
    exe.run(pt.default_startup_program(), scope=scope)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(4, 4).astype("float32"),
            "y": np.zeros((4, 1), dtype="int64")}
    h1 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[cost], scope=scope)
    h2 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[cost], scope=scope)
    # oldest resolves AFTER a newer dispatch — the overlapped steady state
    c1 = float(h1.result()[0])
    c2 = float(h2.result()[0])
    assert np.isfinite(c1) and np.isfinite(c2) and c2 < c1


def test_check_nan_inf_overlapped_still_catches_nan_state():
    """The deferred state scan must still FIRE after its arrays were
    donated away: NaN feeds poison the param update (state) while the
    fetch stays finite, and the late resolve reports the bad state."""
    cost, finite_fetch = _training_program(extra_feed="clean")
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace(), check_nan_inf=True)
    exe.run(pt.default_startup_program(), scope=scope)
    feed = {"x": np.full((4, 4), np.nan, dtype="float32"),
            "y": np.zeros((4, 1), dtype="int64"),
            "clean": np.ones((4, 4), dtype="float32")}
    h1 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[finite_fetch], scope=scope)
    h2 = exe.run_async(pt.default_main_program(), feed=feed,
                       fetch_list=[finite_fetch], scope=scope)
    try:
        h1.result()
    except FloatingPointError as exc:
        assert "NaN" in str(exc)
    else:
        raise AssertionError("NaN in donated state escaped the deferred "
                             "check")
    del h2


def test_train_async_with_check_nan_inf():
    """End to end: SGD.train(async_depth>1) with the NaN check on — every
    overlapped resolve runs the deferred scan against superseded state."""
    _fresh_programs()
    x = layers.data("x", shape=[8])
    y = layers.data("y", shape=[1], dtype="int64")
    cost = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(x, size=3), y))
    trainer = SGD(cost=cost,
                  optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
                  feed_list=[x, y], place=pt.CPUPlace(), scope=pt.Scope(),
                  check_nan_inf=True)
    events = []
    trainer.train(reader_mod.batch(_toy_rows(), 8), num_passes=1,
                  event_handler=events.append, async_depth=3)
    ends = [e for e in events if isinstance(e, event.EndIteration)]
    assert len(ends) == 6 and all(np.isfinite(e.cost) for e in ends)


def test_async_exception_drains_pending_handles():
    """A handler raising mid-pass must not abandon in-flight steps: their
    state writes already landed in the scope, so their EndIterations are
    delivered (drain) before the exception propagates."""
    _fresh_programs()
    trainer = _build_trainer()
    events = []

    class Boom(RuntimeError):
        pass

    def handler(e):
        events.append(e)
        if isinstance(e, event.EndIteration) and e.batch_id == 0:
            raise Boom("handler failure")

    try:
        trainer.train(reader_mod.batch(_toy_rows(), 8), num_passes=1,
                      event_handler=handler, async_depth=3)
    except Boom:
        pass
    else:
        raise AssertionError("handler exception was swallowed")
    ends = [e.batch_id for e in events if isinstance(e, event.EndIteration)]
    begins = [e.batch_id for e in events
              if isinstance(e, event.BeginIteration)]
    # every dispatched step resolved: no BeginIteration without its End
    assert ends == begins == sorted(begins) and len(ends) >= 2


def test_run_async_interpret_mode_resolved_handle():
    x, out = _square_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program(), scope=scope)
    feed = {"x": np.ones((2, 4), dtype="float32")}
    sync = exe.run(pt.default_main_program(), feed=feed, fetch_list=[out],
                   scope=scope)
    handle = exe.run_async(pt.default_main_program(), feed=feed,
                           fetch_list=[out], scope=scope, trace_level=2)
    assert handle.done()
    np.testing.assert_allclose(sync[0], handle.result()[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# Reader fill-thread leak fix
# ---------------------------------------------------------------------------

def _wait_threads_back_to(before, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        extra = [t for t in threading.enumerate()
                 if t not in before and t.is_alive()]
        if not extra:
            return []
        time.sleep(0.02)
    return extra


def test_buffered_early_break_leaves_no_fill_thread():
    def endless():
        i = 0
        while True:
            yield i
            i += 1

    before = set(threading.enumerate())
    it = decorator.buffered(endless, size=2)()
    assert next(it) == 0
    it.close()  # GeneratorExit -> stop flag + queue drain
    assert _wait_threads_back_to(before) == []


def test_device_prefetch_early_break_leaves_no_fill_thread():
    def feeds():
        while True:
            yield {"x": np.ones((2, 2), dtype="float32")}

    before = set(threading.enumerate())

    def consume():
        for i, feed in enumerate(decorator.device_prefetch(feeds, depth=2)()):
            import jax
            assert isinstance(feed["x"], jax.Array)
            if i == 1:
                break  # abandon mid-stream

    consume()
    gc.collect()  # the abandoned generator finalizes -> close path
    assert _wait_threads_back_to(before) == []


def test_background_stage_close_bounded_when_source_blocks():
    """Abandoning a stage whose SOURCE is stalled (pipe/socket that never
    returns) must not hang the consumer's close/GC path: the drain wait
    is bounded and the daemon fill thread is abandoned past it."""
    release = threading.Event()

    def stuck():
        yield 0
        release.wait()  # a read that never completes
        yield 1

    it = decorator.background_stage(stuck, depth=2)()
    assert next(it) == 0
    t0 = time.monotonic()
    it.close()
    assert time.monotonic() - t0 < 2.0
    release.set()  # let the abandoned daemon thread exit


def test_background_stage_propagates_source_error():
    def bad():
        yield 1
        raise RuntimeError("source exploded")

    it = decorator.background_stage(bad, depth=2)()
    assert next(it) == 1
    try:
        next(it)
    except RuntimeError as exc:
        assert "source exploded" in str(exc)
    else:
        raise AssertionError("source error was swallowed")


# ---------------------------------------------------------------------------
# Bucketed varlen padding
# ---------------------------------------------------------------------------

def _varlen_var(name="w"):
    from paddle_tpu.core.program import Variable

    v = layers.data(name, shape=[-1], dtype="int64", lod_level=1)
    assert isinstance(v, Variable)
    return v


def test_feeder_pad_to_multiple_caps_signatures():
    v = _varlen_var()
    feeder = DataFeeder([v], pad_to_multiple=8)
    rng = np.random.RandomState(0)
    shapes = set()
    for max_len in (5, 6, 7, 8):
        batch = [(rng.randint(0, 9, size=(length,)),)
                 for length in range(2, max_len + 1)]
        out = feeder.feed(batch)
        shapes.add(out[v.name].shape[1])
        np.testing.assert_array_equal(
            out[f"{v.name}@len"],
            np.arange(2, max_len + 1, dtype=np.int32))
    # four distinct batch maxes, ONE padded length -> one compile signature
    assert shapes == {8}
    # exact-max padding without the option (the old behavior)
    plain = DataFeeder([_varlen_var("w2")])
    out = plain.feed([(np.arange(5),), (np.arange(3),)])
    assert out["w2"].shape[1] == 5


def test_bucket_by_length_pad_to_multiple_groups_batches():
    rng = np.random.RandomState(0)
    samples = [(list(range(int(n))),) for n in rng.randint(1, 33, size=64)]

    def src():
        return iter(samples)

    batches = list(reader_mod.bucket_by_length(
        src, batch_size=8, buf_size=64, shuffle_buckets=False, seed=0,
        pad_to_multiple=8)())
    feeder = DataFeeder([_varlen_var()], pad_to_multiple=8)
    padded_lens = set()
    for b in batches:
        padded = feeder.feed(b)["w"].shape[1]
        assert padded % 8 == 0
        padded_lens.add(padded)
    # lengths 1..32 with multiple 8: the whole epoch compiles at most the
    # 4 bucket signatures {8, 16, 24, 32} — not one per distinct max
    assert padded_lens <= {8, 16, 24, 32}
    # sorting by the ROUNDED key still groups: most batches are
    # single-bucket (straddles only at bucket boundaries)
    raw = list(reader_mod.bucket_by_length(
        src, batch_size=8, buf_size=64, shuffle_buckets=False, seed=0)())
    raw_feeder = DataFeeder([_varlen_var("w3")])
    raw_lens = {raw_feeder.feed(b)["w3"].shape[1] for b in raw}
    assert len(raw_lens) > len(padded_lens)  # the recompile cliff it fixes


# ---------------------------------------------------------------------------
# Scope key-set memoization
# ---------------------------------------------------------------------------

def test_scope_key_set_memoized_per_version():
    s = pt.Scope()
    s.set("a", 1)
    k1 = s.key_set()
    s.set("a", 2)  # rewrite: key set unchanged -> same cached object
    assert s.key_set() is k1
    s.set("b", 3)  # new name -> invalidated
    k2 = s.key_set()
    assert k2 is not k1 and k2 == frozenset({"a", "b"})
    s.delete("b")
    assert s.key_set() == frozenset({"a"})
    s.delete("missing")  # no-op delete must not invalidate
    k3 = s.key_set()
    assert s.key_set() is k3


def test_scope_key_set_sees_parent_changes():
    parent = pt.Scope()
    parent.set("p", 1)
    child = parent.new_scope()
    child.set("c", 1)
    assert child.key_set() == frozenset({"p", "c"})
    cached = child.key_set()
    parent.set("p2", 1)  # parent key-set change invalidates the child memo
    assert child.key_set() == frozenset({"p", "p2", "c"})
    assert child.key_set() is not cached


def test_executor_cache_key_stable_across_steps():
    """Steady-state training (rewrites only) must reuse the memoized
    key set AND hit the compile cache."""
    x, out = _square_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program(), scope=scope)
    feed = {"x": np.ones((2, 4), dtype="float32")}
    for _ in range(3):
        exe.run(pt.default_main_program(), feed=feed, fetch_list=[out],
                scope=scope)
    stats = exe.cache_stats()
    assert stats["entries"] == 2  # startup + main
    assert stats["misses"] == 2 and stats["hits"] == 2


# ---------------------------------------------------------------------------
# Serving: handle-based non-blocking execute
# ---------------------------------------------------------------------------

def _toy_engine():
    from paddle_tpu.serving import InferenceEngine

    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        x = layers.data("x", shape=[4])
        logits = layers.fc(x, size=2)
    scope = pt.Scope()
    pt.Executor(pt.CPUPlace()).run(startup, scope=scope)
    return InferenceEngine(program=main_prog, feed_names=["x"],
                           fetch_names=[logits.name], scope=scope,
                           batch_buckets=[2, 4], place=pt.CPUPlace(),
                           transpile=False)


def test_engine_run_async_matches_run():
    eng = _toy_engine()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(3, 4).astype("float32")}
    sync = eng.run(feed)
    pending = eng.run_async(feed)
    res = pending.result()
    assert len(res) == 1 and res[0].shape == (3, 2)
    np.testing.assert_array_equal(sync[0], res[0])
    # chunking beyond the largest bucket still works through the handle
    big = {"x": rng.rand(9, 4).astype("float32")}
    np.testing.assert_array_equal(eng.run(big)[0],
                                  eng.run_async(big).result()[0])


def test_engine_async_pipeline_observes_metrics():
    eng = _toy_engine()
    before = eng.metrics.snapshot()["counters"].get("batches_executed", 0)
    pending = eng.run_async({"x": np.ones((2, 4), dtype="float32")})
    pending.result()
    pending.result()  # idempotent
    after = eng.metrics.snapshot()["counters"]["batches_executed"]
    assert after == before + 1


def test_engine_retry_after_chunk_failure_counts_each_chunk_once():
    """If one chunk's resolve fails, a retry must re-resolve ONLY the
    failed chunks — already-resolved ones are memoized, so the batch
    metrics observe each chunk exactly once."""
    eng = _toy_engine()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(6, 4).astype("float32")}  # chunks of 4 + 2
    pending = eng.run_async(feed)
    orig, calls = eng._resolve_padded, []

    def flaky(h, bucket, n, t0):
        calls.append(n)
        if len(calls) == 2:
            raise RuntimeError("transient resolve failure")
        return orig(h, bucket, n, t0)

    eng._resolve_padded = flaky
    try:
        try:
            pending.result()
        except RuntimeError:
            pass
        else:
            raise AssertionError("injected failure did not propagate")
    finally:
        eng._resolve_padded = orig
    mid = eng.metrics.snapshot()["counters"]["batches_executed"]
    res = pending.result()  # retry: resolves only the failed chunk
    after = eng.metrics.snapshot()["counters"]["batches_executed"]
    assert after == mid + 1 == 2
    np.testing.assert_array_equal(res[0], eng.run(feed)[0])
