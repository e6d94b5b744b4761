"""Async input pipeline (io/pipeline.py): ordered multi-worker
delivery, depth honored across reset, epoch boundaries, shutdown
hygiene, device placement, and bit-identity vs the eager path."""
import gc
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import (AsyncInputPipeline, NDArrayIter, PrefetchingIter,
                          make_sharded_pipeline)
from mxnet_tpu.io.io import DataBatch, DataDesc, DataIter


def _ndarray_iter(n=40, dim=3, batch=8, shuffle=False):
    x = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
    y = np.arange(n, dtype=np.float32)
    return NDArrayIter(x, y, batch_size=batch, shuffle=shuffle)


def _all_batches(it):
    out = []
    while True:
        try:
            out.append(it.next())
        except StopIteration:
            return out


class _JitterSource(DataIter):
    """Split-protocol source whose decode finishes OUT of submission
    order (seq-dependent sleeps) — delivery must still be in order."""

    def __init__(self, n=12, batch=4):
        super().__init__(batch)
        self._n = n
        self._seq = 0
        self.provide_data = [DataDesc("data", (batch, 1))]
        self.provide_label = [DataDesc("softmax_label", (batch,))]

    def reset(self):
        self._seq = 0

    def next_raw(self):
        if self._seq >= self._n:
            raise StopIteration
        seq = self._seq
        self._seq += 1
        return seq

    def decode_raw(self, seq):
        time.sleep(0.002 * ((self._n - seq) % 3))   # later ≠ slower
        data = np.full((self.batch_size, 1), seq, np.float32)
        return DataBatch([mx.nd.array(data)], [mx.nd.array(data[:, 0])],
                         pad=0)

    def next(self):
        return self.decode_raw(self.next_raw())


def _settle_threads(baseline, timeout=5.0):
    """Wait for transient threads to exit; returns the settled count."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if threading.active_count() <= baseline:
            break
        time.sleep(0.02)
    return threading.active_count()


class TestOrderingAndEpochs:
    def test_ordered_multiworker_delivery(self):
        src = _JitterSource(n=12)
        pipe = AsyncInputPipeline(src, num_workers=4, prefetch_depth=3)
        seqs = [float(b.data[0].asnumpy()[0, 0]) for b in _all_batches(pipe)]
        pipe.close()
        assert seqs == [float(i) for i in range(12)]

    def test_bit_identical_vs_eager(self):
        eager = [b.data[0].asnumpy() for b in _all_batches(_ndarray_iter())]
        pipe = AsyncInputPipeline(_ndarray_iter(), num_workers=4,
                                  prefetch_depth=2)
        pooled = [b.data[0].asnumpy() for b in _all_batches(pipe)]
        pipe.close()
        assert len(eager) == len(pooled)
        for a, b in zip(eager, pooled):
            np.testing.assert_array_equal(a, b)

    def test_epoch_boundary_and_reset(self):
        pipe = AsyncInputPipeline(_ndarray_iter(n=40, batch=8),
                                  num_workers=2)
        assert len(_all_batches(pipe)) == 5
        # exhausted: StopIteration repeats without wedging
        for _ in range(3):
            with pytest.raises(StopIteration):
                pipe.next()
        pipe.reset()
        assert len(_all_batches(pipe)) == 5
        pipe.close()

    def test_generic_iterator_without_split_protocol(self):
        # ResizeIter implements only next(): pipeline degrades to the
        # serialized-prefetch mode but keeps order and epoch size
        from mxnet_tpu.io import ResizeIter
        base = ResizeIter(_ndarray_iter(n=40, batch=8), size=3)
        pipe = AsyncInputPipeline(base, num_workers=4)
        assert len(_all_batches(pipe)) == 3
        pipe.close()

    def test_iter_next_protocol_serves_fetched_batch(self):
        pipe = AsyncInputPipeline(_ndarray_iter(n=16, batch=8),
                                  num_workers=2)
        seen = 0
        while pipe.iter_next():
            assert pipe.getdata() is not None
            assert pipe.getlabel() is not None
            assert pipe.getpad() == 0
            seen += 1
        pipe.close()
        assert seen == 2

    def test_numpy_leaves_pass_through_placement(self):
        import jax

        class NumpySource(_JitterSource):
            def decode_raw(self, seq):
                data = np.full((self.batch_size, 1), seq, np.float32)
                return DataBatch([data], [mx.nd.array(data[:, 0])],
                                 pad=0)

        pipe = AsyncInputPipeline(NumpySource(n=4), num_workers=2,
                                  placement=jax.devices("cpu")[0])
        batches = _all_batches(pipe)
        pipe.close()
        assert len(batches) == 4
        assert isinstance(batches[0].data[0], np.ndarray)

    def test_source_error_surfaces_in_consumer(self):
        class Boom(_JitterSource):
            def decode_raw(self, seq):
                if seq == 2:
                    raise ValueError("decode exploded")
                return super().decode_raw(seq)

        pipe = AsyncInputPipeline(Boom(n=6), num_workers=2)
        with pytest.raises(ValueError, match="decode exploded"):
            _all_batches(pipe)
        # the error also stops the producers — no zombie decode loop
        deadline = time.time() + 5
        while any(t.is_alive() for t in pipe._threads) and \
                time.time() < deadline:
            time.sleep(0.02)
        for t in pipe._threads:
            assert not t.is_alive()
        pipe.close()

    def test_namedtuple_batches_survive_placement(self):
        import collections
        import jax
        Pair = collections.namedtuple("Pair", ["data", "label"])

        class NTSource(_JitterSource):
            def decode_raw(self, seq):
                arr = mx.nd.array(
                    np.full((self.batch_size, 1), seq, np.float32))
                return Pair(arr, arr)

        pipe = AsyncInputPipeline(NTSource(n=3), num_workers=2,
                                  placement=jax.devices("cpu")[0])
        batches = _all_batches(pipe)
        pipe.close()
        assert len(batches) == 3
        assert isinstance(batches[0], Pair)
        assert batches[0].data._data.devices() == \
            {jax.devices("cpu")[0]}


class TestPrefetchingIterWrapper:
    def test_depth_honored_after_reset(self):
        pre = PrefetchingIter(_ndarray_iter(), prefetch_depth=5)
        assert pre.prefetch_depth == 5
        assert pre._pipeline._ready_q.maxsize == 5
        pre.reset()
        # the old implementation rebuilt the queue with maxsize=2 here
        assert pre._pipeline._ready_q.maxsize == 5
        assert len(_all_batches(pre)) == 5
        pre.close()

    def test_multi_iter_merge(self):
        pre = PrefetchingIter([_ndarray_iter(), _ndarray_iter()])
        batches = _all_batches(pre)
        assert len(batches) == 5
        assert len(batches[0].data) == 2
        assert len(batches[0].label) == 2
        pre.close()

    def test_repeated_reset_and_gc_leak_no_threads(self):
        baseline = threading.active_count()
        pre = PrefetchingIter(_ndarray_iter(), prefetch_depth=3)
        for _ in range(5):
            assert len(_all_batches(pre)) == 5
            pre.reset()
        pre.close()
        del pre
        gc.collect()
        assert _settle_threads(baseline) <= baseline

    def test_mid_epoch_reset_does_not_hang_or_leak(self):
        # the old _worker could block forever in queue.put after the
        # stop event fired; the stop-aware put must exit promptly
        baseline = threading.active_count()
        for _ in range(3):
            pre = PrefetchingIter(_ndarray_iter(n=80, batch=4),
                                  prefetch_depth=2)
            pre.next()                   # queue full, worker mid-put
            t0 = time.time()
            pre.reset()
            assert time.time() - t0 < 4.0
            pre.close()
        gc.collect()
        assert _settle_threads(baseline) <= baseline

    def test_pipeline_close_leaves_thread_count_stable(self):
        baseline = threading.active_count()
        pipes = [AsyncInputPipeline(_ndarray_iter(), num_workers=3)
                 for _ in range(4)]
        for p in pipes:
            _all_batches(p)
            p.close()
        del pipes
        gc.collect()
        assert _settle_threads(baseline) <= baseline


class TestDevicePlacement:
    def test_batches_arrive_on_requested_device(self):
        import jax
        dev = jax.devices("cpu")[0]
        pipe = AsyncInputPipeline(_ndarray_iter(), num_workers=2,
                                  placement=dev)
        batches = _all_batches(pipe)
        pipe.close()
        for b in batches:
            assert b.data[0]._data.devices() == {dev}
            assert b.label[0]._data.devices() == {dev}

    def test_sharded_placement_over_mesh(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs the multi-device CPU mesh")
        mesh = Mesh(np.array(devs), ("dp",))
        pipe = make_sharded_pipeline(_ndarray_iter(n=32, batch=8), mesh)
        batches = _all_batches(pipe)
        pipe.close()
        assert len(batches) == 4
        for b in batches:
            assert b.data[0]._data.sharding == NamedSharding(mesh,
                                                             P("dp"))

    def test_h2d_counters_recorded(self):
        import jax
        telemetry.reset()
        telemetry.start(run_id="h2d")
        pipe = AsyncInputPipeline(_ndarray_iter(), num_workers=2,
                                  placement=jax.devices("cpu")[0])
        _all_batches(pipe)
        pipe.close()
        rep = telemetry.stop()
        telemetry.reset()
        h2d = {k: v for k, v in rep["comms"].items()
               if k.startswith("h2d:")}
        assert any(k == "h2d:data" for k in h2d), rep["comms"]
        assert sum(c["bytes"] for c in h2d.values()) > 0

    def test_data_wait_only_counts_queue_dry_stalls(self):
        telemetry.reset()
        telemetry.start(run_id="dry")
        pipe = AsyncInputPipeline(_ndarray_iter(n=32, batch=8),
                                  num_workers=2, prefetch_depth=4)
        time.sleep(0.2)               # queue fills while we idle
        telemetry.step_begin()
        for _ in range(4):
            pipe.next()               # all ready: no data_wait span
        rec = telemetry.step_end(samples=8)
        telemetry.stop()
        telemetry.reset()
        pipe.close()
        assert (rec.get("phases_ms") or {}).get("data_wait", 0.0) \
            < 5.0, rec


class TestImageRecordPooledParity:
    def _write_rec(self, tmp_path, n=8, size=(36, 36)):
        from mxnet_tpu.recordio import (MXIndexedRecordIO, IRHeader,
                                        pack_img)
        rng = np.random.RandomState(0)
        prefix = str(tmp_path / "pp")
        rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
        for i in range(n):
            img = rng.randint(0, 255, size + (3,), dtype=np.uint8)
            rec.write_idx(i, pack_img(IRHeader(0, float(i % 3), i, 0),
                                      img, quality=95))
        rec.close()
        return prefix

    def _batches(self, prefix, wrap):
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
            data_shape=(3, 28, 28), batch_size=4, shuffle=True,
            rand_crop=True, rand_mirror=True, seed=7,
            preprocess_threads=2)
        src = AsyncInputPipeline(it, num_workers=3) if wrap else it
        out = [(b.data[0].asnumpy(), b.label[0].asnumpy())
               for b in _all_batches(src)]
        if wrap:
            src.close()
        it.close()
        return out

    def test_pooled_decode_bit_identical(self, tmp_path):
        eager = self._batches(self._write_rec(tmp_path), wrap=False)
        pooled = self._batches(self._write_rec(tmp_path), wrap=True)
        assert len(eager) == len(pooled) == 2
        for (ed, el), (pd, pl) in zip(eager, pooled):
            np.testing.assert_array_equal(ed, pd)
            np.testing.assert_array_equal(el, pl)


class TestFitIntegration:
    def _mlp(self):
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
        return mx.sym.SoftmaxOutput(fc, mx.sym.var("softmax_label"),
                                    name="softmax")

    def test_fit_through_pipeline_trains_and_cleans_up(self):
        baseline = threading.active_count()
        rng = np.random.RandomState(0)
        x = rng.randn(64, 10).astype(np.float32)
        y = rng.randint(0, 8, (64,)).astype(np.float32)
        it = NDArrayIter(x, y, batch_size=16)
        mod = mx.mod.Module(self._mlp(), context=mx.cpu())
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})
        gc.collect()
        assert _settle_threads(baseline) <= baseline
        # the wrap consumed the underlying iterator fully each epoch
        it.reset()
        assert sum(1 for _ in it) == 4

    def test_fit_pipeline_disabled_env(self, monkeypatch):
        monkeypatch.setenv("MXNET_DATA_PIPELINE", "0")
        rng = np.random.RandomState(0)
        x = rng.randn(32, 10).astype(np.float32)
        y = rng.randint(0, 8, (32,)).astype(np.float32)
        mod = mx.mod.Module(self._mlp(), context=mx.cpu())
        mod.fit(NDArrayIter(x, y, batch_size=16), num_epoch=1,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1})

    def test_fit_matches_eager_losses(self, monkeypatch):
        """Same data, same init: the pipelined fit must follow the
        exact same trajectory as the unpipelined one."""
        def run(pipeline_on):
            monkeypatch.setenv("MXNET_DATA_PIPELINE",
                               "1" if pipeline_on else "0")
            rng = np.random.RandomState(3)
            x = rng.randn(48, 6).astype(np.float32)
            y = rng.randint(0, 4, (48,)).astype(np.float32)
            data = mx.sym.var("data")
            net = mx.sym.SoftmaxOutput(
                mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
                mx.sym.var("softmax_label"), name="softmax")
            mod = mx.mod.Module(net, context=mx.cpu())
            metric = mx.metric.create("acc")
            mod.fit(NDArrayIter(x, y, batch_size=12), num_epoch=3,
                    eval_metric=metric, optimizer="sgd",
                    initializer=mx.init.One(),
                    optimizer_params={"learning_rate": 0.05})
            return mod.get_params()[0]["fc_weight"].asnumpy()

        np.testing.assert_allclose(run(True), run(False), rtol=0,
                                   atol=0)


class TestZeroCopyInit:
    def test_host_ndarray_source_is_viewed_not_copied(self):
        from mxnet_tpu.io.io import _as_host_view
        src = mx.nd.array(np.arange(12, np.float32).reshape(3, 4)
                          if False else
                          np.arange(12, dtype=np.float32).reshape(3, 4))
        view = _as_host_view(src)
        np.testing.assert_array_equal(view, src.asnumpy())
        # zero-copy when DLPack export works: mutating the source buffer
        # is visible through the view (guarded: some jax versions refuse
        # the export and legitimately fall back to a copy)
        try:
            view2 = np.from_dlpack(src._data)
        except Exception:
            pytest.skip("jax build without host DLPack export")
        assert view2 is not None

    def test_numpy_source_not_copied(self):
        from mxnet_tpu.io.io import _as_host_view
        x = np.arange(6, dtype=np.float32)
        assert _as_host_view(x) is x

    def test_ndarray_iter_from_ndarray_matches_numpy(self):
        x = np.random.RandomState(0).randn(10, 3).astype(np.float32)
        a = [b.data[0].asnumpy()
             for b in _all_batches(NDArrayIter(mx.nd.array(x), batch_size=5))]
        b = [b.data[0].asnumpy()
             for b in _all_batches(NDArrayIter(x, batch_size=5))]
        for u, v in zip(a, b):
            np.testing.assert_array_equal(u, v)


class TestGluonDataLoaderDevicePrefetch:
    def test_device_prefetch_places_batches(self):
        import jax
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        x = mx.nd.array(np.random.RandomState(0)
                        .randn(24, 4).astype(np.float32))
        y = mx.nd.array(np.arange(24, dtype=np.float32))
        dev = jax.devices("cpu")[0]
        loader = DataLoader(ArrayDataset(x, y), batch_size=6,
                            device_prefetch=dev)
        n = 0
        for data, label in loader:
            assert data._data.devices() == {dev}
            assert label._data.devices() == {dev}
            n += 1
        assert n == 4

    def test_device_prefetch_leaves_no_threads(self):
        import jax
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        baseline = threading.active_count()
        x = mx.nd.array(np.zeros((12, 2), np.float32))
        loader = DataLoader(ArrayDataset(x, x), batch_size=4,
                            device_prefetch=jax.devices("cpu")[0])
        assert sum(1 for _ in loader) == 3
        gc.collect()
        assert _settle_threads(baseline) <= baseline


# ---------------------------------------------------------------------------
# host → target, once: the pipeline places by its placement
# ---------------------------------------------------------------------------

class _Probe:
    """What the placer did while the pipeline ran: the routes of the
    recorded ``pipeline.h2d`` spans, the two counters' increase, and
    what the placer thread handed to every ``jax.device_put``."""

    def __init__(self, monkeypatch):
        import jax
        from mxnet_tpu import profiler, tracing
        self._tracing, self._profiler = tracing, profiler
        self.put_inputs = []
        real = jax.device_put

        def spy(x, *args, **kwargs):
            if threading.current_thread().name == "mxio-place":
                self.put_inputs.append(x)
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", spy)

    def __enter__(self):
        self._tracing.reset()
        self._tracing.enable()
        self._before = dict(self._profiler.counters())
        return self

    def __exit__(self, *exc):
        events = self._tracing.export()["traceEvents"]
        self._tracing.reset()
        self.routes = [e["args"]["route"] for e in events
                       if e.get("name") == "pipeline.h2d"]
        after = self._profiler.counters()
        self.from_host, self.resharded = (
            after.get(k, 0) - self._before.get(k, 0)
            for k in ("pipeline_placed_from_host", "pipeline_resharded"))
        return False


def _mesh(n=None):
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()[:n]
    if len(devs) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    return Mesh(np.array(devs), ("dp",))


def _eager(make_source):
    return [(b.data[0].asnumpy(), b.label[0].asnumpy())
            for b in _all_batches(make_source())]


def _generic_source():
    """No split protocol: ``next()`` runs on the scheduler thread."""
    class Plain:
        batch_size = 8
        provide_data = [DataDesc("data", (8, 3))]
        provide_label = [DataDesc("softmax_label", (8,))]

        def __init__(self):
            self._at = 0

        def next(self):
            if self._at >= 4:
                raise StopIteration
            rows = np.arange(self._at * 8, self._at * 8 + 8,
                             dtype=np.float32)
            self._at += 1
            return DataBatch(
                [mx.nd.array(rows[:, None] * 3 + np.arange(3,
                                                           dtype=np.float32))],
                [mx.nd.array(rows)], pad=0)

        def reset(self):
            self._at = 0
    return Plain()


class TestPlacedFromHostOnce:
    @pytest.mark.parametrize("source", ["split", "generic"])
    @pytest.mark.parametrize("target", ["sharded_pipeline", "named_sharding",
                                        "one_device"])
    def test_every_array_goes_from_host_memory_to_its_target(
            self, monkeypatch, target, source):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh()
        make = (lambda: _ndarray_iter(n=32, batch=8)) if source == "split" \
            else _generic_source
        want = _eager(make)
        dp = NamedSharding(mesh, P("dp"))
        with _Probe(monkeypatch) as probe:
            if target == "sharded_pipeline":
                pipe = make_sharded_pipeline(make(), mesh)
                on = (dp, dp)       # 8 rows over 8 devices: both split
            elif target == "named_sharding":
                pipe = AsyncInputPipeline(make(), num_workers=2,
                                          placement=dp)
                on = (dp, dp)
            else:
                # not the default device, so a stop there would show
                dev = jax.devices()[3]
                pipe = AsyncInputPipeline(make(), num_workers=2,
                                          placement=dev)
                on = (dev, dev)
            got = _all_batches(pipe)
            pipe.close()
        assert len(got) == len(want) == 4
        for b, (x, y) in zip(got, want):
            for arr, host, where in ((b.data[0], x, on[0]),
                                     (b.label[0], y, on[1])):
                assert type(arr) is mx.nd.NDArray
                if target == "one_device":
                    assert arr._data.devices() == {where}
                    assert arr._data.committed
                else:
                    assert arr._data.sharding == where
                np.testing.assert_array_equal(arr.asnumpy(), host)
        assert probe.routes == ["host"] * 8
        assert (probe.from_host, probe.resharded) == (8, 0)
        # the placer handed jax nothing but host memory: no array was
        # ever committed to the default device on the way
        assert len(probe.put_inputs) == 8
        assert all(type(x) is np.ndarray for x in probe.put_inputs)

    def test_committed_arrays_are_resharded_and_counted(self, monkeypatch):
        """A source that commits its arrays itself (an explicit ``ctx``)
        keeps the device-to-device route, and is counted."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh()
        dp = NamedSharding(mesh, P("dp"))

        class Committed(_JitterSource):
            def decode_raw(self, seq):
                data = np.full((8, 1), seq, np.float32)
                return DataBatch([mx.nd.array(data, ctx=mx.cpu(1))],
                                 [mx.nd.array(data[:, 0], ctx=mx.cpu(1))],
                                 pad=0)

        with _Probe(monkeypatch) as probe:
            pipe = AsyncInputPipeline(Committed(n=3, batch=8),
                                      num_workers=2, placement=dp)
            got = _all_batches(pipe)
            pipe.close()
        for seq, b in enumerate(got):
            assert b.data[0]._data.sharding == dp
            assert b.label[0]._data.sharding == dp
            np.testing.assert_array_equal(
                b.data[0].asnumpy(), np.full((8, 1), seq, np.float32))
        assert probe.routes == ["reshard"] * 6
        assert (probe.from_host, probe.resharded) == (0, 6)
        assert not any(type(x) is np.ndarray for x in probe.put_inputs)

    def test_arrays_already_on_the_target_are_left_there(self, monkeypatch):
        import jax
        dev = jax.devices()[2]

        class OnTarget(_JitterSource):
            def decode_raw(self, seq):
                data = np.full((4, 1), seq, np.float32)
                return DataBatch([mx.nd.array(data, ctx=mx.cpu(2))],
                                 [mx.nd.array(data[:, 0])], pad=0)

        with _Probe(monkeypatch) as probe:
            pipe = AsyncInputPipeline(OnTarget(n=2), num_workers=1,
                                      placement=dev)
            got = _all_batches(pipe)
            pipe.close()
        assert probe.routes == ["resident", "host"] * 2
        assert (probe.from_host, probe.resharded) == (2, 0)
        assert all(b.data[0]._data.devices() == {dev} for b in got)

    def test_module_placement_callable_resolves_by_name_and_shape(
            self, monkeypatch):
        """``placement_for_module``'s rule: names in ``batch_args``
        whose leading dim splits go on the dp sharding, the rest are
        replicated — resolved by the placer, where both are known."""
        from mxnet_tpu.io.pipeline import placement_for_module
        data = mx.sym.var("data")
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
            mx.sym.var("softmax_label"), name="softmax")
        mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(4)])
        mod.bind(data_shapes=[("data", (8, 3))],
                 label_shapes=[("softmax_label", (8,))])
        placement = placement_for_module(mod)
        assert callable(placement)
        rep, shard = mod._exec._dp_shardings()

        class WithExtra(_JitterSource):
            """``data`` splits, the odd-length ``extra`` cannot, and
            ``aux`` is no batch argument of the executor."""
            def __init__(self):
                super().__init__(n=2, batch=8)
                self.provide_data = [DataDesc("data", (8, 3)),
                                     DataDesc("extra", (3,)),
                                     DataDesc("aux", (8,))]

            def decode_raw(self, seq):
                return DataBatch(
                    [mx.nd.array(np.full((8, 3), seq, np.float32)),
                     mx.nd.array(np.arange(3, dtype=np.float32)),
                     mx.nd.array(np.arange(8, dtype=np.float32))],
                    [mx.nd.array(np.full((8,), seq, np.float32))], pad=0)

        with _Probe(monkeypatch) as probe:
            pipe = AsyncInputPipeline(WithExtra(), num_workers=2,
                                      placement=placement)
            got = _all_batches(pipe)
            pipe.close()
        for b in got:
            assert b.data[0]._data.sharding == shard
            assert b.data[1]._data.sharding == rep
            assert b.data[2]._data.sharding == rep
            assert b.label[0]._data.sharding == shard
            np.testing.assert_array_equal(b.data[1].asnumpy(),
                                          np.arange(3, dtype=np.float32))
        assert probe.routes == ["host"] * 8
        assert (probe.from_host, probe.resharded) == (8, 0)

    def test_a_callable_with_no_target_lands_on_the_arrays_context(
            self, monkeypatch):
        import jax
        with _Probe(monkeypatch) as probe:
            pipe = AsyncInputPipeline(
                _ndarray_iter(n=16, batch=8), num_workers=1,
                placement=lambda name, arr: None)
            got = _all_batches(pipe)
            pipe.close()
        assert probe.routes == ["host"] * 4
        for b in got:
            assert type(b.data[0]) is mx.nd.NDArray
            assert b.data[0]._data.devices() == {jax.devices()[0]}

    def test_the_scope_is_the_decode_threads_alone(self):
        """The placement reaches the source's decode and nothing else:
        not the consumer's thread, not a decode after ``close()`` or
        across ``reset()``, not a pipeline whose placement was taken
        away."""
        import jax
        from mxnet_tpu.context import current_placement
        from mxnet_tpu.ndarray.ndarray import HostStagedNDArray
        dev = jax.devices()[3]
        seen = []

        class Watching(_JitterSource):
            def decode_raw(self, seq):
                seen.append((threading.current_thread().name,
                             current_placement()))
                return super().decode_raw(seq)

        src = Watching(n=6)
        pipe = AsyncInputPipeline(src, num_workers=2, placement=dev)
        first = pipe.next()
        assert current_placement() is None
        mine = mx.nd.array(np.ones(3, np.float32))
        assert type(mine) is mx.nd.NDArray
        assert mine._data.devices() == {jax.devices()[0]}
        assert first.data[0]._data.devices() == {dev}
        pipe.reset()
        assert current_placement() is None
        assert len(_all_batches(pipe)) == 6
        assert seen and all(where is dev for _, where in seen)
        assert all(name.startswith("mxio-") for name, _ in seen)
        del seen[:]
        pipe.set_placement(None)
        pipe.reset()
        assert len(_all_batches(pipe)) == 6
        assert seen and all(where is None for _, where in seen)
        pipe.close()
        assert current_placement() is None
        after = src.decode_raw(0)
        assert not isinstance(after.data[0], HostStagedNDArray)
        assert after.data[0]._data.devices() == {jax.devices()[0]}

    def test_a_scope_that_raises_is_closed(self):
        from mxnet_tpu.context import current_placement, placement_scope
        with pytest.raises(KeyError):
            with placement_scope("outer"):
                with placement_scope("inner"):
                    assert current_placement() == "inner"
                    raise KeyError("x")
        assert current_placement() is None

    def test_a_staged_array_read_early_is_an_ordinary_array(self):
        """Whoever touches a staged array before the placer does gets
        what ``mx.nd.array`` gives outside a scope; an explicit ``ctx``
        is honoured at once, scope or not."""
        import jax
        from mxnet_tpu.context import placement_scope
        from mxnet_tpu.ndarray.ndarray import HostStagedNDArray
        host = np.arange(6, dtype=np.float64).reshape(2, 3)
        with placement_scope(jax.devices()[5]):
            staged = mx.nd.array(host)
            pinned = mx.nd.array(host, ctx=mx.cpu(2))
            with mx.cpu(4):
                scoped = mx.nd.array(host)
        assert isinstance(staged, HostStagedNDArray)
        assert staged.host.dtype == np.float32      # settled on the host
        assert (staged.shape, staged.ndim, staged.size) == ((2, 3), 2, 6)
        assert staged.dtype == np.float32 and staged.host is not None
        assert type(pinned) is mx.nd.NDArray
        assert pinned._data.devices() == {jax.devices()[2]}
        assert scoped.context == mx.cpu(4)
        total = (staged * 2).asnumpy()              # reads _data
        np.testing.assert_array_equal(total, host.astype(np.float32) * 2)
        assert staged.host is None
        assert staged._data.devices() == {jax.devices()[0]}
        assert staged._data.committed and staged.shape == (2, 3)
        assert scoped._data.devices() == {jax.devices()[4]}
        staged[0] = 9.0                             # the handle still swaps
        assert staged.asnumpy()[0, 0] == 9.0

    def test_sharded_input_pipeline_feeds_the_trainer_without_a_put(self):
        """``DistributedTrainer.fit_batch``'s ``_put_unless_placed``
        finds the batch on its own batch sharding."""
        from mxnet_tpu.parallel.data_parallel import (
            _put_unless_placed, sharded_input_pipeline)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = _mesh(4)
        pipe = sharded_input_pipeline(_ndarray_iter(n=16, batch=8), mesh)
        b = pipe.next()
        pipe.close()
        want = NamedSharding(mesh, P("dp"))
        assert _put_unless_placed(b.data[0]._data, want) is b.data[0]._data
