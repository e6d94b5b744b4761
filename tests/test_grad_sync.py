"""Overlapped gradient sync (parallel.grad_sync): bucketed
reduce-scatter + ZeRO-1 sharded optimizer update.

Pins the PR's two oracles: (1) trajectory identity — overlap-on is
bit-exact (rtol=0) against overlap-off for every supported optimizer
on the 8-device CPU mesh, through both the DistributedTrainer step and
the gluon Trainer's fused update; (2) the ZeRO-1 memory layout —
per-device resident optimizer state is 1/N of the replicated baseline,
asserted on the actual device shards. Plus the satellites: backward-
order bucket planning, pad-and-slice reduce_scatter for non-divisible
leading dims, the bucketed eager kvstore exchange, sharded-state
round-trip through checkpoint.py's manifest format (including a
fault-injected killed save → elastic resume on a smaller mesh), and
the diagnose Sync table.
"""
import json
import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, fault, gluon, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (DistributedTrainer, GradSyncPlan,
                                collectives, grad_sync, local_mesh,
                                replicated)
from mxnet_tpu.parallel.mesh import create_mesh

N_DEV = 8

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < N_DEV, reason="needs %d devices" % N_DEV)


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv("MXNET_GRAD_OVERLAP", raising=False)
    monkeypatch.delenv("MXNET_GRAD_BUCKET_MB", raising=False)
    monkeypatch.delenv("MXNET_FAULT_PLAN", raising=False)
    fault.reset()
    telemetry.reset()
    yield
    fault.reset()
    telemetry.reset()


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_plan_backward_order_and_cap():
    """Buckets traverse the roster in REVERSE (late-layer grads reduce
    first), close on the byte cap, and lay every member out by rows a
    chip: a member that fills a tile of every row padded to whole
    tiles, the smaller ones at their exact share behind them, the row
    closed to a whole tile once."""
    shapes = [(100,), (50,), (200,), (10,)]
    plan = GradSyncPlan(shapes, ["float32"] * 4, axis_size=8,
                        cap_bytes=4 * 150)   # 150 f32 elements
    # reverse order: param 3 first; 3+2 exceed? 10+200=210>150 → split
    assert plan.buckets[0].indices == (3,)
    assert plan.buckets[1].indices == (2,)
    assert plan.buckets[2].indices == (1, 0)
    tile = grad_sync.TILE
    for b in plan.buckets:
        assert b.padded_size == 8 * b.row_len
        assert b.row_len % tile == 0 and b.row_len - sum(b.cols) < tile
        for size, c in zip(b.sizes, b.cols):
            assert c == -(-size // 8)     # all small: the exact share
        assert b.total == sum(b.sizes)
        assert b.nbytes == 4 * b.total    # the logical payload
    # roster-order offsets (what leaves the device) are a prefix sum of
    # sizes; a chip's row holds the small members one against the other
    b = plan.buckets[2]
    assert b.offsets == (0, 50)
    assert b.col_offsets == (0, 7) and b.aligned_len == 0
    # a member with a tile or more a chip comes first, on whole tiles
    b = GradSyncPlan([(3,), (8 * tile + 1,), (5,)], ["float32"] * 3, 8,
                     cap_bytes=grad_sync.MONOLITH_CAP).buckets[0]
    assert b.indices == (2, 1, 0) and b.cols == (1, 2 * tile, 1)
    assert b.col_offsets == (2 * tile, 0, 2 * tile + 1)
    assert b.aligned_len == 2 * tile and b.row_len == 3 * tile
    assert plan.signature() == GradSyncPlan(
        shapes, ["float32"] * 4, 8, cap_bytes=600).signature()


@pytest.mark.parametrize("axis", [4, 256])
def test_bucket_padding_does_not_grow_with_the_small_members(axis):
    """A ResNet-like roster (many BatchNorm vectors and biases beside
    a few large kernels) on a narrow and on a wide axis: a small
    member pads by less than one element a chip, a large one by less
    than a tile a chip, the row by a tile once — never a tile a chip
    for EVERY small member (31 M elements on 256 chips)."""
    sizes = [64, 64, 256, 256, 512, 2048, 1000] * 17 + \
        [64 * 3 * 7 * 7, 512 * 512 * 9, 2048 * 1000, 256 * 1024 + 3]
    b = GradSyncPlan([(s,) for s in sizes], ["bfloat16"] * len(sizes),
                     axis, cap_bytes=grad_sync.MONOLITH_CAP).buckets[0]
    tile = grad_sync.TILE
    large = [s for s in sizes if -(-s // axis) >= tile]
    small = len(sizes) - len(large)
    assert b.padded_size - b.total < \
        axis * (small + tile * (len(large) + 1))
    assert b.padded_size < 2 * b.total + axis * tile
    assert b.aligned_len % tile == 0 and b.row_len % tile == 0
    for size, c, off in zip(b.sizes, b.cols, b.col_offsets):
        assert axis * c >= size
        assert (off % tile == 0 and off < b.aligned_len) == \
            (-(-size // axis) >= tile) or off == b.aligned_len
    # every column belongs to at most one member
    taken = np.zeros((b.row_len,), np.int32)
    for c, off in zip(b.cols, b.col_offsets):
        taken[off:off + c] += 1
    assert taken.max() == 1
    assert b.nbytes == 2 * sum(sizes)


def test_plan_dtype_split_and_monolith():
    """A dtype change closes the bucket (flat concat is dtype-uniform);
    MONOLITH_CAP packs each dtype run into one blob."""
    shapes = [(16,), (16,), (16,)]
    dts = ["float32", "float16", "float16"]
    plan = GradSyncPlan(shapes, dts, axis_size=4,
                        cap_bytes=grad_sync.MONOLITH_CAP)
    assert [b.dtype for b in plan.buckets] == ["float16", "float32"]
    assert plan.buckets[0].indices == (2, 1)
    # every param appears exactly once across buckets
    seen = sorted(i for b in plan.buckets for i in b.indices)
    assert seen == [0, 1, 2]
    assert plan.total_bytes() == sum(b.nbytes for b in plan.buckets)
    assert plan.describe()["params"] == 3


def test_bucket_cap_env(monkeypatch):
    monkeypatch.setenv("MXNET_GRAD_BUCKET_MB", "2.5")
    assert grad_sync.bucket_cap_bytes() == int(2.5 * (1 << 20))
    monkeypatch.setenv("MXNET_GRAD_OVERLAP", "on")
    assert grad_sync.overlap_enabled()
    monkeypatch.setenv("MXNET_GRAD_OVERLAP", "0")
    assert not grad_sync.overlap_enabled()


# ---------------------------------------------------------------------------
# collectives: pad-and-slice reduce_scatter + bucket primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d0", [3, 5, 7, 13])
def test_reduce_scatter_pads_odd_leading_dim(d0):
    """A leading dim that does not divide the axis size (a hard XLA
    shape error before) is zero-padded through the collective and
    sliced back: the result is the cross-device sum, original shape."""
    mesh = local_mesh("dp")
    rng = np.random.RandomState(d0)
    # integer-valued floats: the cross-device sum is exact whatever
    # reduction order XLA picks, so equality is a pure padding check
    val = rng.randint(-100, 100, (d0, 3)).astype(np.float32)
    x = jax.device_put(val, NamedSharding(mesh, P()))
    out = collectives.reduce_scatter(x, mesh)
    assert out.shape == (d0, 3)
    np.testing.assert_array_equal(np.asarray(out), val * N_DEV)


def test_reduce_scatter_divisible_unchanged():
    mesh = local_mesh("dp")
    val = np.arange(16, dtype=np.float32).reshape(16, 1)
    x = jax.device_put(val, NamedSharding(mesh, P()))
    out = collectives.reduce_scatter(x, mesh)
    np.testing.assert_array_equal(np.asarray(out), val * N_DEV)


def test_bucket_reduce_scatter_all_gather_roundtrip():
    """One collective for a whole bucket: per-device stacked
    contributions sum into a flat dp-sharded vector; the all-gather
    brings the flat bucket back replicated."""
    mesh = local_mesh("dp")
    rng = np.random.RandomState(3)
    shapes = [(4, 3), (5,), (2, 2)]
    stacked = [jax.device_put(
        rng.normal(0, 1, (N_DEV,) + s).astype(np.float32),
        NamedSharding(mesh, P("dp")))
        for s in shapes]
    flat = collectives.bucket_reduce_scatter(stacked, mesh)
    total = sum(int(np.prod(s)) for s in shapes)
    padded = -(-total // N_DEV) * N_DEV
    assert flat.shape == (padded,)
    expect = np.concatenate(
        [np.asarray(v).sum(axis=0).reshape(-1) for v in stacked])
    got = np.asarray(collectives.bucket_all_gather(flat, mesh))
    np.testing.assert_allclose(got[:total], expect, rtol=1e-6)
    np.testing.assert_array_equal(got[total:],
                                  np.zeros(padded - total, np.float32))


# ---------------------------------------------------------------------------
# trajectory identity: DistributedTrainer (the compiled mesh step)
# ---------------------------------------------------------------------------

OPTIMIZERS = [("sgd", {"learning_rate": 0.05}),
              ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
              ("adam", {"learning_rate": 0.01}),
              ("adagrad", {"learning_rate": 0.05}),
              ("rmsprop", {"learning_rate": 0.01})]

_INIT = {}


# (hidden, classes, inputs): EVEN is the roster these tests always had;
# ODD gives every parameter an odd size (627, 33, 363, 11), so that no
# member fills its rows and the per-parameter padding is exercised
EVEN, ODD = (32, 10, 20), (33, 11, 19)
WIDTHS = pytest.mark.parametrize("widths", [EVEN, ODD],
                                 ids=["even", "odd"])


def _dist_net(widths):
    # fixed prefix: roster names (and so checkpoint arg: keys) are
    # identical across runs instead of riding the global name counter
    hidden, classes, inputs = widths
    net = nn.HybridSequential(prefix="gsync_")
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"), nn.Dense(classes))
    net.initialize()
    _ = net(mx.nd.array(np.zeros((16, inputs), np.float32)))
    return net


def _dist_run(overlap, opt, opt_params, steps=5, bucket_mb=0.001,
              widths=EVEN):
    mesh = local_mesh("dp")
    net = _dist_net(widths)
    plist = sorted(net.collect_params().items())
    key = tuple(tuple(p.data().shape) for _, p in plist)
    if key not in _INIT:
        rng = np.random.RandomState(11)
        _INIT[key] = [rng.randn(*p.data().shape).astype(np.float32)
                      * 0.1 for _, p in plist]
    for (_, p), v in zip(plist, _INIT[key]):
        p.set_data(mx.nd.array(v))
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = DistributedTrainer(net, loss, mesh, optimizer=opt,
                            optimizer_params=opt_params,
                            grad_overlap=overlap, bucket_mb=bucket_mb)
    rng = np.random.RandomState(3)
    losses = []
    for _ in range(steps):
        data = mx.nd.array(rng.randn(16, widths[2]).astype(np.float32))
        label = mx.nd.array(
            rng.randint(0, 10, (16,)).astype(np.float32))
        losses.append(float(tr.fit_batch(data, label).asnumpy()))
    tr.sync_gluon_params()
    params = [p.data().asnumpy()
              for _, p in sorted(net.collect_params().items())]
    return losses, params, tr


@pytest.mark.parametrize("opt,op", OPTIMIZERS,
                         ids=[o + ("_mom" if "momentum" in p else "")
                              for o, p in OPTIMIZERS])
def test_distributed_trainer_bitexact(opt, op):
    """The correctness oracle: overlap-on (bucketed reduce-scatter +
    ZeRO-1 sharded state) is bit-exact (rtol=0) against overlap-off
    (the monolithic post-backward blob) over 5 steps, per optimizer."""
    l0, p0, t0 = _dist_run(False, opt, op)
    l1, p1, t1 = _dist_run(True, opt, op)
    assert l0 == l1
    for i, (a, b) in enumerate(zip(p0, p1)):
        np.testing.assert_array_equal(a, b, err_msg="param %d" % i)
    assert len(t1._plan.buckets) > 1       # actually bucketed
    assert len(t0._plan.buckets) == 1      # actually monolithic
    assert t1.overlap and not t0.overlap


def test_zero1_state_memory_is_one_over_n():
    """The ZeRO-1 memory win, asserted on the real device shards: in
    overlap mode every device holds 1/N of every optimizer-state
    vector; overlap-off keeps the full replicated copy per device."""
    _, _, t_off = _dist_run(False, "adam", {"learning_rate": 0.01},
                            steps=1)
    _, _, t_on = _dist_run(True, "adam", {"learning_rate": 0.01},
                           steps=1)
    off_b, on_b = (t.state_bytes_per_device() for t in (t_off, t_on))
    # the ledger is the resident arrays': all of each with the gate
    # closed, a row of each with it open (each plan closes its own
    # rows to a tile, so the two totals are not one number)
    assert off_b == sum(a.nbytes for a in t_off._state_vals) > 0
    assert on_b * N_DEV == sum(a.nbytes for a in t_on._state_vals)
    assert on_b < off_b
    # the actual arrays agree with the ledger: one addressable shard
    # per device, 1/N (resp. full) of the vector each
    for arr in t_on._state_vals:
        shard = arr.addressable_shards[0]
        assert shard.data.size * N_DEV == arr.size
    for arr in t_off._state_vals:
        assert arr.addressable_shards[0].data.size == arr.size


def test_distributed_trainer_rejects_unknown_optimizer():
    mesh = local_mesh("dp")
    net = nn.Dense(4)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    with pytest.raises(Exception):
        DistributedTrainer(net, loss, mesh, optimizer="no_such_opt")


def test_distributed_trainer_params_placed_once():
    """fit_batch must feed the device-resident roster, not re-place
    Gluon handles per step (the old per-step device_put satellite)."""
    _, _, tr = _dist_run(True, "sgd", {"learning_rate": 0.05}, steps=2)
    assert tr.dispatch_count == 2
    # params stay jax arrays on the mesh between steps
    for v in tr._param_vals:
        assert hasattr(v, "sharding")
    assert tr._gluon_dirty is False        # sync_gluon_params ran


# ---------------------------------------------------------------------------
# the compiled mesh step: what the row layout is for
# ---------------------------------------------------------------------------

class _Lowered(Exception):
    pass


def _conv_step_text(n_blocks, overlap):
    """The compiled text of a ``DistributedTrainer`` step on FOUR host
    devices for a small convolutional net of ``4 * n_blocks + 2``
    parameters, none of a size that divides by 4 (convolutions of 5, 6
    and 7 channels with a bias, BatchNorm, a classifier of 7), and the
    trainer: compiled, not run."""
    mesh = create_mesh({"dp": 4}, devices=jax.devices()[:4])
    net = nn.HybridSequential(prefix="rows%d_" % n_blocks)
    with net.name_scope():
        for b in range(n_blocks):
            net.add(nn.Conv2D(5 + b % 3, 3, padding=1, use_bias=True),
                    nn.BatchNorm(), nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(7))
    net.initialize()
    x = mx.nd.array(np.zeros((8, 3, 9, 9), np.float32))
    y = mx.nd.array(np.zeros((8,), np.float32))
    _ = net(x)
    tr = DistributedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.05,
                                              "momentum": 0.9},
                            grad_overlap=overlap, bucket_mb=0.002)
    tr._build(x, y)
    assert all(int(np.prod(v.shape)) % 4 for v in tr._param_vals)
    jitted = tr._step_fn._jitted

    def lower_only(*args):
        raise _Lowered(jitted.lower(*args).compile().as_text())
    tr._step_fn = lower_only
    with pytest.raises(_Lowered) as caught:
        tr.fit_batch(x, y)
    return str(caught.value), tr


_HLO_LINE = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\((.*)$")
_HLO_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_SAME_VALUES = {"bitcast", "reshape", "copy", "convert", "pad",
                "transpose"}


def _wide_concat_cut_at_run_time(text, widest=4):
    """The ``dynamic-slice`` instructions of a compiled module that
    cut, at an offset that is no constant (on a mesh: the partition
    id's), a ``concatenate`` of more than ``widest`` operands — seen
    through bitcasts, reshapes, pads and the boundaries of fusions.
    That is the shape in which no output element knows at compile time
    which operand it comes from."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = _HLO_HEAD.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        m = _HLO_LINE.match(line)
        if m and cur is not None:
            root, name, op, rest = m.groups()
            operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
            if op == "parameter":
                operands = int(rest.split(")")[0])
            calls = re.search(r"calls=%?([\w.\-]+)", rest)
            cur.append((name, op, operands, calls and calls.group(1),
                        bool(root)))

    def scan(comp, wide_params=()):
        wide, const, found, wide_root = set(), set(), [], False
        for name, op, operands, calls, root in comps[comp]:
            if op == "parameter":
                if operands in wide_params:
                    wide.add(name)
            elif op == "constant":
                const.add(name)
            elif op == "concatenate" and len(operands) > widest:
                wide.add(name)
            elif op in _SAME_VALUES and operands[0] in wide:
                wide.add(name)
            elif op == "dynamic-slice" and operands[0] in wide and \
                    not all(o in const for o in operands[1:]):
                found.append(name)
            elif op == "fusion" and calls in comps:
                inner, inner_wide = scan(
                    calls, [k for k, o in enumerate(operands)
                            if o in wide])
                found.extend(inner)
                if inner_wide:
                    wide.add(name)
            wide_root = wide_root or (root and name in wide)
        return found, wide_root

    return scan(entry)[0]


def test_the_checker_sees_a_wide_concatenate_cut_by_the_partition_id():
    """The shape the row layout removes, written by hand: a 1-D
    roster-order concatenate constrained to P('dp') compiles to a
    concatenate of all members cut at partition-id x shard; the checker
    of the next test has to see it there."""
    mesh = create_mesh({"dp": 4}, devices=jax.devices()[:4])
    rep, cut = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))

    @jax.jit
    def roster_order(*ws):
        ws = [jax.lax.with_sharding_constraint(w, rep) for w in ws]
        flat = jax.numpy.concatenate([w.reshape(-1) for w in ws])
        return jax.lax.with_sharding_constraint(flat * 2, cut)
    ws = [jax.device_put(np.ones((7 + i,), np.float32), rep)
          for i in range(8)]           # 84 elements: 21 a chip
    text = roster_order.lower(*ws).compile().as_text()
    assert "partition-id" in text
    assert _wide_concat_cut_at_run_time(text)


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["gate_closed", "gate_open"])
def test_compiled_step_reads_its_rows_at_fixed_offsets(overlap):
    """What the row layout is for, read from the compiled four-device
    step in both gate positions: the updated buffers are gathered ONCE
    a bucket (the parameters, and each state slot where the state is
    resident replicated) — the count of all-gathers is a multiple of
    the buckets and does not grow with the parameters — and nowhere is
    a concatenate of many members cut at an offset only the partition
    id knows (on the chip XLA fuses that into a select out of every
    member for every output vector: 26 ms of a 169 ms step, ISSUE 32).
    """
    counts = {}
    for n_blocks in (5, 10):                 # 22 and 42 parameters
        text, tr = _conv_step_text(n_blocks, overlap)
        n_params, buckets = len(tr._roster), len(tr._plan.buckets)
        assert n_params == 4 * n_blocks + 2
        assert (buckets == 1) != overlap
        gathers = len(re.findall(r" all-gather(?:-start)?\(", text))
        slots = tr._sync_state.n_slots
        assert 1 <= gathers <= buckets * (slots + 1) + 2, \
            (n_params, buckets, gathers)
        counts[n_params] = gathers - buckets * (slots + 1)
        assert "partition-id" in text        # it IS a partitioned step
        assert _wide_concat_cut_at_run_time(text) == []
        # the exchange itself, as read (not promised): whole gradients
        # summed by all-reduce, no reduce-scatter on this pipeline
        assert re.search(r" all-reduce(?:-start)?\(", text)
    # twice the parameters, not one gather more over what the buckets
    # account for
    assert counts[42] <= counts[22]


# ---------------------------------------------------------------------------
# trajectory identity: gluon Trainer (fused update on a dp mesh)
# ---------------------------------------------------------------------------

def _gluon_run(overlap, bucket_mb, opt="adam", steps=5):
    os.environ["MXNET_GRAD_OVERLAP"] = "1" if overlap else "0"
    if bucket_mb is not None:
        os.environ["MXNET_GRAD_BUCKET_MB"] = str(bucket_mb)
    mesh = local_mesh("dp")
    rep = replicated(mesh)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=20),
            nn.Dense(10, in_units=32))
    net.initialize()
    params = net.collect_params()
    for i, p in enumerate(params.values()):
        v = np.random.RandomState(20 + i).uniform(
            -0.2, 0.2, p.shape).astype(np.float32)
        p.set_data(mx.nd.array(v))
        p._data._set_data(jax.device_put(p._data._data, rep))
    trainer = gluon.Trainer(params, opt, {"learning_rate": 0.05})
    x = mx.nd.array(np.random.RandomState(7).uniform(
        -1, 1, (16, 20)).astype(np.float32))
    x._set_data(jax.device_put(x._data, rep))
    for _ in range(steps):
        with autograd.record():
            out = net(x)
            loss = (out * out).mean()
        loss.backward()
        trainer.step(16)
    return ([p.data().asnumpy().copy() for p in params.values()],
            trainer)


def test_gluon_trainer_sync_bitexact(monkeypatch):
    """The gluon entry point: overlap-off (plain fused per-param
    update), the monolithic one-blob sync, and the bucketed sync all
    produce the bit-identical trajectory; the sync path actually runs
    in-program with sharded state."""
    p_off, t_off = _gluon_run(False, None)
    p_mono, t_mono = _gluon_run(True, 1e6)
    p_buck, t_buck = _gluon_run(True, 0.001)
    for a, b in zip(p_off, p_buck):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(p_mono, p_buck):
        np.testing.assert_array_equal(a, b)
    fu = t_buck._fused_updater
    assert fu is not None and fu._sync_state is not None
    assert len(fu._sync_plan.buckets) > 1
    assert len(t_mono._fused_updater._sync_plan.buckets) == 1
    assert t_off._fused_updater._sync_state is None
    # ZeRO-1: sharded flats hold 1/N per device
    for slots in fu._sync_state._flats:
        for arr in slots:
            assert arr.addressable_shards[0].data.size * N_DEV \
                == arr.size


def test_gluon_sync_states_roundtrip(tmp_path):
    """save_states materializes the ZeRO-sharded flats back into the
    Updater pickle (interchangeable with non-sync runs); load_states
    re-seeds the sharded layout and the trajectory continues exactly
    as an uninterrupted run."""
    fname = str(tmp_path / "t.states")
    # uninterrupted 6-step reference
    p_ref, _ = _gluon_run(True, 0.001, steps=6)
    # 3 steps, save, fresh 3-step continuation from the pickle
    os.environ["MXNET_GRAD_OVERLAP"] = "1"
    os.environ["MXNET_GRAD_BUCKET_MB"] = "0.001"
    mesh = local_mesh("dp")
    rep = replicated(mesh)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu", in_units=20),
                nn.Dense(10, in_units=32))
        net.initialize()
        params = net.collect_params()
        for i, p in enumerate(params.values()):
            v = np.random.RandomState(20 + i).uniform(
                -0.2, 0.2, p.shape).astype(np.float32)
            p.set_data(mx.nd.array(v))
            p._data._set_data(jax.device_put(p._data._data, rep))
        return net, params

    x = mx.nd.array(np.random.RandomState(7).uniform(
        -1, 1, (16, 20)).astype(np.float32))
    x._set_data(jax.device_put(x._data, rep))

    def steps(net, trainer, n):
        for _ in range(n):
            with autograd.record():
                out = net(x)
                loss = (out * out).mean()
            loss.backward()
            trainer.step(16)

    net1, params1 = build()
    tr1 = gluon.Trainer(params1, "adam", {"learning_rate": 0.05})
    steps(net1, tr1, 3)
    tr1.save_states(fname)
    mid = [p.data().asnumpy().copy() for p in params1.values()]

    net2, params2 = build()
    for p, v in zip(params2.values(), mid):
        p.set_data(mx.nd.array(v))
        p._data._set_data(jax.device_put(p._data._data, rep))
    tr2 = gluon.Trainer(params2, "adam", {"learning_rate": 0.05})
    tr2.load_states(fname)
    steps(net2, tr2, 3)
    p_resumed = [p.data().asnumpy() for p in params2.values()]
    for a, b in zip(p_ref, p_resumed):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the eager kvstore leg
# ---------------------------------------------------------------------------

def test_bucketed_kvstore_sync_matches_per_key():
    """Concat-bucket push/pull through the kvstore is exact: the
    summed result equals the per-key exchange."""
    from mxnet_tpu import kvstore as kvs
    rng = np.random.RandomState(9)
    shapes = [(6, 4), (13,), (3, 3)]
    vals = [rng.normal(0, 1, s).astype(np.float32) for s in shapes]

    kv1 = kvs.create("local")
    ref = []
    for i, v in enumerate(vals):
        kv1.init(i, mx.nd.zeros(v.shape))
        g = mx.nd.array(v)
        kv1.push(i, g)
        kv1.pull(i, g)
        ref.append(g.asnumpy())

    kv2 = kvs.create("local")
    grads = [mx.nd.array(v) for v in vals]
    for i, v in enumerate(vals):
        kv2.init(i, mx.nd.zeros(v.shape))
    ran = grad_sync.bucketed_kvstore_sync(
        kv2, list(enumerate(grads)), cap_bytes=80)
    assert ran
    for r, g in zip(ref, grads):
        np.testing.assert_array_equal(r, g.asnumpy())
    # bucket keys are registered once and reused on the next step
    n_keys = len(kv2._grad_bucket_keys)
    assert n_keys >= 2
    assert grad_sync.bucketed_kvstore_sync(
        kv2, list(enumerate(grads)), cap_bytes=80)
    assert len(kv2._grad_bucket_keys) == n_keys


def test_bucketed_kvstore_sync_sparse_falls_back():
    from mxnet_tpu import kvstore as kvs
    kv = kvs.create("local")
    sp = mx.nd.zeros((4, 3)).tostype("row_sparse")
    assert not grad_sync.bucketed_kvstore_sync(kv, [(0, sp)])
    assert not grad_sync.bucketed_kvstore_sync(kv, [])


def test_module_fit_overlap_identity(tmp_path, monkeypatch):
    """Module.fit through a local kvstore: the bucketed exchange
    (MXNET_GRAD_OVERLAP=1) trains the bit-identical model."""
    def fit(overlap):
        monkeypatch.setenv("MXNET_GRAD_OVERLAP",
                           "1" if overlap else "0")
        monkeypatch.setenv("MXNET_UPDATE_ON_KVSTORE", "0")
        rng = np.random.RandomState(5)
        x = rng.normal(0, 1, (64, 32)).astype(np.float32)
        y = rng.randint(0, 10, 64).astype(np.float32)
        it = mx.io.NDArrayIter(x, y, batch_size=32,
                               label_name="softmax_label")
        d = mx.sym.Variable("data")
        f1 = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
        a1 = mx.sym.Activation(f1, act_type="relu")
        f2 = mx.sym.FullyConnected(a1, num_hidden=10, name="fc2")
        s = mx.sym.SoftmaxOutput(f2, name="softmax")
        mx.random.seed(7)
        np.random.seed(7)
        mod = mx.module.Module(s, context=mx.cpu())
        mod.fit(it, optimizer="sgd", kvstore="local",
                optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9},
                num_epoch=2, initializer=mx.init.Xavier())
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    base = fit(False)
    overlapped = fit(True)
    assert base.keys() == overlapped.keys()
    for k in base:
        np.testing.assert_array_equal(base[k], overlapped[k],
                                      err_msg=k)


# ---------------------------------------------------------------------------
# sharded optimizer state through checkpoint.py (manifest format)
# ---------------------------------------------------------------------------

@WIDTHS
def test_checkpoint_roundtrip_sharded_state(tmp_path, widths):
    """Sharded optimizer state rides checkpoint.py's manifest as
    opt:bucketBB.slotS entries whose per-device pieces land in the
    per-mesh-position shard files; the resumed trajectory is
    bit-identical to the uninterrupted run."""
    prefix = str(tmp_path / "ck")
    l_ref, p_ref, _ = _dist_run(True, "adam", {"learning_rate": 0.01},
                                steps=6, widths=widths)
    # 3 steps → save → fresh trainer restores → 3 more steps
    _, _, tr1 = _dist_run(True, "adam", {"learning_rate": 0.01},
                          steps=3, widths=widths)
    tr1.save_checkpoint(prefix, 0)
    manifest = json.load(open("%s-0000.ckpt.json" % prefix))
    opt_keys = [k for k in manifest["params"]
                if k.startswith("opt:bucket")]
    assert opt_keys and all(".slot" in k for k in opt_keys)
    # sharded entries: every mesh position owns a piece
    assert any(len(manifest["params"][k]["pieces"]) == N_DEV
               for k in opt_keys)
    # what the manifest holds is 1-D and in roster order, whatever the
    # device layout: as long as the members and no longer than their
    # sum padded to the axis
    for b, bucket in enumerate(tr1._plan.buckets):
        (length,) = manifest["params"]["opt:bucket%02d.slot0" % b]["shape"]
        assert bucket.total <= length < bucket.total + N_DEV

    _, _, tr2 = _dist_run(True, "adam", {"learning_rate": 0.01},
                          steps=0, widths=widths)
    tr2.load_checkpoint(prefix, 0)
    rng = np.random.RandomState(3)
    for _ in range(3):
        rng.randn(16, widths[2])
        rng.randint(0, 10, (16,))
    losses = []
    for _ in range(3):
        data = mx.nd.array(rng.randn(16, widths[2]).astype(np.float32))
        label = mx.nd.array(
            rng.randint(0, 10, (16,)).astype(np.float32))
        losses.append(float(tr2.fit_batch(data, label).asnumpy()))
    tr2.sync_gluon_params()
    assert losses == l_ref[3:]


@WIDTHS
def test_killed_save_elastic_resume_sharded_state(tmp_path, widths):
    """The PR 6 tie-in end-to-end: a fault-injected kill during the
    sharded save leaves no usable epoch-1 manifest; resume falls back
    to epoch 0 and re-pads the flat sharded optimizer state for a
    SMALLER mesh (8 → 2 devices) — elastic across topologies."""
    from mxnet_tpu import checkpoint as ck
    from mxnet_tpu.model import latest_checkpoint_scan
    prefix = str(tmp_path / "kill")
    _, _, tr = _dist_run(True, "adam", {"learning_rate": 0.01},
                         steps=2, widths=widths)
    tr.save_checkpoint(prefix, 0)
    fault.set_plan("ckpt_write:step=1:raise")
    with pytest.raises(Exception):
        tr.save_checkpoint(prefix, 1)
    fault.set_plan("")
    assert ck.load_manifest(prefix, 1) is None
    found = latest_checkpoint_scan(prefix)
    assert found is not None and found[0] == 0

    # resume the sharded state on a 2-device mesh
    mesh2 = create_mesh({"dp": 2}, devices=jax.devices()[:2])
    net = _dist_net(widths)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    tr2 = DistributedTrainer(net, loss, mesh2, optimizer="adam",
                             optimizer_params={"learning_rate": 0.01},
                             grad_overlap=True, bucket_mb=0.001)
    tr2.load_checkpoint(prefix, 0)
    data = mx.nd.array(np.random.RandomState(0)
                       .randn(16, widths[2]).astype(np.float32))
    label = mx.nd.array(np.random.RandomState(0)
                        .randint(0, 10, (16,)).astype(np.float32))
    # the moments the 8 chips saved are the moments the 2 chips hold,
    # parameter by parameter: rows of another length, the same values
    tr2._build(data, label)          # applies the staged restore
    shapes = {i: tuple(v.shape) for i, v in enumerate(tr._param_vals)}
    saved_state = tr._sync_state.export_per_param(shapes)
    restored = tr2._sync_state.export_per_param(shapes)
    assert sorted(saved_state) == sorted(restored) == sorted(shapes)
    for i in shapes:
        for a, b in zip(saved_state[i], restored[i]):
            np.testing.assert_array_equal(a, b)
            assert np.any(a != 0)
    tr2.fit_batch(data, label).asnumpy()     # steps fine post-restore
    # restored state values equal the saved ones (per-param layout
    # bridges the two plans/topologies)
    saved = ck.load_arrays(prefix, 0)
    tr2.sync_gluon_params()
    for pos, n in enumerate(tr2._roster):
        key = "arg:%s" % n
        assert key in saved


def test_checkpoint_restore_rejects_changed_bucket_layout(tmp_path):
    """A restore under a different bucket partition (another
    MXNET_GRAD_BUCKET_MB) must refuse — a prefix slice could silently
    hand one bucket's moments to another's parameters — and must
    leave the trainer fully untouched (params included: the restore
    validates the opt state before mutating anything)."""
    prefix = str(tmp_path / "ck")
    _, _, tr1 = _dist_run(True, "adam", {"learning_rate": 0.01},
                          steps=1)
    tr1.save_checkpoint(prefix, 0)
    _, _, tr2 = _dist_run(True, "adam", {"learning_rate": 0.01},
                          steps=1, bucket_mb=4.0)   # one big bucket
    assert len(tr2._plan.buckets) != len(tr1._plan.buckets)
    before = [np.asarray(v).copy() for v in tr2._param_vals]
    with pytest.raises(Exception, match="bucket partition"):
        tr2.load_checkpoint(prefix, 0)
    for a, v in zip(before, tr2._param_vals):
        np.testing.assert_array_equal(a, np.asarray(v))


_ODD = [(5, 3), (9001,), (1,), (N_DEV * 1024,), (3, 3)]


@pytest.mark.parametrize("shapes,cap", [
    ([(5, 3), (7,), (2, 2)], 4 * 10),
    # odd sizes; one fills more than a tile a row, one exactly a tile
    (_ODD, 4 * 10),
    # ... and all in ONE bucket: the large members first on whole
    # tiles, the small ones behind them at their exact share
    (_ODD, grad_sync.MONOLITH_CAP)],
    ids=["small", "odd", "odd_one_bucket"])
def test_sharded_state_seed_export_inverse(shapes, cap):
    """seed_per_param and export_per_param are inverses over the
    bucket layout (the Updater-pickle interchange bridge)."""
    mesh = local_mesh("dp")
    n = len(shapes)
    plan = GradSyncPlan(shapes, ["float32"] * n, axis_size=N_DEV,
                        cap_bytes=cap)
    st = grad_sync.ShardedOptState(plan, mesh)
    st.n_slots = 2
    st._slot_dtypes = ["float32", "float32"]
    rng = np.random.RandomState(2)
    per_param = {i: [rng.normal(0, 1, s).astype(np.float32)
                     for _ in range(2)]
                 for i, s in enumerate(shapes)}
    st.seed_per_param(per_param)
    out = st.export_per_param({i: s for i, s in enumerate(shapes)})
    for i in range(n):
        for k in range(2):
            np.testing.assert_array_equal(per_param[i][k], out[i][k])
    # on the device: one row a chip
    for bucket, slots in zip(plan.buckets, st._flats):
        for arr in slots:
            assert arr.shape == (N_DEV, bucket.row_len)
            assert arr.addressable_shards[0].data.shape \
                == (1, bucket.row_len)
    # checkpoint roster keys follow the manifest naming contract,
    # plus the bucket-partition fingerprint guarding restores; the
    # re-layout to roster order is ONE program for every bucket and
    # slot, compiled at the first save and not at the second
    from mxnet_tpu import compile_watch
    was_on = compile_watch.enabled()
    compile_watch.enable()
    try:
        def compiles():
            return sum(s["count"] for s in compile_watch.site_stats(
                "grad_sync:roster_order").values())
        seen = compiles()
        roster = st.checkpoint_roster()
        st.checkpoint_roster()
        assert compiles() == seen + 1
    finally:
        if not was_on:
            compile_watch.disable()
    assert sorted(roster) == sorted(
        ["opt:bucket%02d.slot%d" % (b, k)
         for b in range(len(plan.buckets)) for k in range(2)]
        + ["opt:layout"])
    # load_host_flats re-pads for the current axis: feed back the
    # host values with save-time padding stripped at a DIFFERENT size
    host = {k: np.asarray(v) for k, v in roster.items()}
    # ... which hold each bucket slot 1-D in ROSTER ORDER, the members
    # end to end, whatever the rows on the device look like
    for b, bucket in enumerate(plan.buckets):
        np.testing.assert_array_equal(
            host["opt:bucket%02d.slot1" % b][:bucket.total],
            np.concatenate([per_param[i][1].reshape(-1)
                            for i in bucket.indices]))
    st2 = grad_sync.ShardedOptState(plan, mesh)
    st2.n_slots, st2._slot_dtypes = 2, ["float32", "float32"]
    st2.load_host_flats(host)
    out2 = st2.export_per_param({i: s for i, s in enumerate(shapes)})
    for i in range(n):
        np.testing.assert_array_equal(out[i][0], out2[i][0])
    # a checkpoint saved on another axis size (here: as one chip would
    # pad it, not at all) restores to the same moments
    st3 = grad_sync.ShardedOptState(plan, mesh)
    st3.n_slots, st3._slot_dtypes = 2, ["float32", "float32"]
    st3.load_host_flats({k: v if k == "opt:layout"
                         else v[:plan.buckets[int(k[10:12])].total]
                         for k, v in host.items()})
    out3 = st3.export_per_param({i: s for i, s in enumerate(shapes)})
    for i in range(n):
        np.testing.assert_array_equal(out[i][1], out3[i][1])


# ---------------------------------------------------------------------------
# telemetry: Sync table
# ---------------------------------------------------------------------------

def test_diagnose_sync_table(tmp_path, capsys):
    """grad_sync comm records (in-program bytes + eager spans) render
    as the diagnose Gradient sync table with the sync-phase share."""
    sink = str(tmp_path / "run.jsonl")
    telemetry.start(filename=sink)
    telemetry.step_begin()
    plan = GradSyncPlan([(64,), (32,)], ["float32"] * 2,
                        axis_size=N_DEV, cap_bytes=4 * 40)
    grad_sync.account_in_program_sync(plan)
    with telemetry.span("sync"):
        pass
    telemetry.step_end(samples=16)
    telemetry.stop()

    from mxnet_tpu.tools import diagnose
    tel = diagnose.read_telemetry(sink)
    text = diagnose.format_telemetry(tel)
    assert "Gradient sync" in text
    assert "bucket00" in text and "bucket01" in text
    assert "sync share" in text
    assert "in-program   : 1 step(s)" in text
    # CLI round trip
    rc = diagnose.main([sink])
    assert rc in (None, 0)
    out = capsys.readouterr().out
    assert "Gradient sync" in out


def test_in_program_accounting_bytes():
    """Each bucket ledgers RS+AG payload (2x) under grad_sync with
    zero latency — the exchange is scheduled inside the program."""
    telemetry.start()
    plan = GradSyncPlan([(100,)], ["float32"], axis_size=N_DEV)
    grad_sync.account_in_program_sync(plan)
    rep = telemetry.report()
    row = rep["comms"]["grad_sync:bucket00"]
    assert row["bytes"] == 2 * plan.buckets[0].nbytes
    assert row["time_ms"] == 0.0
    assert rep["events"]["grad_sync_steps"] == 1
    telemetry.stop()
