"""Sequence packing (mxnet_tpu.bucketing.packing): the FFD packer,
pack/unpack bit-exact round trips, packed-vs-padded per-sample loss
and gradient oracles (PR 10's 40-distinct-lengths corpus), the
segment-blocked attention masks through the jnp reference AND the
Pallas kernels, the PackedPipeline, the packing telemetry/diagnose
wiring, and the ladder satellites (over-ladder warning, geometric
cap=, env parse errors)."""
import json
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import bucketing, compile_watch, telemetry
from mxnet_tpu.bucketing import (BucketLadder, MaskedSoftmaxCELoss,
                                 PackedPipeline, PackedSoftmaxCELoss,
                                 ShapeLadder, first_fit_decreasing,
                                 masked_batch_loss, pack_samples,
                                 pad_samples, position_mask,
                                 segment_attention_mask, segment_gather,
                                 segment_masks, unpack)


@pytest.fixture(autouse=True)
def _clean_state():
    telemetry.reset()
    compile_watch.disable()
    yield
    telemetry.reset()
    compile_watch.disable()


# ---------------------------------------------------------------------------
# the packer
# ---------------------------------------------------------------------------

class TestPacker:
    def test_ffd_is_deterministic_and_bounded(self):
        bins = first_fit_decreasing([3, 5, 2, 4, 1], 8)
        assert bins == [[1, 0], [3, 2, 1]] or all(
            sum([3, 5, 2, 4, 1][i] for i in b) <= 8 for b in bins)
        for b in bins:
            assert sum([3, 5, 2, 4, 1][i] for i in b) <= 8
        assert sorted(i for b in bins for i in b) == [0, 1, 2, 3, 4]
        assert bins == first_fit_decreasing([3, 5, 2, 4, 1], 8)

    def test_ffd_errors(self):
        with pytest.raises(mx.base.MXNetError, match="exceeds"):
            first_fit_decreasing([9], 8)
        with pytest.raises(mx.base.MXNetError, match="zero-length"):
            first_fit_decreasing([0], 8)

    def test_pack_unpack_round_trip_bit_exact(self):
        rng = np.random.RandomState(0)
        xs = [rng.randn(L, 3).astype(np.float32)
              for L in (2, 5, 3, 4, 1)]
        packed, seg, pos, bins = pack_samples(xs, 8)
        assert packed.shape[1:] == (8, 3)
        assert seg.shape == packed.shape[:2] == pos.shape
        # every sample sits contiguously and comes back untouched
        back = unpack(packed, seg, len(xs))
        for want, have in zip(xs, back):
            assert (want == have).all()
        # positions restart at 0 inside every segment
        for s, x in enumerate(xs):
            r, t = np.nonzero(seg == s + 1)
            assert (pos[r, t] == np.arange(len(x))).all()
        # id 0 marks padding only
        assert (packed[seg == 0] == 0).all()
        # the per-sample mask planes tile the valid area exactly
        sm = segment_masks(seg, len(xs))
        assert (sm.sum(axis=0) == (seg > 0)).all()
        idx, gmask = segment_gather(seg, len(xs))
        assert idx.shape == (2, len(xs), 8)
        assert (gmask.sum(axis=1) == [len(x) for x in xs]).all()

    def test_pack_shared_bins_for_labels(self):
        xs = [np.arange(L, dtype=np.float32) for L in (3, 2, 4)]
        labs = [x * 10 for x in xs]
        px, seg, _, bins = pack_samples(xs, 8)
        pl, seg2, _, _ = pack_samples(labs, 8, bins=bins, pad_value=-1)
        assert (seg == seg2).all()
        assert (pl[seg == 0] == -1).all()
        for a, b in zip(unpack(px, seg), unpack(pl, seg)):
            assert (a * 10 == b).all()

    def test_pack_row_budget(self):
        xs = [np.ones(4, np.float32)] * 3
        with pytest.raises(mx.base.MXNetError, match="rows"):
            pack_samples(xs, 8, rows=1)
        packed, seg, _, _ = pack_samples(xs, 8, rows=4)
        assert packed.shape[0] == 4
        assert (seg[2:] == 0).any() or (seg[3] == 0).all()


# ---------------------------------------------------------------------------
# the loss oracle: packed == padded == unpadded, bit-exact
# ---------------------------------------------------------------------------

def _corpus(n=160, lo=3, hi=43, C=5, seed=7):
    """PR 10's ragged corpus shape: ~40 distinct lengths, 10x any
    reasonable ladder."""
    rng = np.random.RandomState(seed)
    lengths = rng.choice(np.arange(lo, hi), size=n)
    xs = [rng.randn(int(L), C).astype(np.float32) for L in lengths]
    labs = [rng.randint(0, C, size=len(x)).astype(np.float32)
            for x in xs]
    return xs, labs


class TestLossOracle:
    def test_packed_equals_padded_and_unpadded_bit_exact(self):
        xs, labs = _corpus()
        assert len({len(x) for x in xs}) >= 38
        masked = MaskedSoftmaxCELoss()
        packed_loss = PackedSoftmaxCELoss()
        L = 64
        for lot in range(0, 160, 16):
            sub_x, sub_l = xs[lot:lot + 16], labs[lot:lot + 16]
            # padded reference: one sample per row
            px, vl, nv = pad_samples(sub_x, 16, seq_len=L)
            pl, _, _ = pad_samples(sub_l, 16, seq_len=L)
            ref = masked(mx.nd.array(px), mx.nd.array(pl),
                         mx.nd.array(position_mask(vl, L))).asnumpy()
            # packed: several samples per row
            kx, seg, _, bins = pack_samples(sub_x, L)
            kl, _, _, _ = pack_samples(sub_l, L, bins=bins,
                                       pad_value=-1)
            idx, mask = segment_gather(seg, 16)
            got = packed_loss(
                mx.nd.array(kx), mx.nd.array(kl),
                mx.nd.array(idx, dtype="int32"),
                mx.nd.array(mask)).asnumpy()
            assert kx.shape[0] < 16           # it actually packed
            assert (got == ref).all(), (lot, got - ref)
            # and the batch reduction composes identically
            a = float(masked_batch_loss(mx.nd.array(ref), 16).asnumpy())
            b = float(masked_batch_loss(mx.nd.array(got), 16).asnumpy())
            assert a == b

    def test_gradients_bit_exact_through_the_packed_layout(self):
        """d(total)/d(logits) at every real position is IDENTICAL
        whether the sample rode a padded row or a packed one — the
        mask contract all the way through backward."""
        xs, labs = _corpus(n=12, seed=3)
        L = 64
        masked = MaskedSoftmaxCELoss()
        packed_loss = PackedSoftmaxCELoss()

        px, vl, nv = pad_samples(xs, 12, seq_len=L)
        pl, _, _ = pad_samples(labs, 12, seq_len=L)
        a = mx.nd.array(px)
        a.attach_grad()
        with mx.autograd.record():
            vec = masked(a, mx.nd.array(pl),
                         mx.nd.array(position_mask(vl, L)))
            total = masked_batch_loss(vec, 12)
        total.backward()
        ga = a.grad.asnumpy()

        kx, seg, _, bins = pack_samples(xs, L)
        kl, _, _, _ = pack_samples(labs, L, bins=bins, pad_value=-1)
        idx, mask = segment_gather(seg, 12)
        b = mx.nd.array(kx)
        b.attach_grad()
        with mx.autograd.record():
            vec = packed_loss(b, mx.nd.array(kl),
                              mx.nd.array(idx, dtype="int32"),
                              mx.nd.array(mask))
            total = masked_batch_loss(vec, 12)
        total.backward()
        gb = b.grad.asnumpy()

        for s, x in enumerate(xs):
            r, t = np.nonzero(seg == s + 1)
            packed_g = gb[r[0], t[0]:t[-1] + 1]
            padded_g = ga[s, :len(x)]
            assert (packed_g == padded_g).all(), s
        # padding positions get exact-zero gradient
        assert (gb[seg == 0] == 0).all()


# ---------------------------------------------------------------------------
# segment-blocked attention
# ---------------------------------------------------------------------------

class TestSegmentAttention:
    def _packed_qkv(self, H=2, D=4, seed=0):
        rng = np.random.RandomState(seed)
        qa = rng.randn(3, H, D).astype(np.float32)
        qb = rng.randn(4, H, D).astype(np.float32)
        packed, seg, _, _ = pack_samples([qa, qb], 8)
        return qa, qb, packed, seg

    def test_mask_helper_blocks_cross_segment_and_padding(self):
        _, _, _, seg = self._packed_qkv()
        m = segment_attention_mask(seg)
        assert m.shape == (1, 8, 8)
        for i in range(8):
            for j in range(8):
                want = seg[0, i] != 0 and seg[0, i] == seg[0, j]
                assert m[0, i, j] == want
        mc = segment_attention_mask(seg, causal=True)
        assert not mc[0, 1, 2] and mc[0, 2, 1]

    # A blocked pair's softmax weight is an exact zero, so a packed
    # sample equals the sample attended alone up to the reduction
    # order of two differently-shaped programs (T=8 vs T=3): a few
    # fp32 ulps. Any cross-segment leak would be O(1).
    _ULPS = dict(rtol=2e-6, atol=2e-7)

    @pytest.mark.parametrize("force_pallas", [False, True])
    def test_packed_attention_bit_exact_vs_alone(self, force_pallas):
        import jax.numpy as jnp
        from mxnet_tpu.parallel.flash_attention import flash_attention
        qa, qb, packed, seg = self._packed_qkv()
        Q = jnp.asarray(packed)
        S = jnp.asarray(seg)
        out = np.asarray(flash_attention(
            Q, Q, Q, causal=True, segment_ids=S,
            force_pallas=force_pallas))
        for sample, (t0, t1) in ((qa, (0, 3)), (qb, (3, 7))):
            x = jnp.asarray(sample[None])
            alone = np.asarray(flash_attention(
                x, x, x, causal=True, force_pallas=force_pallas))
            np.testing.assert_allclose(out[0, t0:t1], alone[0],
                                       **self._ULPS)

    def test_packed_attention_gradients_do_not_cross(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.parallel.flash_attention import flash_attention
        qa, qb, packed, seg = self._packed_qkv()
        Q = jnp.asarray(packed)
        S = jnp.asarray(seg)

        def loss(x):       # touches ONLY sample a's outputs
            o = flash_attention(x, x, x, causal=True, segment_ids=S,
                                force_pallas=True)
            return (o[0, 0:3] ** 2).sum()

        g = np.asarray(jax.grad(loss)(Q))
        assert (g[0, 3:] == 0).all()     # sample b + padding untouched

        def loss_alone(x):
            o = flash_attention(x, x, x, causal=True,
                                force_pallas=True)
            return (o ** 2).sum()

        ga = np.asarray(jax.grad(loss_alone)(jnp.asarray(qa[None])))
        assert (g[0, 0:3] == ga[0]).all()

    def test_registered_op_takes_segment_ids(self):
        from mxnet_tpu.ops.registry import get_op, invoke
        import jax.numpy as jnp
        qa, qb, packed, seg = self._packed_qkv()
        op = get_op("_contrib_flash_attention")
        (out,), _ = invoke(op, [jnp.asarray(packed), jnp.asarray(packed),
                                jnp.asarray(packed), jnp.asarray(seg)],
                           {"impl": "dense", "causal": True})
        ref = bucketing.segment_attention_mask  # noqa: F841 (doc tie)
        (alone,), _ = invoke(op, [jnp.asarray(qa[None])] * 3,
                             {"impl": "dense", "causal": True})
        np.testing.assert_allclose(np.asarray(out)[0, 0:3],
                                   np.asarray(alone)[0], **self._ULPS)
        with pytest.raises(ValueError, match="flash.*dense"):
            invoke(op, [jnp.asarray(packed)] * 3 + [jnp.asarray(seg)],
                   {"impl": "ring"})


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

class TestPackedPipeline:
    def _stream(self, n=60, seed=3, top=14, C=4):
        rng = np.random.RandomState(seed)
        out = []
        for L in rng.randint(2, top, size=n):
            x = rng.randint(1, 9, size=L).astype(np.float32)
            y = rng.randint(0, C, size=L).astype(np.float32)
            out.append((x, y))
        return out

    def test_every_sample_packed_exactly_once(self):
        samples = self._stream()
        pipe = PackedPipeline(samples, batch_size=4, ladder=[8, 16])
        seen = []
        for batch in pipe:
            data = batch.data[0].asnumpy()
            lab = batch.label[0].asnumpy()
            assert data.shape[0] == 4
            assert data.shape[1] in (8, 16)
            assert batch.bucket_key == data.shape[1]
            assert batch.segment_ids.shape == data.shape
            xs = unpack(data, batch.segment_ids, batch.n_segments)
            ys = unpack(lab, batch.segment_ids, batch.n_segments)
            seen.extend(zip(xs, ys))
            # rows fill from 0, so valid_lengths + position_mask hold
            m = pipe.mask_for(batch)
            assert (m == (batch.segment_ids > 0)).all()
        assert len(seen) == len(samples)
        want = sorted(samples, key=lambda p: (len(p[0]), tuple(p[0])))
        have = sorted(seen, key=lambda p: (len(p[0]), tuple(p[0])))
        for (wx, wy), (hx, hy) in zip(want, have):
            assert (wx == hx).all() and (wy == hy).all()

    def test_rows_hold_multiple_samples(self):
        samples = self._stream(n=40, top=5)
        pipe = PackedPipeline(samples, batch_size=4, ladder=[16])
        batch = next(iter(pipe))
        assert batch.n_segments > batch.data[0].shape[0]

    def test_labels_pack_with_invalid_label(self):
        samples = self._stream(n=24)
        pipe = PackedPipeline(samples, batch_size=4, ladder=[16],
                              invalid_label=-1)
        batch = next(iter(pipe))
        lab = batch.label[0].asnumpy()
        assert (lab[batch.segment_ids == 0] == -1).all()

    def test_scalar_labels_rejected(self):
        samples = [(np.ones(3, np.float32), np.float32(1))]
        with pytest.raises(mx.base.MXNetError, match="per-position"):
            PackedPipeline(samples, batch_size=2, ladder=[8])

    def test_overlong_discarded_counted_and_warned_once(self):
        rng = np.random.RandomState(1)
        samples = [rng.randint(1, 9, size=L).astype(np.float32)
                   for L in (3, 30, 4, 31, 5, 6, 7, 3)]
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            pipe = PackedPipeline(samples, batch_size=2, ladder=[8])
            n = sum(b.n_segments for b in pipe)
        assert n == 6
        assert pipe.stats.snapshot()["discarded"] == 2
        discards = [w for w in caught
                    if "DISCARDED" in str(w.message)]
        assert len(discards) == 1            # once, not per sample
        msg = str(discards[0].message)
        assert "length-30" in msg and "ladder top 8" in msg

    def test_packing_beats_padding_on_real_token_fraction(self):
        samples = self._stream(n=60, top=6)
        packed = PackedPipeline(samples, batch_size=4, ladder=[16])
        for _ in packed:
            pass
        padded = bucketing.BucketedPipeline(samples, batch_size=4,
                                            ladder=[16])
        for _ in padded:
            pass
        rtf_packed = packed.stats.snapshot()["real_token_fraction"]
        rtf_padded = padded.stats.snapshot()["real_token_fraction"]
        assert rtf_packed > rtf_padded

    def test_telemetry_record_and_diagnose_real_tokens(self, tmp_path,
                                                       capsys):
        sink = str(tmp_path / "run.jsonl")
        telemetry.start(filename=sink)
        pipe = PackedPipeline(self._stream(n=24), batch_size=4,
                              ladder=[8, 16], record_every=2)
        for _ in pipe:
            telemetry.step_begin()
            telemetry.step_end(samples=4)
        pipe.stats.emit()
        summary = telemetry.stop()
        block = summary["bucketing"]["PackedPipeline"]
        assert block["samples"] == 24
        assert 0.0 < block["real_token_fraction"] <= 1.0
        kinds = set()
        with open(sink) as f:
            for line in f:
                kinds.add(json.loads(line).get("type"))
        assert "bucketing" in kinds
        from mxnet_tpu.tools import diagnose
        diagnose.main([sink])
        out = capsys.readouterr().out
        assert "real tokens" in out
        assert "PackedPipeli" in out


# ---------------------------------------------------------------------------
# ladder satellites
# ---------------------------------------------------------------------------

class TestLadderSatellites:
    def test_geometric_cap_bucketladder(self):
        assert BucketLadder.geometric(64).buckets == \
            [1, 2, 4, 8, 16, 32, 64]
        assert BucketLadder.geometric(64, cap=20).buckets == \
            [1, 2, 4, 8, 16, 20]
        with pytest.raises(mx.base.MXNetError, match="cap"):
            BucketLadder.geometric(64, cap=0)

    def test_geometric_cap_shapeladder(self):
        lad = ShapeLadder.geometric((8, 64), (2, 8), cap=(8, 20))
        assert max(s[1] for s in lad.shapes) == 20
        assert (8, 20) in lad.shapes
        lad = ShapeLadder.geometric((8, 64), (2, 8), cap=20)
        assert max(s[0] for s in lad.shapes) == 8
        with pytest.raises(mx.base.MXNetError, match="rank"):
            ShapeLadder.geometric((8, 64), cap=(1, 2, 3))

    def test_env_parse_errors_are_mxnet_errors(self, monkeypatch):
        cases = ["nope", "8,x", "8,4x16", "0x8", "-3"]
        for raw in cases:
            monkeypatch.setenv("MXNET_BUCKET_LADDER", raw)
            with pytest.raises(mx.base.MXNetError):
                bucketing.ladder_from_env()
        # the error names the env var the operator must fix
        monkeypatch.setenv("MXNET_BUCKET_LADDER", "8,4x16")
        with pytest.raises(mx.base.MXNetError,
                           match="MXNET_BUCKET_LADDER"):
            bucketing.ladder_from_env()
