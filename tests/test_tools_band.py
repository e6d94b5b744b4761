"""Tool-band parity: bandwidth probe, rec2idx, parse_log (ref tools/)."""
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import recordio


def test_bandwidth_measure_runs_and_checks(tmp_path):
    from mxnet_tpu.tools.bandwidth import measure
    shapes = [(8, 4), (16,), (3, 3, 2)]
    rows = measure(shapes, num_workers=2, num_batches=2)
    assert len(rows) == 2
    for r in rows:
        assert r["error"] == 0
        # a CPU run supplies counts, never a rate: 264 bytes over a
        # loaded machine's seconds round to 0.0 GB/s
        assert r["time_s"] > 0


def test_rec2idx_roundtrip(tmp_path):
    from mxnet_tpu.tools.rec2idx import build_index
    rec_path = str(tmp_path / "a.rec")
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(5):
        rec.write(recordio.pack(
            recordio.IRHeader(0, float(i), 100 + i, 0),
            b"payload%d" % i))
    rec.close()
    idx_path = str(tmp_path / "a.idx")
    n = build_index(rec_path, idx_path)
    assert n == 5
    indexed = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert indexed.keys == [100 + i for i in range(5)]
    header, payload = recordio.unpack(indexed.read_idx(103))
    assert payload == b"payload3" and header.id == 103


def test_parse_log():
    from mxnet_tpu.tools.parse_log import parse, format_table
    lines = [
        "INFO Epoch[0] Train-accuracy=0.75",
        "INFO Epoch[0] Validation-accuracy=0.70",
        "INFO Epoch[0] Time cost=12.5",
        "INFO Epoch[1] Train-accuracy=0.85",
        "INFO Epoch[1] Time cost=11.0",
    ]
    t = parse(lines)
    assert t[0] == {"train-accuracy": 0.75, "val-accuracy": 0.70,
                    "time": 12.5}
    assert t[1]["train-accuracy"] == 0.85
    txt = format_table(t)
    assert "epoch" in txt and "0.85" in txt and "-" in txt


def test_parse_log_scientific_and_negative_values():
    """The old ([.\\d]+) value pattern silently truncated `1e-07` to 1.0
    and dropped the sign of negative metrics."""
    from mxnet_tpu.tools.parse_log import parse
    lines = [
        "INFO Epoch[0] Train-cross-entropy=1e-07",
        "INFO Epoch[0] Validation-cross-entropy=2.5e-03",
        "INFO Epoch[1] Train-cross-entropy=-0.125",
        "INFO Epoch[1] Validation-cross-entropy=1.5E+02",
        "INFO Epoch[1] Time cost=3.25",
    ]
    t = parse(lines, metric_names=("cross-entropy",))
    assert t[0]["train-cross-entropy"] == 1e-07
    assert t[0]["val-cross-entropy"] == 2.5e-03
    assert t[1]["train-cross-entropy"] == -0.125
    assert t[1]["val-cross-entropy"] == 150.0
    assert t[1]["time"] == 3.25
