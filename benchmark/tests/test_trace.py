"""The trace arithmetic on recorded fixtures: events extracted from real
v5e traces of this program (GBT: 450 ms inside ``jit__gbt_forest_impl``;
NN: one epoch between two validation passes), name, start and duration per
line."""

import os

import pytest

from benchmark import trace as T
from benchmark.readers import hist_roofline, idle_share, nn_epoch, op_share

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


class FakeCtx:
    device_kind = "TPU v5 lite"
    counters = {"params": {"macs_per_row": 165000.0, "train_rows": 2097152 * 0.8}}

    def say(self, msg):
        pass


@pytest.fixture(scope="module")
def gbt():
    return T.Summary(T.read_tsv(os.path.join(FIX, "gbt_trace.tsv")))


@pytest.fixture(scope="module")
def nn():
    return T.Summary(T.read_tsv(os.path.join(FIX, "nn_trace.tsv")))


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.total(T.union([(0, 10), (2, 3), (9, 12)])) == 12
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.short_name("%fusion.355 = f32[4194304]{0:T(1024)} fusion(...)") == "fusion.355"


def test_busy_is_a_union_not_a_sum(gbt):
    """The ``XLA Ops`` line holds the enclosing %while and its body ops:
    durations sum to twice the window, the union is the window."""
    plane = gbt.planes[0]
    summed = sum(e - s for _, s, e in gbt.ops[plane]) / 1e9
    assert summed == pytest.approx(2 * gbt.window_s, rel=0.01)
    assert gbt.busy_s == pytest.approx(gbt.window_s, rel=0.01)
    assert idle_share.read(gbt, FakeCtx()) == pytest.approx(0.0, abs=1.0)


def test_wrappers_and_async_ops_stay_out_of_the_op_table(gbt):
    names = [n for n, _ in gbt.op_table(10)]
    assert not any(n.startswith("while") for n in names)
    assert names[0].startswith("build_histograms_pallas")
    async_sum = sum(e - s for _, s, e in gbt.async_ops[gbt.planes[0]]) / 1e9
    assert async_sum > 2 * gbt.window_s          # they overlap compute, and each other
    assert sum(t for _, t in gbt.op_table(1000)) <= gbt.window_s * 1.001


def test_hist_kernel_share_and_roofline(gbt):
    share = op_share.read(gbt, FakeCtx(), pattern="^build_histograms_pallas")
    assert 75.0 < share < 90.0                   # the old trace: 82 % of device time
    roof = hist_roofline.read(gbt, FakeCtx(), pattern="^build_histograms_pallas",
                              n_feat=66, n_bins=65)
    assert 0.5 < roof < 25.0                     # far under its roofline, and never over 100


def test_nn_epoch_readers(nn):
    ctx = FakeCtx()
    device = nn_epoch.read(nn, ctx, "device", "epoch_steps", "eval_errors")
    assert device == pytest.approx(19.96 + 2 * 4.8, rel=0.02)   # one step program, two validations
    idle = idle_share.read(nn, ctx)
    assert 10.0 < idle < 25.0
    gaps = nn.idle_gaps(5)
    assert gaps and all(name.startswith("bench:train_call") for name, _ in gaps)
    assert nn_epoch.read(nn, ctx, "roofline", "epoch_steps") < 100.0
    assert nn_epoch.read(None, ctx, "device", "epoch_steps") is None


def test_a_reader_with_nothing_to_read_returns_none(nn):
    assert op_share.read(nn, FakeCtx(), pattern="^build_histograms_pallas") is None
    assert hist_roofline.read(nn, FakeCtx(), pattern="^build_histograms_pallas",
                              n_feat=66, n_bins=65) is None
    assert idle_share.read(None, FakeCtx()) is None


def test_markers_set_the_window():
    ev = [("/device:TPU:0", T.OPS_LINE, "%fusion.1 = f32[8]{0} fusion()", 100.0, 50.0),
          ("/host:CPU", "python3", "bench:trace_start", 0.0, 1.0),
          ("/host:CPU", "python3", "bench:trace_stop", 1000.0, 1.0),
          ("/host:CPU", "bench", "bench:job", -500.0, 900.0)]
    s = T.Summary(ev)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(50e-9)
    assert s.spans == [("bench:job", 0.0, 400.0)]
    assert s.idle_gaps(1)[0][0].startswith("bench:job")
