"""The readers of the program's own spans, on synthetic events: a job of two
epochs whose spans and device programs are laid out by hand (times in ms,
given to the readers in ns), and the extraction itself on a real ``.xplane.pb``
written on the CPU."""

import glob
import os

import pytest

from benchmark import spans as S
from benchmark import trace as T
from benchmark.readers import epoch_host, idle_unattributed, job_head, span_seconds

MS = 1e6
DEV = "/device:TPU:0"
CONTAINERS = ["TRAIN", "process", "train"]


class Ctx:
    def __init__(self, spans):
        self.program_spans = spans
        self.said = []

    def say(self, msg):
        self.said.append(msg)


def span(name, a, b, sid, parent=None, **attrs):
    return S.Span(name, a * MS, b * MS, sid, parent, attrs)


def summary(ops, modules=(), window=(0, 1000)):
    ev = [("/host:CPU", "python", "bench:trace_start", window[0] * MS, 0.0),
          ("/host:CPU", "python", "bench:trace_stop", window[1] * MS, 0.0)]
    ev += [(DEV, T.OPS_LINE, f"%fusion.{i} = f32[8]{{0}} fusion()", a * MS, (b - a) * MS)
           for i, (a, b) in enumerate(ops)]
    ev += [(DEV, T.MODULES_LINE, f"{n}({i})", a * MS, (b - a) * MS)
           for i, (n, a, b) in enumerate(modules)]
    return T.Summary(ev)


@pytest.fixture()
def job():
    """0-1000 ms: TRAIN > setup, process > load_data > data.load > 2 decodes +
    concat, train.split, train > nn.init, nn.h2d, nn.repad, 2 epochs.  The
    device runs 600-650 and 700-750 (epoch_steps), 650-660 and 750-760
    (eval_errors): busy 120 ms, idle 880."""
    spans = [
        span("TRAIN", 0, 1000, 1),
        span("setup", 0, 50, 2, 1),
        span("process", 50, 990, 3, 1),
        span("load_data", 60, 300, 4, 3),
        span("data.load", 60, 300, 5, 4),
        span("data.shard_decode", 60, 150, 6, 5, shard=0, rows=10, bytes=100),
        span("data.shard_decode", 150, 240, 7, 5, shard=1, rows=10, bytes=100),
        span("data.concat", 240, 300, 8, 5, bytes=200),
        span("train.split", 300, 350, 9, 3),
        span("train", 350, 980, 10, 3),
        span("nn.init", 350, 400, 11, 10),
        span("nn.h2d", 400, 450, 12, 10, bytes=200),
        span("nn.repad", 450, 590, 13, 10, bytes=220, bytes_down=200),
        span("nn.epoch", 590, 690, 14, 10, epoch=0),
        span("nn.epoch.dispatch", 590, 640, 15, 14),
        S.from_event("shifu:xla.build", 598 * MS, 0.0,
                     {"id": 16, "parent": 15, "secs": 0.006, "stage": "trace"}),
        span("nn.epoch.fetch", 640, 690, 17, 14),
        span("nn.epoch", 690, 790, 18, 10, epoch=1),
        span("nn.epoch.dispatch", 690, 700, 19, 18),
        span("nn.epoch.fetch", 700, 790, 20, 18),
        span("save_models", 980, 990, 21, 3),
    ]
    ops = [(600, 650), (650, 660), (700, 750), (750, 760)]
    mods = [("jit_epoch_steps", 600, 650), ("jit_eval_errors", 650, 660),
            ("jit_epoch_steps", 700, 750), ("jit_eval_errors", 750, 760)]
    return Ctx(sorted(spans, key=lambda s: s.start_ns)), summary(ops, mods)


def test_a_build_marker_gets_its_interval_back():
    s = S.from_event("shifu:xla.build", 630 * MS, 0.0, {"id": 3, "parent": 2, "secs": 0.030})
    assert (s.name, s.start_ns, s.end_ns, s.id, s.parent) == ("xla.build", 600 * MS, 630 * MS, 3, 2)
    assert s.attrs == {"secs": 0.030}


def test_job_head_is_root_start_to_first_training_program(job):
    ctx, summ = job
    args = dict(root="TRAIN", pattern="epoch_steps", containers=CONTAINERS)
    assert job_head.read(summ, ctx, **args) == pytest.approx(0.600)
    # the line that splits it: own times, the build taken out of its dispatch
    line = ctx.said[-1]
    assert "nn.repad 0.140" in line and "xla.build 0.006" in line
    assert "nn.epoch.dispatch 0.004" in line            # 590-600 less the build, 592-598
    # TRAIN 0, process 10 (50-60), train 0, in a head of 600: 590 named
    assert "named 0.590, in a container only 0.010" in line
    assert job_head.read(summ, Ctx([]), **args) is None          # no program span
    assert job_head.read(summary([(0, 5)]), ctx, **args) is None  # no such program
    assert job_head.read(None, ctx, **args) is None


def test_span_seconds_sums_per_job(job):
    ctx, summ = job
    assert span_seconds.read(summ, ctx, names=["data.load"], per="TRAIN") == pytest.approx(0.240)
    assert span_seconds.read(summ, ctx, names=["nn.h2d", "nn.repad"], per="TRAIN") == \
        pytest.approx(0.190)
    two = Ctx(ctx.program_spans + [span("TRAIN", 2000, 3000, 31),
                                   span("data.load", 2010, 2110, 32, 31)])
    assert span_seconds.read(None, two, names=["data.load"], per="TRAIN") == pytest.approx(0.170)
    assert span_seconds.read(summ, ctx, names=["dt.level"], per="TRAIN") is None
    assert span_seconds.read(summ, Ctx([]), names=["data.load"], per="TRAIN") is None


def test_epoch_host_is_span_less_busy_inside(job):
    ctx, summ = job
    # each epoch span is 100 ms with 60 ms of device work inside
    assert epoch_host.read(summ, ctx, span="nn.epoch") == pytest.approx(40.0)
    assert epoch_host.read(summ, Ctx([]), span="nn.epoch") is None
    assert epoch_host.read(None, ctx, span="nn.epoch") is None


def test_idle_unattributed_zero_when_leaves_tile_the_idle_time(job):
    ctx, _ = job
    # idle only where leaves are open: the device works through every container-only stretch
    busy = [(50, 60), (350, 350.5), (590, 590.5), (600, 650), (700, 750),
            (790, 980), (990, 1000)]
    leaves = [s for s in ctx.program_spans if s.name not in CONTAINERS]
    assert T.total(T.subtract([(0, 1000 * MS)],
                              T.union((s.start_ns, s.end_ns) for s in leaves))) > 0
    assert idle_unattributed.read(summary(busy), ctx, containers=CONTAINERS) == \
        pytest.approx(0.0, abs=1e-9)


def test_idle_unattributed_hundred_when_only_containers_cover(job):
    ctx, summ = job
    only = Ctx([s for s in ctx.program_spans if s.name in CONTAINERS])
    assert idle_unattributed.read(summ, only, containers=CONTAINERS) == pytest.approx(100.0)


def test_idle_unattributed_counts_what_no_leaf_names(job):
    ctx, summ = job
    # idle 880 ms; without a leaf's name: 50-60 (process), 790-980 (train), 990-1000 (TRAIN)
    assert idle_unattributed.read(summ, ctx, containers=CONTAINERS) == \
        pytest.approx(100.0 * 210 / 880)
    line = ctx.said[-1]
    assert "train 0.190" in line and "nn.repad 0.140" in line and "outside any span 0.000" in line


def test_idle_unattributed_none_without_program_spans(job):
    _, summ = job
    assert idle_unattributed.read(summ, Ctx([]), containers=CONTAINERS) is None
    assert idle_unattributed.read(None, Ctx([span("TRAIN", 0, 1, 1)]), containers=CONTAINERS) is None


def test_extract_reads_annotations_from_an_xplane(tmp_path):
    """What ``shifu_tpu.obs`` writes (prefix, id, parent, numeric stats, the
    build marker) comes back from a real file; a ``bench:`` marker stays out."""
    import jax
    from jax.profiler import TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench:trace_start"):
        pass
    with TraceAnnotation("shifu:TRAIN", id=1):
        with TraceAnnotation("shifu:data.load", id=2, parent=1) as a:
            a.set_metadata(bytes=7)
        with TraceAnnotation("shifu:xla.build", id=3, parent=1, secs=1e-6, stage="lower"):
            pass
    jax.profiler.stop_trace()

    class Work:
        work = str(tmp_path / "w")
    os.makedirs(os.path.join(Work.work, "trace"))
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "**", "*.xplane.pb"),
                        recursive=True)
    os.rename(path, os.path.join(Work.work, "trace", "t.xplane.pb"))
    ctx = Work()
    got = {s.name: s for s in S.of(ctx)}
    assert set(got) == {"TRAIN", "data.load", "xla.build"} and ctx.program_spans is S.of(ctx)
    assert got["TRAIN"].parent is None and got["data.load"].parent == 1
    assert got["data.load"].attrs == {"bytes": 7}
    assert got["TRAIN"].start_ns <= got["data.load"].start_ns <= got["data.load"].end_ns \
        <= got["TRAIN"].end_ns
    assert got["xla.build"].end_ns - got["xla.build"].start_ns == pytest.approx(1000.0)
    assert got["xla.build"].attrs["stage"] == "lower"

    class Empty:
        work = str(tmp_path / "none")
    assert S.of(Empty()) == []
