"""The start of a process, seen from inside (telemetry/anatomy.StartupLedger):
the ``dls.start/*`` sections on a fake clock, the one ``startup`` record of a
real ``Session`` + ``Trainer.fit`` on the CPU, the spans in a real profile, and
the benchmark's six readers of the record."""

import glob
import json
import math
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import optax
import pytest

import distributeddeeplearningspark_tpu as package
from distributeddeeplearningspark_tpu import (
    PartitionedDataset,
    Session,
    Trainer,
    telemetry,
)
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.telemetry import anatomy, spans
from distributeddeeplearningspark_tpu.train import losses
from distributeddeeplearningspark_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

START_NAMES = ("dls.start/import", "dls.start/session", "dls.start/backend",
               "dls.start/fit", "dls.start/sample", "dls.start/init_state")
#: every key of the record, in seconds: the sections', the first lap's parts
#: and the caller's; they sum to ``to_first_lap_s``
PARTS = ("import_s", "session_s", "backend_s",
         "fit_unaccounted_s", "sample_s", "init_state_s",
         "first_lower_s", "first_backend_s", "first_batch_s",
         "first_dispatch_s", "first_drain_s", "caller_s")
RECORD_KEYS = (*PARTS, "to_first_lap_s", "steps", "attempt")

READERS = {
    "start_import_s": ("import_s",),
    "start_session_s": ("session_s", "backend_s"),
    "start_state_init_s": ("sample_s", "init_state_s"),
    "start_first_batch_s": ("first_batch_s",),
    "start_first_steps_s": ("first_dispatch_s", "first_drain_s"),
    "start_unaccounted_s": ("fit_unaccounted_s",),
}


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def tick(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


def _mnist_like(n=64):
    rng = np.random.default_rng(0)
    return [{"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
             "label": np.int32(i % 10)} for i in range(n)]


@pytest.fixture
def fresh_start(monkeypatch):
    """A process that has not started yet, whatever ran before this test."""
    ledger = anatomy.StartupLedger()
    monkeypatch.setattr(anatomy, "STARTUP", ledger)
    return ledger


def _fit(*, steps=6, trainer=None, callbacks=()):
    spark = Session.builder.master("local[2]").getOrCreate()
    ds = PartitionedDataset.parallelize(_mnist_like(), 2).repeat()
    if trainer is None:
        trainer = Trainer(spark, LeNet5(), losses.softmax_xent,
                          optax.sgd(0.01))
    trainer.fit(ds, batch_size=8, steps=steps, log_every=2,
                callbacks=callbacks)
    return trainer


def _startups(workdir):
    return [e for e in telemetry.read_events(workdir)
            if e["kind"] == "startup"]


# -- the ledger on a fake clock -----------------------------------------------


def _a_start(clock, ledger):
    """import 2 s; the caller 1 s; a session of 3 s with 2.5 s of backend in
    it; the caller 0.625 s; a fit of 20 s holding a sample (0.25) and an init
    (4): 26.625 s from the anchor."""
    with spans.span("dls.start/import", ledger):
        clock.tick(2.0)
    clock.tick(1.0)
    with spans.span("dls.start/session", ledger):
        clock.tick(0.25)
        with spans.span("dls.start/backend", ledger):
            clock.tick(2.5)
        clock.tick(0.25)
    clock.tick(0.625)
    fit = spans.span("dls.start/fit", ledger)
    fit.__enter__()
    clock.tick(0.5)
    with spans.span("dls.start/sample", ledger):
        clock.tick(0.25)
    with spans.span("dls.start/init_state", ledger):
        clock.tick(4.0)
    clock.tick(15.25)            # the first lap, under other sinks
    fit.__exit__(None, None, None)
    return ledger.first_lap(
        steps=10,
        lap={"device_dispatch_s": 0.5, "device_drain_s": 1.5,
             "compile_in_lap_s": 9.0, "callbacks_s": 0.75, "emit_s": 0.0,
             "unaccounted_s": 0.25},
        feed={"input_wait_s": 2.0, "input_put_s": 0.5},
        compiles=[{"compile_s": 9.0, "lower_s": 6.0, "backend_s": 3.0}],
        attempt=2, now=clock())


def test_a_sections_own_time_is_without_the_sections_nested_in_it():
    clock = FakeClock()
    rec = _a_start(clock, anatomy.StartupLedger(clock=clock))
    assert rec["import_s"] == 2.0
    assert rec["session_s"] == 0.5 and rec["backend_s"] == 2.5
    assert rec["sample_s"] == 0.25 and rec["init_state_s"] == 4.0
    # the first lap's parts are that lap's own records, not measured twice
    assert (rec["first_lower_s"], rec["first_backend_s"]) == (6.0, 3.0)
    assert rec["first_batch_s"] == 2.5
    assert (rec["first_dispatch_s"], rec["first_drain_s"]) == (0.5, 1.5)
    # fit ran 20 s: 4.25 in its sections, 13.5 in the lap's named parts,
    # 0.75 in the caller's callbacks; what is left has no section
    assert rec["fit_unaccounted_s"] == pytest.approx(20 - 4.25 - 13.5 - 0.75)
    # the caller: between the outer sections, and the lap's callbacks
    assert rec["caller_s"] == pytest.approx(1.0 + 0.625 + 0.75)
    assert rec["to_first_lap_s"] == 26.625 and set(rec) == set(RECORD_KEYS)
    assert (rec["steps"], rec["attempt"]) == (10, 2)


def test_the_counters_and_the_callers_time_sum_to_the_first_lap():
    clock = FakeClock()
    ledger = anatomy.StartupLedger(clock=clock)
    clock.tick(0.1 + 1e-7)            # nothing here is a power of two
    with spans.span("dls.start/session", ledger):
        clock.tick(1 / 3)
        with spans.span("dls.start/backend", ledger):
            clock.tick(math.pi)
    clock.tick(0.7)
    fit = spans.span("dls.start/fit", ledger)
    fit.__enter__()
    with spans.span("dls.start/init_state", ledger):
        clock.tick(math.e)
    clock.tick(12.3456789)
    fit.__exit__(None, None, None)
    clock.tick(1e-5)
    rec = ledger.first_lap(
        steps=2, lap={"device_dispatch_s": 0.004321, "device_drain_s": 1.1,
                      "callbacks_s": 0.2},
        feed={"input_wait_s": 0.9, "input_put_s": 0.011},
        # a step on the jit fallback cannot tell lowering from the backend
        compiles=[{"compile_s": 7.7}], now=clock())
    assert sum(rec[k] for k in PARTS) == pytest.approx(rec["to_first_lap_s"],
                                                       abs=1e-9)
    assert (rec["first_lower_s"], rec["first_backend_s"]) == (0.0, 7.7)
    assert rec["to_first_lap_s"] == pytest.approx(
        0.1 + 1e-7 + 1 / 3 + math.pi + 0.7 + math.e + 12.3456789 + 1e-5)


def test_a_section_that_raises_is_not_added_and_a_closed_ledger_takes_none():
    clock = FakeClock()
    ledger = anatomy.StartupLedger(clock=clock)
    with pytest.raises(KeyError):
        with spans.span("dls.start/init_state", ledger):
            clock.tick(4.0)
            raise KeyError("out of memory")
    with spans.span("dls.start/init_state", ledger):
        clock.tick(1.0)
    assert ledger.sink() is ledger and ledger.summary() is None
    rec = ledger.first_lap(steps=1, lap={}, feed={}, compiles=[])
    assert rec["init_state_s"] == 1.0 and rec["caller_s"] == 4.0
    # on record: no sink any more, nothing added, the same record again
    assert ledger.sink() is None
    ledger.add("dls.start/sample", 5.0)
    clock.tick(9.0)
    assert ledger.first_lap(steps=7, lap={}, feed={}, compiles=[]) == rec
    assert ledger.summary() == rec


def test_every_start_name_is_in_the_one_list():
    assert set(START_NAMES) == {n for n in spans.COUNTERS
                                if n.startswith(spans.START_PREFIX)}
    assert set(START_NAMES) <= set(spans.SPAN_NAMES)
    counters = [spans.COUNTERS[n] for n in START_NAMES]
    assert len(set(counters)) == len(counters)
    assert set(counters) < set(PARTS)
    assert set(counters) == set(anatomy.StartupLedger()._seconds)


# -- a real Session and a real fit --------------------------------------------


def test_fit_writes_exactly_one_startup_record(tmp_path, monkeypatch,
                                               fresh_start):
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    monkeypatch.setenv("DLS_RESTART", "3")
    trainer = Trainer(Session.builder.master("local[2]").getOrCreate(),
                      LeNet5(), losses.softmax_xent, optax.sgd(0.01))
    assert trainer.startup_summary() is None
    _fit(trainer=trainer, callbacks=[lambda step, metrics: None])
    telemetry.reset()
    events = telemetry.read_events(tmp_path)
    kinds = [e["kind"] for e in events]
    assert kinds.count("startup") == 1
    assert kinds.index("startup") < kinds.index("step_metrics")
    (rec,) = _startups(tmp_path)
    assert set(RECORD_KEYS) <= set(rec)
    assert all(math.isfinite(rec[k]) for k in RECORD_KEYS)
    # the two residuals too: a part counted twice would push one below zero
    assert all(rec[k] >= 0.0 for k in RECORD_KEYS)
    assert sum(rec[k] for k in PARTS) == pytest.approx(rec["to_first_lap_s"],
                                                       abs=1e-9)
    assert (rec["steps"], rec["attempt"]) == (2, 3)
    # this lap compiled the step, waited for a batch and drained the device
    assert rec["first_lower_s"] > 0 and rec["first_backend_s"] > 0
    assert rec["first_batch_s"] > 0 and rec["first_drain_s"] > 0
    assert rec["init_state_s"] > 0 and rec["backend_s"] > 0
    first_lap = next(e for e in events if e["kind"] == "step_metrics")
    assert rec["first_dispatch_s"] == first_lap["device_dispatch_s"]
    (compiled,) = [e for e in events if e["kind"] == "compile"]
    assert rec["first_lower_s"] == compiled["lower_s"]
    assert trainer.startup_summary() == {k: rec[k] for k in RECORD_KEYS}
    assert fresh_start.sink() is None


def test_without_a_writer_no_record_and_the_same_summary(tmp_path,
                                                         monkeypatch,
                                                         fresh_start):
    monkeypatch.delenv(telemetry.WORKDIR_ENV, raising=False)
    telemetry.reset()
    trainer = _fit()
    assert telemetry.get() is None
    rec = trainer.startup_summary()
    assert set(rec) == set(RECORD_KEYS)
    assert sum(rec[k] for k in PARTS) == pytest.approx(rec["to_first_lap_s"],
                                                       abs=1e-9)
    assert all(rec[k] >= 0.0 for k in RECORD_KEYS) and rec["steps"] == 2
    # the step's compile records exist either way; the lap has no record of
    # its own without a writer (tests/test_spans.py: no accumulator is built),
    # so its feed wait, dispatches and drain stay where no section covers
    assert rec["first_lower_s"] > 0 and rec["first_backend_s"] > 0
    assert (rec["first_batch_s"] == rec["first_dispatch_s"]
            == rec["first_drain_s"] == 0.0)
    assert rec["fit_unaccounted_s"] > 0 and rec["init_state_s"] > 0
    assert trainer._train_step._anatomy is None


def test_a_second_fit_trainer_and_get_or_create_add_no_record(tmp_path,
                                                              monkeypatch,
                                                              fresh_start):
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    trainer = _fit()
    rec = trainer.startup_summary()
    _fit(trainer=trainer, steps=10)            # the same trainer again
    assert Session.builder.getOrCreate() is trainer.session  # the active one
    other = _fit()                             # a second trainer
    telemetry.reset()
    assert len(_startups(tmp_path)) == 1
    assert other.startup_summary() == rec == trainer.startup_summary()
    laps = [e for e in telemetry.read_events(tmp_path)
            if e["kind"] == "step_metrics"]
    assert len(laps) == 3 + 2 + 3


def test_a_fit_that_raises_before_its_first_lap_writes_no_record(
        tmp_path, monkeypatch, fresh_start):
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))

    def boom(step, metrics):
        raise KeyError("the first step's callback")

    with pytest.raises(KeyError):
        _fit(callbacks=[boom])
    assert fresh_start.summary() is None and _startups(tmp_path) == []
    # the section was left on the way out: nothing of it is open, or added
    assert spans._open_spans() == []
    assert fresh_start._seconds["fit_unaccounted_s"] == 0.0
    # the start is still open, and the next fit's first lap closes it
    trainer = _fit()
    telemetry.reset()
    (rec,) = _startups(tmp_path)
    assert rec["to_first_lap_s"] == trainer.startup_summary()["to_first_lap_s"]
    assert rec["init_state_s"] > 0 and rec["steps"] == 2


def test_the_start_lies_in_a_real_profile_on_the_main_threads_line(
        tmp_path, monkeypatch, fresh_start):
    from jax.profiler import ProfileData

    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path / "tele"))
    # the name's first import ran long ago: forget it, and the next access is
    # a first one again
    monkeypatch.delitem(package.__dict__, "Session")
    main = threading.current_thread()
    with profiling.trace(str(tmp_path / "prof")):
        assert package.Session is Session
        _fit(callbacks=[lambda step, metrics: None])
    telemetry.reset()
    (xplane,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(xplane).planes:
        for ln in plane.lines:
            found = {}
            for e in ln.events:
                if e.name.startswith("dls."):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if any(n.startswith(spans.START_PREFIX) for n in found):
                lines.append(found)
    # one line holds them all, and it is the loop's: the main thread's
    assert main is threading.main_thread()
    (line,) = lines
    assert set(START_NAMES) <= set(line) and "dls.fit/sync" in line
    assert all(len(line[n]) == 1 for n in START_NAMES)
    (fit,), (session,) = line["dls.start/fit"], line["dls.start/session"]
    for outer, inner in ((session, "dls.start/backend"),
                         (fit, "dls.start/sample"),
                         (fit, "dls.start/init_state"),
                         (fit, "dls.step/lower"), (fit, "dls.step/compile")):
        assert all(outer[0] <= a and b <= outer[1] for a, b in line[inner])
    assert line["dls.start/import"][0][1] <= session[0] <= fit[0]
    # fit's section ends where its first lap closes: after the first sync,
    # before the second
    syncs = sorted(line["dls.fit/sync"])
    assert syncs[0][1] <= fit[1] <= syncs[1][0]


def test_a_process_that_imports_the_package_first(tmp_path):
    """A driver script's own process: the anchor is the package's import,
    the lazy imports that pull in jax are ``import_s``, and the record is in
    the stream an operator reads."""
    script = textwrap.dedent("""
        import json, sys, time
        t0 = time.perf_counter()
        import distributeddeeplearningspark_tpu as dls
        assert "jax" not in sys.modules
        dls.Session, dls.Trainer, dls.PartitionedDataset
        assert "jax" in sys.modules
        import numpy as np, optax
        from distributeddeeplearningspark_tpu.models import LeNet5
        from distributeddeeplearningspark_tpu.train import losses
        rng = np.random.default_rng(0)
        data = [{"image": rng.normal(0, 1, (28, 28, 1)).astype(np.float32),
                 "label": np.int32(i % 10)} for i in range(32)]
        spark = dls.Session.builder.master("local[2]").getOrCreate()
        ds = dls.PartitionedDataset.parallelize(data, 2).repeat()
        trainer = dls.Trainer(spark, LeNet5(), losses.softmax_xent,
                              optax.sgd(0.01))
        marks = []
        trainer.fit(ds, batch_size=8, steps=4, log_every=2,
                    callbacks=[lambda s, m: marks.append(time.perf_counter())])
        print(json.dumps({"summary": trainer.startup_summary(),
                          "to_second_step": marks[1] - t0}))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           telemetry.WORKDIR_ENV: str(tmp_path)}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    said = json.loads(out.stdout.strip().splitlines()[-1])
    (rec,) = _startups(tmp_path)
    assert said["summary"] == {k: rec[k] for k in RECORD_KEYS}
    assert rec["import_s"] > 0.2          # jax, flax, optax, orbax
    assert rec["backend_s"] > 0 and rec["session_s"] >= 0
    # the first lap closed on the second step, just before its callback ran
    assert 0 <= said["to_second_step"] - rec["to_first_lap_s"] < 0.5
    assert sum(rec[k] for k in PARTS) == pytest.approx(rec["to_first_lap_s"],
                                                       abs=1e-9)
    assert rec["caller_s"] >= 0 and rec["fit_unaccounted_s"] >= 0


def test_importing_telemetry_alone_imports_no_jax():
    code = ("import sys\n"
            "import distributeddeeplearningspark_tpu.telemetry\n"
            "from distributeddeeplearningspark_tpu.telemetry import (\n"
            "    anatomy, spans)\n"
            "with spans.span('dls.start/import', anatomy.STARTUP.sink()):\n"
            "    pass\n"
            "assert anatomy.STARTUP._seconds['import_s'] > 0\n"
            "assert 'jax' not in sys.modules, sorted(sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


# -- the benchmark's readers --------------------------------------------------


def _reader(name):
    from benchmark.harness import runner

    return runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


def _hand_made_record():
    rec = {k: float(i + 1) / 8 for i, k in enumerate(PARTS)}
    return {**rec, "to_first_lap_s": sum(rec.values()), "steps": 10,
            "attempt": 0}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_on_a_hand_made_record(name, tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    rec = _hand_made_record()
    writer = telemetry.EventWriter(tmp_path)
    writer.emit("phase", name="run", edge="begin")
    writer.emit("startup", **rec)
    writer.emit("startup", **{**rec, "import_s": 99.0})   # never the second
    writer.close()
    ctx = {"facts": {}}
    assert _reader(name).read(ctx) == sum(rec[k] for k in READERS[name])
    facts = ctx["facts"]
    if name == "start_session_s":
        assert facts[name] == {"backend_s": rec["backend_s"]}
    elif name == "start_state_init_s":
        assert facts[name] == {k: rec[k] for k in READERS[name]}
    elif name == "start_first_steps_s":
        assert facts[name] == {"steps": 10}
    elif name == "start_unaccounted_s":
        assert facts[name] == {"caller_s": rec["caller_s"],
                               "to_first_lap_s": rec["to_first_lap_s"]}
        assert {k: facts["startup"][k] for k in RECORD_KEYS} == rec
    else:
        assert facts == {}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_on_a_stream_with_no_record_reads_nothing(name, tmp_path,
                                                           monkeypatch):
    """The parent of the PR that brought the record writes none."""
    monkeypatch.setenv(telemetry.WORKDIR_ENV, str(tmp_path))
    writer = telemetry.EventWriter(tmp_path)
    writer.step_metrics(10, steps=10, lap_s=1.0, metrics={"loss": 1.0})
    writer.close()
    ctx = {"facts": {}}
    assert _reader(name).read(ctx) is None and ctx["facts"] == {}
    # nor without a stream, nor on a record from before one of its keys
    monkeypatch.delenv(telemetry.WORKDIR_ENV)
    assert _reader(name).read({"facts": {}}) is None
    assert _reader(name).read({"facts": {}, "startup": {"steps": 1}}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_benchmark_lists_the_reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    # (a cell whose own files import the package's modules by name passes by
    # `dls.start/import`, and `import_s` reads nothing of it there)
    cells = [w["name"] for w in bench["workloads"]
             if (name, w["name"]) != ("start_import_s",
                                      "joyai_llm_flash.fit_s16k")]
    assert entry == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "entry and compile",
        "moves": "setup_s", "workloads": cells}
    assert os.path.isfile(os.path.join(REPO, "benchmark", "layer_metrics",
                                       name + ".py"))
    # appended by PR 34: the six after the forty the benchmark had then
    # (later PRs append after them)
    assert name in [m["name"] for m in bench["per_layer"][40:46]]
