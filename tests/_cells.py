"""What the tests of the benchmark's model cells share: a copy of the
benchmark with a cell cut to a toy size, a run of it counted in steps, and
hand-made traces for the per-layer readers. A test file of a cell keeps its
own ``CELL``, ``TOY``, faults and assertions; a new cell adds a file of its
own and takes the rest from here."""

import contextlib
import functools
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import runner, seedcache, window  # noqa: E402

#: a toy run is counted in steps, not seconds: the clock its window reads
#: moves STEP_S a step from the first, so the 5 s of warm-up and a 1-s window
#: are 242 steps on any machine, however loaded. A fault's term follows how
#: far the toy model has trained: ``head_reads_group_h_mod_8``'s
#: ``scan_energy`` (``nemotron3_nano_30b_a3b``) reads 0.041, 0.037, 0.027 and
#: 0.0059 at 202, 242, 302 and 482 steps
STEP_S = 0.025
#: a rehearsal checks the run's control flow and counts, not how far the toy
#: model trains: its clock moves five times as far a step, so the warm-up is
#: 40 steps and a 0.5-s window (``log_every`` 2) two laps, as few as a
#: rehearsal on the wall clock of a loaded host saw
REHEARSAL_STEP_S = 0.125


def load(path):
    """A file of the benchmark, loaded as the harness loads it."""
    return runner.load_module(path)


def json_of(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def copy_of_the_benchmark(root):
    """``BENCHMARK.json`` and ``benchmark/`` (no cache, outputs or tests)
    copied to ``root``, which is made."""
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__", "tests"))
    return root


def toy(root, patch, keep_check=False):
    """Each file ``patch`` names under ``root/benchmark`` with its keys
    replaced; ``keep_check`` keeps the file's own ``check`` (the cell's
    limits) in place of the toy's."""
    for rel, keys in patch.items():
        path = root / "benchmark" / rel
        if keep_check:
            keys = {k: v for k, v in keys.items() if k != "check"}
        path.write_text(json.dumps({**json.loads(path.read_text()), **keys}))


@pytest.fixture(scope="module")
def tree(request, tmp_path_factory):
    """A copy of the benchmark with the module's ``TOY`` in place, one for
    the module's tests, which change none of its files."""
    root = copy_of_the_benchmark(tmp_path_factory.mktemp("tree") / "checkout")
    toy(root, request.module.TOY)
    return root


class StepClock(window.LapWindow):
    """The harness's window on a clock that moves ``step_s`` a step."""

    def __init__(self, step_s, **kw):
        super().__init__(clock=self.steps_s, **kw)
        self.step_s = step_s
        self.first = None

    def steps_s(self):
        if self.first is None:
            self.first = (time.perf_counter(), self.last_step)
        t, step = self.first
        return t + (self.last_step - step) * self.step_s


@contextlib.contextmanager
def run_in(root, step_s=STEP_S):
    """Runs of the harness inside: the seed cache in ``root``, emptied on
    entry (a cache hit inside is one the block caused), telemetry under
    ``root`` (``runner.measure`` sets the variable and never takes it back)
    and the window on a :class:`StepClock` of ``step_s``, or on the wall
    clock for ``step_s=None``. All three are restored on exit."""
    cache = root / "benchmark" / ".cache"
    shutil.rmtree(cache, ignore_errors=True)
    with pytest.MonkeyPatch.context() as mp:
        if step_s is not None:
            mp.setattr(runner, "LapWindow",
                       functools.partial(StepClock, step_s=step_s))
        mp.setattr(seedcache, "ROOT", str(cache))
        mp.setenv("DLS_TELEMETRY_DIR", str(root / "telemetry"))
        yield


def run_controls(tmp_path_factory, name, patch, **kw):
    """``benchmark/controls/<name>.py`` at the toy size ``patch`` on
    ``local[1]``, in float32 but under the cell's OWN limits: one run of the
    cell, the harness's comparison repeated with each of the module's faults
    planted. The module, and what its run saw."""
    root = copy_of_the_benchmark(
        tmp_path_factory.mktemp("controls") / "checkout")
    toy(root, patch, keep_check=True)
    mod = load(str(root / "benchmark" / "controls" / f"{name}.py"))
    with run_in(root):
        return mod, mod.run(2 ** 31 + 11, 1.0, mod.ALL,
                            master="local[1]", **kw)


def ctx(cell, events, steps=2, laps=()):
    """A traced run's context for ``cell`` at its published size, with
    hand-made device events (see :func:`ev`)."""
    bench = json_of("BENCHMARK.json")
    work = next(w for w in bench["workloads"] if w["name"] == cell)
    name = work["config"]
    return {"trace": {"devices": {"0": {"XLA Ops": events}}, "steps": steps,
                      "host": {}},
            "cfg": json_of("benchmark", "configs", name + ".json"),
            "traffic": json_of("benchmark", "traffic",
                               work["traffic"] + ".json"),
            "peaks": json_of("benchmark", "peaks.json")["TPU v5 lite"],
            "cell": {"config_mod": load(os.path.join(
                ROOT, "benchmark", "configs", name + ".py"))},
            "facts": {}, "laps": list(laps)}


def ev(name, start_ms, dur_ms, op="fusion", result=""):
    """A device event: name, start ns, duration ns, and its info, the result
    cut to 120 characters as the trace's extract cuts it."""
    kind = name.rsplit(".", 1)[0]
    return [name, start_ms * 1e6, dur_ms * 1e6,
            {"kind": kind, "op": op, "result": result[:120]}]


def reader(name):
    """A per-layer metric's reader."""
    return load(os.path.join(ROOT, "benchmark", "layer_metrics",
                             name + ".py")).read


def lap(**metrics):
    """A lap of the step's metrics, as a reader gets it."""
    return {"step": 10, "metrics": metrics}
