"""What the benchmark's ``correct`` holds the program to, on the eight-device
CPU mesh at toy widths, for each model family that has a driver script: the
script itself runs (``examples/train_*.py``, its own ``Session``, dataset,
model, optimizer, loss and ``Trainer`` arguments), its one ``Trainer.fit`` is
cut to three steps of one lap each, and the verdicts are the harness's own
(``benchmark/harness/checks.py``, ``compile_watch.py``).
``tests/test_sparse_moe_benchmark.py`` does this for the sparse decoder
through the harness. A CPU run checks control flow and counts; it yields no
time, rate or utilisation.
"""

import functools
import math
import os
import runpy
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributeddeeplearningspark_tpu import Trainer, telemetry  # noqa: E402
from distributeddeeplearningspark_tpu.telemetry import anatomy, spans  # noqa: E402

STEPS = 3

#: family -> (driver script, its arguments): the smallest variant each script
#: offers, its own mesh (``data=8``; Llama's ``fsdp=8``) on all eight devices
FAMILIES = {
    "bert": ("train_bert.py", [
        "--master", "local[8]", "--variant", "tiny", "--seq-len", "128",
        "--batch-size", "16"]),
    "resnet": ("train_resnet.py", [
        "--master", "local[8]", "--variant", "resnet18", "--image-size", "32",
        "--num-classes", "10", "--batch-size", "16"]),
    "llama_lora": ("train_llama_lora.py", [
        "--master", "local[8]", "--variant", "tiny", "--seq-len", "128",
        "--batch-size", "8"]),
    "dlrm_sparse_tables": ("train_dlrm.py", [
        "--master", "local[8]", "--vocab-size", "50", "--num-sparse", "4",
        "--embed-dim", "8", "--batch-size", "32", "--eval-examples", "32"]),
    # latent attention, a shared expert beside the routed ones (their
    # ``shard_map`` over the mesh) and the MTP module's second loss term
    "latent_moe_lm": ("train_latent_moe_lm.py", [
        "--master", "local[8]", "--variant", "tiny", "--seq-len", "128",
        "--batch-size", "8"]),
    # blocks of one sublayer: the chunked state-space scan and its
    # convolution over packed documents, relu² experts in their ``shard_map``
    "ssm_moe_lm": ("train_ssm_moe_lm.py", [
        "--master", "local[8]", "--variant", "tiny", "--seq-len", "128",
        "--batch-size", "8"]),
}

#: counters of ``spans.COUNTERS`` that only some feeds write: the map's
#: where ``map_parallel`` runs, the decode's where JPEGs are decoded
FEED_SPECIFIC = {"input_map_s", "input_decode_s"}
APPLIES = {"resnet": {"input_map_s"}}

@functools.cache
def _compile_watch():
    """One listener a process: jax offers no way to take one off again."""
    from benchmark.harness.compile_watch import CompileWatch

    return CompileWatch()


@pytest.fixture(scope="module", params=list(FAMILIES))
def run(request, tmp_path_factory):
    """Run the family's driver script once and keep what ``correct`` reads."""
    from benchmark.harness import checks

    script, argv = FAMILIES[request.param]
    tele_dir = str(tmp_path_factory.mktemp(request.param))
    watch = _compile_watch()
    seen = {"family": request.param}
    fit = Trainer.fit

    def three_steps(self, dataset, **kw):
        laps = []  # (clock at the lap's end, step, what the callback was given)
        kw.update(steps=STEPS, log_every=1, callbacks=[
            lambda step, metrics: laps.append(
                (time.perf_counter(), step, dict(metrics)))])
        out = fit(self, dataset, **kw)
        step_fn = self._train_step
        devices = list(self.session.mesh.devices.flat)
        seen.update(
            step_fn=step_fn, ledger=step_fn.compile_summary(),
            late_compiles=watch.compiles_after(laps[0][0]),
            losses=[(step, float(m["loss"])) for _, step, m in laps],
            devices=len(devices), batch_size=kw["batch_size"],
            sparse_tables=len(self.state.embed_state),
            placement=checks.placement(step_fn, batch_size=kw["batch_size"],
                                       devices=devices))
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLS_TELEMETRY_DIR", tele_dir)
        mp.setattr(sys, "argv", [script, *argv])
        mp.syspath_prepend(os.path.join(ROOT, "examples"))  # scripts' siblings
        mp.setattr(Trainer, "fit", three_steps)
        runpy.run_path(os.path.join(ROOT, "examples", script),
                       run_name="__main__")
    seen["laps"] = [e for e in telemetry.read_events(tele_dir)
                    if e.get("kind") == "step_metrics"]
    return seen


def test_one_compile_of_the_train_step_and_none_after_the_first_step(run):
    from benchmark.harness import checks

    assert checks.compile_failures(run["ledger"], run["late_compiles"]) == []
    assert [e["step"] for e in run["laps"]] == [1, 2, 3]
    assert run["laps"][0]["compile_in_lap_s"] > 0
    assert all(e["compile_in_lap_s"] == 0 for e in run["laps"][1:])


def test_the_step_stayed_on_the_aot_path(run):
    step_fn = run["step_fn"]
    assert isinstance(step_fn, anatomy.InstrumentedFunction)
    assert run["ledger"]["aot"] is True
    assert len(step_fn.executables()) == 1
    # the row-sparse table step (train/embed.py) is a train step like any other
    assert (run["sparse_tables"] > 0) == (run["family"] == "dlrm_sparse_tables")


def test_every_device_holds_state_and_an_equal_share_of_each_batch(run):
    failures, facts = run["placement"]
    assert failures == []
    assert run["devices"] == 8
    for name, shape in facts["batch_arrays"].items():
        assert shape[0] == run["batch_size"], name


def test_every_laps_loss_is_finite(run):
    assert [step for step, _ in run["losses"]] == [1, 2, 3]
    assert all(math.isfinite(loss) for _, loss in run["losses"]), run["losses"]


def test_every_lap_carries_the_counters_that_apply(run):
    # (the start's counters ride the process's one `startup` record)
    of_start = {k for n, k in spans.COUNTERS.items()
                if n.startswith(spans.START_PREFIX)}
    want = (set(spans.COUNTERS.values()) - of_start - FEED_SPECIFIC
            | APPLIES.get(run["family"], set()))
    for e in run["laps"]:
        assert want <= set(e), sorted(want - set(e))
        assert all(e[k] >= 0 for k in want)
    absent = FEED_SPECIFIC - APPLIES.get(run["family"], set())
    assert not absent & set(run["laps"][-1])
