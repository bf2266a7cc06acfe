"""Unified GSPMD Plan compile layer (ISSUE 15): Plan validation (incl. the
tensor-axis skew guard), serialization, layout fingerprint parity through
the new layer, ZeRO weight-update sharding (bitwise vs the replicated
optimizer + memory_analysis evidence), per-plan donation, the shard_map
compile style, plan-tagged compile ledger rows, and the plan_sweep
ranking."""

import dataclasses
import importlib.util
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.data.feed import put_global, stack_examples
from distributeddeeplearningspark_tpu.models import (
    LlamaConfig,
    LlamaForCausalLM,
    llama_rules,
)
from distributeddeeplearningspark_tpu.parallel import plan as plan_lib
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.parallel.plan import (
    DP,
    Plan,
    PlanError,
    PlanValidationError,
    compile_step_with_plan,
    plan_for_rules,
    stage_plan,
    zero_plan,
)
from distributeddeeplearningspark_tpu.parallel.sharding import (
    REPLICATED,
    ShardingRules,
    add_axis_spec,
    path_str,
)
from distributeddeeplearningspark_tpu.telemetry import anatomy
from distributeddeeplearningspark_tpu.train import losses, step as step_lib


def _load_plan_sweep():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "plan_sweep.py")
    spec = importlib.util.spec_from_file_location("plan_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _llama_batch(cfg, rows=8, seq=16):
    return stack_examples([
        {"input_ids": np.full((seq,), i % cfg.vocab_size, np.int32),
         "loss_mask": np.ones((seq,), np.float32)}
        for i in range(rows)])


# -- validation ---------------------------------------------------------------


def test_validate_rejects_unknown_axes():
    mesh = MeshSpec(data=-1).build()
    with pytest.raises(PlanValidationError, match="do not exist"):
        Plan(name="bad", batch_axes=("data", "nonsense")).validate(mesh)
    with pytest.raises(PlanValidationError, match="do not exist"):
        Plan(name="bad2",
             rules=ShardingRules(rules=((r"w", P("warp")),))).validate(mesh)
    with pytest.raises(PlanValidationError, match="style"):
        Plan(name="bad3", style="pmap").validate(mesh)
    with pytest.raises(PlanValidationError, match="replica"):
        # zero axes must be replica (batch) axes — 'seq' replicates nothing
        Plan(name="bad4", zero_axes=("seq",)).validate(mesh)
    DP.validate(mesh)  # sane plan passes


def test_tensor_sharded_step_matches_replicated():
    """What replaced the tensor-axis refusal (PlanTensorAxisWarning, which
    pinned a jax 0.4.37 partitioner miscompute): on this jax the pinned
    repro layout computes the replicated reference's loss and post-SGD
    params, and validates without a warning. The same fingerprint passed
    on four real v5e chips (dryrun_multichip(4), CHANGES.md PR 21)."""
    import __graft_entry__ as graft

    cfg = LlamaConfig.tiny(lora_rank=4)
    model = LlamaForCausalLM(cfg)
    rules = llama_rules(cfg, fsdp_min_size=1)
    batch = stack_examples([
        {"input_ids": np.full((32,), i % cfg.vocab_size, np.int32),
         "loss_mask": np.ones((32,), np.float32)} for i in range(16)])
    mesh = MeshSpec(data=2, fsdp=2, tensor=2).build()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        DP.validate(mesh)
    got, loss_g, _ = graft._sgd_step_fingerprint(model, mesh, rules, False,
                                                 batch)
    want, loss_w, _ = graft._sgd_step_fingerprint(
        model, MeshSpec(data=1).build(jax.devices()[:1]), rules, False, batch)
    graft._assert_params_match(jax, got, loss_g, want, loss_w,
                               layout="data=2 x fsdp=2 x tensor=2")


# -- serialization / identity -------------------------------------------------


def test_compile_path_names_its_mesh_while_tracing():
    """Ops that lay themselves out by hand (the shard_map'd flash kernel,
    ring/ulysses) resolve the mesh a step is being compiled for — Session or
    not — because the compile path sets it for exactly the trace."""
    from distributeddeeplearningspark_tpu.ops import ring_attention

    mesh = MeshSpec(data=-1).build()
    seen = []

    def step(state, batch):
        seen.append(ring_attention.resolve_mesh())
        return state, {"loss": jnp.sum(batch["x"])}

    state = {"w": jnp.ones((4,), jnp.float32)}
    fn = compile_step_with_plan(step, DP, mesh, state_abstract=state,
                                kind="train", instrument=False)
    assert ring_attention.resolve_mesh() is None
    fn(state, put_global({"x": np.ones((8, 2), np.float32)}, mesh))
    assert seen == [mesh]
    assert ring_attention.resolve_mesh() is None


def test_plan_roundtrip_and_signature(tmp_path):
    cfg = LlamaConfig.tiny()
    p = Plan(name="ulysses+fsdp",
             rules=llama_rules(cfg, fsdp=True, fsdp_min_size=1),
             seq_axis="seq", zero_axes=("data",),
             model_hints=(("attention_impl", "ulysses"),),
             description="composed layout")
    path = str(tmp_path / "p.plan.json")
    p.save(path)
    q = Plan.load(path)
    assert q == p
    assert q.signature() == p.signature()
    assert q.hints() == {"attention_impl": "ulysses"}
    # description is NOT identity: same compile-relevant content, same sig
    r = dataclasses.replace(p, description="different words")
    assert r.signature() == p.signature()
    assert dataclasses.replace(p, zero_axes=()).signature() != p.signature()
    la = q.logical_axes()
    assert la["batch"] == ("data", "fsdp")
    assert la["sequence"] == ("seq",)
    assert la["weight_update"] == ("data",)
    assert "tensor" in la["params"] and "fsdp" in la["params"]
    # a record claiming a future format refuses instead of misparsing
    rec = p.to_record()
    rec["plan_format"] = 99
    with pytest.raises(PlanError, match="newer"):
        Plan.from_record(rec)


def test_plan_for_rules_naming():
    assert plan_for_rules(REPLICATED).name == "dp"
    assert plan_for_rules(ShardingRules(fsdp=True)).name == "fsdp"
    p = plan_for_rules(REPLICATED, context_parallel=True)
    assert p.name == "dp+seq" and p.seq_axis == "seq"


def test_stage_plan_names():
    cfg = LlamaConfig.tiny()
    assert stage_plan("replicated").rules == ShardingRules()
    assert stage_plan("fsdp", fsdp_min_size=64).rules.fsdp
    assert stage_plan("zero").zero_axes == ("data", "fsdp")
    assert stage_plan("tensor", cfg).rules.rules  # llama TP rules present
    with pytest.raises(PlanError, match="tensor.*cfg"):
        stage_plan("tensor")
    with pytest.raises(PlanError, match="unknown stage plan"):
        stage_plan("magic")


# -- add_axis_spec (the generalized auto-shard pass) --------------------------


def test_add_axis_spec_placement():
    mesh = MeshSpec(data=2, fsdp=2, seq=2).build()
    # single axis on the largest divisible dim
    assert add_axis_spec(P(), (8, 4), mesh, ("data",), 1) == P("data", None)
    # multi-axis tuple lands on ONE dim divisible by the product
    assert add_axis_spec(P(), (8, 3), mesh, ("data", "fsdp"), 1) == \
        P(("data", "fsdp"), None)
    # no dim takes the product: axes placed separately (tie on dim size
    # resolves to the later dim, the rule engine's max() tiebreak)
    assert add_axis_spec(P(), (2, 2), mesh, ("data", "fsdp"), 1) == \
        P("fsdp", "data")
    # below min size / already mentioned / indivisible: untouched
    assert add_axis_spec(P(), (2, 2), mesh, ("data",), 1000) == P()
    assert add_axis_spec(P("data"), (8, 4), mesh, ("data",), 1) == P("data")
    assert add_axis_spec(P(), (3, 5), mesh, ("data",), 1) == P()


# -- the ZeRO plan + fingerprint parity (one shared compiled setup) -----------


@pytest.fixture(scope="module")
def zero_vs_replicated():
    """Replicated-DP vs ZeRO-plan train setups on the same tiny llama —
    shared by the parity/memory/donation/ledger tests below (compiles are
    the expensive part; pay them once)."""
    mesh = MeshSpec(data=4).build(jax.devices()[:4])
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    batch = _llama_batch(cfg)
    gbatch = put_global(batch, mesh)
    out = {}
    for name, plan in (("dp", DP), ("zero", zero_plan(DP, axes=("data",)))):
        tx = plan.wrap_optimizer(optax.adam(1e-3), mesh)
        state, shardings = step_lib.init_state(
            model, tx, batch, mesh, plan.rules, plan=plan)
        step = compile_step_with_plan(
            step_lib.make_train_step(model.apply, tx, losses.causal_lm),
            plan, mesh, state_shardings=shardings, name=f"t-{name}",
            instrument=True)
        ledger = step.prepare(state, gbatch)
        donated = state
        traj = []
        for _ in range(3):
            state, metrics = step(state, gbatch)
            traj.append(float(jax.device_get(metrics["loss"])))
        out[name] = {
            "plan": plan, "shardings": shardings, "ledger": ledger,
            "step": step, "donated": donated, "losses": traj,
            "params": jax.device_get(state.params),
            "opt": jax.device_get(state.opt_state),
        }
    return out


def test_zero_plan_shards_optimizer_state(zero_vs_replicated):
    sh = zero_vs_replicated["zero"]["shardings"]
    flat = [(path_str(p), s) for p, s in
            jax.tree_util.tree_flatten_with_path(sh)[0]]
    opt = [(p, s) for p, s in flat if p.startswith("opt_state")
           and hasattr(s, "spec")]
    sharded = [p for p, s in opt if "data" in str(s.spec)]
    assert sharded, "no optimizer-state leaf sharded over the replica axis"
    # params stay replicated (this is weight-UPDATE sharding, not FSDP)
    for p, s in flat:
        if p.startswith("params"):
            assert "data" not in str(s.spec), (p, s)


def test_zero_plan_memory_analysis_evidence(zero_vs_replicated):
    """The anatomy ledger's memory_analysis is the acceptance evidence:
    the ZeRO executable's per-device argument bytes must drop vs the
    replicated layout (Adam moments stop being replicated 4x)."""
    rep = zero_vs_replicated["dp"]["ledger"]
    zero = zero_vs_replicated["zero"]["ledger"]
    assert rep and rep.get("argument_bytes"), rep
    assert zero and zero.get("argument_bytes"), zero
    assert zero["argument_bytes"] < 0.75 * rep["argument_bytes"], (
        rep["argument_bytes"], zero["argument_bytes"])


def test_zero_plan_matches_replicated_bitwise(zero_vs_replicated):
    """ZeRO weight-update sharding is a LAYOUT, not different math: the
    3-step loss trajectory, final params, and final optimizer state all
    match the replicated optimizer bit for bit (Plan.wrap_optimizer pins
    the gradient all-reduce; without it GSPMD's reduce-scatter order
    drifts the trajectory at step 2 — measured on this jax)."""
    rep, zero = zero_vs_replicated["dp"], zero_vs_replicated["zero"]
    assert rep["losses"] == zero["losses"]
    for a, b in zip(jax.tree.leaves(rep["params"]),
                    jax.tree.leaves(zero["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(rep["opt"]),
                    jax.tree.leaves(zero["opt"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_donation_frees_input_state_per_plan(zero_vs_replicated):
    """donate_state=True plans actually free the donated buffers — the
    input state of the first step call is deleted for BOTH layouts."""
    for name in ("dp", "zero"):
        donated = zero_vs_replicated[name]["donated"]
        leaves = jax.tree.leaves(donated.params)
        assert leaves and all(x.is_deleted() for x in leaves), name


def test_compile_ledger_rows_carry_plan_identity(zero_vs_replicated):
    for name in ("dp", "zero"):
        step = zero_vs_replicated[name]["step"]
        plan = zero_vs_replicated[name]["plan"]
        rec = step.records[-1]
        assert rec["plan"] == plan.name
        assert rec["plan_sig"] == plan.signature()
        s = step.compile_summary()
        assert s["plan"] == plan.name and s["plan_sig"] == plan.signature()


def test_plan_path_matches_direct_jit_bitwise():
    """Fingerprint parity: the SAME step jitted directly (the pre-plan
    wiring) and compiled through the plan layer produce bit-identical
    losses and post-step params — the layer changes where compiles are
    declared, never what they compute."""
    mesh = MeshSpec(data=2, fsdp=2).build(jax.devices()[:4])
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rules = llama_rules(cfg, fsdp_min_size=1)
    batch = _llama_batch(cfg)
    tx = optax.sgd(1e-2)
    train = step_lib.make_train_step(model.apply, tx, losses.causal_lm)

    st1, sh = step_lib.init_state(model, tx, batch, mesh, rules)
    direct = jax.jit(train, in_shardings=(sh, None),
                     out_shardings=(sh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
    st1, m1 = direct(st1, put_global(batch, mesh))

    st2, sh2 = step_lib.init_state(model, tx, batch, mesh, rules)
    plan = Plan(name="llama-fsdp", rules=rules)
    planned = compile_step_with_plan(train, plan, mesh,
                                     state_shardings=sh2, instrument=False)
    st2, m2 = planned(st2, put_global(batch, mesh))

    assert float(jax.device_get(m1["loss"])) == \
        float(jax.device_get(m2["loss"]))
    for a, b in zip(jax.tree.leaves(jax.device_get(st1.params)),
                    jax.tree.leaves(jax.device_get(st2.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- shard_map style ----------------------------------------------------------


def test_shard_map_style_matches_jit_style():
    """A map-style step (explicit all_reduce_mean over the batch axes)
    compiled via style='shard_map' equals the jit-style GSPMD step on the
    same data — the one compile path serves both idioms."""
    from distributeddeeplearningspark_tpu.parallel import collectives

    mesh = MeshSpec(data=4).build(jax.devices()[:4])
    w0 = np.linspace(-1, 1, 8).astype(np.float32).reshape(2, 4)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32.0
    y = np.ones((8, 2), np.float32)

    def grads_of(state, batch):
        def loss(w):
            pred = batch["x"] @ w.T
            return jnp.mean((pred - batch["y"]) ** 2)

        return jax.grad(loss)(state["w"])

    def map_step(state, batch):
        g = grads_of(state, batch)
        g = collectives.all_reduce_mean({"w": g}, ("data", "fsdp"))["w"]
        new = {"w": state["w"] - 0.1 * g}
        return new, {"gnorm": jnp.sqrt(jnp.sum(
            collectives.all_reduce_mean({"g": g},
                                        ("data", "fsdp"))["g"] ** 2))}

    def jit_step(state, batch):
        g = grads_of(state, batch)
        new = {"w": state["w"] - 0.1 * g}
        return new, {"gnorm": jnp.sqrt(jnp.sum(g ** 2))}

    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(("data", "fsdp")))
    batch = {"x": jax.device_put(x, row), "y": jax.device_put(y, row)}

    sm_plan = Plan(name="map-style", style="shard_map", donate_state=False)
    sm = compile_step_with_plan(
        map_step, sm_plan, mesh,
        state_shardings={"w": rep}, instrument=False)
    s1, m1 = sm({"w": jax.device_put(w0, rep)}, batch)

    jp = compile_step_with_plan(
        jit_step, Plan(name="gspmd", donate_state=False), mesh,
        state_shardings={"w": rep}, instrument=False)
    s2, m2 = jp({"w": jax.device_put(w0, rep)}, batch)

    np.testing.assert_allclose(np.asarray(s1["w"]), np.asarray(s2["w"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["gnorm"]), float(m2["gnorm"]),
                               rtol=1e-6)


# -- trainer integration ------------------------------------------------------


def test_trainer_accepts_plan_and_tags_ledger(tmp_path):
    """Trainer(plan=...) trains end to end with the plan's layout, the
    instrumented train step carries the plan identity, and telemetry
    compile events + chrome_trace compile spans are plan-tagged."""
    from distributeddeeplearningspark_tpu.session import Session
    from distributeddeeplearningspark_tpu.telemetry.trace import chrome_trace
    from distributeddeeplearningspark_tpu.train.trainer import Trainer
    from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

    import flax.linen as nn

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, batch, train=False):
            h = nn.Dense(16)(batch["x"])
            return nn.Dense(2)(nn.relu(h))

    def loss_fn(outputs, batch):
        onehot = jax.nn.one_hot(batch["label"], 2)
        loss = jnp.mean(optax.softmax_cross_entropy(outputs, onehot))
        return loss, {"loss": loss}

    rng = np.random.default_rng(0)
    examples = [{"x": rng.normal(0, 1, (8,)).astype(np.float32),
                 "label": np.int32(i % 2)} for i in range(64)]
    ds = PartitionedDataset.parallelize(examples, 2)
    telemetry.configure(tmp_path)
    try:
        spec = MeshSpec(data=4)
        session = Session("plan-test", {}, spec.build(jax.devices()[:4]),
                          spec)
        plan = dataclasses.replace(
            zero_plan(DP, axes=("data",), name="mlp-zero"),
            zero_min_size=64)  # the tiny MLP's leaves still shard
        tr = Trainer(session, TinyMLP(), loss_fn, optax.adam(1e-2),
                     plan=plan)
        os.environ["DLS_TELEMETRY_DIR"] = str(tmp_path)
        try:
            _, summary = tr.fit(ds, batch_size=16, steps=4, log_every=2)
        finally:
            os.environ.pop("DLS_TELEMETRY_DIR", None)
        assert np.isfinite(summary["loss"])
        assert tr._train_step.plan_name == "mlp-zero"
        events = telemetry.read_events(tmp_path)
        comp = [e for e in events if e.get("kind") == "compile"
                and e.get("fn") == "train_step"]
        assert comp and comp[0]["plan"] == "mlp-zero"
        assert comp[0]["plan_sig"] == plan.signature()
        # opt state actually sharded over the replica axis
        flat = [(path_str(p), s) for p, s in
                jax.tree_util.tree_flatten_with_path(tr.state_shardings)[0]]
        assert any(p.startswith("opt_state") and "data" in str(s.spec)
                   for p, s in flat if hasattr(s, "spec"))
        # chrome_trace: the compile span's args carry the plan tag
        trace = chrome_trace(events)
        spans = [e for e in trace["traceEvents"]
                 if e.get("name") == "compile" and e.get("ph") in ("X", "B")]
        assert spans and any(
            e["args"].get("plan") == "mlp-zero" for e in spans), spans
    finally:
        telemetry.reset()


def test_trainer_rejects_shard_map_plans():
    """Trainer's step bodies rely on GSPMD's implicit grad reduction —
    a shard_map plan would silently skip it, so construction refuses."""
    from distributeddeeplearningspark_tpu.session import Session
    from distributeddeeplearningspark_tpu.train.trainer import Trainer

    spec = MeshSpec(data=4)
    session = Session("plan-style-test", {}, spec.build(jax.devices()[:4]),
                      spec)
    with pytest.raises(PlanValidationError, match="style='jit'"):
        Trainer(session, object(), lambda o, b: (o, {}), optax.sgd(1e-2),
                plan=Plan(name="mapstyle", style="shard_map"))


# -- anatomy report / dlstatus ------------------------------------------------


def test_anatomy_report_by_fn_carries_plan():
    events = [
        {"kind": "compile", "ts": 1.0, "fn": "plan:dp", "sig": "f32[2]",
         "sig_hash": "aa", "compile_s": 0.5, "flops": 10.0,
         "bytes_accessed": 100.0, "plan": "dp", "plan_sig": "0123456789ab",
         "recompile": False, "aot": True},
        {"kind": "compile", "ts": 2.0, "fn": "plan:zero", "sig": "f32[2]",
         "sig_hash": "bb", "compile_s": 0.6, "plan": "dp+zero",
         "plan_sig": "ba9876543210", "recompile": False, "aot": True},
    ]
    rep = anatomy.anatomy_report(events)
    by_fn = rep["compile_ledger"]["by_fn"]
    assert by_fn["plan:dp"]["plan"] == "dp"
    assert by_fn["plan:dp"]["plan_sig"] == "0123456789ab"
    assert by_fn["plan:zero"]["plan"] == "dp+zero"
    assert all(e.get("plan") for e in rep["compile_ledger"]["events"])


# -- plan sweep ---------------------------------------------------------------


def test_plan_sweep_ranks_and_pins(tmp_path):
    sweep = _load_plan_sweep()
    mesh = MeshSpec(data=4).build(jax.devices()[:4])
    cfg = LlamaConfig.tiny()
    batch, digest = sweep._build_batch(cfg, 8, 16)
    assert digest == sweep._build_batch(cfg, 8, 16)[1]
    report = sweep.run_sweep(mesh, cfg, batch, steps=2, warmup=1,
                             rerun_steps=1, only={"dp", "dp+zero", "fsdp"})
    ranked = report["ranked"]
    assert {r["plan"] for r in ranked} == {"dp", "dp+zero"}
    times = [r["step_time_s"] for r in ranked]
    assert times == sorted(times)
    # fsdp needs an fsdp axis > 1: skipped WITH a reason, not missing
    sk = [r for r in report["skipped"] if r["plan"] == "fsdp"]
    assert sk and "mesh axes too small" in sk[0]["reason"]
    assert report["winner"] == ranked[0]["plan"]
    assert report["winner_rerun_new_compiles"] == 0
    for r in ranked:
        assert r["compiles"] == 1 and r["recompiles"] == 0
        assert r["steps_per_sec"] and r["compile_s"] is not None
        assert "_runtime" not in r
    # the winner serializes and re-loads identically (the pin contract)
    plans, _ = sweep.build_candidates(mesh, cfg,
                                      only={report["winner"]})
    path = str(tmp_path / "w.plan.json")
    plans[0].save(path)
    assert Plan.load(path).signature() == report["winner_sig"]


def test_pipeline_stage_plan_spec_parsing():
    from distributeddeeplearningspark_tpu.train.pipeline_trainer import (
        _stage_plan,
    )

    cfg = LlamaConfig.tiny()
    spec = {"stage_plans": {"0": "fsdp", "1": "tensor"}}
    assert _stage_plan(spec, 0, cfg).rules.fsdp
    assert _stage_plan(spec, 1, cfg).rules.rules
    # legacy key still honored
    assert _stage_plan({"stage_rules": {"0": "zero"}}, 0, cfg).zero_axes
    # inline serialized plan record (a pinned sweep winner)
    rec = zero_plan(DP, name="pinned").to_record()
    assert _stage_plan({"stage_plans": {"0": rec}}, 0, cfg).name == "pinned"
    with pytest.raises(ValueError, match="DLS_PIPE_SPEC"):
        _stage_plan({"stage_rules": {"0": "magic"}}, 0, cfg)
