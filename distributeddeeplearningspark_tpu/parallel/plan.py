"""Unified GSPMD compile layer: one ``Plan`` object drives every layout.

Until now each of the seven parallelism strategies hand-wired its own jit
call: ``train/step.py`` built jit-with-explicit-shardings for DP/FSDP/TP,
``pipeline_trainer`` wired per-stage rules by name, the dryrun fingerprints
called ``jit_train_step`` directly, and every new composition (ulysses×fsdp,
per-stage pipeline layouts) meant new wiring. GSPMD (PAPERS.md 2105.04663)
shows the alternative: ONE declarative object mapping logical axes → mesh
axes is enough to drive all of them through a single compile path.

:class:`Plan` is that object —

- a **logical-axis → mesh-axis mapping** (``batch_axes`` for the input
  batch, ``seq_axis`` for context parallelism) plus **per-leaf sharding
  rules** (:class:`~.sharding.ShardingRules`) for params/optimizer state;
- a **donation spec** (``donate_state``) and a compile **style** —
  ``"jit"`` (jit-with-explicit-shardings, the GSPMD path every strategy
  uses today) or ``"shard_map"`` for map-style bodies that call the
  explicit Horovod verb set;
- **ZeRO weight-update sharding** (PAPERS.md 2004.13336) as plain plan
  data: ``zero_axes`` shards optimizer-state leaves across the replica
  axes while :meth:`Plan.wrap_optimizer` pins the gradient all-reduce to
  the replicated layout — so the update math stays BITWISE identical to
  the replicated optimizer (GSPMD would otherwise switch to a
  reduce-scatter whose different reduction order drifts fp) and no new
  collective code exists anywhere: sharded storage is just out/in
  shardings, the gather-at-apply is GSPMD's.

:func:`compile_step_with_plan` is the single compile path: spec validation
and donation centralized, every executable routed through
``telemetry/anatomy.instrument()`` so each plan gets a ledgered,
cost-analyzed compile for free — which is what makes ``tools/plan_sweep.py``
possible: candidate plans are ranked by *measured* step time / MFU /
bytes-accessed instead of folklore, and the winner serializes
(:meth:`Plan.save` / :meth:`Plan.load`) so a training run can pin it.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
from typing import Any, Callable, Mapping

from distributeddeeplearningspark_tpu.parallel.mesh import BATCH_AXES
from distributeddeeplearningspark_tpu.parallel.sharding import (
    REPLICATED,
    ShardingRules,
    add_axis_spec,
    path_str,
)

#: current on-disk plan format (Plan.save / Plan.load).
PLAN_FORMAT = 1


class PlanError(ValueError):
    """Base for plan-layer errors."""


class PlanValidationError(PlanError):
    """A plan cannot compile on this mesh (axis mismatch, bad style)."""


def _spec_entries(spec) -> list:
    """PartitionSpec → plain list (None | str | list[str]) for JSON."""
    out = []
    for e in spec:
        if e is None or isinstance(e, str):
            out.append(e)
        else:
            out.append(list(e))
    return out


def _entries_spec(entries):
    from jax.sharding import PartitionSpec as P

    return P(*[tuple(e) if isinstance(e, list) else e for e in entries])


def _rules_record(rules: ShardingRules) -> dict:
    return {
        "rules": [[pat, _spec_entries(spec)] for pat, spec in rules.rules],
        "fsdp": bool(rules.fsdp),
        "fsdp_min_size": int(rules.fsdp_min_size),
        "fsdp_exclude": list(rules.fsdp_exclude),
    }


def _record_rules(rec: Mapping) -> ShardingRules:
    return ShardingRules(
        rules=tuple((pat, _entries_spec(entries))
                    for pat, entries in rec.get("rules", ())),
        fsdp=bool(rec.get("fsdp", False)),
        fsdp_min_size=int(rec.get("fsdp_min_size", 2**14)),
        fsdp_exclude=tuple(rec.get("fsdp_exclude", ())),
    )


def _spec_axes(spec) -> set[str]:
    axes: set[str] = set()
    for e in spec:
        if e is None:
            continue
        if isinstance(e, str):
            axes.add(e)
        else:
            axes.update(e)
    return axes


@dataclasses.dataclass(frozen=True)
class Plan:
    """Declarative layout: logical axes → mesh axes + per-leaf rules +
    donation, the one object :func:`compile_step_with_plan` compiles.

    ``batch_axes`` — mesh axes the logical ``batch`` axis splits over
    (the input feed and map-style bodies both read it).
    ``seq_axis`` — mesh axis for the logical ``sequence`` axis (context
    parallelism); ``None`` = sequence replicated.
    ``rules`` — the per-leaf param/optimizer sharding rule engine.
    ``zero_axes`` — ZeRO weight-update sharding: optimizer-state leaves
    (size ≥ ``zero_min_size``) get their largest divisible dim sharded
    over these replica axes; pair with :meth:`wrap_optimizer` for the
    bitwise-parity gradient pin.
    ``style`` — ``"jit"`` (GSPMD jit with explicit shardings) or
    ``"shard_map"`` (map-style body using explicit collectives).
    ``model_hints`` — serializable model-config overrides a probe/driver
    applies before building the model (e.g. ``attention_impl=ulysses``);
    the plan layer itself never reads them.
    """

    name: str
    rules: ShardingRules = REPLICATED
    batch_axes: tuple[str, ...] = BATCH_AXES
    seq_axis: str | None = None
    style: str = "jit"
    zero_axes: tuple[str, ...] = ()
    zero_min_size: int = 2**11
    donate_state: bool = True
    model_hints: tuple[tuple[str, str], ...] = ()
    description: str = ""

    def __post_init__(self):
        object.__setattr__(self, "batch_axes", tuple(self.batch_axes))
        object.__setattr__(self, "zero_axes", tuple(self.zero_axes))
        object.__setattr__(self, "model_hints",
                           tuple((str(k), str(v))
                                 for k, v in dict(self.model_hints).items()))

    # -- logical view --------------------------------------------------------

    @property
    def seq_sharded(self) -> bool:
        return self.seq_axis is not None

    def logical_axes(self) -> dict[str, tuple[str, ...]]:
        """The logical-axis → mesh-axis mapping this plan declares."""
        out: dict[str, tuple[str, ...]] = {"batch": self.batch_axes}
        if self.seq_axis:
            out["sequence"] = (self.seq_axis,)
        if self.zero_axes:
            out["weight_update"] = self.zero_axes
        param_axes: set[str] = set()
        for _, spec in self.rules.rules:
            param_axes.update(_spec_axes(spec))
        if self.rules.fsdp:
            param_axes.add("fsdp")
        if param_axes:
            out["params"] = tuple(sorted(param_axes))
        return out

    def hints(self) -> dict[str, str]:
        return dict(self.model_hints)

    # -- validation ----------------------------------------------------------

    def validate(self, mesh) -> None:
        """Centralized spec validation for this plan on ``mesh``: every mesh
        axis the plan mentions exists, the style is known, and ZeRO axes
        are replica axes."""
        if self.style not in ("jit", "shard_map"):
            raise PlanValidationError(
                f"plan {self.name!r}: style must be 'jit'|'shard_map', got "
                f"{self.style!r}")
        names = set(mesh.axis_names)
        mentioned: set[str] = set(self.batch_axes) | set(self.zero_axes)
        if self.seq_axis:
            mentioned.add(self.seq_axis)
        for _, spec in self.rules.rules:
            mentioned.update(_spec_axes(spec))
        missing = sorted(mentioned - names)
        if missing:
            raise PlanValidationError(
                f"plan {self.name!r} maps logical axes onto mesh axes "
                f"{missing} that do not exist on this mesh (axes: "
                f"{sorted(names)})")
        if not self.batch_axes:
            raise PlanValidationError(
                f"plan {self.name!r}: batch_axes must name at least one "
                f"mesh axis")
        overlap = set(self.zero_axes) - set(self.batch_axes)
        if self.zero_axes and overlap:
            raise PlanValidationError(
                f"plan {self.name!r}: zero_axes {sorted(overlap)} are not "
                f"replica (batch) axes — ZeRO shards optimizer state across "
                f"the axes that replicate it, i.e. a subset of batch_axes "
                f"{self.batch_axes}")

    # -- shardings -----------------------------------------------------------

    def state_shardings(self, state_abstract: Any, mesh) -> Any:
        """Shardings for a full TrainState pytree under this plan.

        Params and mutables follow ``rules`` exactly like
        :func:`~.sharding.state_shardings`; optimizer-state leaves
        additionally get the ZeRO pass (``zero_axes``) — their largest
        still-unsharded divisible dim shards across the replica axes, so
        Adam moments stop being replicated per data-parallel copy."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def leaf_sharding(path, leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            if not shape:
                return NamedSharding(mesh, P())
            p = path_str(path)
            spec = self.rules.spec_for(p, shape, mesh)
            if self.zero_axes and p.startswith("opt_state"):
                spec = add_axis_spec(spec, shape, mesh, self.zero_axes,
                                     self.zero_min_size)
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(leaf_sharding, state_abstract)

    def wrap_optimizer(self, tx, mesh):
        """The ZeRO bitwise-parity pin: constrain the gradients entering
        ``tx.update`` to the replicated layout.

        With optimizer state sharded over the replica axes, GSPMD would
        otherwise lower the gradient sync as a reduce-scatter — a
        different reduction order, so the trajectory drifts from the
        replicated optimizer at the second step. Pinning grads replicated
        keeps the IDENTICAL all-reduce; the elementwise update then
        computes bit-equal moments per shard, and the gather at apply is
        a pure layout move. No-op when the plan has no ``zero_axes``."""
        if not self.zero_axes:
            return tx
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())

        def update(grads, state, params=None):
            grads = jax.lax.with_sharding_constraint(grads, rep)
            return tx.update(grads, state, params)

        return optax.GradientTransformation(tx.init, update)

    # -- identity / serialization -------------------------------------------

    def to_record(self) -> dict:
        return {
            "plan_format": PLAN_FORMAT,
            "name": self.name,
            "description": self.description,
            "rules": _rules_record(self.rules),
            "batch_axes": list(self.batch_axes),
            "seq_axis": self.seq_axis,
            "style": self.style,
            "zero_axes": list(self.zero_axes),
            "zero_min_size": int(self.zero_min_size),
            "donate_state": bool(self.donate_state),
            "model_hints": dict(self.model_hints),
        }

    @classmethod
    def from_record(cls, rec: Mapping) -> "Plan":
        fmt = int(rec.get("plan_format", PLAN_FORMAT))
        if fmt > PLAN_FORMAT:
            raise PlanError(
                f"plan record format {fmt} is newer than this build's "
                f"{PLAN_FORMAT}")
        return cls(
            name=str(rec["name"]),
            description=str(rec.get("description", "")),
            rules=_record_rules(rec.get("rules", {})),
            batch_axes=tuple(rec.get("batch_axes", BATCH_AXES)),
            seq_axis=rec.get("seq_axis"),
            style=str(rec.get("style", "jit")),
            zero_axes=tuple(rec.get("zero_axes", ())),
            zero_min_size=int(rec.get("zero_min_size", 2**11)),
            donate_state=bool(rec.get("donate_state", True)),
            model_hints=tuple(dict(rec.get("model_hints", {})).items()),
        )

    def signature(self) -> str:
        """Stable content hash of everything compile-relevant (NOT the
        description) — the id the compile ledger and sweep tables carry."""
        rec = self.to_record()
        rec.pop("description", None)
        return hashlib.blake2b(
            json.dumps(rec, sort_keys=True).encode(),
            digest_size=6).hexdigest()

    def save(self, path: str) -> None:
        """Serialize so a training run can pin a sweep winner."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_record(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Plan":
        with open(path) as f:
            return cls.from_record(json.load(f))

    def describe(self) -> str:
        la = ", ".join(f"{k}→{'×'.join(v)}"
                       for k, v in self.logical_axes().items())
        return (f"Plan({self.name} [{self.signature()}] {self.style}: {la}"
                + (f", hints={self.hints()}" if self.model_hints else "")
                + ")")


# -- the single compile path --------------------------------------------------


def compile_step_with_plan(
    step_fn: Callable,
    plan: Plan,
    mesh,
    *,
    state_shardings: Any = None,
    state_abstract: Any = None,
    kind: str = "train",
    name: str | None = None,
    instrument: bool = True,
    expected_signatures: int = 1,
):
    """Compile ``step_fn`` under ``plan`` — the one jit call every
    strategy shares.

    ``kind``: ``"train"`` ((state, batch) → (state, metrics), state
    donated per the plan), ``"eval"`` ((state, batch) → metrics), or
    ``"predict"`` ((state, batch) → replicated outputs).

    ``style="jit"`` compiles via jit-with-explicit-shardings (batch
    shardings inherited from the arrays — ``put_global`` stays the single
    source of truth for the input layout); ``style="shard_map"`` wraps
    the body in :func:`jax.shard_map` over the plan's batch
    axes so map-style code using the explicit Horovod verbs compiles
    through the same path.

    With ``instrument=True`` the executable is routed through
    ``telemetry/anatomy.instrument()``: every compile becomes a ledgered,
    cost-analyzed ``compile`` event TAGGED with the plan's name and
    signature — the measurements ``tools/plan_sweep.py`` ranks on.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if kind not in ("train", "eval", "predict"):
        raise PlanError(f"kind must be 'train'|'eval'|'predict', got {kind!r}")
    plan.validate(mesh)
    if state_shardings is None:
        if state_abstract is None:
            raise PlanError(
                "compile_step_with_plan needs state_shardings or an "
                "abstract state to derive them from the plan's rules")
        state_shardings = plan.state_shardings(state_abstract, mesh)
    rep = NamedSharding(mesh, P())
    donate = (0,) if (kind == "train" and plan.donate_state) else ()
    step_fn = traced_on(step_fn, mesh)

    if plan.style == "shard_map":
        row = P(plan.batch_axes)
        out_specs = (P(), P()) if kind == "train" else P()
        body = jax.shard_map(step_fn, mesh=mesh, in_specs=(P(), row),
                         out_specs=out_specs, check_vma=False)
        jitted = jax.jit(body, donate_argnums=donate)
    else:
        out_sh = ((state_shardings, rep) if kind == "train" else rep)
        jitted = jax.jit(step_fn, in_shardings=(state_shardings, None),
                         out_shardings=out_sh, donate_argnums=donate)
    if not instrument:
        return jitted
    from distributeddeeplearningspark_tpu.telemetry import anatomy as anatomy_lib

    return anatomy_lib.instrument(
        jitted, name=name or f"plan:{plan.name}",
        expected_signatures=expected_signatures, plan=plan)


def traced_on(fn: Callable, mesh) -> Callable:
    """``fn`` with ``mesh`` as the ops' default mesh while its Python body
    runs, i.e. while jit traces it — so a kernel that must lay itself out by
    hand (a Mosaic kernel cannot be auto-partitioned) and the ring/ulysses
    paths find the mesh the program is being compiled for, with or without
    a Session. Both places that trace a model for a mesh use it: this
    module's compile path and ``train/step.init_state``."""
    from distributeddeeplearningspark_tpu.ops import ring_attention

    @functools.wraps(fn)
    def traced(*args):
        prev = ring_attention._default_mesh
        ring_attention.set_default_mesh(mesh)
        try:
            return fn(*args)
        finally:
            ring_attention.set_default_mesh(prev)

    return traced


# -- canned plans -------------------------------------------------------------

#: Pure data parallelism — params/opt replicated, batch over (data, fsdp).
DP = Plan(name="dp", rules=REPLICATED,
          description="replicated params, batch over (data, fsdp)")

#: ZeRO-style FSDP: every large param (and its optimizer moments, which
#: follow the same rules) sharded over the ``fsdp`` axis.
FSDP_PLAN = Plan(name="fsdp", rules=ShardingRules(fsdp=True),
                 description="auto-FSDP params + moments over 'fsdp'")


def zero_plan(base: Plan = DP, *, axes: tuple[str, ...] | None = None,
              name: str | None = None) -> Plan:
    """ZeRO weight-update sharding as *just another plan*: ``base``'s
    param layout, optimizer state sharded across the replica axes.

    Defaults to sharding over every batch axis the base declares (the
    axes that replicate the optimizer state today). Pair with
    :meth:`Plan.wrap_optimizer` — :func:`compile_step_with_plan` callers
    (Trainer, the sweep) do this automatically."""
    axes = tuple(axes if axes is not None else base.batch_axes)
    return dataclasses.replace(
        base, name=name or f"{base.name}+zero", zero_axes=axes,
        description=(base.description + " + ZeRO weight-update sharding "
                     f"over {axes}").strip())


def plan_for_rules(rules: ShardingRules, *, context_parallel: bool = False,
                   name: str | None = None) -> Plan:
    """Wrap a legacy (rules, context_parallel) trainer config as a Plan —
    how pre-plan call sites route through the new layer unchanged."""
    if name is None:
        name = "fsdp" if rules.fsdp else ("dp" if not rules.rules else "rules")
        if context_parallel:
            name += "+seq"
    return Plan(name=name, rules=rules,
                seq_axis="seq" if context_parallel else None)


def stage_plan(name: str, cfg=None, *, fsdp_min_size: int = 2**14) -> Plan:
    """Per-stage pipeline layouts by name (``DLS_PIPE_SPEC``'s
    ``stage_plans``/``stage_rules`` values): ``replicated`` | ``fsdp`` |
    ``tensor`` (needs the model cfg) | ``zero``."""
    if name == "replicated":
        return Plan(name="stage-replicated")
    if name == "fsdp":
        return Plan(name="stage-fsdp",
                    rules=ShardingRules(fsdp=True, fsdp_min_size=fsdp_min_size))
    if name == "zero":
        return zero_plan(Plan(name="stage"), name="stage-zero")
    if name == "tensor":
        if cfg is None:
            raise PlanError("stage_plan('tensor') needs the model cfg")
        from distributeddeeplearningspark_tpu.models.llama import llama_rules

        return Plan(name="stage-tensor", rules=llama_rules(cfg, fsdp=False))
    raise PlanError(
        f"unknown stage plan {name!r} (want replicated|fsdp|tensor|zero)")
