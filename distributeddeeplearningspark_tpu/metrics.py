"""Metrics & observability: throughput, step time, achieved MFU.

The reference reports loss/accuracy per Spark round plus whatever the Spark UI
shows per stage (SURVEY.md §5). The rebuild reports the BASELINE.json headline
metrics directly: images/sec/chip & tokens/sec/chip, plus step time and
achieved MFU (model FLOPs from XLA's own cost analysis of the compiled step ÷
chip peak).

Peak FLOPs table is bf16 dense peak per chip (public TPU spec sheet numbers).
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Any

import jax

logger = logging.getLogger("distributeddeeplearningspark_tpu.metrics")

#: bf16 dense peak FLOPs/s per chip, by jax device_kind (public spec numbers).
PEAK_FLOPS: dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def env_peak_flops_override() -> float | None:
    """The validated ``DLS_PEAK_FLOPS`` env override, or None — the ONE
    parse shared by :func:`device_peak_flops` and the anatomy layer's
    labeled resolution (:func:`..telemetry.anatomy.resolve_peak_flops`)."""
    raw = os.environ.get("DLS_PEAK_FLOPS")
    if raw:
        try:
            v = float(raw)
        except ValueError:
            logger.warning("ignoring malformed DLS_PEAK_FLOPS=%r", raw)
            return None
        if v > 0:
            return v
    return None


def spec_peak_flops(device: jax.Device) -> float | None:
    """The spec-table peak for ``device``; None on the host CPU (no peak, so
    no utilization). An accelerator whose ``device_kind`` is not in
    :data:`PEAK_FLOPS` is an error, not a default."""
    peak = PEAK_FLOPS.get(device.device_kind)
    if peak is None and device.platform != "cpu":
        raise ValueError(
            f"no peak FLOPs entry for {device.platform} device_kind "
            f"{device.device_kind!r}: add it to metrics.PEAK_FLOPS with its "
            f"source (known: {sorted(PEAK_FLOPS)})")
    return peak


def device_peak_flops(device: jax.Device | None = None) -> float | None:
    """Per-chip peak FLOPs/s for the MFU denominator.

    ``DLS_PEAK_FLOPS`` overrides the spec table — price a derated clock, or
    pin a projection's denominator explicitly (:mod:`.telemetry.anatomy`
    resolves the same order and adds a labeled nominal CPU figure for the
    anatomy gauges)."""
    v = env_peak_flops_override()
    if v is not None:
        return v
    return spec_peak_flops(device if device is not None else jax.devices()[0])


def attention_matmul_flops(
    batch: int,
    heads: int,
    seq: int,
    head_dim: int,
    *,
    causal: bool = False,
    train: bool = True,
) -> float:
    """Model matmul FLOPs of ONE attention op, for MFU accounting.

    XLA's cost analysis cannot see inside a Pallas custom call, so a step
    whose attention runs the flash kernel under-reports FLOPs (and therefore
    MFU) by exactly this amount per attention. Convention: model flops, not
    implementation flops — the backward's in-kernel recompute of the score
    matrix is NOT counted, matching how published MFU numbers are computed.

    fwd = QKᵀ + PV = 2 matmuls = 2 · (2·B·H·S²·D); bwd adds dV, dP, dQ, dK =
    4 more. GQA does not change this: both matmuls run at the q-head count.
    Causal masking halves the useful score footprint.
    """
    one_matmul = 2.0 * batch * heads * seq * seq * head_dim
    total = 2 * one_matmul + (4 * one_matmul if train else 0.0)
    return total * (0.5 if causal else 1.0)


def llama_model_flops_per_token(cfg, seq: int, *,
                                frozen_base: bool = True) -> float:
    """Analytic MODEL FLOPs per trained token (2 flops per MAC — the
    convention published MFU numbers use, cf. the PaLM appendix formula).

    Exists because ``compiled.cost_analysis()`` cannot be trusted for the
    SCANNED Llama step on any backend: XLA's cost analysis reports the
    while/scan body ONCE, not × trip count, so with ``scan_layers=True`` the
    count does not grow with depth, while the unrolled step scales with L
    and lands within ~6–13% of this formula (XLA counts 2 flops/MAC; the
    excess is elementwise work the formula excludes). Both are held by
    ``tests/test_metrics_flops.py``. A low ``mfu`` from the raw compiled
    count is therefore a structural property of scanned models, not a
    backend bug.

    Counted: projection/FFN/head matmuls (embedding lookup is a gather),
    attention score/value matmuls (causal halving, q-head count — GQA does
    not change matmul FLOPs), LoRA adapter matmuls. Forward = 2·P; backward
    dx = 2·P again; backward dW = 2·P only for trainable params (the
    frozen-base step excludes base dW). Not
    counted: elementwise/norm/softmax work and the optimizer (sub-1% at
    transformer shapes), remat recompute (model flops, not implementation
    flops — matches how published MFU is computed).
    """
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    kvh = cfg.num_kv_heads * cfg.head_dim
    # MoE (moe_experts > 0): each token runs top_k expert FFNs plus the
    # router projection — that is the model work. The GShard dispatch/
    # combine einsums and capacity-dropped tokens are implementation- and
    # load-dependent and are excluded, same as remat recompute.
    ffn = 3 * h * i
    if getattr(cfg, "moe_experts", 0):
        ffn = cfg.moe_top_k * 3 * h * i + h * cfg.moe_experts
    p_layer = h * h + 2 * h * kvh + h * h + ffn
    p_matmul = cfg.num_layers * p_layer + v * h  # + head, embed is a gather
    lora = 0
    if cfg.lora_rank:
        sizes = {"wq": (h, h), "wk": (h, kvh), "wv": (h, kvh), "wo": (h, h),
                 "gate": (h, i), "up": (h, i), "down": (i, h)}
        lora = sum(cfg.num_layers * cfg.lora_rank * (fi + fo)
                   for t, (fi, fo) in sizes.items() if t in cfg.lora_targets)
    # fwd + bwd-dx always; dW for the trainable set only
    dense = (4 * p_matmul if frozen_base else 6 * p_matmul) + 6 * lora
    attn = cfg.num_layers * attention_matmul_flops(
        1, cfg.num_heads, seq, cfg.head_dim, causal=True, train=True) / seq
    return float(dense + attn)


def compiled_flops_per_step(compiled) -> float | None:
    """Total FLOPs of one compiled step from XLA cost analysis (global).

    CAVEAT: XLA cost analysis reports a ``lax.scan``/while body ONCE, not
    multiplied by trip count (measured r5: scanned-Llama counts identical
    at L=2/4/8), so this number undercounts scanned models by ~L× on the
    scanned terms. Valid for unrolled models (ResNet/BERT reconcile with
    their rooflines); for scanned Llama use
    :func:`llama_model_flops_per_token`.
    """
    try:
        cost = compiled.cost_analysis()
        return float(cost.get("flops", 0.0)) or None
    except Exception:  # cost analysis unsupported on some backends
        return None


class StreamingAUC:
    """Histogram-binned ROC AUC over a prediction stream (config 4's metric).

    CTR accuracy is degenerate at Criteo's ~3% positive rate (predicting
    "no click" scores 97%); ranking quality — AUC — is the metric the
    reference's recommender workload is actually judged by. Exact AUC needs
    a global sort, which neither streams nor shards; the standard
    large-scale estimator bins scores into a fixed histogram per class and
    trapezoid-integrates the binned ROC — error is O(1/bins), and 4096 bins
    puts it far below run-to-run training noise.

    Feed sigmoid probabilities (or any monotone score mapped to [0, 1])
    batch by batch from ``Trainer.predict``; ``compute()`` at the end.
    """

    def __init__(self, num_bins: int = 4096):
        import numpy as np

        self.num_bins = num_bins
        self._pos = np.zeros(num_bins, np.int64)
        self._neg = np.zeros(num_bins, np.int64)

    def update(self, scores, labels) -> None:
        import numpy as np

        s = np.clip(np.asarray(scores, np.float64).reshape(-1), 0.0, 1.0)
        y = np.asarray(labels).reshape(-1)
        if s.shape != y.shape:
            raise ValueError(f"scores {s.shape} vs labels {y.shape}")
        bins = np.minimum((s * self.num_bins).astype(np.int64),
                          self.num_bins - 1)
        self._pos += np.bincount(bins[y > 0], minlength=self.num_bins)
        self._neg += np.bincount(bins[y <= 0], minlength=self.num_bins)

    def compute(self) -> float:
        """AUC = P(score⁺ > score⁻) + ½·P(tie), from the class histograms."""
        import numpy as np

        npos, nneg = self._pos.sum(), self._neg.sum()
        if npos == 0 or nneg == 0:
            return float("nan")  # undefined without both classes
        # for each positive bin b: negatives strictly below + half of ties
        neg_below = np.concatenate(([0], np.cumsum(self._neg)[:-1]))
        wins = float((self._pos * neg_below).sum())
        ties = 0.5 * float((self._pos * self._neg).sum())
        return (wins + ties) / (float(npos) * float(nneg))


def auc_from_predictions(
    predictions,
    *,
    num_bins: int = 4096,
    label_key: str = "label",
    max_examples: int | None = None,
    chunk: int = 8192,
) -> float:
    """AUC over a prediction stream, buffered into chunked updates.

    Accepts the two stream shapes that occur in practice:

    - ``Trainer.predict(..., with_inputs=True)`` pairs: ``(example_dict,
      score)`` — the label is read from ``example_dict[label_key]``;
    - plain ``(score, label)`` pairs.

    Rows are buffered and fed to :meth:`StreamingAUC.update` in ``chunk``
    batches (per-row updates would pay two ``num_bins``-length histogram
    adds per example). ``max_examples`` stops consuming the stream early —
    essential when the source is a full Criteo day file.
    """
    import itertools

    import numpy as np

    auc = StreamingAUC(num_bins)
    scores: list = []
    labels: list = []
    buffered_rows = 0  # ADVICE r3: count ROWS, not arrays — a stream of
    # batched arrays would otherwise hold chunk×batch rows before flushing

    def flush():
        nonlocal buffered_rows
        if scores:
            auc.update(np.concatenate(scores), np.concatenate(labels))
            scores.clear()
            labels.clear()
            buffered_rows = 0

    stream = (predictions if max_examples is None
              else itertools.islice(predictions, max_examples))
    for a, b_ in stream:
        if isinstance(a, dict):
            score, label = b_, a[label_key]
        else:
            score, label = a, b_
        s = np.asarray(score, np.float64).reshape(-1)
        scores.append(s)
        labels.append(np.asarray(label).reshape(-1))
        buffered_rows += s.size
        if buffered_rows >= chunk:
            flush()
    flush()
    return auc.compute()


class Meter:
    """Per-step wall-clock + throughput + MFU accounting.

    Usage::

        meter = Meter(examples_per_step=global_batch, tokens_per_step=...)
        meter.set_flops(compiled_flops_per_step(step_fn.lower(...).compile()))
        meter.start()
        for i, batch in enumerate(feed, 1):
            state, m = step_fn(state, batch)
            if i % log_every == 0:
                meter.lap(log_every, jax.device_get(m))  # sync point
    """

    def __init__(
        self,
        *,
        examples_per_step: int = 0,
        tokens_per_step: int = 0,
        num_chips: int | None = None,
        warmup_laps: int = 1,
    ):
        self.examples_per_step = examples_per_step
        self.tokens_per_step = tokens_per_step
        self.num_chips = num_chips or jax.device_count()
        self.warmup_laps = warmup_laps
        self.flops_per_step: float | None = None
        # (elapsed_seconds, num_steps) per lap; laps must be recorded at
        # device-sync points or the timing measures async dispatch, not compute
        self._laps: list[tuple[float, int]] = []
        self._last: float | None = None
        self._metrics_history: list[dict[str, float]] = []
        #: the most recent (elapsed_s, num_steps) lap — telemetry reads it to
        #: stamp the step_metrics record without reaching into _laps
        self.last_lap: tuple[float, int] | None = None

    def set_flops(self, flops: float | None) -> None:
        self.flops_per_step = flops

    def start(self) -> None:
        self._last = time.perf_counter()

    def lap(self, num_steps: int, device_metrics: dict[str, Any] | None = None) -> dict[str, float]:
        """Record a timing lap covering ``num_steps`` steps.

        Call ONLY at points where the host has just synchronized with the
        device (e.g. right after ``device_get`` of that step's metrics) —
        JAX dispatch is async, so unsynchronized wall-clock deltas measure
        enqueue time and overstate throughput by up to the lap length.
        """
        now = time.perf_counter()
        if self._last is not None and num_steps > 0:
            self.last_lap = (now - self._last, num_steps)
            self._laps.append(self.last_lap)
        self._last = now
        record: dict[str, float] = {}
        if device_metrics is not None:
            # 0-d device arrays / numpy scalars coerce through float(); a
            # leaf that doesn't (a string, a vector) is dropped rather than
            # crashing the lap — EXCEPT a numeric non-scalar carrying a
            # non-finite entry, which must surface as NaN: the returned
            # record feeds fit()'s divergence detection, and a NaN hidden
            # in a vector metric must stay loud, not vanish silently
            import numpy as np

            for k, v in device_metrics.items():
                try:
                    record[k] = float(v)
                except (TypeError, ValueError):
                    try:
                        arr = np.asarray(v, dtype=np.float64)
                    except (TypeError, ValueError):
                        continue  # non-numeric: reporting only, skip
                    if arr.size and not np.all(np.isfinite(arr)):
                        record[k] = float("nan")
            # the RETURNED record keeps non-finite values (divergence
            # detection in Trainer.fit reads them), but the history feeding
            # summary()'s final-metrics merge takes only the finite subset —
            # one NaN lap must not poison the run summary
            finite = {k: v for k, v in record.items() if math.isfinite(v)}
            if finite:
                self._metrics_history.append(finite)
        return record

    @property
    def steady_laps(self) -> list[tuple[float, int]]:
        # first lap(s) include jit compile; drop when there is anything after
        return self._laps[self.warmup_laps:] if len(self._laps) > self.warmup_laps else self._laps

    def summary(self) -> dict[str, float]:
        laps = self.steady_laps
        if not laps:
            return {}
        step_time = sum(t for t, _ in laps) / sum(n for _, n in laps)
        out: dict[str, float] = {
            "step_time_ms": step_time * 1e3,
            "steps_per_sec": 1.0 / step_time,
        }
        if self.examples_per_step:
            out["examples_per_sec"] = self.examples_per_step / step_time
            out["examples_per_sec_per_chip"] = out["examples_per_sec"] / self.num_chips
        if self.tokens_per_step:
            out["tokens_per_sec"] = self.tokens_per_step / step_time
            out["tokens_per_sec_per_chip"] = out["tokens_per_sec"] / self.num_chips
        peak = device_peak_flops()
        if self.flops_per_step and peak:
            out["model_flops_per_sec_per_chip"] = self.flops_per_step / step_time / self.num_chips
            out["mfu"] = out["model_flops_per_sec_per_chip"] / peak
        if self._metrics_history:
            out.update(self._metrics_history[-1])
        return out


def _log_value(v):
    """Display form of one metric value: counter-like values (step, tokens,
    examples — integral floats) print as exact ints, because ``round(v, 6)``
    keeps them floats and json renders large ones in scientific notation
    (``1e+16``), mangling the very counters operators grep for. Everything
    else keeps the historical 6-decimal rounding."""
    try:
        f = float(v)
    except (TypeError, ValueError):
        return v
    if math.isfinite(f) and f.is_integer() and abs(f) < 2**63:
        return int(f)
    return round(f, 6)


class MetricLogger:
    """Structured per-step logging on process 0; optional TensorBoard.

    ``telemetry`` (an :class:`~..telemetry.EventWriter`) mirrors recovery
    events into the run's durable JSONL stream — stderr lines and TB scalars
    die with the process/viewer, but ``dlstatus`` reads the stream after the
    fact, including for crashed runs."""

    def __init__(self, log_every: int = 10, tensorboard_dir: str | None = None,
                 telemetry=None):
        self.log_every = log_every
        self._telemetry = telemetry
        self._tb = None
        if tensorboard_dir and jax.process_index() == 0:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(tensorboard_dir)
            except Exception:
                logger.warning("tensorboard writer unavailable; file logging only")

    def log(self, step: int, metrics: dict[str, float]) -> None:
        """Emit unconditionally — cadence is the caller's decision."""
        if jax.process_index() != 0:
            return
        logger.info("step %d: %s", step,
                    json.dumps({k: _log_value(v) for k, v in metrics.items()}))
        if self._tb is not None:
            for k, v in metrics.items():
                self._tb.add_scalar(k, v, step)

    def event(self, step: int, kind: str, **fields) -> None:
        """Surface a recovery event (divergence skip, rollback, restore
        fallback) as its own WARNING log line + a ``recovery/<kind>`` TB
        scalar — these are the lines an operator greps for after an incident,
        so they must not drown in the per-step metric stream — and mirror it
        into the telemetry JSONL so the audit trail survives the process."""
        if jax.process_index() != 0:
            return
        logger.warning("recovery event at step %d: %s %s", step, kind,
                       json.dumps(fields, default=str))
        if self._telemetry is not None:
            self._telemetry.recovery(step, kind, **fields)
        if self._tb is not None:
            self._tb.add_scalar(f"recovery/{kind}", 1.0, step)

    def close(self) -> None:
        if self._tb is not None:
            self._tb.close()
