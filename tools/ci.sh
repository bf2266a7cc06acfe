#!/usr/bin/env bash
# Suite proof-of-run.
#
# The fast tier is what every driver run executes; the slow tier (whole-model
# jits, multi-process gangs, SIGKILL drills) only runs when someone remembers
# — so this script runs BOTH and prints one row per tier (a CPU run: counts
# and verdicts, never a speed; it writes to no tracked file):
#
#   bash tools/ci.sh            # both tiers
#   bash tools/ci.sh fast       # fast tier only
#   bash tools/ci.sh slow       # slow tier only
#   bash tools/ci.sh chaos      # fault-injection recovery drills only
set -u -o pipefail  # pipefail: the tier's rc must be pytest's, not tail's
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
# repo root on PYTHONPATH: the driver-script smokes (`python examples/...`)
# import the package from the source tree, not an installed wheel
export PYTHONPATH="$(pwd):${PYTHONPATH:-}"

log() {  # tier, summary-tail, exit-code, seconds
  printf '| %s | %s | %s | rc=%s | %ss |\n' \
    "$(date -u +%Y-%m-%dT%H:%M:%SZ)" "$1" "$2" "$3" "$4"
}

run_tier() {  # name, marker-expr, [test-path]
  local t0 rc out secs
  t0=$(date +%s)
  out=$(python -m pytest "${3:-tests/}" -q -m "$2" --tb=no 2>&1 | tail -1)
  rc=$?
  secs=$(( $(date +%s) - t0 ))
  log "$1" "${out}" "${rc}" "${secs}"
  echo "[$1] ${out} (rc=${rc}, ${secs}s)"
  return $rc
}

run_script_tier() {  # name, script
  local t0 rc secs
  t0=$(date +%s)
  bash "$2"
  rc=$?
  secs=$(( $(date +%s) - t0 ))
  log "$1" "(rows above)" "${rc}" "${secs}"
  echo "[$1] rc=${rc} (${secs}s)"
  return $rc
}

# dlstatus smoke (ISSUE 2 satellite): a short real driver run must leave a
# telemetry stream from which dlstatus reports a goodput_frac > 0.
run_dlstatus_smoke() {
  local t0 rc wd frac
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_status_smoke.XXXXXX)
  DLS_TELEMETRY_DIR="$wd" \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python examples/train_mnist.py --master local[2] \
      --steps 6 --batch-size 16 > "$wd/driver.log" 2>&1 || rc=$?
  if [ "$rc" -eq 0 ]; then
    # one CLI invocation: --json carries both the exit-code check and the
    # goodput_frac assertion (strict-JSON parse included)
    frac=$(python -m distributeddeeplearningspark_tpu.status "$wd" --json \
           | python -c 'import json,sys; print(json.load(sys.stdin)["goodput"]["goodput_frac"])') \
      || rc=$?
    python -c "import sys; sys.exit(0 if float('${frac:-0}') > 0 else 1)" \
      || rc=$?
  else
    tail -5 "$wd/driver.log"
  fi
  log dlstatus "goodput_frac=${frac:-n/a}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[dlstatus] goodput_frac=${frac:-n/a} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# fleet/hosts smoke (ISSUE 3 satellite): replay the bundled 3-host hang
# fixture through `dlstatus --hosts` — the stalled host must be NAMED (host
# 2, phase restore) with a nonzero heartbeat age, from the files alone.
run_hosts_smoke() {
  local t0 rc out
  t0=$(date +%s)
  rc=0
  out=$(python -m distributeddeeplearningspark_tpu.status \
          tests/fixtures/fleet_3host --hosts --json \
        | python -c '
import json, sys
fl = json.load(sys.stdin)["fleet"]
hang = fl["hang"] or {}
assert hang.get("host") == 2 and hang.get("phase") == "restore", hang
row = next(h for h in fl["hosts"] if h["host"] == 2)
assert row["heartbeat_age_s"] and row["heartbeat_age_s"] > 0, row
print("culprit=host%s phase=%s hb_age=%.1fs"
      % (hang["host"], hang["phase"], row["heartbeat_age_s"]))
') || rc=$?
  log hosts "${out:-fleet assertion failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[hosts] ${out:-FAILED} (rc=${rc})"
  return $rc
}

# input-pipeline smoke (ISSUE 5 satellite): synthetic JPEG corpus through
# the REAL path twice — the serial in-process map vs a 2-process
# data/workers.py pool. The pool must win on throughput (byte-identical
# stream is the tier-1 tests' job), and the run's telemetry must carry the
# new per-worker utilization gauges.
run_input_smoke() {
  local t0 rc out
  t0=$(date +%s)
  rc=0
  out=$(python - <<'PYEOF'
import json, os, sys, tempfile, time
import numpy as np
from PIL import Image

root = tempfile.mkdtemp(prefix="dls_input_smoke_")
rng = np.random.default_rng(0)
for cls in range(2):
    d = os.path.join(root, f"class_{cls}")
    os.makedirs(d)
    for i in range(24):
        arr = rng.integers(0, 255, (500, 500, 3), np.uint8)
        Image.fromarray(arr).save(os.path.join(d, f"i{i}.jpg"), quality=90)

from distributeddeeplearningspark_tpu import status, telemetry
from distributeddeeplearningspark_tpu.data.feed import host_batches
from distributeddeeplearningspark_tpu.data.prefetch import StarvationProbe
from distributeddeeplearningspark_tpu.data.sources import imagenet_folder
from distributeddeeplearningspark_tpu.data.vision import imagenet_train

base = imagenet_folder(root, num_partitions=1, decode=False)
wd = tempfile.mkdtemp(prefix="dls_input_tele_")
writer = telemetry.EventWriter(wd, process=0, host=0)
probe = StarvationProbe()

def rate(nw, num_threads=None):
    ds = imagenet_train(base, seed=0, repeat=True, num_workers=nw,
                        num_threads=num_threads)
    feed = host_batches(ds, 32)
    next(feed)  # pool spin-up + warm caches outside the window
    t0 = time.perf_counter()
    seen = 0
    for _ in range(4):
        seen += len(next(feed)["label"])
    r = seen / (time.perf_counter() - t0)
    if nw:  # snapshot while the pool is live → worker gauges ride along
        writer.step_metrics(1, steps=4, lap_s=seen / r,
                            metrics={"images_per_sec": r},
                            **probe.snapshot())
    feed.close()
    return r

# shared/throttled CI vCPUs swing ±50% between back-to-back runs, so a
# single A-vs-B window can be decided by a neighbor's load spike:
# interleave the arms (A,B,A,B) and compare best-of-each (peak capability)
serial = pooled = 0.0
for _ in range(2):
    serial = max(serial, rate(0, num_threads=0))
    pooled = max(pooled, rate(2))
writer.close()
rep = status.report(wd)
iw = rep["input_workers"]
assert iw and iw["input_workers"] == 2, f"worker gauges missing: {iw}"
assert iw["worker_util_mean"] > 0.0
assert "input workers: 2 process(es)" in status.render(rep)
speedup = pooled / serial
assert speedup > 1.0, (
    f"2-worker pool ({pooled:.1f} img/s) did not beat the serial map "
    f"({serial:.1f} img/s)")
print(f"serial={serial:.1f} pooled2={pooled:.1f} img/s "
      f"speedup={speedup:.2f} util={iw['worker_util_mean']:.2f}")
PYEOF
) || rc=$?
  log input "${out:-input smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[input] ${out:-FAILED} (rc=${rc})"
  return $rc
}

# serve smoke (ISSUE 4 satellite): train a few LeNet steps, serve them with
# the dynamic-batching engine under concurrent clients, hot-reload a newer
# checkpoint mid-traffic — batched throughput must beat the single-request
# engine, with zero shed requests and at least one hot reload.
run_serve_smoke() {
  local t0 rc out
  t0=$(date +%s)
  rc=0
  out=$(JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python examples/serve_mnist.py --steps 6 --clients 16 --requests 4 \
          2>/dev/null \
        | python -c '
import json, sys
r = json.loads(sys.stdin.readlines()[-1])
e = r["extra"]
assert r["value"] > e["sequential_requests_per_sec"], (
    "batched throughput did not beat sequential", r)
assert e["requests_shed"] == 0 and e["hot_reloads"] >= 1, r
print("rps=%s seq=%s speedup=%s reloads=%s p50=%sms"
      % (r["value"], e["sequential_requests_per_sec"],
         e["batching_speedup"], e["hot_reloads"], e["latency_p50_ms"]))
') || rc=$?
  log serve "${out:-serve smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[serve] ${out:-FAILED} (rc=${rc})"
  return $rc
}

# fleet-serve smoke (ISSUE 6 satellite): 2 tinyllama replica PROCESSES
# (paged KV arena + prefix cache) behind the router under concurrent
# synthetic load sharing a system prompt, one rolling hot-reload
# mid-traffic. Asserts zero dropped in-flight requests, >=1 prefix-cache
# hit, both replicas reloaded, and the `dlstatus --fleet-serve` JSON schema.
run_fleet_serve_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_fleet_smoke.XXXXXX)
  out=$( (python -m distributeddeeplearningspark_tpu.serve.cli \
          --model tinyllama --replicas 2 --rolling-reload \
          --clients 4 --requests-per-client 4 --tenants 2 \
          --prefix-tokens 32 --suffix-tokens 8 --max-new-tokens 8 \
          --workdir "$wd" 2>"$wd/dlserve.log" \
        && python -m distributeddeeplearningspark_tpu.status "$wd" \
             --fleet-serve --json) \
        | python -c '
import json, sys
lines = sys.stdin.read().strip().splitlines()
serve, stat = json.loads(lines[0]), json.loads(lines[-1])
e = serve["extra"]
assert e["requests_dropped"] == 0 and e["requests_failed"] == 0, e
assert e["rolling_reload"]["performed"], e["rolling_reload"]
assert e["rolling_reload"]["replicas_reloaded"] == 2, e["rolling_reload"]
assert e["prefix"]["hits"] >= 1, e["prefix"]
fs = stat["fleet_serve"]
assert fs is not None, "dlstatus --fleet-serve found no serving events"
procs = {r["process"] for r in fs["replicas"]}
assert {"p0", "p1"} <= procs, procs
for r in fs["replicas"]:
    for k in ("requests", "ok", "shed", "shed_rate", "latency_p50_s",
              "latency_p99_s"):
        assert k in r, (k, r)
t = fs["totals"]
for k in ("requests", "ok", "shed", "prefix_hits", "prefix_hit_rate",
          "prefix_tokens_saved", "kv_page_occupancy_max"):
    assert k in t, (k, t)
assert t["ok"] >= 16, t
print("rps=%s ok=%s dropped=0 reloads=%s prefix_hits=%s hit_rate=%s"
      % (serve["value"], t["ok"],
         e["rolling_reload"]["replicas_reloaded"],
         t["prefix_hits"], t["prefix_hit_rate"]))
') || { rc=$?; tail -5 "$wd/dlserve.log" 2>/dev/null; }
  log fleet-serve "${out:-fleet-serve smoke failed}" "${rc}" \
    $(( $(date +%s) - t0 ))
  echo "[fleet-serve] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# trace smoke (ISSUE 7): the same 2-replica tinyllama fleet under load,
# twice — once healthy, once with a sleep fault injected into replica 0's
# decode loop. Every completed request must yield a COMPLETE causal span
# tree whose stage sum covers >=95% of its end-to-end latency;
# `dlstatus --export-trace` must emit loadable Chrome trace_event JSON;
# and `dlstatus --slo` must flip its verdict from GOOD on the healthy run
# to BURNING/EXHAUSTED on the faulted one at the SAME target.
run_trace_smoke() {
  local t0 rc wd wdf out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_trace_smoke.XXXXXX)
  wdf=$(mktemp -d /tmp/dls_trace_fault.XXXXXX)
  python -m distributeddeeplearningspark_tpu.serve.cli \
      --model tinyllama --replicas 2 --clients 4 --requests-per-client 3 \
      --tenants 2 --prefix-tokens 32 --suffix-tokens 8 --max-new-tokens 8 \
      --workdir "$wd" >"$wd/serve.json" 2>"$wd/dlserve.log" || rc=$?
  if [ "$rc" -eq 0 ]; then
    python -m distributeddeeplearningspark_tpu.serve.cli \
        --model tinyllama --replicas 2 --clients 4 --requests-per-client 3 \
        --tenants 2 --prefix-tokens 32 --suffix-tokens 8 --max-new-tokens 8 \
        --fault-sleep-ms 1000 --fault-replica 0 \
        --workdir "$wdf" >"$wdf/serve.json" 2>"$wdf/dlserve.log" || rc=$?
  fi
  if [ "$rc" -eq 0 ]; then
    out=$(WD="$wd" WDF="$wdf" python - <<'PYEOF'
import json, os, subprocess, sys

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.telemetry import trace as trace_lib

wd, wdf = os.environ["WD"], os.environ["WDF"]

def dlstatus(*argv):
    p = subprocess.run(
        [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
         *argv], capture_output=True, text=True)
    assert p.returncode == 0, (argv, p.stderr[-500:])
    return p

# 1) every request the healthy fleet completed left a complete causal
#    tree, and its stage sum explains >=95% of the e2e latency
anat = trace_lib.request_anatomy(telemetry.read_events(wd))
done = [r for r in anat if r["outcome"] == "ok"]
assert len(done) >= 12, f"expected 12 completed traced requests: {len(done)}"
for r in done:
    assert not r["incomplete"], r
    assert r["coverage"] is not None and r["coverage"] >= 0.95, (
        r["trace_id"], r["coverage"], r["stages"])

# 2) --export-trace emits loadable Chrome trace_event JSON
export = os.path.join(wd, "trace.json")
dlstatus(wd, "--export-trace", export, "--json")
data = json.load(open(export))
spans = [e for e in data["traceEvents"] if e.get("ph") in ("X", "B")]
assert spans, "export produced no span events"

# 3) the SLO sentinel flips on the injected sleep fault: one target,
#    derived from the healthy run's own p99, judges both runs
rep = json.loads(dlstatus(wd, "--json", "--traces").stdout)
target = max(1.0, 1.5 * rep["traces"]["e2e_p99_s"])
healthy = json.loads(
    dlstatus(wd, "--json", "--slo", str(target)).stdout)["slo"]["totals"]
faulted = json.loads(
    dlstatus(wdf, "--json", "--slo", str(target)).stdout)["slo"]["totals"]
assert healthy["verdict"] == "GOOD", healthy
assert faulted["verdict"] in ("BURNING", "EXHAUSTED"), faulted
assert faulted["slow"] >= 1, faulted

# 4) the anatomy names the culprit: the faulted replica's decode p99
#    carries the injected 1s-per-step sleep; the healthy replica's doesn't
anat_f = json.loads(dlstatus(wdf, "--json", "--traces").stdout)["traces"]
slow_decode = anat_f["per_process"]["p0"].get("decode", {})
assert (slow_decode.get("p99_s") or 0) >= 0.5, anat_f["per_process"]

cov = min(r["coverage"] for r in done)
print(f"requests={len(done)} min_coverage={cov:.3f} "
      f"export_spans={len(spans)} target_p99={target:.2f}s "
      f"healthy={healthy['verdict']} faulted={faulted['verdict']} "
      f"burn={faulted['burn_rate']}x")
PYEOF
) || { rc=$?; tail -5 "$wd/dlserve.log" "$wdf/dlserve.log" 2>/dev/null; }
  else
    tail -5 "$wd/dlserve.log" "$wdf/dlserve.log" 2>/dev/null
  fi
  log trace "${out:-trace smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[trace] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd" "$wdf"
  return $rc
}

# shuffle smoke (ISSUE 8 + 12): a 10M-key groupBy().agg — the workload
# the serial max_groups ceiling REFUSES (asserted first) — completes
# through the 2-worker exchange under a DLS_SHUFFLE_MEM_MB budget, TWICE:
# once forced onto the tuple transport (content-verified, blake2b
# checksum + keys/s logged) and once through the columnar transport at
# the SAME budget, asserting the checksum matches the tuple path's, the
# >=5x keys/s gate, >=1 reducer spill, and the dlstatus shuffle block's
# per-format rows. Then a 1M-key device-transport stage: bit-equal
# checksum, compiles in the PR 9 ledger, and a warm repeat that compiles
# NOTHING (no recompile flag).
run_shuffle_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_shuffle_smoke.XXXXXX)
  out=$( (WD="$wd" DLS_SHUFFLE_MEM_MB=64 JAX_PLATFORMS=cpu python - <<'PYEOF'
import hashlib, os, sys, time
import numpy as np

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.data import exchange
from distributeddeeplearningspark_tpu.data.dataframe import DataFrame
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

N, NCHUNK, DUP = 10_000_000, 20, 100_000
rows = N // NCHUNK

def chunk(i, n):
    if i == NCHUNK:  # duplicate chunk: keys 0..DUP reappear, so the
        k = np.arange(min(DUP, n), dtype=np.int64)  # reducers really
    else:           # combine across partitions, not just concatenate
        r = n // NCHUNK
        k = np.arange(i * r, (i + 1) * r, dtype=np.int64)
    return {"k": k, "v": (k % 97).astype(np.float64)}

def df(n=N):
    ds = PartitionedDataset.from_generators(
        [(lambda i=i: iter([chunk(i, n)])) for i in range(NCHUNK + 1)])
    return DataFrame(ds, ["k", "v"])

def run_and_verify(transport, n=N, workers=2, order_checks=True):
    """One full agg pass: vectorized content check + canonical-order
    spot checks + blake2b over the concatenated column stream (chunk
    boundaries are layout, not content — they differ by transport)."""
    g = df(n).groupBy("k").agg({"v": "sum", "k": "count"},
                               num_workers=workers, transport=transport)
    t0 = time.perf_counter()
    parts = [[ch for ch in g._chunks.iter_partition(p)]
             for p in range(g._chunks.num_partitions)]
    dt = time.perf_counter() - t0
    nrows, keys = 0, []
    for chunks_p in parts:
        prev_kb = None
        for ch in chunks_p:
            k, s, c = ch["k"], ch["sum(v)"], ch["count(k)"]
            expect_c = 1 + (k < DUP)
            assert np.array_equal(c, expect_c), "bad counts"
            assert np.array_equal(
                s, expect_c * (k % 97).astype(np.float64)), "bad sums"
            if order_checks:
                for i in range(0, len(k), 4096):  # canonical-order spots
                    kb = exchange.key_bytes((int(k[i]),))
                    assert prev_kb is None or kb > prev_kb, \
                        "not in key_bytes order"
                    prev_kb = kb
            keys.append(k)
            nrows += len(k)
    assert nrows == n, (nrows, n)
    allk = np.concatenate(keys)
    assert np.array_equal(np.sort(allk), np.arange(n, dtype=np.int64)), \
        "key set wrong"
    flat = [ch for chunks_p in parts for ch in chunks_p]
    h = hashlib.blake2b(digest_size=16)
    for c in sorted(flat[0]):
        h.update(np.ascontiguousarray(
            np.concatenate([ch[c] for ch in flat])).tobytes())
    return n / dt, h.hexdigest()

# 1) the old ceiling refuses this workload on the serial path
try:
    g = df().groupBy("k").agg({"v": "sum", "k": "count"}, num_workers=0)
    next(iter(g._chunks.iter_partition(0)))
    sys.exit("serial path did not refuse a 10M-key agg")
except ValueError as e:
    assert "max_groups" in str(e) and "DLS_DATA_WORKERS" in str(e), str(e)

telemetry.configure(os.environ["WD"])

# 2) tuple transport: the pre-columnar baseline, content-verified
tuple_rate, tuple_sum = run_and_verify("tuple", order_checks=False)

# 3) columnar transport, same workload, same 64MB budget: checksum must
#    match the tuple path's, and the keys/s gate is >=5x
ev_mark = len(telemetry.read_events(os.environ["WD"]))
cols_rate, cols_sum = run_and_verify("columnar")
assert cols_sum == tuple_sum, f"checksum diverged: {cols_sum} vs {tuple_sum}"
speedup = cols_rate / tuple_rate
assert speedup >= 5.0, \
    f"columnar {cols_rate:.0f} keys/s is only {speedup:.1f}x tuple " \
    f"{tuple_rate:.0f} keys/s (gate: >=5x)"
cols_events = telemetry.read_events(os.environ["WD"])[ev_mark:]
cols_spills = [e for e in cols_events
               if e.get("kind") == "shuffle" and e.get("edge") == "spill"]
assert cols_spills, "no columnar spill events under a 64MB budget at 10M keys"
cols_done = [e for e in cols_events
             if e.get("kind") == "shuffle" and e.get("edge") == "done"][-1]
assert cols_done["transport"] == "columnar", cols_done["transport"]
assert cols_done["columnar_pairs"] == N + DUP and cols_done["tuple_pairs"] == 0

# 4) device transport at 1M keys: bit-equal, ledgered compiles, and a
#    warm repeat that compiles nothing
ND = 1_000_000
_, cols_sum_1m = run_and_verify("columnar", n=ND, order_checks=False)
_, dev_sum = run_and_verify("device", n=ND, workers=0, order_checks=False)
assert dev_sum == cols_sum_1m, "device output diverged from the exchange"
events = telemetry.read_events(os.environ["WD"])
compiles = [e for e in events if e.get("kind") == "compile"
            and str(e.get("fn", "")).startswith("device_agg.")]
assert compiles, "device-agg compiles missing from the ledger"
n_compiles = len(compiles)
_, dev_sum2 = run_and_verify("device", n=ND, workers=0, order_checks=False)
assert dev_sum2 == dev_sum
events = telemetry.read_events(os.environ["WD"])
compiles2 = [e for e in events if e.get("kind") == "compile"
             and str(e.get("fn", "")).startswith("device_agg.")]
assert len(compiles2) == n_compiles, \
    f"warm device repeat recompiled ({len(compiles2)} vs {n_compiles})"
assert not any(e.get("recompile") for e in compiles2), \
    "device-agg compile flagged recompile"
telemetry.reset()

# 5) the dlstatus shuffle block schema, incl. the per-format rows
from distributeddeeplearningspark_tpu import status

rep = status.report(os.environ["WD"], anatomy=True)
sh = rep["shuffle"]
assert sh is not None, "dlstatus found no shuffle block"
for key in ("ops", "pairs_in", "rows_out", "bytes_moved", "spills",
            "spill_events", "overflow", "formats", "last"):
    assert key in sh, key
for key in ("op", "workers", "buckets", "map_s", "merge_s", "spills",
            "mem_budget_mb", "transport", "bucket_rows_max",
            "bucket_rows_mean", "skew", "verdict"):
    assert key in sh["last"], key
for fmt in ("columnar", "tuple"):
    for key in ("pairs", "bytes", "buckets"):
        assert key in sh["formats"][fmt], (fmt, key)
assert sh["formats"]["columnar"]["pairs"] > 0
assert sh["formats"]["tuple"]["pairs"] > 0  # the forced-tuple baseline run
assert sh["last"]["op"] == "groupBy.agg"
# the device compiles surface through `dlstatus --anatomy` itself
anat = rep.get("anatomy")
assert anat is not None, "no anatomy block despite device compiles"
by_fn = anat["compile_ledger"]["by_fn"]
dev_rows = {fn: r for fn, r in by_fn.items()
            if fn.startswith("device_agg.")}
assert dev_rows, f"device_agg missing from the anatomy ledger: {list(by_fn)}"
assert all(r["flagged_recompiles"] == 0 for r in dev_rows.values()), dev_rows
print(f"keys=10M budget=64MB tuple={tuple_rate / 1e3:.0f}k/s "
      f"columnar={cols_rate / 1e3:.0f}k/s speedup={speedup:.1f}x "
      f"spills={len(cols_spills)} checksum={cols_sum} "
      f"device_compiles={n_compiles}")
PYEOF
) ) || rc=$?
  log shuffle "${out:-shuffle smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[shuffle] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# shuffle-chaos drill (ISSUE 14): the 10M-key groupBy.agg again, but a
# mapper AND a reducer are SIGKILLed mid-exchange
# (DLS_FAULT=die_shuffle_worker, role=both) — the exchange must
# self-heal: >=1 recorded retry per role, blake2b output checksum
# IDENTICAL to the clean run, zero orphaned processes/shm/spill files.
# Then the same drill under DLS_SHUFFLE_MAX_RETRIES=0 must raise the
# typed WorkerCrashed with full teardown (the fail-fast contract).
run_shuffle_chaos() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_shuffle_chaos.XXXXXX)
  out=$( (WD="$wd" DLS_SHUFFLE_MEM_MB=64 DLS_SHUFFLE_SPILL_DIR="$wd/spill" \
          JAX_PLATFORMS=cpu python - <<'PYEOF'
import gc, hashlib, os, sys, time
import multiprocessing as mp
import numpy as np

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.data.dataframe import DataFrame
from distributeddeeplearningspark_tpu.data.workers import WorkerCrashed
from distributeddeeplearningspark_tpu.rdd import PartitionedDataset

N, NCHUNK, DUP = 10_000_000, 20, 100_000

def chunk(i):
    if i == NCHUNK:
        k = np.arange(DUP, dtype=np.int64)
    else:
        r = N // NCHUNK
        k = np.arange(i * r, (i + 1) * r, dtype=np.int64)
    return {"k": k, "v": (k % 97).astype(np.float64)}

def run():
    ds = PartitionedDataset.from_generators(
        [(lambda i=i: iter([chunk(i)])) for i in range(NCHUNK + 1)])
    g = DataFrame(ds, ["k", "v"]).groupBy("k").agg(
        {"v": "sum", "k": "count"}, num_workers=2, transport="columnar")
    chunks = [ch for p in range(g._chunks.num_partitions)
              for ch in g._chunks.iter_partition(p)]
    h = hashlib.blake2b(digest_size=16)
    for c in sorted(chunks[0]):
        h.update(np.ascontiguousarray(
            np.concatenate([ch[c] for ch in chunks])).tobytes())
    return h.hexdigest()

def assert_no_orphans(tag):
    deadline = time.time() + 5.0
    while time.time() < deadline and [p for p in mp.active_children()
                                      if p.name.startswith("dlsx-")]:
        time.sleep(0.05)
    left = [p.name for p in mp.active_children()
            if p.name.startswith("dlsx-")]
    assert not left, f"{tag}: orphan children {left}"
    if os.path.isdir("/dev/shm"):
        shm = [f for f in os.listdir("/dev/shm")
               if f.startswith(f"dlsx-{os.getpid()}-")]
        assert not shm, f"{tag}: orphan shm {shm}"
    gc.collect()
    spill = [f for d in os.listdir(os.environ["DLS_SHUFFLE_SPILL_DIR"])
             for f in os.listdir(
                 os.path.join(os.environ["DLS_SHUFFLE_SPILL_DIR"], d))]
    assert not spill, f"{tag}: orphan spill files {spill[:5]}"

telemetry.configure(os.environ["WD"])

# 1) clean run: the checksum oracle
clean_sum = run()
gc.collect()

# 2) kill one mapper (at its 5th element — elements here are whole
#    500k-row chunks) AND one reducer (at its 5th merged frame)
#    mid-exchange; the run must complete bit-equal
os.environ["DLS_FAULT"] = "die_shuffle_worker@5"
os.environ["DLS_FAULT_SHUFFLE_ROLE"] = "both"
os.environ["DLS_FAULT_SHUFFLE_ID"] = "0"
t_f = time.time()
fault_sum = run()
fault_s = time.time() - t_f
assert fault_sum == clean_sum, \
    f"faulted checksum diverged: {fault_sum} vs {clean_sum}"
events = telemetry.read_events(os.environ["WD"])
retries = [e for e in events
           if e.get("kind") == "shuffle" and e.get("edge") == "retry"]
m_retries = [e for e in retries if e.get("role") == "mapper"]
r_retries = [e for e in retries if e.get("role") == "reducer"]
assert m_retries, "no mapper retry recorded"
assert r_retries, "no reducer retry recorded"
assert_no_orphans("faulted run")

# 3) the dlstatus recovery line renders from those events
from distributeddeeplearningspark_tpu import status
rep = status.report(os.environ["WD"])
rec = rep["shuffle"]["recovery"]
assert rec["mapper_retries"] >= 1 and rec["reducer_retries"] >= 1, rec
assert "recovery:" in status.render(rep)

# 4) DLS_SHUFFLE_MAX_RETRIES=0: today's fail-fast — typed WorkerCrashed,
#    full teardown
os.environ["DLS_SHUFFLE_MAX_RETRIES"] = "0"
try:
    run()
    sys.exit("retries=0 did not escalate")
except WorkerCrashed as e:
    assert "died" in str(e), str(e)
assert_no_orphans("fail-fast run")
telemetry.reset()
print(f"chaos: mapper+reducer killed mid-10M-key agg; "
      f"retries m={len(m_retries)} r={len(r_retries)}; "
      f"checksum={fault_sum} == clean; faulted wall {fault_s:.0f}s; "
      f"retries=0 escalated typed; zero orphans")
PYEOF
) ) || rc=$?
  log shuffle-chaos "${out:-shuffle chaos drill failed}" "${rc}" \
    $(( $(date +%s) - t0 ))
  echo "[shuffle-chaos] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# anatomy smoke (ISSUE 10): a short real train run must leave a compile
# ledger with exactly one compile per signature (zero flagged recompiles),
# a device/host/input/compile lap split that explains the independently
# measured Meter lap wall within 5%, and a finite MFU > 0 (nominal CPU
# peak; DLS_PEAK_FLOPS overrides) — all from `dlstatus --anatomy` alone.
run_anatomy_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_anatomy_smoke.XXXXXX)
  DLS_TELEMETRY_DIR="$wd" \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python examples/train_mnist.py --master local[2] \
      --steps 6 --batch-size 16 > "$wd/driver.log" 2>&1 || rc=$?
  if [ "$rc" -eq 0 ]; then
    out=$(WD="$wd" python - <<'PYEOF'
import json, math, os, subprocess, sys

from distributeddeeplearningspark_tpu import telemetry

wd = os.environ["WD"]
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     wd, "--anatomy", "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
an = json.loads(p.stdout)["anatomy"]

# 1) exactly-once compile per signature: nothing flagged, no duplicates
cl = an["compile_ledger"]
assert cl["compiles"] >= 1, cl
assert cl["compiles"] == cl["distinct_signatures"], cl
assert cl["flagged_recompiles"] == 0 and cl["duplicate_signatures"] == 0, cl

# 2) the anatomy split explains the independently measured lap wall:
#    device+host+input+compile tiles the anatomy clock (coverage == 1),
#    and the anatomy clock agrees with the Meter's lap_s within 5%
st = an["steps"]
covered = (st["device_s"] + st["host_s"] + st["input_wait_s"]
           + st["compile_s"])
assert st["wall_s"] > 0 and abs(covered / st["wall_s"] - 1.0) <= 0.05, st
meter_wall = sum(
    float(e.get("lap_s", 0.0) or 0.0)
    for e in telemetry.read_events(wd) if e.get("kind") == "step_metrics")
assert meter_wall > 0 and abs(st["wall_s"] / meter_wall - 1.0) <= 0.05, (
    st["wall_s"], meter_wall)

# 3) finite MFU > 0 from the ledger's analytic FLOPs over the peak table
mfu = an["mfu"]["mfu"]
assert mfu is not None and math.isfinite(mfu) and mfu > 0, an["mfu"]
assert an["mfu"]["flops_per_step"] and an["mfu"]["peak_flops_per_chip"]

# 4) memory watermarks present (live-buffer fallback on CPU)
assert an["memory"] is not None and an["memory"]["source"] in (
    "memory_stats", "live-buffers"), an["memory"]

print(f"compiles={cl['compiles']} recompiles=0 "
      f"split={covered / st['wall_s']:.3f}x_anatomy "
      f"{st['wall_s'] / meter_wall:.3f}x_meter mfu={mfu:.6f} "
      f"mem={an['memory']['source']}")
PYEOF
) || rc=$?
  else
    tail -5 "$wd/driver.log"
  fi
  log anatomy "${out:-anatomy smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[anatomy] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# plan smoke (ISSUE 15): the measured layout search end-to-end — sweep >=3
# candidate Plans on a tiny llama mesh through the unified compile layer,
# assert the ranked table is ordered by MEASURED step time, the winner
# re-runs on its kept executable with ZERO new compiles, and `dlstatus
# --anatomy` shows exactly one ledgered, plan-tagged compile per plan.
run_plan_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_plan_smoke.XXXXXX)
  DLS_TELEMETRY_DIR="$wd" \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python tools/plan_sweep.py --steps 4 --warmup 1 --rerun-steps 2 \
      --json --pin "$wd/winner.plan.json" > "$wd/sweep.json" \
      2> "$wd/sweep.log" || rc=$?
  if [ "$rc" -eq 0 ]; then
    out=$(WD="$wd" python - <<'PYEOF'
import json, os, subprocess, sys

wd = os.environ["WD"]
rep = json.load(open(os.path.join(wd, "sweep.json")))
ranked = rep["ranked"]
assert len(ranked) >= 3, f"want >=3 ranked plans, got {len(ranked)}"
times = [r["step_time_s"] for r in ranked]
assert times == sorted(times), f"table not ordered by step time: {times}"
assert rep["winner"] == ranked[0]["plan"], rep["winner"]
assert rep["winner_rerun_new_compiles"] == 0, rep
assert all(r["compiles"] == 1 and r["recompiles"] == 0 for r in ranked), \
    [(r["plan"], r["compiles"]) for r in ranked]

# the pinned winner round-trips
from distributeddeeplearningspark_tpu.parallel.plan import Plan
pinned = Plan.load(os.path.join(wd, "winner.plan.json"))
assert pinned.name == rep["winner"], pinned.name
assert pinned.signature() == rep["winner_sig"]

# --anatomy: one ledgered, plan-tagged compile per plan
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     wd, "--anatomy", "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
an = json.loads(p.stdout)["anatomy"]
by_fn = an["compile_ledger"]["by_fn"]
for r in ranked:
    row = by_fn[f"plan:{r['plan']}"]
    assert row["compiles"] == 1 and row["plan"] == r["plan"], (r["plan"], row)
    assert row["plan_sig"] == r["plan_sig"], row
assert an["compile_ledger"]["flagged_recompiles"] == 0

print(f"plans={len(ranked)} winner={rep['winner']} "
      f"{rep['best_steps_per_sec']}steps/s rerun_compiles=0 "
      f"ledgered={an['compile_ledger']['compiles']}")
PYEOF
) || rc=$?
  else
    tail -5 "$wd/sweep.log"
  fi
  log plan "${out:-plan smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[plan] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# elastic smoke (ISSUE 11): the kill-a-host drill end-to-end — a 2-host
# supervised run loses host 1 mid-run (DLS_FAULT=die_host@N, the host stays
# dead across attempts), the supervisor shrinks the gang to the survivor
# after 2 same-host verdicts, and training CONTINUES TO COMPLETION on 1
# host from the last verified checkpoint; `dlstatus` must show the
# geometry change, and an fsdp-saved → tensor-restored params round-trip
# must be bitwise.
run_elastic_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_elastic_smoke.XXXXXX)
  out=$(WD="$wd" python - <<'PYEOF'
import json, os, subprocess, sys

import numpy as np

wd = os.environ["WD"]
run_dir = os.path.join(wd, "run")
os.makedirs(run_dir)
worker = os.path.join("tests", "workers", "worker.py")

from distributeddeeplearningspark_tpu.supervisor import Supervisor

sup = Supervisor(
    [sys.executable, worker, "elastic", "--ckpt-dir", run_dir,
     "--steps", "18", "--checkpoint-every", "6"],
    num_processes=2, max_restarts=4, restart_backoff_s=0.05,
    backoff_jitter=0.0, shrink_after=2,
    env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu",
         "DLS_FAULT": "die_host@9"},
    progress_path=run_dir,
)
result = sup.run()
assert result.ok, [(a.ordinal, a.returncodes, a.classification)
                   for a in result.attempts]
step, attempt, nprocs = open(os.path.join(run_dir, "DONE")).read().split()
assert (int(step), int(nprocs)) == (18, 1), (step, attempt, nprocs)

# dlstatus shows the shrink as a first-class event, attempts carry np=
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     run_dir, "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
rep = json.loads(p.stdout)
geo = [e for e in rep["recovery_events"]
       if e.get("event") == "geometry_change"]
assert geo and geo[0]["from_processes"] == 2 \
    and geo[0]["to_processes"] == 1 and geo[0]["dead_host"] == 1, geo
assert [a.get("num_processes") for a in rep["attempts"]][-1] == 1
human = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     run_dir], capture_output=True, text=True)
assert "geometry change: 2 -> 1" in human.stdout, human.stdout[-800:]

# bitwise fsdp-saved → tensor-restored params round-trip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax

jax.config.update("jax_platforms", "cpu")
import optax
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.checkpoint import Checkpointer
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.parallel.sharding import FSDP, ShardingRules
from distributeddeeplearningspark_tpu.train import step as step_lib

rng = np.random.default_rng(0)
batch = {"image": rng.normal(0, 1, (8, 28, 28, 1)).astype(np.float32),
         "label": rng.integers(0, 10, (8,)).astype(np.int32)}
state, _ = step_lib.init_state(
    LeNet5(), optax.sgd(0.1, momentum=0.9), batch,
    MeshSpec(data=2, fsdp=4).build(), FSDP, seed=3)
ck_dir = os.path.join(wd, "ck")
with Checkpointer(ck_dir, async_save=False) as ck:
    ck.save(1, state)
    ck.wait()
    params, _ = ck.restore_params(
        mesh=MeshSpec(data=1, tensor=8).build(),
        rules=ShardingRules(rules=((r"Dense_0/kernel", P(None, "tensor")),
                                   (r"Dense_1/kernel", P("tensor", None)))))
src = {tuple(map(str, p)): v for p, v in
       jax.tree_util.tree_flatten_with_path(state.params)[0]}
dst = {tuple(map(str, p)): v for p, v in
       jax.tree_util.tree_flatten_with_path(params)[0]}
bitwise = all(
    np.asarray(jax.device_get(v)).tobytes()
    == np.asarray(jax.device_get(dst[k])).tobytes()
    for k, v in src.items())
assert bitwise, "fsdp->tensor restore was not bitwise"
specs = {str(l.sharding.spec) for l in jax.tree.leaves(params)}
assert any("tensor" in s for s in specs), specs

print(f"survived=1host step={step} attempts={len(result.attempts)} "
      f"shrink=2->1 dead_host={geo[0]['dead_host']} "
      f"resume_step={geo[0].get('step')} bitwise_fsdp->tensor=ok")
PYEOF
) || rc=$?
  log elastic "${out:-elastic smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[elastic] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# live-reshard smoke (ISSUE 16): checkpoint-free resharding end to end —
# (1) graceful preemption: DLS_FAULT=sigterm@9 drains host 1 at step 9,
# the supervisor classifies graceful-shutdown (no backoff slot burned),
# shrinks 2->1 and the survivor resumes from the CURRENT step via the
# live handoff (no walk_back anywhere in the event stream, dlstatus
# renders the move as checkpoint-free); (2) a hard die_host@9 kill still
# walks back through the checkpoint (resume="checkpoint"); (3) a live
# fsdp->tensor redistribute of a full TrainState is BITWISE equal to the
# checkpoint save+restore round trip at <=50% of its wall, peak in-flight
# bytes within DLS_RESHARD_MEM_MB (docs/POD_PLAYBOOK.md "We got a
# preemption notice").
run_live_reshard_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_live_reshard.XXXXXX)
  out=$(WD="$wd" python - <<'PYEOF'
import json, os, subprocess, sys, time

import numpy as np

wd = os.environ["WD"]
worker = os.path.join("tests", "workers", "worker.py")

from distributeddeeplearningspark_tpu.supervisor import Supervisor

# -- graceful preemption: SIGTERM@9 -> drain -> shrink -> resume at 9 ---------
sig_dir = os.path.join(wd, "sig")
os.makedirs(sig_dir)
sup = Supervisor(
    [sys.executable, worker, "elastic", "--ckpt-dir", sig_dir,
     "--steps", "18", "--checkpoint-every", "6"],
    num_processes=2, max_restarts=4, restart_backoff_s=0.05,
    backoff_jitter=0.0, shrink_after=2,
    env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu",
         "DLS_FAULT": "sigterm@9"},
    progress_path=sig_dir,
)
result = sup.run()
assert result.ok, [(a.ordinal, a.returncodes, a.classification)
                   for a in result.attempts]
assert result.attempts[0].classification == "graceful-shutdown", \
    result.attempts[0].classification
step, attempt, nprocs = open(os.path.join(sig_dir, "DONE")).read().split()
assert (int(step), int(nprocs)) == (18, 1), (step, attempt, nprocs)

p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     sig_dir, "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
rep = json.loads(p.stdout)
ev = rep["recovery_events"]
geo = [e for e in ev if e.get("event") == "geometry_change"]
assert geo and geo[0].get("resume") == "live-handoff" \
    and geo[0].get("step") == 9, geo
gs = [e for e in ev if e.get("event") == "graceful_shutdown"]
assert gs and gs[0].get("dead_host") == 1 and gs[0].get("step") == 9, gs
moves = [e for e in ev if e.get("event") == "reshard"]
assert any(e.get("transport") == "handoff" for e in moves), moves
assert not any(e.get("walk_back") for e in moves), moves
rs = rep.get("reshard") or {}
assert rs.get("walk_back_moves") == 0 and rs.get("live_moves", 0) >= 2, rs
human = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     sig_dir], capture_output=True, text=True)
assert "graceful shutdown: host 1" in human.stdout, human.stdout[-800:]
assert "checkpoint-free (live)" in human.stdout, human.stdout[-800:]

# -- a hard kill still walks back through the checkpoint ----------------------
die_dir = os.path.join(wd, "die")
os.makedirs(die_dir)
sup = Supervisor(
    [sys.executable, worker, "elastic", "--ckpt-dir", die_dir,
     "--steps", "12", "--checkpoint-every", "6"],
    num_processes=2, max_restarts=4, restart_backoff_s=0.05,
    backoff_jitter=0.0, shrink_after=2,
    env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu",
         "DLS_FAULT": "die_host@9"},
    progress_path=die_dir,
)
result = sup.run()
assert result.ok, [(a.ordinal, a.returncodes, a.classification)
                   for a in result.attempts]
step, _, nprocs = open(os.path.join(die_dir, "DONE")).read().split()
assert (int(step), int(nprocs)) == (12, 1), (step, nprocs)
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     die_dir, "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
geo2 = [e for e in json.loads(p.stdout)["recovery_events"]
        if e.get("event") == "geometry_change"]
assert geo2 and geo2[0].get("resume") == "checkpoint", geo2

# -- live redistribute vs the checkpoint round trip it replaces ---------------
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax

jax.config.update("jax_platforms", "cpu")
import optax
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.checkpoint import (
    Checkpointer, abstract_like)
from distributeddeeplearningspark_tpu.models import LeNet5
from distributeddeeplearningspark_tpu.parallel import live_reshard
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.parallel.sharding import (
    FSDP, ShardingRules, state_shardings)
from distributeddeeplearningspark_tpu.train import step as step_lib

rng = np.random.default_rng(0)
batch = {"image": rng.normal(0, 1, (8, 28, 28, 1)).astype(np.float32),
         "label": rng.integers(0, 10, (8,)).astype(np.int32)}
state, _ = step_lib.init_state(
    LeNet5(), optax.adamw(1e-3), batch,
    MeshSpec(data=2, fsdp=4).build(), FSDP, seed=3)
targets = state_shardings(
    abstract_like(state), MeshSpec(data=1, tensor=8).build(),
    ShardingRules(rules=((r"Dense_0/kernel", P(None, "tensor")),)))

t0 = time.perf_counter()
ck_dir = os.path.join(wd, "ck")
with Checkpointer(ck_dir, async_save=False) as ck:
    ck.save(0, state)
    ck.wait()
    via_disk, _ = ck.restore(abstract_like(state), shardings=targets)
ckpt_wall = time.perf_counter() - t0

live, stats = live_reshard.redistribute(state, targets)
host = lambda t: jax.tree.map(  # noqa: E731
    lambda x: np.asarray(jax.device_get(x)).tobytes(), t)
assert host(live) == host(via_disk), "live != checkpoint round trip"
assert host(live) == host(state), "live reshard changed bytes"
assert stats.verified and stats.leaves_moved >= 2, stats.to_record()
assert stats.peak_inflight_bytes <= stats.mem_budget_bytes, stats.to_record()
ratio = stats.wall_s / max(ckpt_wall, 1e-9)
assert ratio <= 0.5, (
    f"live reshard took {stats.wall_s:.3f}s vs checkpoint round trip "
    f"{ckpt_wall:.3f}s (ratio {ratio:.2f} > 0.50)")

print(f"sigterm: drained@9 shrink=2->1 resume=live-handoff done=18 "
      f"walk_back_moves=0 | die_host: resume=checkpoint done=12 | "
      f"live-vs-ckpt: bitwise=ok leaves_moved={stats.leaves_moved} "
      f"peak={stats.peak_inflight_bytes}B<=budget ratio={ratio:.2f}<=0.50")
PYEOF
) || rc=$?
  log live-reshard "${out:-live-reshard smoke failed}" "${rc}" \
    $(( $(date +%s) - t0 ))
  echo "[live-reshard] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# mpmd smoke (ISSUE 13): the MPMD stage-pipeline end to end — (1) a
# 2-stage x 2-fake-device pipeline over the socket transport matches the
# single-program llama_pp baseline BITWISE (per-step losses), (2) a
# supervised process-level run reports its bubble fraction via the trace
# spans and lands under the (P-1)/(M+P-1) bound + 10%, and (3) the
# stage-kill chaos drill (DLS_FAULT=die_host targeted at stage 1's gang)
# recovers with ONLY that stage restarting and a loss trajectory that
# matches the clean run bitwise.
run_mpmd_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_mpmd_smoke.XXXXXX)
  out=$(WD="$wd" python - <<'PYEOF'
import json, os, secrets, subprocess, sys, threading
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
    + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except AttributeError:
    pass
import numpy as np, optax

from distributeddeeplearningspark_tpu.data.feed import put_global
from distributeddeeplearningspark_tpu.models import (
    LlamaConfig, LlamaForCausalLM, llama_rules)
from distributeddeeplearningspark_tpu.models.llama_pp import make_pp_apply
from distributeddeeplearningspark_tpu.parallel import mpmd
from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
from distributeddeeplearningspark_tpu.supervisor import free_port
from distributeddeeplearningspark_tpu.train import losses, step as step_lib
from distributeddeeplearningspark_tpu.train.pipeline_trainer import (
    LlamaStageProgram, PipelineStageRunner, StageRunConfig)

cfg = LlamaConfig.tiny()
STEPS, B, T, M, SEED = 3, 8, 32, 4, 7
def batch_fn(step):
    rng = np.random.default_rng(100 + step)
    ids = rng.permutation(cfg.vocab_size)[:B*T].reshape(B, T)
    return {"input_ids": ids.astype(np.int32),
            "loss_mask": np.ones((B, T), np.float32)}

# 1) bitwise parity vs the single-program llama_pp train step
devs = jax.devices()
mesh_pp = MeshSpec(data=2, pipe=2).build(devs[:4])
tx = optax.adamw(1e-3)
state, sh = step_lib.init_state(
    LlamaForCausalLM(cfg), tx, batch_fn(0), mesh_pp,
    llama_rules(cfg, fsdp=False, pipeline=True), seed=SEED)
ts = step_lib.jit_train_step(
    step_lib.make_train_step(make_pp_apply(cfg, mesh_pp, M), tx,
                             losses.causal_lm), mesh_pp, sh)
base = []
for s in range(STEPS):
    state, met = ts(state, put_global(batch_fn(s), mesh_pp))
    base.append(float(jax.device_get(met["loss"])))

ports, key = [free_port()], secrets.token_bytes(16)
results, errors = {}, {}
def run_stage(stage):
    try:
        mesh = MeshSpec(data=2).build(devs[2*stage:2*stage+2])
        prog = LlamaStageProgram(cfg, stage, 2, mesh, optax.adamw(1e-3),
                                 mode="exact")
        tr = mpmd.PipelineTransport(stage, 2, ports, key, connect_timeout=120)
        r = PipelineStageRunner(
            prog, tr, StageRunConfig(steps=STEPS, batch_size=B,
                                     microbatches=M, seed=SEED),
            batch_fn=batch_fn if stage == 0 else None)
        results[stage] = r.run()
    except BaseException as e:
        import traceback; traceback.print_exc(); errors[stage] = e
ths = [threading.Thread(target=run_stage, args=(s,)) for s in range(2)]
[t.start() for t in ths]; [t.join(900) for t in ths]
assert not errors, errors
mp = results[0]["losses"]
assert [np.float32(x).tobytes() for x in base] == \
    [np.float32(x).tobytes() for x in mp], (base, mp)

# 2) supervised process pipeline: bubble reported, under bound + 10%.
# seq 96: per-microbatch compute must dominate socket transport on the
# shared CI box, or the measured bubble reads transport noise, not
# schedule (docs/PERFORMANCE.md "Sizing the microbatch")
wd = os.environ["WD"]
def example(*extra):
    p = subprocess.run(
        [sys.executable, "examples/train_llama_mpmd.py", "--steps", "8",
         "--microbatches", "4", "--seq", "96", *extra],
        capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-800:]
    return json.loads(p.stdout.strip().splitlines()[-1])

clean = example("--workdir", os.path.join(wd, "clean"))
e = clean["extra"]
assert e["ok"] and e["final_step"] == 8, e
bub, theo = e["pipeline_bubble_frac"], e["theoretical_bubble_frac"]
assert bub is not None and theo is not None, e
assert bub < theo + 0.10, f"bubble {bub} over bound {theo}+0.10"
assert e["microbatch_traces"] >= 8, e  # cross-stage trace context landed

# 3) stage-kill drill: only stage 1 restarts, trajectory bitwise clean
drill = example("--workdir", os.path.join(wd, "drill"),
                "--kill-stage", "1", "--kill-at", "5")
d = drill["extra"]
assert d["ok"], d
assert d["restarts_per_stage"] == {"0": 0, "1": 1}, d["restarts_per_stage"]
assert [np.float32(x).tobytes() for x in e["losses"]] == \
    [np.float32(x).tobytes() for x in d["losses"]], (e["losses"], d["losses"])

print(f"parity=bitwise({STEPS} steps) bubble={bub:.3f} bound={theo:.3f} "
      f"traces={e['microbatch_traces']} drill_restarts={d['restarts_per_stage']}")
PYEOF
) || rc=$?
  log mpmd "${out:-mpmd smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[mpmd] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

# health smoke (ISSUE 17): the continuous health engine end-to-end on a
# REAL fleet. A faulted 2-replica tinyllama run (sleep injected into
# replica 0) must confirm a CRIT SLO alert NAMING the replica after the
# damping hold; removing the fault (clean rerun with a rolling reload
# appended to the SAME workdir) must emit the paired clear edge;
# health.json must carry the exact schema key set at BOTH edges;
# `dlstatus --incidents` must order raise -> recovery -> clear; and
# `dlstatus --cluster` over a root holding this workdir plus a tenanted
# train_mnist run must show both rows under the right tenants
# (docs/OBSERVABILITY.md "Alerts, health.json, and the cluster view").
run_health_smoke() {
  local t0 rc root out
  t0=$(date +%s)
  rc=0
  root=$(mktemp -d /tmp/dls_health_smoke.XXXXXX)
  out=$(ROOT="$root" python - <<'PYEOF'
import json, os, subprocess, sys

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.telemetry import health

root = os.environ["ROOT"]
wd = os.path.join(root, "serve")
wdt = os.path.join(root, "train")

SERVE = [sys.executable, "-m", "distributeddeeplearningspark_tpu.serve.cli",
         "--model", "tinyllama", "--replicas", "2", "--clients", "4",
         "--requests-per-client", "3", "--tenants", "2",
         "--prefix-tokens", "32", "--suffix-tokens", "8",
         "--max-new-tokens", "8", "--workdir", wd]

HEALTH_KEYS = {
    "schema", "generated_ts", "workdir", "worst_severity", "rules",
    "goodput", "slo", "queue_depth", "tenants", "last_step",
    "last_heartbeat_age_s", "stream", "evaluations", "alerts_active",
    "engine"}


def run(cmd, log, env=None):
    with open(log, "w") as f:
        p = subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT, env=env)
    assert p.returncode == 0, (cmd[-6:], open(log).read()[-800:])


def dlstatus(*argv):
    p = subprocess.run(
        [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
         *argv], capture_output=True, text=True)
    assert p.returncode == 0, (argv, p.stderr[-500:])
    return json.loads(p.stdout)


def last_ts():
    return max(float(e["ts"]) for e in telemetry.read_events(wd))


def health_doc():
    with open(os.path.join(wd, health.HEALTH_FILENAME)) as f:
        doc = json.load(f)
    assert set(doc) == HEALTH_KEYS, sorted(set(doc) ^ HEALTH_KEYS)
    assert doc["schema"] == health.HEALTH_SCHEMA
    return doc

# A) healthy baseline: the fleet's own p99 derives the SLO target, so the
#    drill judges fault-vs-clean, not this machine's absolute speed
run(SERVE, os.path.join(root, "serve-baseline.log"))
lats = sorted(float(e["latency_s"]) for e in telemetry.read_events(wd)
              if e.get("kind") == "request" and e.get("outcome") == "ok"
              and e.get("latency_s") is not None)
assert lats, "baseline served nothing"
target = max(1.0, 1.5 * lats[int(0.99 * (len(lats) - 1))])
boundary = last_ts()

# B) fault injected into replica 0 -> CRIT raise edge naming it. The
#    engine's event-time window is sized to hold exactly the events past
#    the boundary, so the healthy baseline can't dilute the burn rate.
run(SERVE + ["--fault-sleep-ms", "2000", "--fault-replica", "0"],
    os.path.join(root, "serve-faulted.log"))
eng = health.HealthEngine(wd, damping=2, slo_target_s=target,
                          window_s=(last_ts() - boundary) * 0.9)
rep = eng.evaluate()
assert rep["worst_severity"] == "OK", ("raised before damping hold", rep)
rep = eng.evaluate()
slo_alerts = [a for a in rep["alerts_active"] if a["rule"] == "slo"]
assert rep["worst_severity"] == "CRIT" and slo_alerts, rep["alerts_active"]
assert slo_alerts[0]["evidence"]["worst_replica"] == "p0", slo_alerts
crit_doc = health_doc()
assert crit_doc["worst_severity"] == "CRIT", crit_doc["worst_severity"]

# C) fault removed: a clean rerun (with a rolling reload, so a recovery
#    event lands between the edges) appended to the SAME workdir must
#    clear -- same damping hold, paired edge
boundary = last_ts()
run(SERVE + ["--rolling-reload"], os.path.join(root, "serve-rerun.log"))
eng.window_s = (last_ts() - boundary) * 0.9
eng.evaluate()
rep = eng.evaluate()
eng.close()
assert rep["worst_severity"] == "OK", rep["alerts_active"]
assert rep["alerts_active"] == [], rep["alerts_active"]
ok_doc = health_doc()
assert ok_doc["worst_severity"] == "OK", ok_doc["worst_severity"]

# D) the incident timeline orders raise -> recovery -> clear
rows = dlstatus(wd, "--incidents", "--json")["incidents"]
raise_ts = min(r["ts"] for r in rows
               if r["type"] == "alert-raise" and r["rule"] == "slo")
clear_ts = max(r["ts"] for r in rows
               if r["type"] == "alert-clear" and r["rule"] == "slo")
reloads = [r["ts"] for r in rows
           if r["type"] == "recovery" and r["key"] == "rolling-reload"]
assert raise_ts < clear_ts, (raise_ts, clear_ts)
assert any(raise_ts < t < clear_ts for t in reloads), (
    raise_ts, reloads, clear_ts)

# E) a second, tenanted train workdir under the same root: the cluster
#    view folds both with the right kinds and tenants
env = dict(os.environ, DLS_TELEMETRY_DIR=wdt, DLS_TENANT="research",
           XLA_FLAGS="--xla_force_host_platform_device_count=8")
run([sys.executable, "examples/train_mnist.py", "--master", "local[2]",
     "--steps", "6", "--batch-size", "16"],
    os.path.join(root, "train.log"), env=env)
cl = dlstatus("--cluster", root, "--json")
by_wd = {r["workdir"]: r for r in cl["workdirs"]}
assert set(by_wd) == {wd, wdt}, sorted(by_wd)
assert by_wd[wd]["kind"] == "serve" and by_wd[wdt]["kind"] == "train", by_wd
assert by_wd[wdt]["tenants"] == ["research"], by_wd[wdt]["tenants"]
assert {"tenant0", "tenant1"} <= set(by_wd[wd]["tenants"]), by_wd[wd]
assert cl["tenants"]["research"]["train_workdirs"] == 1, cl["tenants"]
assert cl["tenants"]["tenant0"]["requests"] > 0, cl["tenants"]

print(f"target_p99={target:.2f}s raise=CRIT(worst=p0) clear=OK "
      f"incidents={len(rows)} cluster_workdirs={len(by_wd)} "
      f"tenants={sorted(cl['tenants'])}")
PYEOF
) || { rc=$?; tail -5 "$root"/*.log 2>/dev/null; }
  log health "${out:-health smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[health] ${out:-FAILED} (rc=${rc})"
  rm -rf "$root"
  return $rc
}

# history smoke (ISSUE 18): the metrics time-series plane end-to-end on
# REAL runs. (a) a train_mnist run replayed through the HealthEngine
# leaves populated series at >=2 resolutions with the engine's re-read
# bytes bounded by the append rate (cursor accounting); `dlstatus
# --history` renders finite sparklines and its --json matches the pinned
# schema. (b) a healthy + faulted 2-replica tinyllama fleet: the engine
# sweeps anchors across the fault's violation completions and the
# predictive trend:slo WARN (burn-rate slope projecting EXHAUSTED) must
# raise STRICTLY BEFORE the damped level CRIT. (c) an HTTP scrape of
# `dlstatus --serve-metrics` parses as OpenMetrics and its gauge values
# bitwise-tie to health.json (docs/OBSERVABILITY.md "History, trends,
# and the metrics endpoint").
run_history_smoke() {
  local t0 rc root out
  t0=$(date +%s)
  rc=0
  root=$(mktemp -d /tmp/dls_history_smoke.XXXXXX)
  out=$(ROOT="$root" python - <<'PYEOF'
import json, os, re, subprocess, sys, urllib.request

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.telemetry import fleet as fleet_lib
from distributeddeeplearningspark_tpu.telemetry import health
from distributeddeeplearningspark_tpu.telemetry import series

root = os.environ["ROOT"]
wdt = os.path.join(root, "train")
wds = os.path.join(root, "serve")


def run(cmd, log, env=None):
    with open(log, "w") as f:
        p = subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT, env=env)
    assert p.returncode == 0, (cmd[-6:], open(log).read()[-800:])


def dlstatus(*argv):
    p = subprocess.run(
        [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
         *argv], capture_output=True, text=True)
    assert p.returncode == 0, (argv, p.stderr[-500:])
    return p


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# -- (a) train run -> engine replay -> multi-resolution series ----------------
env = dict(os.environ, DLS_TELEMETRY_DIR=wdt,
           XLA_FLAGS="--xla_force_host_platform_device_count=8")
run([sys.executable, "examples/train_mnist.py", "--master", "local[2]",
     "--steps", "6", "--batch-size", "16"],
    os.path.join(root, "train.log"), env=env)
ev = telemetry.read_events(wdt)
t_lo = min(float(e["ts"]) for e in ev)
t_hi = max(float(e["ts"]) for e in ev)
appended = sum(os.path.getsize(p) for p in telemetry.event_files(wdt))
clock = Clock()
eng = health.HealthEngine(wdt, damping=2, clock=clock, write_alerts=False)
n_anchor = max(8, int((t_hi - t_lo) / 5.0))
for i in range(1, n_anchor + 1):
    clock.t = t_lo + (t_hi - t_lo) * i / n_anchor + 1e-3
    eng.window_s = clock.t - t_lo + 60.0
    rep = eng.evaluate()
eng.close()
# cursor accounting: N evaluations read each appended byte AT MOST once —
# history costs the append rate, never an N x re-scan
train_bytes = rep["engine"]["bytes_read"]
assert 0 < train_bytes <= appended, (train_bytes, appended, n_anchor)

ladder = series.list_resolutions(wdt)
assert len(ladder) >= 2, ladder
pops = sum(
    1 for res, _cap in ladder
    if series.GOODPUT_SERIES in series.read_buckets(wdt, res)
    and series.STEPS_SERIES in series.read_buckets(wdt, res))
assert pops >= 2, f"series populated at only {pops} resolutions: {ladder}"

# --history: finite sparklines in the human render, pinned --json schema
p = dlstatus(wdt, "--history", "--since", "1h")
assert "nan" not in p.stdout.lower(), p.stdout
assert any(g in p.stdout for g in "▁▂▃▄▅▆▇█"), p.stdout
assert series.STEPS_SERIES in p.stdout, p.stdout
doc = json.loads(dlstatus(wdt, "--history", "--json").stdout)
assert tuple(doc) == series.HISTORY_KEYS, list(doc)
assert doc["series"] and all(
    tuple(r) == series.HISTORY_ROW_KEYS for r in doc["series"]), doc
# the within-run decline sentinel reads the same store (verdict informative
# here: a 6-step run rarely spans the 8-bucket minimum)
g = subprocess.run([sys.executable, "tools/perf_guard.py", "--series", wdt,
                    "--json"], capture_output=True, text=True)
assert g.returncode in (0, 1), g.stderr[-300:]
guard = json.loads(g.stdout)["verdict"]

# -- (b) fault drill: predictive WARN strictly before the damped CRIT ---------
SERVE = [sys.executable, "-m", "distributeddeeplearningspark_tpu.serve.cli",
         "--model", "tinyllama", "--replicas", "2", "--clients", "8",
         "--requests-per-client", "2", "--tenants", "2",
         "--prefix-tokens", "32", "--suffix-tokens", "8",
         "--max-new-tokens", "8", "--workdir", wds]
run(SERVE, os.path.join(root, "serve-baseline.log"))
lats = sorted(float(e["latency_s"]) for e in telemetry.read_events(wds)
              if e.get("kind") == "request" and e.get("outcome") == "ok"
              and e.get("latency_s") is not None)
assert lats, "baseline served nothing"
target = max(1.0, 1.5 * lats[int(0.99 * (len(lats) - 1))])
run(SERVE + ["--requests-per-client", "3",
             "--fault-sleep-ms", "2000", "--fault-replica", "0"],
    os.path.join(root, "serve-faulted.log"))

# rebuild the per-tenant violation trajectory exactly as slo_report
# attributes it (root request spans + untraced sheds), keyed by each
# event's ts — the same visibility order the engine's window filter sees
ev = telemetry.read_events(wds)
t0g = min(float(e["ts"]) for e in ev)
rows = []  # (visibility ts, tenant, violates?)
for e in ev:
    if (e.get("kind") == "span" and e.get("name") == "request"
            and not e.get("parent_id") and e.get("t1") is not None):
        a = e.get("attrs") or {}
        lat = max(0.0, float(e["t1"]) - float(e["t0"]))
        bad = a.get("outcome") != "ok" or lat > target
        rows.append((float(e["ts"]), str(a.get("tenant") or "default"), bad))
    elif (e.get("kind") == "request" and e.get("outcome") == "shed"
          and e.get("trace") is None):
        rows.append((float(e["ts"]), str(e.get("tenant") or "default"), True))
by_tenant = {}
for ts, ten, bad in rows:
    by_tenant.setdefault(ten, []).append((ts, bad))
viol_counts = {t: sum(1 for _, b in r if b) for t, r in by_tenant.items()}
assert any(viol_counts.values()), \
    f"fault drill produced no violations vs {target:.2f}s target"
tenant = max(viol_counts, key=lambda t: viol_counts[t])


def frac_at(ts):
    n = sum(1 for x, _ in by_tenant[tenant] if x <= ts)
    v = sum(1 for x, b in by_tenant[tenant] if b and x <= ts)
    return v / n if n else 0.0


# anchor the engine where the tenant's violation frac strictly rises: the
# greedy monotone subsequence of its violation completions (ok requests
# completing in between can locally dilute the frac — skip those anchors)
vts = sorted(x for x, b in by_tenant[tenant] if b)
S, last_f = [], 0.0
for t in vts:
    f = frac_at(t + 1e-4)
    if f > last_f:
        S.append((t + 1e-4, f))
        last_f = f
assert len(S) >= 4, (
    f"only {len(S)} monotone violation anchors for {tenant} "
    f"(of {len(vts)} violations) — fault too weak vs {target:.2f}s target")
final_frac = frac_at(vts[-1] + 60.0)
assert S[-2][1] < min(S[-1][1], final_frac), (S, final_frac)

# scale the error budget so burn crosses EXHAUSTED (10x) between the last
# two monotone anchors: >=3 anchors sit in the band below CRIT for the
# trend rule to see the rise, and the crossing + trailing anchors carry
# the level rule to its damped CRIT
thresh = (S[-2][1] + min(S[-1][1], final_frac)) / 2.0
budget = thresh / fleet_lib.SLO_EXHAUST_BURN

os.environ["DLS_HEALTH_TREND_N"] = "2"
clock = Clock()
eng = health.HealthEngine(wds, damping=2, clock=clock, slo_target_s=target,
                          slo_budget=budget)
anchors = ([S[0][0] - 2.0, S[0][0] - 1.0] + [t for t, _ in S]
           + [vts[-1] + 60.0, vts[-1] + 61.0])
for a in anchors:
    clock.t = a
    eng.window_s = a - t0g + 60.0
    rep = eng.evaluate()
eng.close()
del os.environ["DLS_HEALTH_TREND_N"]
serve_bytes = rep["engine"]["bytes_read"]
disk = sum(os.path.getsize(p) for p in telemetry.event_files(wds))
assert 0 < serve_bytes <= disk, (serve_bytes, disk)

alerts = [e for e in telemetry.read_events(wds) if e.get("kind") == "alert"]
trend_raises = [e for e in alerts if e.get("edge") == "raise"
                and e.get("key") == f"trend:slo:{tenant}"]
crit_raises = [e for e in alerts if e.get("edge") == "raise"
               and e.get("key") == f"slo:{tenant}"
               and e.get("severity") == "CRIT"]
assert trend_raises, [(e.get("key"), e.get("severity")) for e in alerts]
assert crit_raises, [(e.get("key"), e.get("severity")) for e in alerts]
t_warn = min(float(e["ts"]) for e in trend_raises)
t_crit = min(float(e["ts"]) for e in crit_raises)
assert t_warn < t_crit, (t_warn, t_crit)
proj = trend_raises[0]["evidence"]["projected_exhausted_in_s"]
assert proj >= 0, trend_raises[0]["evidence"]
pops_s = sum(1 for res, _cap in ladder
             if series.read_buckets(wds, res))
assert pops_s >= 2, f"serve series at only {pops_s} resolutions"

# -- (c) OpenMetrics scrape bitwise-ties to health.json -----------------------
srv = subprocess.Popen(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status", wds,
     "--serve-metrics", "0", "--watch-count", "1"],
    stderr=subprocess.PIPE, text=True)
try:
    banner = srv.stderr.readline()
    m = re.search(r"http://([\d.]+):(\d+)/metrics", banner)
    assert m, banner
    with urllib.request.urlopen(
            f"http://{m.group(1)}:{m.group(2)}/metrics", timeout=30) as r:
        ctype = r.headers["Content-Type"]
        body = r.read().decode("utf-8")
    assert srv.wait(timeout=30) == 0
finally:
    if srv.poll() is None:
        srv.kill()
        srv.wait()
assert ctype == series.OPENMETRICS_CONTENT_TYPE, ctype
lines = body.splitlines()
assert lines[-1] == "# EOF", lines[-1]
LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9+.eEnaIf-]+$")
fams, vals = set(), {}
for ln in lines[:-1]:
    if ln.startswith("# TYPE "):
        assert ln.endswith(" gauge"), ln
        fams.add(ln.split()[2])
        continue
    assert LINE.match(ln), ln
    name_labels, _, raw = ln.rpartition(" ")
    assert name_labels.split("{", 1)[0] in fams, ln
    vals[name_labels] = float(raw)
with open(os.path.join(wds, health.HEALTH_FILENAME)) as f:
    hdoc = json.load(f)
sev = {s: i for i, s in enumerate(health.SEVERITIES)}
assert vals[f'dls_health_worst_severity{{workdir="{wds}"}}'] == (
    sev[hdoc["worst_severity"]])
assert vals[f'dls_health_alerts_active{{workdir="{wds}"}}'] == len(
    hdoc["alerts_active"])
assert vals[f'dls_queue_depth{{replica="p0",workdir="{wds}"}}'] == (
    hdoc["queue_depth"]["p0"])
burn_doc = hdoc["slo"]["tenants"][tenant]["burn_rate"]
assert vals[
    f'dls_slo_burn_rate{{tenant="{tenant}",workdir="{wds}"}}'] == burn_doc

print(f"train_series={pops}res bytes={train_bytes}<= {appended} "
      f"guard={guard} drill: tenant={tenant} viols={len(vts)}({len(S)}mono) "
      f"warn@{t_warn - t0g:.1f}s < crit@{t_crit - t0g:.1f}s "
      f"proj={proj:.0f}s burn={burn_doc}x scrape={len(vals)}gauges bitwise=ok")
PYEOF
) || { rc=$?; tail -5 "$root"/*.log 2>/dev/null; }
  log history "${out:-history smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[history] ${out:-FAILED} (rc=${rc})"
  rm -rf "$root"
  return $rc
}

# sched smoke (ISSUE 19): the multi-tenant scheduler end to end — two
# tenants oversubscribe a fixed 2-host inventory: a low-priority elastic
# train gang fills the cluster, a high-priority serve submission forces a
# graceful shrink preemption (notice file -> in-flight drain -> live
# handoff -> supervisor shrink), the freed host runs the serve job, the
# train job completes on fewer hosts with a loss trajectory matching an
# unpreempted control run, quota is never exceeded at any ledger prefix,
# the accounting ties out across `dlstatus --cluster --json`, and zero
# processes outlive the drill (docs/CLUSTER.md).
run_sched_smoke() {
  local t0 rc wd out
  t0=$(date +%s)
  rc=0
  wd=$(mktemp -d /tmp/dls_sched.XXXXXX)
  out=$(WD="$wd" python - <<'PYEOF'
import glob, json, os, subprocess, sys, time

import numpy as np

wd = os.environ["WD"]
root = os.path.join(wd, "pool")
worker = os.path.abspath(os.path.join("tests", "workers", "worker.py"))

from distributeddeeplearningspark_tpu import telemetry
from distributeddeeplearningspark_tpu.scheduler import core, ledger
from distributeddeeplearningspark_tpu.supervisor import Supervisor

# -- two tenants oversubscribe 2 hosts ----------------------------------------
ledger.init_cluster(root, hosts=2, quotas={"research": 2, "prod": 1})
s = core.Scheduler(root)
lo = s.submit(
    [sys.executable, worker, "elastic", "--ckpt-dir", "{ckpt}",
     "--steps", "28", "--checkpoint-every", "6"],
    tenant="research", priority=0, gangs=2, min_hosts=1, name="train-lo",
    env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"})
s.tick()
lo_wd = ledger.load_state(root).jobs[lo].workdir

def last_step():
    best = 0
    for e in telemetry.read_events(lo_wd):
        st = e.get("step")
        if (e.get("kind") in ("step_metrics", "heartbeat")
                and isinstance(st, (int, float))):
            best = max(best, int(st))
    return best

deadline = time.time() + 240
while last_step() < 4 and time.time() < deadline:
    s.tick()
    time.sleep(0.5)
assert last_step() >= 4, "train job never made progress"

# -- the high-priority serve submission forces a shrink preemption ------------
serve_script = os.path.join(wd, "serve.py")
with open(serve_script, "w") as f:
    f.write("import time\ntime.sleep(3)\nprint('served')\n")
hi = s.submit([sys.executable, serve_script], tenant="prod", priority=10,
              gangs=1, name="serve-hi", kind="serve")
s.run(interval=0.4, max_ticks=450, until_idle=True)
s.close()

st = ledger.load_state(root)
jlo, jhi = st.jobs[lo], st.jobs[hi]
runner_log = os.path.join(lo_wd, "runner.log")
tail = open(runner_log).read()[-2000:] if os.path.exists(runner_log) else ""
assert jlo.status == "COMPLETED" and jlo.rc == 0, (jlo.status, jlo.rc, tail)
assert jhi.status == "COMPLETED" and jhi.rc == 0, (jhi.status, jhi.rc)

recs = ledger.read_ledger(root)
pre = [r for r in recs if r["edge"] == "preempt"]
assert pre and pre[0]["job"] == lo and pre[0]["mode"] == "shrink" \
    and pre[0]["victim_of"] == hi, pre
assert any(r["edge"] == "shrink" and r["job"] == lo for r in recs), \
    [r["edge"] for r in recs]

# the gang finished all 28 steps at width 1 after the drain
step, attempt, width = open(
    os.path.join(lo_wd, "ckpt", "DONE")).read().split()
assert (int(step), int(width)) == (28, 1), (step, attempt, width)

# -- graceful drain + live handoff, visible in the victim's own stream --------
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     lo_wd, "--json", "--incidents"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
doc = json.loads(p.stdout)
ev = doc["recovery_events"]
geo = [e for e in ev if e.get("event") == "geometry_change"]
assert geo and geo[-1].get("resume") == "live-handoff", geo
gs = [e for e in ev if e.get("event") == "graceful_shutdown"]
assert gs and gs[-1].get("dead_host") == 1, gs
drain_step = int(gs[-1]["step"])
moves = [e for e in ev if e.get("event") == "reshard"]
assert not any(e.get("walk_back") for e in moves), moves
itypes = [r["type"] for r in doc["incidents"]]
assert "sched-preempt" in itypes and "sched-shrink" in itypes, itypes

# -- quota is never exceeded at ANY prefix of the ledger ----------------------
cfg = ledger.load_config(root)
replay = ledger.ClusterState(root=os.path.abspath(root),
                             hosts=list(cfg["hosts"]),
                             quotas=dict(cfg["quotas"]))
for rec in recs:
    replay.apply(rec)
    for t, u in replay.used_by_tenant().items():
        q = replay.quotas.get(t)
        assert q is None or u <= q, (rec, t, u, q)

# -- accounting ties out across dlstatus --cluster ----------------------------
p = subprocess.run(
    [sys.executable, "-m", "distributeddeeplearningspark_tpu.status",
     "--cluster", root, "--json"], capture_output=True, text=True)
assert p.returncode == 0, p.stderr[-500:]
cdoc = json.loads(p.stdout)
assert cdoc["sched"] == ledger.load_state(root).to_report()
assert cdoc["sched"]["hosts"] == {"total": 2, "free": 2}
assert all(row["used"] == 0 for row in cdoc["sched"]["tenants"].values())
assert {j["status"] for j in cdoc["sched"]["jobs"]} == {"COMPLETED"}

# -- zero orphaned processes --------------------------------------------------
orphans = []
for path in glob.glob("/proc/[0-9]*/cmdline"):
    try:
        with open(path, "rb") as f:
            cmd = f.read().decode(errors="replace").replace("\0", " ")
    except OSError:
        continue
    if wd in cmd and str(os.getpid()) != path.split("/")[2]:
        orphans.append(cmd)
assert not orphans, orphans

# -- the preempted trajectory matches an unpreempted control run --------------
ctl = os.path.join(wd, "ctl")
os.makedirs(ctl)
sup = Supervisor(
    [sys.executable, worker, "elastic", "--ckpt-dir", ctl,
     "--steps", "28", "--checkpoint-every", "6"],
    num_processes=1, max_restarts=1, restart_backoff_s=0.05,
    backoff_jitter=0.0,
    env={"XLA_FLAGS": "", "JAX_PLATFORMS": "cpu"},
    progress_path=ctl, telemetry_dir=ctl)
result = sup.run()
assert result.ok, [(a.ordinal, a.returncodes, a.classification)
                   for a in result.attempts]

def losses(d):
    out = {}
    for e in telemetry.read_events(d):
        if e.get("kind") == "step_metrics":
            loss = (e.get("metrics") or {}).get("loss")
            if loss is not None:
                out[int(e["step"])] = float(loss)
    return out

lo_losses, ctl_losses = losses(lo_wd), losses(ctl)
common = sorted(set(lo_losses) & set(ctl_losses))
post = [c for c in common if c >= drain_step]
assert post, (sorted(lo_losses), sorted(ctl_losses), drain_step)
assert np.allclose([lo_losses[c] for c in common],
                   [ctl_losses[c] for c in common], rtol=0, atol=1e-6), [
    (c, lo_losses[c], ctl_losses[c]) for c in common
    if abs(lo_losses[c] - ctl_losses[c]) > 1e-6]

print(f"sched: preempt=shrink@{drain_step} victim={lo} for={hi} "
      f"done=28@width1 resume=live-handoff quota=never-exceeded "
      f"tieout=ok orphans=0 loss-match={len(common)}steps"
      f"({len(post)}post-drain)")
PYEOF
) || rc=$?
  log sched "${out:-sched smoke failed}" "${rc}" $(( $(date +%s) - t0 ))
  echo "[sched] ${out:-FAILED} (rc=${rc})"
  rm -rf "$wd"
  return $rc
}

overall=0
case "${1:-both}" in
  fast) run_tier fast "not slow" || overall=$? ;;
  slow) run_tier slow "slow" || overall=$? ;;
  both) run_tier fast "not slow" || overall=$?
        run_tier slow "slow" || overall=$?
        run_shuffle_smoke || overall=$?
        run_shuffle_chaos || overall=$?
        run_elastic_smoke || overall=$?
        run_live_reshard_smoke || overall=$?
        run_mpmd_smoke || overall=$?
        run_plan_smoke || overall=$?
        run_health_smoke || overall=$?
        run_history_smoke || overall=$?
        run_sched_smoke || overall=$? ;;
  # the recovery drills (kill-mid-finalize, poisoned restore, hang, NaN
  # spike) end-to-end — slow-marked, so the fast tier never pays for gangs
  chaos) run_tier chaos "slow or not slow" tests/test_chaos.py || overall=$? ;;
  # real-driver telemetry smoke: train a few steps, dlstatus must parse the
  # stream and report goodput_frac > 0 (docs/OBSERVABILITY.md)
  dlstatus) run_dlstatus_smoke || overall=$? ;;
  # pod-level fleet view: bundled 3-host hang fixture through
  # `dlstatus --hosts` (stalled host named, nonzero heartbeat age)
  hosts) run_hosts_smoke || overall=$? ;;
  # serving: train→serve→hot-reload end-to-end on CPU LeNet (docs/SERVING.md)
  serve) run_serve_smoke || overall=$? ;;
  # serving fleet: 2 replica processes + router + rolling reload + paged
  # KV/prefix cache, zero dropped requests (docs/SERVING.md "Fleet")
  fleet-serve) run_fleet_serve_smoke || overall=$? ;;
  # request tracing: span-tree coverage >=95% per completed request,
  # loadable --export-trace JSON, --slo verdict flip on an injected sleep
  # fault (docs/OBSERVABILITY.md "Tracing a request")
  trace) run_trace_smoke || overall=$? ;;
  # input pipeline: 2-worker pool beats the serial map on a synthetic JPEG
  # corpus, and telemetry carries the per-worker gauges (docs/PERFORMANCE.md)
  input) run_input_smoke || overall=$? ;;
  # distributed shuffle: 10M-key groupBy.agg the serial ceiling refuses
  # completes via the 2-worker exchange under DLS_SHUFFLE_MEM_MB, exact
  # result + >=1 spill + dlstatus shuffle block (docs/PERFORMANCE.md)
  shuffle) run_shuffle_smoke || overall=$? ;;
  # shuffle fault tolerance: mapper+reducer SIGKILL mid-10M-key agg →
  # self-heals checksum-identical with >=1 retry each and zero orphans;
  # DLS_SHUFFLE_MAX_RETRIES=0 → typed WorkerCrashed, full teardown
  # (docs/POD_PLAYBOOK.md "A shuffle worker died")
  shuffle-chaos) run_shuffle_chaos || overall=$? ;;
  # device anatomy: compile ledger exactly-once, lap split explains the
  # Meter wall within 5%, finite MFU (docs/OBSERVABILITY.md "Device
  # anatomy")
  anatomy) run_anatomy_smoke || overall=$? ;;
  # elastic recovery: kill-a-host drill (die_host@N, shrink-to-survive,
  # completion on the survivor) + dlstatus geometry change + bitwise
  # fsdp→tensor restore (docs/POD_PLAYBOOK.md "We lost a host")
  elastic) run_elastic_smoke || overall=$? ;;
  # checkpoint-free live resharding: SIGTERM graceful drain resumes from
  # the CURRENT step via the live handoff (no walk-back), die_host still
  # walks back through the checkpoint, live fsdp->tensor redistribute
  # bitwise == the disk round trip at <=50% of its wall
  # (docs/POD_PLAYBOOK.md "We got a preemption notice")
  live-reshard) run_live_reshard_smoke || overall=$? ;;
  # MPMD pipeline: 2-stage bitwise parity vs llama_pp, bubble under the
  # (P-1)/(M+P-1) bound + 10%, stage-kill drill restarts ONLY the dead
  # stage (docs/PERFORMANCE.md "MPMD pipelines")
  mpmd) run_mpmd_smoke || overall=$? ;;
  # measured layout search: >=3 plans swept on a tiny llama mesh, ranked
  # table ordered by measured step time, winner re-runs with zero new
  # compiles, one plan-tagged ledger compile per plan (docs/PERFORMANCE.md
  # "Choosing a layout with plan_sweep")
  plan) run_plan_smoke || overall=$? ;;
  # continuous health engine: faulted fleet -> damped CRIT SLO alert
  # naming the replica -> clean rerun -> paired clear edge, health.json
  # schema at both edges, --incidents ordering, --cluster fold
  # (docs/OBSERVABILITY.md "Alerts, health.json, and the cluster view")
  health) run_health_smoke || overall=$? ;;
  # metrics time-series plane: real runs leave multi-resolution series
  # (re-read bytes bounded by the append rate), predictive trend WARN
  # strictly before the damped CRIT in the fault drill, --history pinned
  # schema + finite sparklines, OpenMetrics scrape bitwise-ties to
  # health.json (docs/OBSERVABILITY.md "History, trends, and the metrics
  # endpoint")
  history) run_history_smoke || overall=$? ;;
  # multi-tenant scheduler: two tenants oversubscribe 2 hosts, the
  # high-priority serve submission shrink-preempts the elastic train
  # gang (notice -> drain -> live handoff), both complete, loss
  # trajectory matches an unpreempted control, quota never exceeded,
  # accounting ties out, zero orphans (docs/CLUSTER.md)
  sched) run_sched_smoke || overall=$? ;;
  # the executable pod-day scripts, logged with the same audit trail
  # (VERDICT r4 next-#9's done-condition: rehearsal green in CI)
  smoke)     run_script_tier smoke tools/smoke.sh || overall=$? ;;
  rehearsal) run_script_tier rehearsal tools/pod_rehearsal.sh || overall=$? ;;
  *) echo "usage: tools/ci.sh [fast|slow|both|chaos|dlstatus|hosts|serve|fleet-serve|trace|input|shuffle|shuffle-chaos|anatomy|elastic|live-reshard|mpmd|plan|health|history|sched|smoke|rehearsal]"; exit 2 ;;
esac
exit $overall
