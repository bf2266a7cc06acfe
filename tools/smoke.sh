#!/usr/bin/env bash
# One-command end-to-end smoke of all five workload drivers on the fake
# 8-device CPU mesh (the .claude/skills/verify playbook, executable).
# Each driver must finish AND print its final-metrics line; MNIST must
# actually learn (accuracy 1.0 on the synthetic set — the PR1 acceptance
# shape). Prints one row per driver.
#
#   bash tools/smoke.sh          # all five (~10 min on one contended core)
#   bash tools/smoke.sh mnist [bert ...]   # a subset
set -u -o pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
export PYTHONPATH="$(pwd):${PYTHONPATH:-}"

declare -A CMD GREP
CMD[mnist]="python examples/train_mnist.py --master local[2] --steps 150"
GREP[mnist]="test metrics:.*'accuracy': 1.0"
CMD[resnet]="python examples/train_resnet.py --master local[2] --variant resnet18 --image-size 32 --steps 3 --batch-size 8"
GREP[resnet]="train summary"
CMD[bert]="python examples/train_bert.py --master local[2] --variant tiny --steps 6"
GREP[bert]="train summary"
CMD[dlrm]="python examples/train_dlrm.py --master local[2] --steps 30 --batch-size 64 --vocab-size 100"
GREP[dlrm]="eval AUC"
CMD[llama]="python examples/train_llama_lora.py --master local[2] --expert 2 --moe-experts 4 --segment-ids --steps 4"
GREP[llama]="moe_aux"

# "${@:-...}" expands to ONE word when $@ is empty, which sent the whole
# default list into the unknown-driver branch (ADVICE r4, confirmed by
# execution) — set the positional params explicitly instead
if [ $# -eq 0 ]; then set -- mnist resnet bert dlrm llama; fi

overall=0
for d in "$@"; do
  if [ -z "${CMD[$d]:-}" ]; then
    echo "unknown driver '$d'; valid: ${!CMD[*]}" >&2
    exit 2
  fi
  t0=$(date +%s)
  out=$(eval "${CMD[$d]}" 2>&1)
  rc=$?
  secs=$(( $(date +%s) - t0 ))
  if [ $rc -eq 0 ] && grep -q "${GREP[$d]}" <<<"$out"; then
    ok=yes
  else
    ok="NO (rc=$rc)"
    overall=1
    echo "---- $d failed; last lines:"; tail -5 <<<"$out"
  fi
  printf '| %s | %s | %s | %ss |\n' \
    "$(date -u +%Y-%m-%dT%H:%M:%SZ)" "$d" "$ok" "$secs"
done
exit $overall
