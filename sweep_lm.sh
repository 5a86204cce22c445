#!/bin/bash
# LM MFU frontier sweep (VERDICT r2 #7). Run on an idle chip; each line
# prints "config -> tok/s TF/s MFU". Results land in BASELINE.md.
#
# Measured 2026-07-31 (TPU v5 lite): winner is d=2048x8 B=16 remat=dots
# head-chunk=128 at 43.5% model MFU / 85.7 TF/s — now bench.py's lm
# DEFAULTS, so every line here pins its full config explicitly (the
# annotations were measured with exactly these flags). The commented
# configs at the bottom OOM on a 16 GB chip (adam state for ~436M params
# is 5.2 GB before activations) — the documented memory boundary.
#
# bench.py exits non-zero at once without a TPU. Its stderr is shown, not
# hidden: a config past the memory boundary fails with RESOURCE_EXHAUSTED,
# the sweep records FAILED with the exit code and moves on (finding that
# boundary is what a sweep is for), and the script exits 1 if any run
# failed.
cd "$(dirname "$0")"
failed=0
run() {
  echo "=== $*"
  out=$(timeout 500 python bench.py --suite lm "$@")
  rc=$?
  [ $rc -eq 3 ] && exit 3   # bench.py found no TPU: nothing to sweep
  if [ $rc -ne 0 ]; then
    echo "  FAILED rc=$rc"
    failed=1
    return
  fi
  echo "$out" | python -c "
import sys, json
d = json.loads(sys.stdin.read().strip().splitlines()[-1])
s = d['suites']['lm']
print(' ', s['samples_per_sec_per_chip'], 'tok/s,', s['tflops_per_chip'], 'TF/s, MFU', s['mfu_vs_bf16_peak'], 'hw', s.get('mfu_hw_vs_bf16_peak'), s['config'], '('+d['device']+')')
" || failed=1
}
run --lm-dim 512  --lm-depth 4 --lm-batch 64 --no-lm-remat --lm-head-chunk 0                      # r2 base: 32.0% (2026-07-31)
run --lm-dim 1024 --lm-depth 8 --lm-batch 32 --no-lm-remat --lm-head-chunk 128                    # 40.5%, no remat
run --lm-dim 2048 --lm-depth 8 --lm-batch 32 --lm-remat --lm-remat-mode attn --lm-head-chunk 128  # 40.9%
run --lm-dim 2048 --lm-depth 8 --lm-batch 16 --lm-remat --lm-remat-mode dots --lm-head-chunk 128  # 43.5% WINNER (= bench defaults)
run --lm-dim 2048 --lm-depth 12 --lm-batch 16 --lm-remat --lm-remat-mode attn --lm-head-chunk 128 # 39.8% model / 53.3% hw
# unmeasured: candidates between the fit/OOM line
run --lm-dim 2048 --lm-depth 8 --lm-batch 24 --lm-remat --lm-remat-mode dots --lm-head-chunk 128
run --lm-dim 2048 --lm-depth 8 --lm-batch 8 --lm-seq 2048 --lm-remat --lm-remat-mode dots --lm-head-chunk 128
# round-4 optimizer-state levers (tables/updaters.py): f32 adam state is
# what bounds the frontier (5.2 GB at 436M params). bf16 moments halve
# it, int8 quarters it — the freed HBM buys batch (B=24/32 at the winner
# config) and deeper/wider points that used to OOM. Unmeasured;
# past-50%-model-MFU is the round-4 target.
run --lm-dim 2048 --lm-depth 8 --lm-batch 16 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state bf16   # state-dtype control at the winner
run --lm-dim 2048 --lm-depth 8 --lm-batch 24 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state bf16
run --lm-dim 2048 --lm-depth 8 --lm-batch 32 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state bf16
run --lm-dim 2048 --lm-depth 8 --lm-batch 32 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state int8
run --lm-dim 2048 --lm-depth 12 --lm-batch 16 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state bf16
run --lm-dim 4096 --lm-depth 4 --lm-batch 16 --lm-remat --lm-remat-mode dots --lm-head-chunk 128 --lm-opt-state int8
# OOM boundary on 16 GB (RESOURCE_EXHAUSTED) with f32 adam state, do not
# re-run blindly WITHOUT an opt-state lever:
#   d=2048x8 B=64 (any remat); d=2048x8 B=32 remat=dots/hybrid/hybrid_qkv
#   d=2048x4 B=32 no remat; d=1024x16 B=32 no remat; d=4096x4 B=32 full remat
exit $failed
