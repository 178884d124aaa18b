#!/bin/bash
# Round-7 on-chip measurement checklist, in priority order — round 6's
# successor, folding in the ring-vs-gather sequence-parallel A/B
# (GIGAPATH_RING_ATTN). Each step is timeout-bounded and logs to
# /tmp/r7_*.log; artifacts land in the repo.
# Run when a MULTI-CHIP slice is up:  bash scripts/round7_measure.sh
set -x
cd "$(dirname "$0")/.."

# 1. headline bench (one JSON line naming the device; fails without a TPU)
timeout 1800 python bench.py 2>/tmp/r7_bench.err | tee /tmp/r7_bench.log

# 2. gate the kernels at the bench geometry (incl. flagged combos)
timeout 2400 python scripts/tpu_selfcheck.py > /tmp/r7_selfcheck.log 2>&1
tail -5 /tmp/r7_selfcheck.log

# 3. THE round-7 decision: all-gather vs ring K/V exchange for the
#    oversized branches at the 1M operating point (power-of-two L so the
#    2^20 segment divides into whole shards). Decision-table JSON
#    (adopt_ring_attn verdict) + obs run_end -> AB_DILATED_OBS.jsonl.
#    NEEDS >= 2 devices; on one chip it exits with a message.
timeout 2400 python scripts/ab_dilated.py --variants gather,ring \
  --n 1048576 --iters 8 --json AB_RING.json > /tmp/r7_ab_ring.log 2>&1
tail -12 /tmp/r7_ab_ring.log

# 4. same decision for the grad step (the reverse ring vs the implicit
#    backward reduce-scatter of the differentiable all-gather)
timeout 2400 python scripts/ab_dilated.py --variants gather,ring \
  --n 1048576 --iters 8 --grad --json AB_RING_GRAD.json \
  > /tmp/r7_ab_ring_grad.log 2>&1
tail -12 /tmp/r7_ab_ring_grad.log

# 5. per-shard slice of the 1M recipe with the ring memory/comm fields:
#    branch_*_{gather,ring}_{arg,temp,peak}_mb + *_comm_mb in
#    SEQ_SHARD.json, full profiles in SEQ_SHARD.json.ledger.json ->
#    diff per-shard bytes with scripts/ledger_diff.py
timeout 2400 python scripts/seq_shard_slice.py --out SEQ_SHARD.json \
  > /tmp/r7_slice.log 2>&1
tail -4 /tmp/r7_slice.log

# 6. the memory half of the claim, past the 393k wall: long-context
#    envelope with the ring flag on (streaming fusion composed in, per
#    the round-3 playbook)
GIGAPATH_RING_ATTN=1 GIGAPATH_STREAMING_FUSION=1 GIGAPATH_STREAM_FUSION=1 \
  timeout 2400 python scripts/long_context_smoke.py > /tmp/r7_envelope.log 2>&1
tail -8 /tmp/r7_envelope.log

# 7. the serving stack at flagship shape (ROADMAP item 1): bucketed AOT
#    executables + continuous batching + content-hash cache, hard
#    assertions baked in (zero mid-serve retraces, warm restart loads
#    artifacts, repeats cache-served), plus the PR-9 latency surface —
#    the smoke's metrics snapshot (queue-wait / dispatch / e2e
#    histograms with p50/p90/p99) and Perfetto request-trace export.
#    The ingest below lands BOTH trend entries (serve|smoke throughput
#    AND serve|latency tail latency) in PERF_HISTORY.json; on-chip
#    numbers move the trends, the committed CPU points are stale
#    provenance only. NO SLO target here: the smoke's clean-run
#    assertion demands ZERO slo_burn anomalies, but e2e latency counts
#    queue wait stacked behind each bucket's cold AOT compile — minutes
#    at flagship shape — so any honest target would fail a healthy
#    measurement run. The latency histograms flow regardless; SLO
#    tuning happens against warm serving, not a cold-compile sweep.
timeout 2400 python scripts/serve_smoke.py \
  --arch gigapath_slide_enc12l768d --input-dim 1536 --latent-dim 768 \
  --bucket-min 1024 --bucket-align 128 --bucket-max 131072 \
  --json SERVE_SMOKE.json > /tmp/r7_serve.log 2>&1
tail -3 /tmp/r7_serve.log

# 8. the disaggregated cross-stage boundary (ROADMAP item 4's dryrun):
#    two tile-worker processes + the slide consumer over the credit-
#    based channel — clean parity, kill-recover bit-exactness, straggler
#    skew, drop/dup dedup, the TCP transport under drop_conn/
#    corrupt_frame frame chaos (reconnect_s trend key), and consumer
#    SIGKILL-and-resume from the checkpoint watermark
#    (consumer_recover_s), all hard-asserted. The ingest below folds the
#    dist|smoke entry next to the serve ones (the label lands once, with
#    every snapshot measured this round). --fleet-json additionally
#    writes the cross-process fleet-trace payload (critical-path shares
#    over the merged timeline from check 9) for the dist|trace entry;
#    scripts/fleet_report.py renders the same run's merged timeline.
timeout 1200 python scripts/dist_smoke.py --json DIST_SMOKE.json \
  --fleet-json FLEET_SMOKE.json > /tmp/r7_dist.log 2>&1
tail -3 /tmp/r7_dist.log

# 9. streaming chunked prefill (ROADMAP item 2): the
#    adopt_chunked_prefill decision table — per-variant XLA
#    memory-analysis {arg,temp,peak}_mb of the dense forward vs the
#    per-chunk fold executable, walltime, and dense-oracle parity, at
#    the 16k smoke geometry. On-chip numbers land the prefill|stream
#    trend entry; the committed CPU point is stale provenance.
timeout 1200 python scripts/long_context_smoke.py --stream \
  --json PREFILL_SMOKE.json 16384 > /tmp/r7_prefill.log 2>&1
tail -3 /tmp/r7_prefill.log

# 10. quantized tile tier (ROADMAP item 3): bf16 vs int8 at the
#     flagship tile shape — tiles/s per variant, drift vs the f32
#     oracle on the committed fixture weights, and the adopt_quant_tile
#     decision table (parity gates + the >=3% speed gate that only an
#     on-chip row can pass). The ingest lands the tile|quant trend
#     entry next to the others.
timeout 2400 python scripts/ab_tile.py --variants bf16,int8 \
  --arch gigapath_tile_enc --batch 128 --pallas \
  --json AB_TILE.json > /tmp/r7_tile.log 2>&1
tail -4 /tmp/r7_tile.log

# 12. model-health loop (drift sentinel + anytime confidence): baseline
#     sketch off the streaming path, clean re-serve (zero embedding_drift
#     anomalies), chaos-shifted serve (EXACTLY ONE, with flight dump) —
#     both ways hard-asserted inside the smoke. The ingest folds the
#     serve|drift trend entry (clean-phase drift scores down-good,
#     provisional-vs-final stream confidence up-good); CPU points land
#     stale, as everywhere else.
timeout 1200 python scripts/serve_smoke.py --drift-slides 16 \
  --json DRIFT_SMOKE.json > /tmp/r7_drift.log 2>&1
tail -3 /tmp/r7_drift.log

python scripts/perf_history.py ingest --label r07 --serve SERVE_SMOKE.json \
  --dist DIST_SMOKE.json --fleet FLEET_SMOKE.json \
  --prefill PREFILL_SMOKE.json \
  --tile AB_TILE.json \
  --drift DRIFT_SMOKE.json || true
