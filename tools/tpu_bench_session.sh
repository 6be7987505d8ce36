#!/bin/bash
# One-shot TPU measurement session: every bench in priority order, one
# process at a time (each owns the chip in turn), so a session cut
# short still yields the headline artifact first. Through the chip
# tool only chiprun_out/ comes back, so send the captures there:
#
#   chiprun --timeout 3600 -- bash tools/tpu_bench_session.sh chiprun_out/bench
#
# Produces in <outdir> (default bench_out/):
#   resnet50.json            headline (the BENCH_rN.json payload)
#   transformer_lm.json      MFU workload
#   sweep.jsonl              catalog sweep (one line per network)
#   decode*.json             KV-cache generation (greedy/int8/beam/gqa/spec)
#   longcontext.jsonl        4k..32k single-chip context sweep
#   raw_jax_control.txt      framework-overhead control
#   trace/ + trace_summary.txt   xplane device-time breakdown
#
# Artifacts are written through a temp file and installed ONLY on
# stage success — a failed stage must never overwrite a good capture
# with a value:null diagnostic.
set -u -o pipefail
cd "$(dirname "$0")/.."
export OUT="${1:-bench_out}"
mkdir -p "$OUT"
FAILED=()
REFRESHED=()
note() { [ "$1" -ne 0 ] && FAILED+=("$2 (rc=$1)"); true; }

# Validate a would-be JSON capture BEFORE install: a diagnostic line
# (value null + error — the bench_common fail_payload contract,
# including the SIGTERM death stub) or torn/garbled output must never
# overwrite a good capture.
# Non-JSON artifacts (trace_summary.txt etc.) skip the check.
ok_capture() {  # ok_capture <dest-name> <content-file>
  case "$1" in *.json|*.jsonl|*.jsonl.new) ;; *) return 0 ;; esac
  python - "$2" <<'PY'
import json, sys
ok = False
with open(sys.argv[1]) as f:
    for line in f:
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            sys.exit(1)            # torn output: not installable
        # the fail_payload/death-stub diagnostic signature is a
        # top-level null value beside an error (every whole-run
        # failure path prints it). Anything else that parses counts as
        # a capture: micro benches carry their own keys (one_pass_ms
        # etc.), and a per-row error stub (the gspmd row) rides inside
        # an otherwise good sweep by design.
        if "error" in rec and rec.get("value") is None \
                and "metric" in rec:
            sys.exit(1)
        ok = True
sys.exit(0 if ok else 1)
PY
}

# stdout ONLY goes through tee into the artifact (stderr stays on the
# console/session log — backend warnings must never land inside a
# committed .json and break strict consumers)
cap() {   # cap <outfile> <label> <cmd...>: install output on success only
  local out="$1" label="$2"; shift 2
  local tmp; tmp="$(mktemp)"
  "$@" | tee "$tmp"
  local rc=${PIPESTATUS[0]}
  if [ "$rc" -eq 0 ] && [ -s "$tmp" ] && ok_capture "$out" "$tmp"; then
    mv "$tmp" "$out"; REFRESHED+=("$out")
  else rm -f "$tmp"; fi
  note "$rc" "$label"
}
capa() {  # capa <outfile> <label> <cmd...>: append on success only
  local out="$1" label="$2"; shift 2
  local tmp; tmp="$(mktemp)"
  "$@" | tee "$tmp"
  local rc=${PIPESTATUS[0]}
  if [ "$rc" -eq 0 ] && [ -s "$tmp" ] && ok_capture "$out" "$tmp"; then
    cat "$tmp" >> "$out"; REFRESHED+=("$out")
  fi
  rm -f "$tmp"
  note "$rc" "$label"
}

echo "== 1. headline resnet-50 =="
cap "$OUT/resnet50.json" resnet50 python bench.py

echo "== 2. transformer LM (MFU workload) =="
cap "$OUT/transformer_lm.json" transformer_lm \
    python bench.py --network transformer_lm

echo "== 3. catalog sweep =="
SWEEP="$OUT/sweep.jsonl.new"; : > "$SWEEP"
for net in resnet-18 resnet-34 resnet-101 resnet-152 inception-bn \
           inception-v3 alexnet; do
  echo "-- $net"
  capa "$SWEEP" "sweep:$net" python bench.py --network "$net"
done
[ -s "$SWEEP" ] && mv "$SWEEP" "$OUT/sweep.jsonl" || rm -f "$SWEEP"

echo "== 3b. decode throughput (float + int8 + beam + gqa + spec) =="
cap "$OUT/decode.json" decode \
    python bench.py --network transformer_lm --decode
cap "$OUT/decode_int8.json" decode_int8 \
    python bench.py --network transformer_lm --decode --quantize int8
cap "$OUT/decode_beam4.json" decode_beam4 \
    python bench.py --network transformer_lm --decode --beam 4
cap "$OUT/decode_gqa4.json" decode_gqa4 \
    env BENCH_TLM_KV_HEADS=4 python bench.py --network transformer_lm \
        --decode
cap "$OUT/decode_spec4.json" decode_spec4 \
    python bench.py --network transformer_lm --decode --speculative 4
# int8 KV caches matter most at long prompts (cache reads dominate)
cap "$OUT/decode_kv8.json" decode_kv8 \
    python bench.py --network transformer_lm --decode --quantize kv8 \
        --seq-len 1024
cap "$OUT/decode_int8kv8.json" decode_int8kv8 \
    python bench.py --network transformer_lm --decode \
        --quantize int8+kv8 --seq-len 1024
# serve-path A/B through the ContinuousDecoder slot pool: bf16 vs
# int8 cache bytes/slot + decode step ms + slots-per-HBM-budget
# (benchmark/bench_decode.py; the per-row q8 path, slot turnover on)
cap "$OUT/decode_kv_ab.json" decode_kv_ab \
    python benchmark/bench_decode.py
# O(1)-state decode A/B: f32 attention vs block_type="ssm" at long
# context — bytes/slot constant in max_len, slots-in-budget ratio,
# handoff bytes constant in prompt length (ISSUE 19)
cap "$OUT/decode_ssm_ab.json" decode_ssm_ab \
    env BENCH_DECODE_MODE=ssm python benchmark/bench_decode.py

echo "== 3c. long-context sweep (batch 1) =="
LCTX="$OUT/longcontext.jsonl.new"; : > "$LCTX"
for T in 4096 8192 16384; do
  capa "$LCTX" "lctx:$T" env BENCH_ITERS=10 python bench.py \
      --network transformer_lm --batch 1 --seq-len "$T"
done
capa "$LCTX" lctx:32768 env BENCH_ITERS=5 python bench.py \
    --network transformer_lm --batch 1 --seq-len 32768 --remat
# windowed attention cuts FLOPs, not activation residency: pair it
# with remat (un-rematerialized 32k OOMs — measured round 5)
capa "$LCTX" lctx:32768w4096 env BENCH_ITERS=5 python bench.py \
    --network transformer_lm --batch 1 --seq-len 32768 --window 4096 \
    --remat
# the chunked fused-CE head unlocks everything past 32k (the dense
# head's (B*T, vocab) logits are the OOM); 49152 = the longest
# single-chip config run so far (2026-08-01).
capa "$LCTX" lctx:32768w4096chunk env BENCH_ITERS=5 \
    BENCH_TLM_LOSS_CHUNK=4096 python bench.py \
    --network transformer_lm --batch 1 --seq-len 32768 --window 4096 \
    --remat
capa "$LCTX" lctx:49152w4096chunk env BENCH_ITERS=3 \
    BENCH_TLM_LOSS_CHUNK=4096 python bench.py \
    --network transformer_lm --batch 1 --seq-len 49152 --window 4096 \
    --remat
[ -s "$LCTX" ] && mv "$LCTX" "$OUT/longcontext.jsonl" || rm -f "$LCTX"

echo "== 3d2. embedding-grad formulation (scatter vs segsum vs matmul) =="
# BENCH_EMBGRAD_MODEL=1 adds the whole-model A/B (two bench.py runs):
# the round-5 lesson is that micro wins routinely lose at model level,
# so the staged capture must carry both or it cannot decide the knob
cap "$OUT/embgrad_micro.jsonl" embgrad_micro \
    env BENCH_EMBGRAD_MODEL=1 python benchmark/bench_embgrad.py

echo "== 3d. input-pipeline train overlap (net img/s with real decode) =="
cap "$OUT/pipeline_overlap.json" pipeline_overlap \
    python benchmark/bench_input_pipeline.py --train-overlap \
        --n 512 --batch-size 128 --threads 8

echo "== 4. raw-JAX controls (resnet-50 + the sub-30%-MFU nets) =="
CTRL="$OUT/raw_jax_control.txt.new"; : > "$CTRL"
capa "$CTRL" raw_jax_control python benchmark/raw_jax_resnet.py
capa "$CTRL" raw_jax_alexnet \
    python benchmark/raw_jax_controls.py --network alexnet
capa "$CTRL" raw_jax_inception \
    python benchmark/raw_jax_controls.py --network inception-v3
[ -s "$CTRL" ] && mv "$CTRL" "$OUT/raw_jax_control.txt" || rm -f "$CTRL"

echo "== 4b. serve engine offered-load sweep =="
# the modes of bench_serve.py that spawn replica processes (--replicas,
# --disagg, --controller) are not here: N processes cannot share a
# chip, so they run on the CPU only (bench_common.require_cpu_fleet)
cap "$OUT/serve.json" serve python bench_serve.py

echo "== 4b4. streaming + chunked-prefill A/B =="
# streamed frames vs one-shot (TTFT p50 <= 0.25x one-shot total at
# max_new >= 32) and chunked vs monolithic prefill under long-prompt
# load (inter-token p99 <= 0.5x) — docs/serving.md §streaming
cap "$OUT/serve_streaming.json" serve_streaming \
    python bench_serve.py --streaming

echo "== 4b5. speculative decoding A/B =="
# plain vs draft/verify continuous batching on one doctored target
# (effective inter-token p99 ratio < 1.0 and tokens per target
# forward > 1.5 at gamma=4, byte-identical output asserted) —
# docs/serving.md §speculative
cap "$OUT/serve_spec.json" serve_spec \
    python bench_serve.py --speculative

echo "== 4c. scaling sweep + GSPMD one-jit row =="
# single chip unless the host offers more (BENCH_SCALING_DEVICES=1,4
# under chiprun --chips 4); the gspmd row is the one-jit path of
# docs/parallelism.md "One-jit GSPMD path"
cap "$OUT/scaling.json" scaling \
    python bench_scaling.py --devices "${BENCH_SCALING_DEVICES:-1}"

echo "== 5. device trace + breakdown =="
python - <<'PY'
import os, sys
sys.path.insert(0, os.getcwd())
import numpy as np, jax
from mxnet_tpu.models import resnet
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.initializer import Xavier
sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                        image_shape=(3, 224, 224))
step = make_train_step(sym, optimizer="sgd",
                       optimizer_params={"momentum": 0.9,
                                         "rescale_grad": 1.0 / 128},
                       compute_dtype="bfloat16")
state = step.init_state(Xavier(), {"data": (128, 3, 224, 224),
                                   "softmax_label": (128,)})
b = step.place_batch({
    "data": np.zeros((128, 3, 224, 224), np.float32),
    "softmax_label": np.zeros((128,), np.float32)})
rng = jax.random.PRNGKey(0)
state, outs = step(state, b, 0.1, rng)          # compile
jax.block_until_ready(outs)
out = os.environ.get("OUT", "bench_out")
jax.profiler.start_trace(out + "/trace")
for _ in range(5):
    state, outs = step(state, b, 0.1, rng)
jax.block_until_ready(outs)
jax.profiler.stop_trace()
print("trace done")
PY
cap "$OUT/trace_summary.txt" trace_summary \
    python tools/xplane_summary.py "$OUT/trace"

# -- refresh summary: which captures this session replaced --------------
# Deduplicate (capa appends touch the same file repeatedly).
if [ ${#REFRESHED[@]} -gt 0 ]; then
  UNIQ=$(printf '%s\n' "${REFRESHED[@]}" | sort -u)
  echo "== refreshed captures this session =="
  printf '  %s\n' $UNIQ
else
  echo "== no captures refreshed (nothing installable this session) =="
fi

if [ ${#FAILED[@]} -gt 0 ]; then
  echo "== session FINISHED WITH FAILURES: ${FAILED[*]}; artifacts in $OUT =="
  exit 1
fi
echo "== session complete; artifacts in $OUT =="
