#!/usr/bin/env bash
# CI entrypoint: static analysis first, then the telemetry trace smoke,
# then the 8-process kvstore bucket/overlap smoke, then the serving smoke,
# then the elastic fault-tolerance chaos smoke, then the tier-1 test suite.
#
# Step 1 dogfoods the graphlint subsystem on every bundled model (the
# acceptance gate: every model must lint with zero error-severity
# diagnostics), then runs the graph-rewrite gate: the zoo sweep under
# MXNET_GRAPHREWRITE=verify (zero GL601/602/604, transformer node-count
# reduction), the 3-model raw-vs-rewritten bit-parity subcheck (tests/nightly/rewrite_parity.py),
# and the GL7xx dispatch-discipline gates: the zoo mesh sweep must carry
# zero GL7xx findings while the `graphlint --dispatch` source scan must
# keep flagging the known kv_decode host-sync sites — present AND waived
# since the lax.scan decode megastep became the default K-amortized
# shape, leaving only acknowledged K=1 tails — with everything outside
# kv_decode waived. Step 2 lints the sources with
# ruff when installed (pinned rule set: ruff.toml) and otherwise with the
# dependency-free tools/src_lint.py fallback — always-on either way; the
# every-source-compiles floor is additionally enforced by
# tests/test_graphlint.py::test_package_sources_compile.
# Step 3 runs a tiny fit loop under MXNET_TELEMETRY=trace,
# dumps the chrome trace, and gates it with tools/mxtrace --check
# (docs/OBSERVABILITY.md — the telemetry dump is a machine contract, so CI
# smokes it end to end). Step 4 runs the 8-process CPU kvstore smoke
# (tests/nightly/dist_kvstore_overlap.py): bucket-plan overlap counters
# during a Module.fit, sharded-vs-replicated weight parity, and the
# bucketed allreduce bandwidth floor (docs/PERF.md §11).
# Step 5 runs the 2-process recommender sparse-kvstore smoke
# (tests/nightly/dist_sparse_kvstore.py, docs/SPARSE.md): a sparse-push fit
# must be weight-parity (atol 1e-6) with a dense-push control while moving
# strictly fewer wire bytes (kvstore.bytes.sparse < the control's
# allreduce bytes), plus the budget-armed autoplan gate: the 8-device plan
# for the recommender must shard an embedding table over the model axis.
# Step 6 runs the serving engine smoke (tools/serve_bench.py --check):
# QPS/p99 under a tiny open-loop load with zero post-warmup retraces, for
# both the bucketed engine and the transformer KV-cache decode path
# including the K=8 decode-megastep leg (token-identical parity +
# host-gap-per-token >=2x drop, docs/SERVING.md §Megasteps), the
# shared-prefix cache + speculative-decoding smoke (--workload
# zipf-prefix: hit rate, bitwise cached-vs-cold admits, spec-vs-greedy
# token parity and p50), plus the serving CHAOS smoke (--chaos): deterministic
# fault injection on the dispatch path + a mid-run hitless weight reload,
# gated on zero hung futures, zero retraces, and recovery to `healthy`
# (docs/RESILIENCE.md).
# Step 7 runs the serving FLEET chaos smoke (serve_bench --fleet,
# docs/SERVING.md §Fleet): open-loop load through the replica router over
# 4 replica processes with injected dispatch faults, a mid-run replica
# SIGKILL (supervised restart), and a mid-run fleet-wide hitless rollout —
# gated on zero hung/lost requests, aggregate QPS above the single-replica
# closed-loop baseline and recovery to healthy.
# Step 8 runs the elastic fault-tolerance chaos smoke
# (tests/nightly/dist_elastic_chaos.py --orchestrate): an 8-process
# Module.fit in sharded-update mode with periodic async checkpoints, one
# worker killed mid-run — the survivors must re-form to 7, reseed from the
# sharded checkpoint, resume, and reach weight parity with an uninterrupted
# 7-process control run; it also asserts checkpoint.inflight was observed
# > 0 mid-fit, i.e. the async write really overlapped the step
# (docs/FAULT_TOLERANCE.md).
# Step 9 is the repo's tier-1 pytest command (ROADMAP.md).
set -uo pipefail
cd "$(dirname "$0")/.."

echo "== [1/9] graphlint: all bundled models (plain + sharding-plan sweep) =="
JAX_PLATFORMS=cpu python tools/graphlint --all-models --min-severity warning \
    || { echo "graphlint FAILED"; exit 1; }
# the same zoo under an abstract dp=8,model=2 mesh: the GL4xx sharding-plan
# lint and GL5xx memory planner must run the whole sweep clean of errors AND
# produce a finite peak-HBM estimate for every model (docs/static_analysis.md)
MESH_SWEEP="$(mktemp /tmp/graphlint_mesh_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu python tools/graphlint --all-models --mesh dp=8,model=2 \
    --format json > "$MESH_SWEEP" \
    || { echo "graphlint mesh sweep FAILED"; rm -f "$MESH_SWEEP"; exit 1; }
python - "$MESH_SWEEP" <<'PYEOF' || { echo "mesh sweep peak-HBM gate FAILED"; rm -f "$MESH_SWEEP"; exit 1; }
import json, math, sys
payload = json.load(open(sys.argv[1]))
assert payload, "empty mesh sweep"
bad = []
for entry in payload:
    plan = entry.get("memory_plan")
    peak = plan and plan["per_device"]["peak"]
    if not peak or not math.isfinite(peak) or peak <= 0:
        bad.append(entry["target"])
assert not bad, "models without a finite peak-HBM estimate: %s" % bad
# GL7xx dispatch-discipline zoo gate (docs/static_analysis.md §GL7xx):
# every bundled model's graph must lint clean of dispatch findings — the
# known host-sync sites live in serving/kv_decode.py, not in any model
gl7 = sorted({(e["target"], d["code"]) for e in payload
              for d in e["diagnostics"] if d["code"].startswith("GL7")})
assert not gl7, "zoo models with GL7xx dispatch findings: %s" % gl7
peaks = [e["memory_plan"]["per_device"]["peak"] / 2**30 for e in payload]
print("mesh sweep OK: %d models, peak-HBM %.3f..%.3f GiB/device, "
      "zero GL7xx" % (len(payload), min(peaks), max(peaks)))
PYEOF
rm -f "$MESH_SWEEP"
# auto-parallel planner sweep (docs/PARALLEL_PLANNER.md): every zoo model at
# 8 abstract devices must receive a budget-feasible ParallelPlan (or an
# explicit structured infeasibility reason — a planner CRASH is the failure
# mode this gates); the transformer's planner-chosen plan must additionally
# predict no more comm bytes than the naive all-dp plan
AUTOPLAN_SWEEP="$(mktemp /tmp/graphlint_autoplan_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu python tools/graphlint --autoplan --all-models \
    --mesh-devices 8 --format json > "$AUTOPLAN_SWEEP" \
    || { echo "graphlint autoplan sweep FAILED"; rm -f "$AUTOPLAN_SWEEP"; exit 1; }
python - "$AUTOPLAN_SWEEP" <<'PYEOF' || { echo "autoplan sweep gate FAILED"; rm -f "$AUTOPLAN_SWEEP"; exit 1; }
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload, "empty autoplan sweep"
bad, n_pipe = [], 0
for entry in payload:
    plan = entry.get("autoplan")
    if plan is None:
        bad.append("%s: planner error: %s"
                   % (entry["target"], entry.get("plan_error")))
    elif not plan["feasible"] and not plan.get("reason"):
        bad.append("%s: infeasible with NO structured reason"
                   % entry["target"])
    elif plan["pipeline_stages"] > 1:
        n_pipe += 1
assert not bad, "autoplan gate: %s" % "; ".join(bad)
tf = next(e["autoplan"] for e in payload if e["target"] == "transformer")
chosen, naive = tf["predicted"]["comm_bytes"], tf["naive"]["comm_bytes"]
assert chosen <= naive, \
    "transformer: planner comm %d B > naive all-dp %d B" % (chosen, naive)
print("autoplan sweep OK: %d models planned (%d pipelined); transformer "
      "comm %.2f MiB vs naive %.2f MiB"
      % (len(payload), n_pipe, chosen / 2**20, naive / 2**20))
PYEOF
rm -f "$AUTOPLAN_SWEEP"
# graph-rewrite gate (docs/static_analysis.md §GL6xx): the whole zoo must
# rewrite + verify under MXNET_GRAPHREWRITE=verify with ZERO GL601/602/604,
# and the transformer must show real gains — nodes merged/removed > 0 (the
# sloppy-frontend LN contract, models/transformer.py). The JSON dump is
# the committed CI record of the per-model rewrite plans.
REWRITE_SWEEP="$(mktemp /tmp/graphlint_rewrite_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu MXNET_GRAPHREWRITE=verify \
python tools/graphlint --all-models --rewrite --format json \
    > "$REWRITE_SWEEP" \
    || { echo "graphlint rewrite sweep FAILED"; rm -f "$REWRITE_SWEEP"; exit 1; }
python - "$REWRITE_SWEEP" <<'PYEOF' || { echo "rewrite sweep gate FAILED"; rm -f "$REWRITE_SWEEP"; exit 1; }
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload, "empty rewrite sweep"
bad = []
for entry in payload:
    if "rewrite" not in entry:
        bad.append("%s: %s" % (entry["target"],
                               entry.get("rewrite_error")
                               or entry.get("load_error")))
        continue
    codes = [d["code"] for d in entry["verify"]["diagnostics"]
             if d["code"] in ("GL601", "GL602", "GL604")]
    if codes:
        bad.append("%s: %s" % (entry["target"], codes))
assert not bad, "rewrite verify errors: %s" % "; ".join(bad)
tf = next(e for e in payload if e["target"] == "transformer")
c = tf["rewrite"]["counts"]
assert c["merged"] + c["removed"] + c["folded"] > 0, c
print("rewrite sweep OK: %d models verified; transformer %d->%d nodes"
      % (len(payload), tf["rewrite"]["nodes_before"],
         tf["rewrite"]["nodes_after"]))
PYEOF
rm -f "$REWRITE_SWEEP"
# bit-parity subcheck on 3 representative models: forward must be BITWISE
# identical raw-vs-rewritten, backward bitwise (atol 1e-6 where CSE's
# cotangent reassociation applies) — docs/static_analysis.md §GL6xx
JAX_PLATFORMS=cpu python tests/nightly/rewrite_parity.py \
    || { echo "rewrite bit-parity gate FAILED"; exit 1; }
# GL7xx dispatch-discipline source gate (docs/static_analysis.md §GL7xx):
# the scan over the serving surface must keep FINDING the known kv_decode
# host-sync sites (GL701 in both greedy decode loops — these are now the
# acknowledged K=1 TAILS of the megastep path and carry waivers naming
# the lax.scan megastep as the K-amortized shape, so every kv_decode
# GL701 must be BOTH present and waived: a refactor that silently stops
# detecting them fails here, and so does a new unwaived host sync),
# while every serve_bench/bench finding stays waived.  Exit 1 (live
# findings) is expected — only exit 2 (unreadable target) hard-fails the
# scan itself.
DISPATCH_SCAN="$(mktemp /tmp/graphlint_dispatch_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu python tools/graphlint --dispatch --format json \
    > "$DISPATCH_SCAN"
DISPATCH_RC=$?
if [ "$DISPATCH_RC" -ge 2 ]; then
    echo "graphlint --dispatch FAILED (exit $DISPATCH_RC)"
    rm -f "$DISPATCH_SCAN"; exit 1
fi
python - "$DISPATCH_SCAN" <<'PYEOF' || { echo "dispatch source gate FAILED"; rm -f "$DISPATCH_SCAN"; exit 1; }
import json, sys
payload = json.load(open(sys.argv[1]))
sites = payload["sites"]
kv = [s for s in sites if s["file"].endswith("serving/kv_decode.py")]
gl701 = {s["function"] for s in kv if s["code"] == "GL701"}
need = {"PagedKVDecoder.greedy"}
assert need <= gl701, \
    "kv_decode GL701 anchors missing: %s (got %s)" % (need - gl701, gl701)
# re-anchored for the megastep era: the megastep lax.scan is the default
# scan-clean decode shape, so every REMAINING kv_decode host sync must be
# a deliberately waived K=1 tail — an unwaived GL701 here is a regression
unwaived = [(s["function"], s["line"]) for s in kv
            if s["code"] == "GL701" and not s["waived"]]
assert not unwaived, \
    "unwaived kv_decode GL701 host syncs (megastep tails must carry " \
    "waivers): %s" % unwaived
bad = [s for s in kv
       if s["line"] <= 0 or (s["code"] == "GL701" and not s["provenance"])]
assert not bad, "kv_decode sites without file:line provenance: %s" % bad
stray = [(s["code"], "%s:%d" % (s["file"], s["line"])) for s in sites
         if s not in kv and not s["waived"]]
assert not stray, "unwaived dispatch findings outside kv_decode: %s" % stray
n_waived = sum(1 for s in sites if s["waived"])
print("dispatch source gate OK: %d sites (%d waived); kv_decode anchors %s"
      % (len(sites), n_waived, sorted(gl701)))
PYEOF
rm -f "$DISPATCH_SCAN"

# GL8xx concurrency repo gate (docs/static_analysis.md §GL8xx): the static
# lint over the threaded/distributed surface must be clean — every finding
# fixed or carrying a '# graphlint: waive GL80x -- reason'. Exit 1 means an
# unwaived finding (a new rank-divergent collective, unguarded shared
# attribute, lock-order cycle, or blocking-while-locked site) slipped in.
JAX_PLATFORMS=cpu python tools/graphlint --concurrency --format json \
    > /dev/null \
    || { echo "graphlint --concurrency FAILED (unwaived GL8xx)"; exit 1; }
echo "concurrency source gate OK (zero unwaived GL8xx)"

echo "== [2/9] source lint (pinned ruff, src_lint.py fallback — always on) =="
# the rule set is pinned in ruff.toml; when ruff is absent (the CI image
# ships no third-party linters and must not pip install) the
# dependency-free tools/src_lint.py enforces the same codes, so this step
# GATES unconditionally — there is no skip branch any more
if command -v ruff >/dev/null 2>&1; then
    ruff check mxnet_tpu/ tools/ bench.py || { echo "ruff FAILED"; exit 1; }
else
    python tools/src_lint.py mxnet_tpu tools tools/graphlint tools/mxtrace \
        bench.py || { echo "src_lint fallback FAILED"; exit 1; }
fi

echo "== [3/9] telemetry: trace-on fit smoke + mxtrace schema gate =="
TRACE_DIR="$(mktemp -d /tmp/mxtrace_ci.XXXXXX)"
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu MXNET_TELEMETRY=trace \
python - "$TRACE_DIR" <<'PYEOF' || { echo "telemetry fit smoke FAILED"; rm -rf "$TRACE_DIR"; exit 1; }
import json, sys, os
import numpy as np
import mxnet_tpu as mx

tmp = sys.argv[1]
sym = mx.sym.Variable("data")
sym = mx.sym.Convolution(sym, kernel=(3, 3), pad=(1, 1), num_filter=8,
                         no_bias=True, name="conv1")
sym = mx.sym.BatchNorm(sym, name="bn1")
sym = mx.sym.Activation(sym, act_type="relu")
sym = mx.sym.Flatten(sym)
sym = mx.sym.FullyConnected(sym, num_hidden=16, name="fc1")
sym = mx.sym.Activation(sym, act_type="relu")
sym = mx.sym.FullyConnected(sym, num_hidden=4, name="fc")
sym = mx.sym.SoftmaxOutput(sym, name="softmax")
rs = np.random.RandomState(0)
it = mx.io.NDArrayIter(rs.rand(12, 3, 8, 8).astype("float32"),
                       rs.randint(0, 4, (12,)).astype("float32"),
                       batch_size=4)
mx.profiler.profiler_set_config(filename=os.path.join(tmp, "profile.json"))
mx.profiler.profiler_set_state("run")
mod = mx.mod.Module(sym, context=mx.cpu())
mod.fit(it, num_epoch=1, kvstore=mx.kv.create("local"),
        epoch_end_callback=mx.callback.do_checkpoint(os.path.join(tmp, "ck")))
mx.nd.waitall()
path = mx.profiler.dump_profile()
trace = json.load(open(path))
cats = {e.get("cat") for e in trace["traceEvents"] if e.get("ph") == "X"}
need = {"engine", "executor", "kvstore", "io"}
assert need <= cats, "missing span families: %s" % (need - cats)
c = trace["otherData"]["counters"]
assert c.get("executor.compile", 0) >= 1 and c.get("executor.cache_hit", 0) >= 1, c
assert len(trace["otherData"]["steps"]) == 3
print("telemetry fit smoke OK: %s (%d events)" % (path, len(trace["traceEvents"])))
PYEOF
python tools/mxtrace "$TRACE_DIR/profile.json" --check \
    || { echo "mxtrace --check FAILED"; rm -rf "$TRACE_DIR"; exit 1; }
rm -rf "$TRACE_DIR"

echo "== [4/9] kvstore: 8-process bucket/overlap smoke (docs/PERF.md §11) =="
# functional leg: overlap counters fire during Module.fit on the per-key
# priority path, and sharded-update weights bit-match replicated (atol 1e-6)
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/launch.py -n 8 --launcher local \
    python tests/nightly/dist_kvstore_overlap.py --skip-bandwidth \
    || { echo "kvstore overlap/parity smoke FAILED"; exit 1; }
# bandwidth leg (fresh processes, nothing else resident): the bucketed
# push+pull round-trip must stay >= the r05 scoreboard number (0.056 GB/s).
# One retry absorbs transient host load — the floor is a regression gate,
# not a record attempt.
BW_CMD=(python tools/launch.py -n 8 --launcher local
        python tests/nightly/dist_kvstore_overlap.py --only-bandwidth
        --size-mb 64 --iters 4 --min-gbps 0.056)
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu MXNET_KVSTORE_BUCKET_MB=16 \
"${BW_CMD[@]}" || {
    echo "kvstore bandwidth smoke below floor; retrying once...";
    JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu MXNET_KVSTORE_BUCKET_MB=16 \
    "${BW_CMD[@]}" || { echo "kvstore bandwidth smoke FAILED"; exit 1; }
}

echo "== [5/9] sparse kvstore: 2-proc recommender smoke (docs/SPARSE.md) =="
# sparse-push fit weight-parity with the dense-push control (atol 1e-6) AND
# kvstore.bytes.sparse strictly below the control's table allreduce bytes;
# both gates assert inside the script on every rank
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/launch.py -n 2 --launcher local --cpu-devices 1 \
    python tests/nightly/dist_sparse_kvstore.py \
    || { echo "sparse kvstore smoke FAILED"; exit 1; }
# budget-armed autoplan gate: with replicated tables over the HBM budget,
# the 8-device per-param search must shard an embedding table over the
# model axis and beat naive all-dp on predicted comm
SPARSE_PLAN="$(mktemp /tmp/graphlint_recsys_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu python tools/graphlint --autoplan recommender \
    --mesh-devices 8 --budget-gb 0.0625 --format json > "$SPARSE_PLAN" \
    || { echo "recommender autoplan FAILED"; rm -f "$SPARSE_PLAN"; exit 1; }
python - "$SPARSE_PLAN" <<'PYEOF' || { echo "recommender autoplan gate FAILED"; rm -f "$SPARSE_PLAN"; exit 1; }
import json, sys
plan = json.load(open(sys.argv[1]))[0]["autoplan"]
assert plan["feasible"], plan.get("reason")
assert plan["mesh"].get("model", 1) > 1, plan["mesh"]
tables = [n for n in ("user_embed_weight", "item_embed_weight")
          if any(plan["param_specs"].get(n, []))]
assert tables, "no embedding table sharded: %s" % plan["param_specs"]
chosen, naive = plan["predicted"]["comm_bytes"], plan["naive"]["comm_bytes"]
assert chosen < naive, "recommender: %d B >= naive %d B" % (chosen, naive)
print("recommender autoplan OK: mesh %s, sharded tables %s, comm %.2f KiB "
      "vs naive %.2f MiB" % (plan["mesh"], tables, chosen / 2**10,
                             naive / 2**20))
PYEOF
rm -f "$SPARSE_PLAN"

echo "== [6/9] serving: serve_bench smoke (docs/SERVING.md) =="
# tiny-model CPU serving smoke: sustained QPS > 0, finite p99, ZERO
# post-warmup retraces/compiles (the sealed executable-cache contract,
# gated via the GL201-203 guard + executor compile/cache-hit telemetry),
# and the serving.* span families present in the trace buffer
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/serve_bench.py --model mlp --qps 100 --duration 1 --check \
    || { echo "serve_bench engine smoke FAILED"; exit 1; }
# the kv-decode smoke includes the megastep leg (--megastep-k 8): K
# tokens per dispatch through the sealed lax.scan program, gated on
# token-identical parity with single-step greedy, zero post-warmup
# retraces, and host_gap_per_token at K=8 <= 0.5x the K=1 baseline
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/serve_bench.py --model transformer-decode --qps 16 \
    --duration 1 --rows 2 --megastep-k 8 --check \
    || { echo "serve_bench kv-decode smoke FAILED"; exit 1; }
# shared-prefix cache + speculative decoding smoke (docs/SERVING.md
# §Prefix cache & speculative decoding): zipf shared-prefix workload
# against the COW paged pool, gated on chunk hit rate > 0.5, prefill
# FLOPs saved > 0, BITWISE-identical cached-vs-cold admit logits,
# speculative greedy token-identical to plain greedy with accepted-draft
# rate > 0 and per-token p50 <= the non-speculative baseline, and zero
# post-warmup retraces/compiles across both legs
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/serve_bench.py --workload zipf-prefix --qps 20 \
    --duration 2 --check \
    || { echo "serve_bench prefix/speculative smoke FAILED"; exit 1; }
# serving chaos smoke (docs/RESILIENCE.md): open-loop load with seeded
# dispatch raises + delays injected (mxnet_tpu/faultinject.py) and one
# mid-run hitless reload(); the gate asserts zero hung futures (every
# request reaches a terminal state), zero post-warmup retraces/compiles,
# the reload applied, p99 of completed requests in bound, and the engine
# back to `healthy` once injection stops. MXNET_CONCLINT=witness arms the
# lock witness for the run: serve_bench additionally fails on any GL805
# (witnessed lock-order inversion / >threshold hold across a dispatch seam)
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu MXNET_CONCLINT=witness \
python tools/serve_bench.py --model mlp --chaos --qps 150 --duration 2 \
    --check \
    || { echo "serve_bench chaos smoke FAILED"; exit 1; }

echo "== [7/9] serving fleet: 4-replica router chaos smoke (docs/SERVING.md §Fleet) =="
# open-loop load through the Router over 4 replica PROCESSES with the
# seeded chaos plan: injected fleet.dispatch faults (re-dispatch path),
# one replica SIGKILLed mid-run (supervisor restart with capped backoff),
# and one mid-run fleet-wide hitless rollout. The gate asserts zero
# hung/lost requests (every request reaches a terminal state),
# completed>0, the rollout applied, the fleet back to healthy, aggregate
# QPS above the single-replica closed-loop baseline and p99 in bound.
# The same run also drives the fleet OBSERVABILITY plane
# (docs/OBSERVABILITY.md §Fleet): --check additionally gates the
# fleet.request histogram p50/p99 against client-side percentiles, the
# seeded 100%-fault burst tripping the SLO burn-rate gate (and clearing
# after recovery, with structured slo.violation/slo.clear events), and
# --trace-out writes the merged clock-aligned fleet chrome trace.
FLEET_TRACE="$(mktemp /tmp/fleet_trace_ci.XXXXXX.json)"
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tools/serve_bench.py --model mlp --fleet --fleet-replicas 4 \
    --qps 100 --duration 4 --check --trace-out "$FLEET_TRACE" \
    || { echo "serve_bench fleet smoke FAILED"; rm -f "$FLEET_TRACE"; exit 1; }
# the merged dump is a machine contract like the single-process one:
# mxtrace must schema-gate it, and at least one request chain must span
# >=2 processes (router pid + replica pid) joined by ONE trace_id
python tools/mxtrace "$FLEET_TRACE" --check \
    || { echo "mxtrace --check on merged fleet trace FAILED"; rm -f "$FLEET_TRACE"; exit 1; }
python tools/mxtrace "$FLEET_TRACE" --fleet >/dev/null \
    || { echo "mxtrace --fleet on merged fleet trace FAILED"; rm -f "$FLEET_TRACE"; exit 1; }
python - "$FLEET_TRACE" <<'PYEOF' || { echo "fleet trace cross-process gate FAILED"; rm -f "$FLEET_TRACE"; exit 1; }
import json, sys
trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
by_tid = {}
for ev in events:
    a = ev.get("args") or {}
    for tid in ([a["trace_id"]] if a.get("trace_id") else []) \
            + list(a.get("trace_ids") or []):
        by_tid.setdefault(tid, set()).add(ev.get("pid"))
cross = {t: sorted(p) for t, p in by_tid.items() if len(p) >= 2}
assert cross, "no trace_id joins spans from >=2 processes (%d traced)" \
    % len(by_tid)
pids = {ev.get("pid") for ev in events if ev.get("ph") == "X"}
assert len(pids) >= 2, "merged trace has spans from only %s" % pids
other = trace["otherData"]
assert other.get("merged") and other.get("fleet"), "otherData not merged"
print("fleet trace gate OK: %d events across %d pids, %d cross-process "
      "request chains" % (len(events), len(pids), len(cross)))
PYEOF
rm -f "$FLEET_TRACE"

echo "== [8/9] elastic: 8-proc chaos smoke (docs/FAULT_TOLERANCE.md) =="
# kill 1 of 8 workers mid-fit: survivors pause, re-form to 7, reseed from
# the sharded async checkpoint, resume — and must reach weight parity with
# an uninterrupted 7-proc control run; checkpoint.inflight must have been
# observed > 0 mid-fit (the async write overlaps the step)
CHAOS_DIR="$(mktemp -d /tmp/dist_elastic_chaos.XXXXXX)"
JAX_PLATFORMS=cpu MXNET_DEFAULT_CONTEXT=cpu \
python tests/nightly/dist_elastic_chaos.py --orchestrate "$CHAOS_DIR" \
    --world 8 \
    || { echo "elastic chaos smoke FAILED"; rm -rf "$CHAOS_DIR"; exit 1; }
rm -rf "$CHAOS_DIR"

echo "== [9/9] tier-1 tests =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
exit "${PIPESTATUS[0]}"
