#!/usr/bin/env bash
# PR gate: tier-1 tests + a short continuous-serving smoke so the
# paged-KV scheduler path is exercised on every change, plus a doc-link
# check so README.md / docs/*.md never reference a module path or CLI
# flag that no longer exists.  CI (.github/workflows/ci.yml) runs the
# same entry points, one job per lane.
#
#   tools/check.sh            # lint + docs + tier-1 + serving smoke
#   tools/check.sh --smoke    # serving smoke only (~60 s): engine
#                             # drivers + a live HTTP front door with
#                             # 2 engine-worker replicas (streamed
#                             # completion, /healthz, /metrics,
#                             # /metrics.json via repro.obs.validate),
#                             # then a chaos lane: REPRO_FAULTS-injected
#                             # worker latency, an overload burst that
#                             # must shed (429 + Retry-After), and a
#                             # SIGKILLed worker the fleet must survive
#                             # (breaker opens, requests fail over)
#   tools/check.sh --docs     # doc-link check only (<1 s)
#   tools/check.sh --lint     # ruff check + format check (skips with a
#                             # warning when ruff is not installed)
#   tools/check.sh --bench    # bench-regression gate: runs the key
#                             # serving_bench sections, writes
#                             # BENCH_PR10.json, fails on a >20%
#                             # regression vs the newest BENCH_*.json
#                             # (knob: BENCH_REGRESSION_PCT=<percent>)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

lint_check() {
    echo "== lint: ruff =="
    if ! command -v ruff >/dev/null 2>&1; then
        echo "lint: ruff not installed — skipping (CI's lint job runs it)"
        return 0
    fi
    ruff check src benchmarks tools tests examples
    # formatting is advisory: the codebase is hand-formatted (aligned
    # jax shapes); `ruff check` (E/W/F in pyproject.toml) is the gate
    ruff format --check src benchmarks tools tests examples \
        || echo "lint: ruff format differences (advisory, not a gate)"
    echo "lint: OK"
}

doc_check() {
    echo "== doc check: module paths and CLI flags =="
    local docs=(README.md docs/*.md) fail=0

    # 1. literal file paths like src/repro/serving/kv_pool.py
    for p in $(grep -hoE 'src/repro/[A-Za-z0-9_/.-]+\.py' "${docs[@]}" \
                   | sort -u); do
        if [[ ! -f "$p" ]]; then
            echo "doc-check: missing file referenced in docs: $p"
            fail=1
        fi
    done

    # 2. dotted module paths like repro.launch.serve (last component may
    #    be an attribute, so also accept the parent resolving)
    for m in $(grep -hoE '\brepro\.[a-z0-9_.]+[a-z0-9_]' "${docs[@]}" \
                   | sort -u); do
        local f="src/${m//./\/}" parent
        parent="$(dirname "$f")"
        if [[ ! -f "$f.py" && ! -d "$f" && ! -f "$parent.py" \
              && ! -d "$parent" ]]; then
            echo "doc-check: missing module referenced in docs: $m"
            fail=1
        fi
    done

    # 3. CLI flags like --prefill-chunk must appear in some source file
    #    under src/, benchmarks/, tools/ or chip_smoke.py (argparse /
    #    script flags)
    for flag in $(grep -hoE '(^|[^-])--[a-z][a-z0-9-]+' "${docs[@]}" \
                      | grep -oE '\-\-[a-z][a-z0-9-]+' | sort -u); do
        if ! grep -rqF -- "\"$flag\"" src benchmarks tools chip_smoke.py; then
            echo "doc-check: flag $flag in docs but not in any CLI"
            fail=1
        fi
    done

    if [[ "$fail" != 0 ]]; then
        echo "doc check: FAILED"
        return 1
    fi
    echo "doc check: OK"
}

if [[ "${1:-}" == "--docs" ]]; then
    doc_check
    exit 0
fi

if [[ "${1:-}" == "--lint" ]]; then
    lint_check
    exit 0
fi

if [[ "${1:-}" == "--bench" ]]; then
    echo "== bench-regression gate (serving_bench key sections) =="
    python tools/bench_gate.py run
    exit 0
fi

if [[ "${1:-}" != "--smoke" ]]; then
    lint_check
    doc_check
    echo "== tier-1: pytest =="
    python -m pytest -x -q
fi

echo "== serving smoke: continuous engine, tiny arch =="
python -m repro.launch.serve --arch qwen3-1.7b --engine continuous \
    --max-new 8 --max-running 4 --page-size 8 --prefill-chunk 16 \
    --warmup-steps 0
echo "== serving smoke: async engine, live submit/stream =="
python -m repro.launch.serve --arch qwen3-1.7b --engine async \
    --max-new 8 --max-running 4 --page-size 8 --prefill-chunk 16 \
    --warmup-steps 0
echo "== serving smoke: bucket baseline parity path =="
python -m repro.launch.serve --arch qwen3-1.7b --engine bucket \
    --max-new 8 --warmup-steps 0
echo "== serving smoke: quantized path (q4 weights, int8 KV pages) =="
python -m repro.launch.serve --arch qwen3-1.7b --engine async \
    --quant q4 --kv-dtype int8 --max-new 8 --max-running 4 \
    --page-size 8 --prefill-chunk 16 --warmup-steps 0
echo "== serving smoke: tensor-parallel paged engine (2 shards) =="
python -m repro.launch.serve --arch qwen3-1.7b --engine continuous \
    --tp-shards 2 --max-new 8 --max-running 4 --page-size 8 \
    --warmup-steps 0
echo "== serving smoke: observability exports (async, 2 shards) =="
OBS_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP"' EXIT
python -m repro.launch.serve --arch qwen3-1.7b --engine async \
    --tp-shards 2 --max-new 8 --max-running 4 --page-size 8 \
    --prefill-chunk 16 --warmup-steps 0 \
    --metrics-json "$OBS_TMP/metrics.json" --trace "$OBS_TMP/trace.jsonl"
python -m repro.obs.validate --metrics "$OBS_TMP/metrics.json" \
    --trace "$OBS_TMP/trace.jsonl" \
    --require-gauge kv_pool.pages_free:node,shard
echo "== serving smoke: self-speculative decoding (async, k=4) =="
python -m repro.launch.serve --arch qwen3-1.7b --engine async \
    --spec-decode 4 --max-new 8 --max-running 4 --page-size 8 \
    --prefill-chunk 16 --warmup-steps 0 \
    --metrics-json "$OBS_TMP/spec_metrics.json"
python -m repro.obs.validate --metrics "$OBS_TMP/spec_metrics.json" \
    --require-counter spec.accepted
echo "== serving smoke: http front door, router over 2 replicas =="
python -m repro.launch.serve --arch tiny --engine async --http \
    --replicas 2 --port 0 --port-file "$OBS_TMP/http.port" &
SERVE_PID=$!
for _ in $(seq 1 600); do
    [[ -s "$OBS_TMP/http.port" ]] && break
    if ! kill -0 "$SERVE_PID" 2>/dev/null; then
        echo "smoke: http serve exited before listening"
        exit 1
    fi
    sleep 0.5
done
[[ -s "$OBS_TMP/http.port" ]] || { echo "smoke: no port file"; exit 1; }
python - "$(cat "$OBS_TMP/http.port")" "$OBS_TMP/http_metrics.json" <<'PY'
import json
import sys
import urllib.request

port, out = int(sys.argv[1]), sys.argv[2]
base = f"http://127.0.0.1:{port}"
body = json.dumps({"prompt": list(range(1, 40)), "max_tokens": 4,
                   "stream": True}).encode()
req = urllib.request.Request(
    base + "/v1/completions", data=body,
    headers={"Content-Type": "application/json"})
toks = []
with urllib.request.urlopen(req, timeout=300) as resp:
    for line in resp:
        payload = line.strip()[5:].strip() \
            if line.startswith(b"data:") else None
        if payload is None or not payload:
            continue
        if payload == b"[DONE]":
            break
        ev = json.loads(payload)
        if "error" in ev:
            sys.exit(f"smoke: stream error: {ev['error']}")
        if "token" in ev:
            toks.append(ev["token"])
assert len(toks) == 4, f"smoke: wanted 4 streamed tokens, got {toks}"
health = json.load(urllib.request.urlopen(base + "/healthz", timeout=30))
assert health.get("status") == "ok", health
prom = urllib.request.urlopen(base + "/metrics", timeout=30).read()
assert b"http_requests" in prom and b"router_requests" in prom, prom[:300]
with urllib.request.urlopen(base + "/metrics.json", timeout=30) as r:
    open(out, "wb").write(r.read())
print(f"smoke: streamed {toks} over {base}")
PY
python -m repro.obs.validate --metrics "$OBS_TMP/http_metrics.json" \
    --require-gauge router.inflight:replica \
    --require-counter router.requests:replica
kill -TERM "$SERVE_PID"
wait "$SERVE_PID"
echo "== serving smoke: chaos lane (injected faults + SIGKILL) =="
# two replicas under injected 30ms/step worker latency, a 1-deep
# admission gate so a burst must shed, and a hair-trigger breaker so
# one worker SIGKILL opens it; the fleet must keep serving throughout
REPRO_FAULTS="step.latency_ms=30" \
python -m repro.launch.serve --arch tiny --engine async --http \
    --replicas 2 --port 0 --port-file "$OBS_TMP/chaos.port" \
    --breaker-threshold 1 --max-inflight 1 &
CHAOS_PID=$!
for _ in $(seq 1 600); do
    [[ -s "$OBS_TMP/chaos.port" ]] && break
    if ! kill -0 "$CHAOS_PID" 2>/dev/null; then
        echo "smoke: chaos serve exited before listening"
        exit 1
    fi
    sleep 0.5
done
[[ -s "$OBS_TMP/chaos.port" ]] || { echo "smoke: no chaos port"; exit 1; }
CHAOS_WORKER=$(pgrep -P "$CHAOS_PID" -f "repro.serving.worker" | head -1)
[[ -n "$CHAOS_WORKER" ]] || { echo "smoke: no worker to kill"; exit 1; }
python - "$(cat "$OBS_TMP/chaos.port")" "$CHAOS_WORKER" \
    "$OBS_TMP/chaos_metrics.json" <<'PY'
import json
import os
import signal
import sys
import threading
import urllib.error
import urllib.request

port, victim, out = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
base = f"http://127.0.0.1:{port}"


def post(prompt, timeout=300):
    """Blocked completion; returns (status, headers, body-dict)."""
    body = json.dumps({"prompt": prompt, "max_tokens": 4}).encode()
    req = urllib.request.Request(
        base + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"{}")


# 1. overload burst: 6 concurrent against a 1-deep gate under 30ms/step
#    injected latency — the extras must shed as 429 + Retry-After with
#    a structured, retryable error body
results = []
lock = threading.Lock()


def worker(i):
    r = post(list(range(1 + i, 30 + i)))
    with lock:
        results.append(r)


threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
[t.start() for t in threads]
[t.join() for t in threads]
sheds = [(h, b) for s, h, b in results if s == 429]
assert any(s == 200 for s, _h, _b in results), results
assert sheds, "smoke: 6-burst against --max-inflight 1 never shed"
for h, b in sheds:
    assert h.get("Retry-After"), f"smoke: 429 without Retry-After: {h}"
    err = b.get("error", {})
    assert err.get("type") == "Overloaded" and err.get("retryable"), b
print(f"smoke: chaos burst shed {len(sheds)}/6 with Retry-After")

# 2. SIGKILL one worker, then keep serving: the router must open the
#    breaker on the corpse and fail over — every request still succeeds
os.kill(victim, signal.SIGKILL)
for i in range(12):
    s, _h, b = post(list(range(40 + 3 * i, 70 + 3 * i)))
    assert s == 200, f"smoke: post-kill request {i} failed: {s} {b}"
    assert len(b["choices"][0]["tokens"]) == 4, b
print("smoke: 12/12 completions served across a SIGKILLed worker")

with urllib.request.urlopen(base + "/metrics.json", timeout=30) as r:
    open(out, "wb").write(r.read())
PY
python -m repro.obs.validate --metrics "$OBS_TMP/chaos_metrics.json" \
    --require-counter http.shed \
    --require-counter router.breaker_open
kill -TERM "$CHAOS_PID"
wait "$CHAOS_PID" || true
echo "check.sh: OK"
