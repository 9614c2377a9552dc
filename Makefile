# paddle_tpu test entry points.
#
# lint    — tpulint trace-safety static analysis (paddle_tpu/analysis/).
#           Pure stdlib, no jax import, fast. Gates `test`.
# analyze — tpucheck jaxpr-level analysis (paddle_tpu/analysis/jaxpr/):
#           peak-memory liveness, collective/mesh consistency, donation,
#           roofline cost over the real entry points. Traces tiny
#           configs under JAX_PLATFORMS=cpu; gates `test` like lint.
# chaos   — the fault-injection suites: serving (ISSUE 6 — every named
#           injection point must isolate/retry/degrade, never crash
#           Engine.step()) and training (ISSUE 7 — kill/resume must be
#           bit-identical, no fault can commit a torn checkpoint).
#           CPU-safe, deterministic (seed-driven plans); gates `test`.
# test    — the virtual-8-CPU-device suite (mesh/sharding logic, kernel
#           math in interpret mode). Safe anywhere.
# chip-smoke — the quickest proof the system starts on the chip: serving at
#           llama2_7b() widths over HTTP and five GPT-medium train steps,
#           each checked against a reference (chip_smoke.py). Needs one TPU
#           chip and fails without one; from a sandbox that has none, run
#           it through the chip tool (`--chips 4`, to the tool and to the
#           script, for the sharded paths). Budget ~10 min, mostly compiles.
# onchip  — the real-TPU lane (VERDICT r3 #4): Pallas kernels through
#           Mosaic (non-interpret) + PJRT memory tests. Needs the chip, and
#           the chip belongs to ONE process: run one lane at a time.

lint:
	python tools/lint_tpu.py paddle_tpu examples tools --fail-on-violation

# races — tpurace cross-module thread-ownership analysis (ISSUE 19):
#         discover thread domains (engine / kv-spill worker / router
#         monitor / SSE readers / asyncio), check per-class attribute
#         write sets across them (TPL1501-TPL1504), fail on any live
#         finding — and on suppression creep past the audited count.
#         Pure stdlib, no jax import; gates `test` like lint.
races:
	python tools/race_tpu.py paddle_tpu --fail-on-violation \
		--max-suppressions 8

analyze:
	JAX_PLATFORMS=cpu python tools/analyze_tpu.py --fail-on-violation \
		--mesh 1 --mesh 4 --mesh 8

# plan — tpuplan autosharding planner (ISSUE 16): plan every meshable
#        registry entry at mesh 4 and 8, fail if any entry ends with no
#        feasible plan, if a chosen plan would cost more than the
#        hand-written specs under the calibrated model, if any winner
#        trips the TPC501/502/503 self-audit, or if a plan drifts from
#        the committed goldens (tests/fixtures/plan/). Gates `test`.
plan:
	JAX_PLATFORMS=cpu python tools/plan_tpu.py --mesh 4 --mesh 8 \
		--fail-on-audit --check-goldens tests/fixtures/plan

chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fault_tolerance.py \
		tests/test_train_resilience.py tests/test_prefix_cache.py \
		tests/test_chunked_prefill.py tests/test_tp_serving.py \
		tests/test_moe_serving.py tests/test_multi_step.py \
		tests/test_api_server.py tests/test_replica_failover.py \
		tests/test_integrity.py tests/test_kv_tier.py \
		tests/test_tracing.py tests/test_ownership.py \
		tests/test_cluster_serving.py -q

# chaos-serve — the multi-replica failover suite alone (ISSUE 13):
# SIGKILL/poison a replica mid-stream, assert every client stream
# completes bit-identically with zero failed requests. Subset of
# `chaos`, split out because the subprocess cases are the slowest
# chaos lane and iterate independently.
chaos-serve:
	JAX_PLATFORMS=cpu python -m pytest tests/test_replica_failover.py -q

# chaos-integrity — the silent-data-corruption suite alone (ISSUE 14):
# every bit-flip-* fault point must be DETECTED (digest/checksum/shadow
# probes), no injected corruption may ever produce a wrong delivered
# token (streams bit-identical to uninjected runs after containment),
# checkpoint restore must fall back to the newest verifying step, and a
# weight-audit failure must drain the replica via /readyz with zero
# failed requests. Subset of `chaos`.
chaos-integrity:
	JAX_PLATFORMS=cpu python -m pytest tests/test_integrity.py -q

# chaos-tier — the tiered-KV-cache suite alone (ISSUE 15): streams must
# be bit-identical tier-on vs tier-off across greedy/sampled/spec/
# chunked/preemption, a demote/promote round trip must preserve page
# bytes exactly, kv-spill-corrupt must checksum-fail into invalidate +
# recompute-as-miss, and slow-host-copy must degrade hits to misses
# without stalling the engine. Subset of `chaos`.
chaos-tier:
	JAX_PLATFORMS=cpu python -m pytest tests/test_kv_tier.py -q

serve-smoke:
	JAX_PLATFORMS=cpu python \
		examples/serve_llama_paged.py --tiny --api-port 0 --api-smoke \
		--multi-step 2 --tenant-weights "interactive=4,batch=1"

# trace-smoke — end-to-end tracing surface (ISSUE 18): serve the tiny
# demo with --trace on, dump the ring snapshot, convert it to Chrome
# trace-event JSON through tools/trace_tpu.py, and validate the result
# round-trips (non-empty, phase-correct events). Gates `test`.
trace-smoke:
	JAX_PLATFORMS=cpu python \
		examples/serve_llama_paged.py --tiny --trace on \
		--trace-dump /tmp/paddle_tpu_trace_snap.json
	python tools/trace_tpu.py \
		--from-file /tmp/paddle_tpu_trace_snap.json \
		--out /tmp/paddle_tpu_trace_chrome.json
	python tools/trace_tpu.py --check /tmp/paddle_tpu_trace_chrome.json

test: lint races analyze plan chaos trace-smoke
	python -m pytest tests/ -x -q --ignore=tests/onchip

chip-smoke:
	python3 chip_smoke.py

onchip:
	PADDLE_TPU_ONCHIP=1 python -m pytest tests/onchip -q -rs

.PHONY: lint races analyze plan chaos chaos-serve chaos-integrity \
	chaos-tier serve-smoke trace-smoke test chip-smoke onchip
