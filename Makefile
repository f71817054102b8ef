# Developer / CI targets.  `make verify` is the PR gate: tier-1 tests
# plus the graph-invariant linter (wtf_tpu/analysis) — both CPU-only.
# `make mesh-smoke` is the fast end-to-end check of the mesh campaign
# driver (wtf_tpu/meshrun) on a forced 8-device CPU mesh; run it when
# touching the sharded executors or the --mesh-devices path.

PY ?= python

.PHONY: verify test lint lint-rebaseline slow mesh-smoke chaos-smoke \
	triage-smoke tenancy-smoke fleet-smoke device-chaos-smoke \
	decode-smoke obs-smoke

verify: test lint chaos-smoke triage-smoke tenancy-smoke fleet-smoke \
	device-chaos-smoke decode-smoke obs-smoke

# tier-1 (the ROADMAP.md command without the driver's log plumbing)
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# hot-path contract lint: fails (exit 1) on ANY finding.  JSON output so
# CI logs carry the kernel counts + finding provenance machine-readably.
# All families run, including the dataflow contract trio
# (state/transfer/thread) and the contracts.json hygiene family; the
# jaxpr host-transfer census rides the budget family's traces for free,
# so --deep is only needed when filtering the budget family out.
lint:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.analysis --json --deep

# re-pin analysis/budgets.json AND analysis/contracts.json after a PR
# that legitimately changes the step ladder's kernel count or the
# contract surfaces — record the why in PERF.md (rounds 9 and 21).
# Both files ratchet: growth requires --allow-regression.
lint-rebaseline:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.analysis --rebaseline

slow:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m slow \
		-p no:cacheprovider

# fast forced-8-device mesh campaign smoke: the whole
# `campaign --mesh-devices N --mutator devmangle` path (shard_map
# executors, on-chip coverage merge, device mutation per shard) in one
# process with no hardware
mesh-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m wtf_tpu campaign --name demo_tlv --mesh-devices 8 \
		--mutator devmangle --lanes 16 --runs 32 --limit 20000 --seed 7

# batched-triage smoke (wtf_tpu/testing/triage_smoke): tiny demo_tlv
# minimize + distill through the real CLI — the seeded crasher must
# shrink to the known-minimal reproducer of the SAME crash bucket, and
# the distilled minset must be a corpus subset with full coverage
triage-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.triage_smoke

# multi-tenant smoke (wtf_tpu/testing/tenancy_smoke): a mixed
# demo_tlv+demo_kernel batch must be bit-identical per tenant to the
# same campaigns run alone, and the `wtf-tpu sched` preemption drill
# (checkpoint tenant A, backfill with B, resume A) must end
# bit-identical to an uninterrupted run
tenancy-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.tenancy_smoke

# fleet-tier soak (wtf_tpu/testing/fleet_smoke): 64 simulated clients
# over the real WTF2/WTF3 wire with scripted frame drops + resets —
# zero lost testcases, aggregate coverage byte-identical to a serial
# replay, coverage wire bytes >=10x smaller than whole-bitmap
# exchange, store fsck clean
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.fleet_smoke

# deterministic fault-tolerance soak (wtf_tpu/testing/chaos_smoke):
# seeded fault schedule over the real socket + checkpoint seams —
# >=1 reconnect, >=1 reclaim, >=1 torn-checkpoint .prev fallback, zero
# lost testcases, bit-identical kill/resume parity.  Exit 0 = all held.
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.chaos_smoke

# self-healing device runtime soak (wtf_tpu/testing/device_chaos_smoke):
# scripted device hang/error/poison against the supervised dispatch
# seams — >=1 watchdog fire, >=1 ladder degradation + re-promotion,
# >=1 quarantined lane, and every recovery bit-identical to the
# fault-free run (coverage, edge bytes, corpus digests, crash buckets)
device-chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.device_chaos_smoke

# zero-host steady-state smoke (wtf_tpu/testing/decode_smoke): a
# cold-cache --device-decode demo_tlv campaign must finish its
# megachunk windows with ZERO host decode services, a clean
# device-vs-host cross-check, >=1 adopted pipelined-harvest prelaunch,
# and stay bit-identical to the host-serviced reference
decode-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.decode_smoke

# observability smoke (wtf_tpu/testing/obs_smoke): a real master + 4
# WTF3 sim clients under scripted faults and re-sent TAG_TELEM frames —
# the fleet aggregate must be byte-equal to the serial sum of node
# snapshots — plus one campaign run producing a schema-valid Chrome
# trace (>=1 fenced device span, >=1 megachunk window) and a rendering
# `wtf-tpu status` surface
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) -m wtf_tpu.testing.obs_smoke
