# Convenience targets mirroring the reference's per-variant makefiles
# (fortran/*/makefile: main/init/out/clean) in one place.

PY ?= python
# tier1 needs pipefail (a dash /bin/sh has no `set -o pipefail`)
SHELL := /bin/bash

.PHONY: test tier1 chaos race lint check audit bench bench-all bench-smoke chip-check \
        weak-scaling collective-overhead exchange-lab sharded3d-check \
        compile-bisect topology-schedule topology-validate \
        serve-lab serve-chaos-lab frontend-lab trace-lab prof-lab \
        numerics-lab steady-lab lane-lab mega-lab resume-lab fleet-lab \
        resilience-lab cache-lab perfcheck native run viz clean smoke

test:
	$(PY) -m pytest tests/ -q

tier1:          # the ROADMAP.md tier-1 verify command, verbatim semantics
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

chaos:          # the full-fidelity chaos suite tier-1 deselects (slow
                # marker): supervisor crash-resume e2e over real 2-process
                # worlds + the serve per-lane fault-domain e2e
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -q -m slow \
	  -p no:cacheprovider

race:           # the dynamic race sanitizer over the chaos + serving
                # e2e surface (ISSUE 14): every scheduler/writer/tracer/
                # gateway wave re-run with HEAT_TPU_RACECHECK=1 armed —
                # a cross-thread write with an empty candidate lockset
                # raises RaceError and fails the suite
	env JAX_PLATFORMS=cpu HEAT_TPU_RACECHECK=1 $(PY) -m pytest \
	  tests/test_chaos.py tests/test_serve.py tests/test_gateway.py \
	  tests/test_fleet.py tests/test_solvecache.py -q -p no:cacheprovider

lint:           # ruff when installed; syntax-level fallback otherwise
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
	  $(PY) -m ruff check heat_tpu tests benchmarks; \
	elif command -v ruff >/dev/null 2>&1; then \
	  ruff check heat_tpu tests benchmarks; \
	else \
	  echo "lint: ruff not installed — falling back to compileall syntax check"; \
	  $(PY) -m compileall -q heat_tpu tests benchmarks; \
	fi

check: lint     # the invariant gate (ISSUE 11 + 13 + 14): generic lint
                # + the project-native analyzer (hot-path purity, lock
                # discipline, traced determinism, Mosaic kernel safety,
                # race lockset/guard-map) + the record-schema and
                # guard-map drift gates — all in heat-tpu check — plus
                # the fast tier of the program auditor (digest /
                # donation / purity / budget contracts over traced
                # jaxprs; full audit = `make audit`)
	$(PY) -m heat_tpu check
	env JAX_PLATFORMS=cpu $(PY) -m heat_tpu audit --fast

audit:          # the full program auditor (ISSUE 13): every registered
                # family traced to jaxpr + AOT StableHLO on abstract
                # inputs (no device) and gated on all five contract
                # families, dtype discipline and roofline extraction
                # included
	env JAX_PLATFORMS=cpu $(PY) -m heat_tpu audit

bench:
	$(PY) bench.py

bench-all:
	$(PY) benchmarks/run_all.py

chip-check:
	$(PY) benchmarks/chip_check.py

bench-smoke:
	$(PY) benchmarks/run_all.py --smoke

weak-scaling:
	$(PY) benchmarks/weak_scaling.py --virtual 8

collective-overhead:   # measured anchor for the weak-scaling projection
	$(PY) benchmarks/collective_overhead.py

exchange-lab:          # where does the per-exchange cost go (HLO census)
	$(PY) benchmarks/exchange_lab.py

sharded3d-check:       # 512^3 sharded fuse-depth no-regression
	$(PY) benchmarks/sharded3d_check.py

compile-bisect:        # fuse-depth compile-time curve (on chip)
	$(PY) benchmarks/compile_bisect.py

# the chipless labs: AOT topology compile, no chip involved
topology-schedule:     # multi-chip schedule census of the sharded advance
	$(PY) benchmarks/topology_schedule.py

topology-validate:     # cross-chip machine-model compile validation
	$(PY) benchmarks/topology_validate.py

serve-lab:             # serving A/B: dispatch-ahead vs sync fallback vs
                       # sequential solos (boundary-wait + device-idle est.)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_lab.py

serve-chaos-lab:       # serving chaos A/B: clean wave vs ~10% lane-nan
                       # poisoned (quarantine cost on healthy tenants)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_chaos_lab.py

frontend-lab:          # online front-end A/B: Poisson arrivals, EDF vs
                       # FIFO deadline-hit rate + policy-layer cost check
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_frontend_lab.py

trace-lab:             # tracing-overhead A/B: off vs flight-recorder vs
                       # full --trace on the serve_lab wave (<= 2% gate)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/trace_overhead_lab.py

prof-lab:              # observatory-overhead A/B: full cost-model/ledger/
                       # watermark/burn-rate metering vs off (<= 2% gate,
                       # npz bit-identity at depths 0 and 2)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/prof_overhead_lab.py

numerics-lab:          # numerics-observatory A/B: boundary-vector stats
                       # ingestion vs off (<= 2% gate, npz bit-identity at
                       # depths 0 and 2, live-gateway probe verification)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/numerics_overhead_lab.py

steady-lab:            # semantic-scheduling A/B: until=steady early exit
                       # vs fixed-step (>= 1.5x effective throughput gate;
                       # steady + co-lane bit-identity, zero added D2H)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_steady_lab.py

lane-lab:              # serve lane-kernel A/B: Pallas lane program vs XLA
                       # lane program vs solo Pallas drives (bit-identity
                       # hard gate; perf gate on TPU, informational on CPU)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_lane_kernel_lab.py
	env JAX_PLATFORMS=cpu $(PY) benchmarks/lane_kernel_compile_check.py

mega-lab:              # two-tier placement A/B (virtual 8-device mesh):
                       # oversized requests served as sharded mega-lanes,
                       # npz byte-identity vs solo sharded drive, packed
                       # throughput within 10% with a mega-lane resident
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_mega_lab.py

resume-lab:            # zero-downtime serving A/B: uninterrupted wave vs
                       # kill-at-50%-then-resume (npz byte-identity over
                       # all 64 requests, zero re-stepped chunks, recovery
                       # overhead = one manifest load + lane reseed)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_resume_lab.py

fleet-lab:             # pod-scale fleet: 1/2/4 serve subprocesses behind
                       # the router (>= 1.7x at 2 backends, monotone at
                       # 4), SIGKILL drill with zero lost/duplicated
                       # requests, forced checkpoint-handoff steal with
                       # recovery overhead recorded
	env JAX_PLATFORMS=cpu $(PY) benchmarks/fleet_lab.py

resilience-lab:        # fleet resilience drills: flapping backend (breaker
                       # + canary re-admission, availability >= 0.99, p99
                       # <= 1.5x, zero steal thrash), mid-stream cut with
                       # exactly-once re-drive, hedged interactive tail,
                       # deadline shedding with zero billed device steps
	env JAX_PLATFORMS=cpu $(PY) benchmarks/fleet_resilience_lab.py

cache-lab:             # solve-cache A/B: repeat-heavy wave cold vs warm
                       # (warm >= 5x, full hits byte-identical + zero
                       # device dispatch, prefix steps exactly the delta,
                       # --cache off bit-identical)
	env JAX_PLATFORMS=cpu $(PY) benchmarks/serve_cache_lab.py

perfcheck:             # CI perf gate: fresh prof-lab vs committed baseline
                       # (tolerance band) + every committed lab's internal
                       # gates + cost-model-vs-calibration cross-check
	env JAX_PLATFORMS=cpu $(PY) -m heat_tpu perfcheck

smoke:                 # bring-up smoke on the chip: run + serve, checked
	chiprun -- $(PY) chip_smoke.py

native:
	$(MAKE) -C heat_tpu/io/native

run:            # ≙ the reference's `make main && ./a.out`
	$(PY) -m heat_tpu run

viz:            # ≙ the reference's `make out` (plot soln.dat)
	$(PY) -m heat_tpu viz soln.dat

clean:
	rm -rf __pycache__ .pytest_cache checkpoints
	$(MAKE) -C heat_tpu/io/native clean
