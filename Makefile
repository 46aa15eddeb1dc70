# Build system (reference: Makefile — dev/ci/test/battletest/verify/codegen,
# plus the ko-based publish/apply flow the image targets mirror).
PYTHON ?= python
# Container engine + image coordinates (reference: KO_DOCKER_REPO/RELEASE_REPO)
ENGINE ?= $(shell command -v docker || command -v podman)
IMAGE_REPO ?= karpenter-tpu
IMAGE_TAG ?= latest
IMAGE = $(IMAGE_REPO):$(IMAGE_TAG)
JAX_EXTRAS ?= tpu

help: ## Display help
	@grep -E '^[a-zA-Z_-]+:.*## ' $(MAKEFILE_LIST) | awk -F':.*## ' '{printf "%-12s %s\n", $$1, $$2}'

dev: codegen verify test ## Codegen, lint, test — the inner loop

ci: codegen verify battletest ## Everything the gate runs

test: ## Run the test suite (virtual 8-device CPU mesh)
	$(PYTHON) -m pytest tests/ -x -q
	@echo "note: ~300 skips are the battletest-gated tiers (fuzz sweep," \
		"scale/stress, real-backend/apiserver) — 'make battletest' or" \
		"'make ci' runs them"

test-chaos: ## Seeded chaos suite: runtime + solver under injected faults (docs/resilience.md)
	$(PYTHON) -m pytest tests/test_faults.py tests/test_chaos.py -q

test-recovery: ## Seeded kill-and-restart suite: crash-safe state, fencing, warm-up (docs/resilience.md "Crash recovery")
	$(PYTHON) -m pytest tests/test_recovery.py tests/test_restart_chaos.py -q

test-failover: ## Replicated control plane: leader-kill handoff, exactly-once actuation, split-brain fencing (docs/resilience.md "Replicated control plane")
	$(PYTHON) -m pytest tests/test_failover.py -q

battletest: ## Randomized order + scale + stress + coverage when available (reference: Makefile battletest)
	@# coverage is opportunistic but NEVER silent: the gate says which
	@# mode it runs in, and a failing test fails it in either mode
	@if $(PYTHON) -c "import pytest_cov" 2>/dev/null; then \
		echo "battletest: with coverage"; \
		KARPENTER_TEST_SHUFFLE=random KARPENTER_SCALE_TESTS=1 $(PYTHON) -m pytest tests/ -q --cov=karpenter_tpu --cov-report=term-missing; \
	else \
		echo "battletest: pytest-cov not installed, running WITHOUT coverage"; \
		KARPENTER_TEST_SHUFFLE=random KARPENTER_SCALE_TESTS=1 $(PYTHON) -m pytest tests/ -q; \
	fi

verify: ## Static checks: compile, import, AST lint (complexity bound + unused imports)
	$(PYTHON) -m compileall -q karpenter_tpu tests hack bench.py __graft_entry__.py
	$(PYTHON) -c "import karpenter_tpu"
	$(PYTHON) hack/lint.py

codegen: ## Regenerate config/crd/*.yaml + releases/manifest.yaml from the API types
	bash hack/release.sh

docs: ## Generate docs/API.md from the API types (reference: Makefile docs target)
	$(PYTHON) -m karpenter_tpu.codegen --docs docs/API.md

native: ## Pre-build the C accelerators (otherwise built lazily in background)
	$(PYTHON) -c "from karpenter_tpu.native import load_kquantity; \
		assert load_kquantity() is not None, 'native build failed'; print('native ok')"

bench: ## Headline benchmark (needs the TPU; JAX_PLATFORMS=cpu for a CPU trajectory run)
	$(PYTHON) bench.py

bench-solver: ## Direct vs coalesced solver-service p50/p99 (10k pods x 50 types); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --solver-service --pods 10000 --types 50 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-hotpath: ## Idle-queue service vs direct p50 + per-stage breakdown (10k pods x 50 types); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --hotpath --pods 10000 --types 50 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-consolidate: ## Batched vs sequential drain-candidate evaluation (32 candidates x 480 bound pods); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --consolidate --candidates 32 --pods 480 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-forecast: ## Batched one-dispatch fleet forecast vs per-series loop (512 series x 64 samples); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --forecast --series 512 --history 64 \
		--iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-preempt: ## Batched one-dispatch eviction planning vs per-candidate loop (32 candidates x 50 node columns x 10k victims); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --preempt --candidates 32 --types 50 \
		--pods 10000 --backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-cost: ## Batched multi-objective cost/SLO refine vs per-HA sequential loop (512 autoscalers x 3 metrics, numpy parity pinned); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --cost --cost-rows 512 --cost-metrics 3 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-poolgroup: ## One batched joint pool-group dispatch vs the groups*pools per-pool cost dispatches it replaces (64 groups x 4 pools, numpy + cost-ladder parity pinned); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --poolgroup --poolgroup-groups 64 \
		--poolgroup-pools 4 --poolgroup-metrics 3 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-journal: ## Protective-state journal overhead on the reconcile hot path (target <5% tick-latency regression); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --journal --journal-ticks 40 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-trace: ## Reconcile-tracing overhead on the hot path: tracer enabled vs disabled, interleaved (target <5% tick-latency regression); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --trace --trace-ticks 200 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-provenance: ## Decision-provenance ledger overhead on the reconcile hot path: ledger enabled vs disabled, interleaved over the shared churn world (target <=5% tick-latency regression); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --provenance --provenance-ticks 200 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-resident: ## Device-resident fleet state: churn-tick solve with resident scatter ON vs full re-upload OFF over one watch-fed world (shipped + forced-scatter arms, unchanged-tick column, parity pinned every tick); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --resident --pods 10000 --types 50 \
		--backend xla --resident-ticks 60 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-shard: ## Sharded fleet-scale solve (1M pods x 1k types through the SolverService seam on an 8-device mesh, 1/2/4/8 scaling + parity pins); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --shard --pods 1000000 --types 1000 \
		--backend xla --iters 3 --shard-scaling 1,2,4,8 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-multitenant: ## Aggregate decisions/sec at 1k simulated tenants: cross-tenant concatenated decide+cost vs a sequential per-tenant loop (concat == independent parity pinned on device + numpy paths); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --multitenant --tenants 1000 --tenant-rows 4 \
		--backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-eventloop: ## Event-driven reconcile: one seeded pod-arrival trace replayed tick-paced vs event-driven (e2e p50/p99 off karpenter_reconcile_e2e_seconds, solve amplification, 1k-event churn-storm coalescing); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --eventloop --eventloop-ticks 40 \
		--eventloop-arrivals 60 --eventloop-storm 1000 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-introspect: ## Solver introspection-plane overhead on the reconcile hot path: compile ledger + device telemetry + XLA cost attribution enabled vs disabled, interleaved over the shared churn world (target <=2% tick-latency regression); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --introspect --introspect-ticks 200 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-constraints: ## Batched constrained solve (spread + reservation + anti-affinity + compact groups as masked integer operands, ONE dispatch) vs the per-group sequential loop, interleaved arms, parity pinned; appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --constraints --pods 20000 --types 48 \
		--constraint-groups 8 --backend xla --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

test-simlab: ## SimLab non-slow suite: gym/batched parity pins, scenario fuzz, policy search, catalog drift lint (docs/simulator.md)
	$(PYTHON) -m pytest tests/test_simlab.py -q

bench-simlab: ## SimLab batched cluster stepping: N seeded clusters as ONE vmapped sim_rollout dispatch vs the per-cluster sequential loop (batched == sequential == numpy pinned bitwise before timing); appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --simlab --simlab-clusters 256 \
		--simlab-ticks 64 --simlab-rows 8 --iters 10 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-fusedtick: ## Fused steady-state tick: the fleet batch's forecast -> decide -> cost ladder as ONE compiled program (--fused-tick) vs the chained per-stage wire (fused == chained == numpy pinned bitwise before timing), plus the dispatches-per-tick collapse over the shared churn world; appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --fusedtick --fusedtick-rows 256 \
		--fusedtick-metrics 3 --fusedtick-series 128 \
		--fusedtick-samples 32 --fusedtick-ticks 40 --iters 20 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

bench-failover: ## Replicated-control-plane leader kill at fleet scale (256 tenants x 4 replicas): handoff blackout p99 + exactly-once audit; appends a BENCHMARKS row + publishes to BASELINE.json
	$(PYTHON) bench.py --failover --failover-tenants 256 \
		--failover-replicas 4 --failover-partitions 16 \
		--failover-ticks 40 \
		--publish-baseline --append-benchmarks docs/BENCHMARKS.md

dryrun: ## Multi-chip sharding compile check on 8 virtual CPU devices
	$(PYTHON) -c "import os; \
		os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS','') + ' --xla_force_host_platform_device_count=8').strip(); \
		import jax; jax.config.update('jax_platforms', 'cpu'); \
		import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

image: ## Build the controller+solver OCI image (reference: ko publish --local)
	@test -n "$(ENGINE)" || { echo "no docker/podman found; install one or set ENGINE="; exit 1; }
	$(ENGINE) build --build-arg JAX_EXTRAS=$(JAX_EXTRAS) -t $(IMAGE) .

publish: image ## Push the image to IMAGE_REPO (reference: Makefile publish via ko)
	$(ENGINE) push $(IMAGE)

apply: image ## Build/push the image and apply config/ with it (reference: Makefile apply via ko resolve)
	@# registry-qualified repos (contain a /) are pushed like ko does;
	@# bare local names (the kind/kind-load path) are not pushable
	@if echo "$(IMAGE_REPO)" | grep -q /; then $(ENGINE) push $(IMAGE); fi
	kubectl kustomize config/ | sed "s|karpenter-tpu:latest|$(IMAGE)|g" | kubectl apply -f -

delete: ## Remove the applied resources (reference: Makefile delete)
	kubectl kustomize config/ | kubectl delete --ignore-not-found -f -

kind-load: image ## Side-load the image into a kind cluster (no registry needed)
	@# `kind load docker-image` reads the DOCKER daemon; podman builds
	@# need the archive path
	@case "$(notdir $(ENGINE))" in \
	  docker) kind load docker-image $(IMAGE) ;; \
	  *) $(ENGINE) save $(IMAGE) | kind load image-archive /dev/stdin ;; \
	esac

conformance: ## Run the real-apiserver tier against a kind-booted apiserver (the envtest analog)
	bash hack/conformance-kind.sh

kind-smoke: ## Deploy smoke on kind: image -> apply -> pod Ready -> one HA end to end
	bash hack/kind-smoke.sh

.PHONY: help dev ci test test-chaos test-recovery test-failover battletest verify codegen \
	docs native bench bench-solver bench-hotpath bench-consolidate \
	bench-forecast bench-preempt bench-cost bench-journal bench-trace \
	bench-provenance bench-resident bench-shard bench-multitenant \
	bench-eventloop bench-introspect bench-constraints test-simlab \
	bench-simlab bench-fusedtick bench-failover bench-poolgroup dryrun \
	image publish apply delete kind-load conformance kind-smoke
