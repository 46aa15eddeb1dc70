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
	$(PYTHON) -m compileall -q karpenter_tpu tests hack __graft_entry__.py
	$(PYTHON) -c "import karpenter_tpu"
	$(PYTHON) hack/lint.py

codegen: ## Regenerate config/crd/*.yaml + releases/manifest.yaml from the API types
	bash hack/release.sh

docs: ## Generate docs/API.md from the API types (reference: Makefile docs target)
	$(PYTHON) -m karpenter_tpu.codegen --docs docs/API.md

native: ## Pre-build the C accelerators (otherwise built lazily in background)
	$(PYTHON) -c "from karpenter_tpu.native import load_kquantity; \
		assert load_kquantity() is not None, 'native build failed'; print('native ok')"

test-simlab: ## SimLab non-slow suite: gym/batched parity pins, scenario fuzz, policy search, catalog drift lint (docs/simulator.md)
	$(PYTHON) -m pytest tests/test_simlab.py -q

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
	docs native test-simlab dryrun \
	image publish apply delete kind-load conformance kind-smoke
