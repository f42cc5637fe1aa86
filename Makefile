# Top-level build/test entry points (reference: Makefile + make/ps.mk).
#
#   make native         build the C++ transport core
#   make native ASAN=1  ... with AddressSanitizer
#   make native TSAN=1  ... with ThreadSanitizer (io thread vs callers)
#   make test           run the full suite (virtual 8-device CPU mesh)
#   make tier1          THE tier-1 gate, as the driver runs it
#   make lint           byte-compile every Python module
#
# Speed is measured on the chip by benchmark/run.py, one cell of
# BENCHMARK.json a call (benchmark/README.md); chip_smoke.py is the smoke.

SHELL := /bin/bash

ASAN ?= 0
TSAN ?= 0
ifeq ($(ASAN)$(TSAN), 11)
$(error ASAN and TSAN are mutually exclusive)
endif
ifeq ($(ASAN), 1)
CPPFLAGS_EXTRA = CXXFLAGS="-O1 -g -std=c++17 -fPIC -Wall -Wextra -pthread -fsanitize=address"
endif
ifeq ($(TSAN), 1)
CPPFLAGS_EXTRA = CXXFLAGS="-O1 -g -std=c++17 -fPIC -Wall -Wextra -pthread -fsanitize=thread"
endif

.PHONY: all native test tier1 soak soak-smoke lint clean

all: native

native:
	$(MAKE) -C cpp $(CPPFLAGS_EXTRA)

test: native
	python -m pytest tests/ -x -q

# The tier-1 verification gate: pytest as the driver runs it after every
# PR (the `commands` of its TESTS_LAST_RUN.json: six xdist workers, a
# file to a worker, 1470 s), so builder and reviewer run ONE invocation.
# Prints DOTS_PASSED=<n> and exits with pytest's status.
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Graded production-matrix soak (tools/pssoak.py): tenants x
# replication x elastic x batching x tracing x native cells, each
# verified for correctness, with telemetry overhead self-measured and
# asserted < 2% of op wall.  Exits nonzero on grade C/F.
soak: native
	env JAX_PLATFORMS=cpu python tools/pssoak.py

# Tier-1-safe scaled-down soak: python plane only, <= 45 s wall,
# CPU-only (referenced by tests/test_pssoak.py).
soak-smoke:
	env JAX_PLATFORMS=cpu python tools/pssoak.py --smoke

lint:
	python -m compileall -q pslite_tpu tests chip_smoke.py __graft_entry__.py

clean:
	$(MAKE) -C cpp clean
	find . -name __pycache__ -type d -exec rm -rf {} +
