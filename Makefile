# Dev task runner (parity: the reference's poe tasks — run, health_check,
# test; pyproject.toml:45-57 — done as make targets since this project is
# setuptools-based).

# verify uses bash-only ${PIPESTATUS[0]} (the ROADMAP tier-1 command verbatim).
SHELL := /bin/bash

.PHONY: all executor run health-check test test-sanitizers verify bench chip-smoke proto clean

all: executor

executor:
	$(MAKE) -C executor

run: executor
	APP_EXECUTOR_BACKEND=local python -m bee_code_interpreter_fs_tpu

health-check:
	python -m bee_code_interpreter_fs_tpu.health_check

test: executor
	python -m pytest tests/ -q

# The ROADMAP.md "Tier-1 verify" command, verbatim ($ doubled for make):
# the acceptance gate every PR must keep no worse than the seed. CI calls
# this so local `make verify` and the workflow can never drift apart.
verify:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

test-sanitizers:
	$(MAKE) -C executor asan tsan
	ASAN_OPTIONS=detect_leaks=1 TEST_EXECUTOR_BINARY=$(CURDIR)/executor/build/executor-server-asan \
		python -m pytest tests/unit/test_executor_server.py tests/unit/test_executor_limits.py tests/unit/test_executor_cgroup.py tests/unit/test_executor_perf.py -q
	TSAN_OPTIONS=halt_on_error=1 TEST_EXECUTOR_BINARY=$(CURDIR)/executor/build/executor-server-tsan \
		python -m pytest tests/unit/test_executor_server.py tests/unit/test_executor_limits.py tests/unit/test_executor_cgroup.py tests/unit/test_executor_perf.py -q

# Both run on the chip only and rebuild the executor from source every time
# (executor/build/ is git-ignored; a stale binary would be what runs).
bench:
	$(MAKE) -B -C executor
	python bench.py

# The quickest proof that the served path still starts on the chip; through
# the builder's tool: `chiprun -- python3 chip_smoke.py` (`--chips 4` for the
# path across four chips). It builds the executor itself.
chip-smoke:
	python chip_smoke.py

proto:
	scripts/genproto.sh

clean:
	$(MAKE) -C executor clean
