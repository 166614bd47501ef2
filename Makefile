# Dev task runner (parity: the reference's poe tasks — run, health_check,
# test; pyproject.toml:45-57 — done as make targets since this project is
# setuptools-based).

# verify uses bash-only ${PIPESTATUS[0]}.
SHELL := /bin/bash

.PHONY: all executor run health-check test test-sanitizers verify chip-smoke proto clean

all: executor

executor:
	$(MAKE) -C executor

run: executor
	APP_EXECUTOR_BACKEND=local python -m bee_code_interpreter_fs_tpu

health-check:
	python -m bee_code_interpreter_fs_tpu.health_check

test: executor
	python -m pytest tests/ -q

# The driver's own tier-1 command (`commands` of its last run: six xdist
# workers, `--dist loadfile`, cut at 1470 s), $$ doubled for make. CI's
# "Tier-1 verify gate" calls this target, so the two cannot drift apart.
verify:
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $$rc

test-sanitizers:
	$(MAKE) -C executor asan tsan
	ASAN_OPTIONS=detect_leaks=1 TEST_EXECUTOR_BINARY=$(CURDIR)/executor/build/executor-server-asan \
		python -m pytest tests/unit/test_executor_server.py tests/unit/test_executor_limits.py tests/unit/test_executor_cgroup.py tests/unit/test_executor_perf.py -q
	TSAN_OPTIONS=halt_on_error=1 TEST_EXECUTOR_BINARY=$(CURDIR)/executor/build/executor-server-tsan \
		python -m pytest tests/unit/test_executor_server.py tests/unit/test_executor_limits.py tests/unit/test_executor_cgroup.py tests/unit/test_executor_perf.py -q

# The quickest proof that the served path still starts on the chip; through
# the builder's tool: `chiprun -- python3 chip_smoke.py` (`--chips 4` for the
# path across four chips). It builds the executor itself.
chip-smoke:
	python chip_smoke.py

proto:
	scripts/genproto.sh

clean:
	$(MAKE) -C executor clean
