# Tier-1 verification: dependency hygiene + the full test suite, plus both
# alternate dispatch configurations.
#
#   make verify      - what CI runs; catches the dacite-class regression
#                      (a third-party import sneaking into the core path),
#                      then re-exercises the Pallas interpret dispatch layer,
#                      the 4-host-device data-parallel configuration, and the
#                      serving engine (incl. 4-fake-device sharded serving)
#   make smoke       - 2-step end-to-end training run through the Experiment
#                      front door (launch CLI + config-file path)
#   make smoke-dist  - same, sharded over 4 faked CPU devices with
#                      gradient-accumulation microbatching
#   make smoke-dist-2d - same on the 2-D dp=2×mp=2 mesh (FSDP/expert/head
#                      sharding per the PartitionPlan)
#   make test-serve  - serving engine suite on 4 faked devices + the
#                      sharded serve CLI end-to-end
#   make fuzz-serve  - 200 seeded submit/poll/fetch/drain interleavings
#                      against one warmed multi-tenant engine (deterministic:
#                      injected clock, seeded RNG, zero invariant violations)

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
DIST_FLAGS := --xla_force_host_platform_device_count=4

.PHONY: verify deps-check lint test test-interpret test-dist test-serve \
	test-perf-dist test-pipeline fuzz-serve smoke smoke-dist smoke-dist-2d

verify: deps-check lint test test-interpret test-dist test-serve \
	test-perf-dist test-pipeline fuzz-serve

# Core modules must import on a bare jax+numpy interpreter: no dacite, and
# zstandard/msgpack/hypothesis only ever loaded behind soft gates; the
# analysis package must import on NO third-party modules at all.
deps-check:
	$(PY) scripts/check_deps.py

# jaxlint: stdlib-ast static analysis for this repo's JAX bug classes
# (R001-R007; see `python -m repro.analysis --catalog`).  Fails on any
# finding that is neither inline-suppressed nor in .jaxlint-baseline.json.
lint:
	$(PY) -m repro.analysis src/repro benchmarks examples

test:
	$(PY) -m pytest -x -q

# Pallas dispatch layer: per-kernel oracles plus full trainer steps with
# REPRO_PALLAS=interpret (reward_improves is excluded — 45 interpret-mode
# steps add ~10 min for a signal the kernel mode doesn't change).
test-interpret:
	REPRO_PALLAS=interpret $(PY) -m pytest -x -q tests/test_kernels.py \
	    tests/test_trainers.py -k "not reward_improves"

# Distributed configuration: the in-process distributed tests re-run ON
# 4 faked host devices (the subprocess equivalence tests are deselected —
# they spawn their own 4-device children and already ran in `make test`),
# then the sharded + microbatched launch CLI end-to-end, in both mesh
# layouts: 1-D dp=4 and 2-D dp=2×mp=2.
test-dist:
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m pytest -x -q \
	    tests/test_distributed.py \
	    -k "not sharded_training and not shard_map and not two_axis and not portable"
	$(MAKE) smoke-dist
	$(MAKE) smoke-dist-2d

# Serving engine: the suite re-run ON 4 faked host devices (the sharded
# subprocess test is deselected — it spawns its own 4-device child and
# already ran in `make test`), then the bucketed + sharded serve CLI
# end-to-end (dist.data_parallel=4, per-request bit-identical to dp=1).
test-serve:
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m pytest -x -q tests/test_serving.py \
	    -k "not subprocess"
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m repro.launch.serve --reduced \
	    --requests 9 --max-batch 4 --deadline-ms 2 \
	    --step-tiers 2 --stats-json /tmp/repro-serve-stats.json \
	    --set flow.num_steps=2 --set dist.data_parallel=4 \
	    --set 'data.encoder={"cond_dim": 512, "cond_len": 8, "vocab": 512, "hidden": 64}'
	$(PY) -c "import json; s = json.load(open('/tmp/repro-serve-stats.json')); \
	    assert s['cold_dispatches'] == 0 and s['step_tiers'] == [2], s"

# The serving fuzz corpus at full depth: 200 seeded interleavings (the
# tier-1 run uses the default 25).  Deterministic — same seeds, same
# injected clock, same op sequences — so a failure here is reproducible
# with REPRO_FUZZ_SEEDS=200 pytest tests/test_serving.py -k fuzz.
fuzz-serve:
	REPRO_FUZZ_SEEDS=200 $(PY) -m pytest -x -q tests/test_serving.py \
	    -k "fuzz"

# repro.perf composition: the perf tests whose remat/fusion × data-parallel
# × microbatch assertions need real (faked) devices re-run ON 4 of them
# (the single-device semantics already ran in `make test`)
test-perf-dist:
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m pytest -x -q tests/test_perf.py \
	    -k "data_parallel or under_mesh"

# Pipelined train loop: the pipeline=K-vs-sequential equivalence suite
# re-run ON 4 faked host devices so the fused × data_parallel=4 × K
# composition test (skipped in `make test`) executes too.
test-pipeline:
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m pytest -x -q tests/test_pipeline.py

smoke:
	$(PY) -m repro.launch.train --reduced --steps 2 \
	    --set flow.num_steps=2 --set flow.group_size=2 \
	    --set flow.cache_dir=/tmp/repro-smoke/cache \
	    --set loop.ckpt_dir=/tmp/repro-smoke/ckpt

smoke-dist:
	rm -rf /tmp/repro-smoke-dist
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m repro.launch.train --reduced \
	    --steps 2 --set dist.data_parallel=4 --set dist.microbatch=2 \
	    --set flow.cache_dir=/tmp/repro-smoke-dist/cache \
	    --set loop.ckpt_dir=/tmp/repro-smoke-dist/ckpt

# 2-D mesh smoke: dp=2 × mp=2 on 4 faked devices, params/moments sharded
# over the model axis per the PartitionPlan (perf.log_memory surfaces the
# per-device state bytes)
smoke-dist-2d:
	rm -rf /tmp/repro-smoke-dist-2d
	XLA_FLAGS="$(DIST_FLAGS)" $(PY) -m repro.launch.train --reduced \
	    --steps 2 --set dist.data_parallel=2 --set dist.model_parallel=2 \
	    --set perf.log_memory=true \
	    --set flow.cache_dir=/tmp/repro-smoke-dist-2d/cache \
	    --set loop.ckpt_dir=/tmp/repro-smoke-dist-2d/ckpt
