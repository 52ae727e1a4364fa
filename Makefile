# Native host runtime (engine / storage pool / recordio / batch loader).
# `make native` -> mxnet_tpu/_native/libmxtpu_runtime.so
CXX ?= g++
CXXFLAGS ?= -O2 -std=c++17 -fPIC -Wall -pthread -fvisibility=hidden
SRCS := src/runtime/storage.cc src/runtime/engine.cc \
        src/runtime/recordio.cc src/runtime/prefetch.cc
LIB := mxnet_tpu/_native/libmxtpu_runtime.so

.PHONY: native test chaos chaos-train chaos-serve lint-graft autotune-smoke shard-smoke decode-smoke embed-smoke report clean cpp_example predict_capi capi_example

native: $(LIB)

$(LIB): $(SRCS) src/runtime/mxt_runtime.h
	@mkdir -p mxnet_tpu/_native
	$(CXX) $(CXXFLAGS) -shared -o $@ $(SRCS)

# C inference API (c_predict_api analog): flat MXTPred* calls over an
# embedded CPython driving mxnet_tpu.predictor.Predictor.
PY_INC = $(shell python3 -c "import sysconfig; print(sysconfig.get_paths()['include'])")
PY_LIBDIR = $(shell python3 -c "import sysconfig; print(sysconfig.get_config_var('LIBDIR'))")
# LDVERSION includes ABI flags (e.g. '3.11d' for debug builds) where
# plain VERSION would link a nonexistent libpython; fall back to VERSION
PY_LIB = $(shell python3 -c "import sysconfig; print('python' + (sysconfig.get_config_var('LDVERSION') or sysconfig.get_config_var('VERSION')))")
PRED_LIB := mxnet_tpu/_native/libmxt_predict.so

predict_capi: $(PRED_LIB)

# the lib re-dlopens libpython RTLD_GLOBAL at init (predict_capi.cc
# ensure_python) so RTLD_LOCAL hosts (perl/R/JNI bindings) can import
# python C-extensions; pass the soname the link resolves to
PY_SONAME = $(shell python3 -c "import sysconfig; print(sysconfig.get_config_var('INSTSONAME') or 'lib' + 'python' + sysconfig.get_config_var('LDVERSION') + '.so')")

$(PRED_LIB): src/runtime/predict_capi.cc src/runtime/capi.cc \
	     src/runtime/py_embed.cc src/runtime/mxt_predict.h \
	     src/runtime/mxt_capi.h src/runtime/py_embed.h
	@mkdir -p mxnet_tpu/_native
	$(CXX) $(CXXFLAGS) -I$(PY_INC) -shared -o $@ \
	    -DMXT_LIBPYTHON_SO='"$(PY_SONAME)"' \
	    src/runtime/predict_capi.cc src/runtime/capi.cc \
	    src/runtime/py_embed.cc \
	    -L$(PY_LIBDIR) -l$(PY_LIB) -ldl -Wl,-rpath,$(PY_LIBDIR)

# C++ consumer of the native runtime (cpp-package analog): predict-only
# MLP from a python-trained checkpoint, streamed via the batch loader.
CPP_EX := cpp-package/example/mlp_predict

cpp_example: $(CPP_EX)

$(CPP_EX): cpp-package/example/mlp_predict.cc $(LIB) \
           $(wildcard cpp-package/include/mxnet_tpu_cpp/*.hpp)
	$(CXX) $(CXXFLAGS) -o $@ $< \
	    -Lmxnet_tpu/_native -lmxtpu_runtime \
	    -Wl,-rpath,'$$ORIGIN/../../mxnet_tpu/_native'

CAPI_EX := cpp-package/example/capi_predict
CAPI_TRAIN_EX := cpp-package/example/capi_train
CAPI_KV_EX := cpp-package/example/capi_kv_iter
CAPI_LM_EX := cpp-package/example/capi_lm_decode
CAPI_AG_EX := cpp-package/example/capi_autograd

capi_example: $(CAPI_EX) $(CAPI_TRAIN_EX) $(CAPI_KV_EX) $(CAPI_LM_EX) \
              $(CAPI_AG_EX)

# one link recipe for every plain-C capi example (predict ABI; -lm is
# harmless where unused, and both headers are cheap prereqs)
cpp-package/example/capi_%: cpp-package/example/capi_%.c $(PRED_LIB) \
            src/runtime/mxt_predict.h src/runtime/mxt_capi.h
	$(CC) -O2 -Wall -o $@ $< \
	    -Lmxnet_tpu/_native -lmxt_predict -lm \
	    -Wl,-rpath,'$$ORIGIN/../../mxnet_tpu/_native'

test: native
	python -m pytest tests/ -x -q

# the full chaos plan: every fault-injection / overload resilience
# drill, including the slow sustained legs the default tier-1 run
# (-m 'not slow') skips.  docs/serving_resilience.md is the guide.
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos

# the training-side chaos drills (ISSUE 12,
# docs/training_resilience.md): supervisor retry/watchdog suites,
# prefetcher fault containment, checkpoint restore diagnostics +
# preemption — the full files, chaos-marked legs included
# (MXNET_CHECKPOINT_FSYNC=0: the SIGKILL/SIGTERM subprocess drills
# write real checkpoints; atomicity holds without the fsyncs).
chaos-train:
	JAX_PLATFORMS=cpu MXNET_CHECKPOINT_FSYNC=0 python -m pytest \
	    tests/test_supervisor.py tests/test_prefetcher.py \
	    tests/test_faultinject.py tests/test_checkpoint.py -q

# the serving-side chaos drills (ISSUE 14, docs/multi_model.md):
# multi-model registry churn under an HBM budget (LRU eviction,
# restart-free readmission, OOM second chance) + the ResilientServer
# overload/readiness suites + the fault-injection harness — full
# files, chaos-marked legs included.
chaos-serve:
	JAX_PLATFORMS=cpu python -m pytest \
	    tests/test_registry.py tests/test_resilience.py \
	    tests/test_faultinject.py -q

# graft-lint: the repo-specific static analysis gate (ISSUE 7 + 15,
# docs/static_analysis.md).  Exit nonzero on any non-baselined finding
# of the nine rules (thread-safety, host-sync, atomic-write, env-sync,
# metrics-hygiene, memory-hygiene, use-after-donate, retrace-hazard,
# gate-hygiene) OR any failed compiled-program contract
# (--audit-programs: donation really became input-output aliasing,
# zero host callbacks, collective count matches the plan);
# tests/test_analysis.py + tests/test_program_audit.py run the same
# checks in tier-1.  JAX_PLATFORMS=cpu: the lint needs no chip and must
# not take one from a job that does (same reason as the chaos target).
lint-graft:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.analysis --audit-programs mxnet_tpu

# autotune smoke gate (ISSUE 17, docs/perf_tuning.md): the measured
# sweep on a tiny pinned MLP completes fast, persists its decision,
# and a SECOND PROCESS with the same (model-signature, platform) is a
# pure cache hit — zero measured runs (--expect-cached exits nonzero
# otherwise).  Each invocation also asserts the decision file
# round-trips through decisions.load.
autotune-smoke:
	@tmp=$$(mktemp -d); rc=0; \
	JAX_PLATFORMS=cpu MXNET_AUTOTUNE=1 MXNET_AUTOTUNE_DIR=$$tmp \
	    timeout 60 python -m mxnet_tpu.autotune --smoke && \
	JAX_PLATFORMS=cpu MXNET_AUTOTUNE=1 MXNET_AUTOTUNE_DIR=$$tmp \
	    timeout 60 python -m mxnet_tpu.autotune --smoke --expect-cached \
	    || rc=$$?; \
	rm -rf $$tmp; exit $$rc

# continuous-batching decode smoke gate (ISSUE 19,
# docs/decode_serving.md): mixed-length traffic with per-step
# join/leave over a warmed (slots, pages) lattice — asserts exactly
# ONE donated dispatch per decode step, ZERO post-warmup compiles,
# and every admitted sequence finishing.  (-c import keeps runpy from
# double-importing the module the serving package already loaded.)
decode-smoke:
	JAX_PLATFORMS=cpu timeout 60 python -c "from mxnet_tpu.serving \
	    import decode; raise SystemExit(decode.main(['--smoke']))"

# GSPMD sharding smoke gate (ISSUE 18, docs/parallel.md): 8 virtual
# CPU devices, 2-D batch=4,model=2 mesh, whole-step train — asserts
# the sharded program still dispatches exactly once per step, donation
# stayed aliased, and every sized mesh axis carries its planned
# collectives (audit_program on the captured HLO).
shard-smoke:
	JAX_PLATFORMS=cpu timeout 60 python -m mxnet_tpu.parallel --smoke

# sharded-embedding smoke gate (ISSUE 20, docs/embedding.md): 8 virtual
# CPU devices, 2-way model-sharded ShardedEmbedding + dense tower
# whole-step train — asserts 1 dispatch/step, the table's donation
# survived the in-program scatter (alias table), the sharded program
# carries its id/row exchange collectives, and embed_shards bytes are
# on the memory ledger.
embed-smoke:
	JAX_PLATFORMS=cpu timeout 60 python -m mxnet_tpu.embedding --smoke

# render the offline run report for the newest run journal under
# MXNET_RUN_DIR (or ./runs); `make report RUN_DIR=/path` overrides
RUN_DIR ?= $(or $(MXNET_RUN_DIR),runs)
report:
	JAX_PLATFORMS=cpu python -m mxnet_tpu.observability.report $(RUN_DIR)

clean:
	rm -f $(LIB) $(CPP_EX) $(PRED_LIB) $(CAPI_EX) $(CAPI_TRAIN_EX) \
	    $(CAPI_KV_EX) $(CAPI_LM_EX) $(CAPI_AG_EX)
