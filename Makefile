# Test entry points (see .github/workflows/ci.yml).  The benchmark is not a
# target: its command is in BENCHMARK.json (benchmark/README.md).

.PHONY: test test-serial test-fast test-core dryrun examples bench-loader

# full suite, parallelized over cores (pytest-xdist): each worker is its
# own process with its own 8-virtual-device CPU mesh, so distribution
# tests stay isolated.  ~12.5 min serial on 1 core; -n auto cuts CI
# (2-core) wall time roughly in half.
test:
	python -m pytest tests/ -q -n auto

test-serial:
	python -m pytest tests/ -q

# the quick pre-commit loop: skips tests marked slow (multi-process
# integration + minutes-scale compile-shape checks); CI's `make test`
# still runs everything.
test-fast:
	python -m pytest tests/ -q -x -m "not slow" -n auto

# the contributor loop: the core path, serial, budgeted <= 5 min warm on
# 1 core — covers tensor ops, layers, optim, the sharded train step,
# records, serving and storage without the long tail of integration files.
CORE_TESTS = tests/test_tensor.py tests/test_nn_layers.py \
  tests/test_optim.py tests/test_distri_optimizer.py \
  tests/test_parallel.py tests/test_records.py tests/test_serving.py \
  tests/test_storage_remote.py
test-core:
	python -m pytest $(CORE_TESTS) -q

dryrun:
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench-loader:
	python bench_loader.py

# every example end-to-end at tiny sizes (the reference's nightly example
# runs, SURVEY.md §5, scaled for CI); fails on the first broken example
examples:
	BIGDL_TPU_EXAMPLES_TINY=1 sh -c '\
	  set -e; \
	  for f in examples/*.py; do \
	    case $$f in */_sim_mesh.py) continue;; esac; \
	    echo "== $$f"; python $$f; \
	  done'
