#!/usr/bin/env bash
# ASAN+UBSAN gate for the native host core — the reference ships exactly
# this discipline for its C (valgrind_ctime_test.c, fuzz harnesses); 3.8k
# lines of C++ that parse adversarial transaction bytes get the same.
#
# Builds native/libnat_san.so (-fsanitize=address,undefined plus an
# explicit -fsanitize=shift,signed-integer-overflow for the consensus
# arithmetic, -fno-sanitize-recover=all: any diagnostic aborts the run)
# and replays
# the native byte-identity suites, the batched driver tests, the success
# caches' native key set against its Python twin (tests/test_sigcache.py),
# and the
# drop-in ABI corpus (script_tests.json + byte mutations — the
# adversarial codec paths) through the sanitized library.
#
# detect_leaks=0: CPython itself "leaks" interned objects at exit; leak
# checking would fail on the interpreter, not our code. Heap corruption,
# OOB, use-after-free and UB all still abort.
set -euo pipefail
cd "$(dirname "$0")/.."

make -C native san

ASAN_RT="$(g++ -print-file-name=libasan.so)"
if [ ! -e "$ASAN_RT" ]; then
    echo "sanitize: libasan runtime not found (g++ without asan?)" >&2
    exit 1
fi

# libstdc++ must be loaded when ASAN resolves its __cxa_throw interceptor:
# CPython itself doesn't link it, so without the explicit preload the
# first C++ exception inside libnat_san.so hits
# "real___cxa_throw != 0" CHECK-abort in asan_interceptors.
STDCXX="$(g++ -print-file-name=libstdc++.so.6)"
export LD_PRELOAD="$ASAN_RT $STDCXX"
export BITCOINCONSENSUS_NAT_SO="$PWD/native/libnat_san.so"
export ASAN_OPTIONS="detect_leaks=0:abort_on_error=1:strict_string_checks=1"
export UBSAN_OPTIONS="print_stacktrace=1:halt_on_error=1"
export JAX_PLATFORMS=cpu

# The suites below skipif on library availability; a .so that fails to
# load would skip everything and report a vacuous "clean". Assert the
# sanitized library actually loads and answers before running the corpus.
python - <<'EOF'
import sys
sys.path.insert(0, ".")
from bitcoinconsensus_tpu import native_bridge as NB
if not NB.available() or NB.lib().nat_version() < 4:
    sys.exit("sanitize: libnat_san.so failed to load — gate would be vacuous")
print("sanitize: sanitized library loaded, nat_version", NB.lib().nat_version())
EOF

python -m pytest \
    tests/test_native.py \
    tests/test_native_interp.py \
    tests/test_legacy_template.py \
    tests/test_native_batch.py \
    tests/test_native_idx.py \
    tests/test_native_block.py \
    tests/test_native_front.py \
    tests/test_drop_in_abi.py \
    tests/test_sigcache.py \
    -q "$@"
echo "sanitize: ASAN+UBSAN clean"
