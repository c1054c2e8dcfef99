#!/bin/sh
# tools/check.sh — the natcheck gate (also `make -C native check`).
#
# Runs the fast static passes first (concurrency lint + ABI/FFI contract
# + lock-order verification + refown ownership contracts + wiretrust
# wire-input taint — pure Python, seconds), then the lock-rank runtime
# validator (NAT_LOCKRANK build of the .so driven by the smoke — a rank
# inversion or a NatMutex held across a fiber switch aborts it), the
# refguard refcount validator (NAT_REFGUARD build: an unbalanced
# acquire/release tag pair aborts the smoke with the pair printed), and
# the strict UBSan smoke (-fno-sanitize-recover build: any undefined
# behaviour aborts); all skipped with a note when the toolchain is
# absent.
#
# NATCHECK_SLOW=1 adds the sanitizer lane (ASan+UBSan and TSan builds +
# smoke; several minutes of compile) and the dsched interleaving smoke.
# --soak (or NATCHECK_SOAK=1) additionally runs the full sanitizer soak
# matrix and writes native/SOAK.md (see tools/natcheck/soak.py).
# --refguard (or NATCHECK_REFGUARD=1) additionally runs the pytest
# native matrix against the refguard .so (BRPC_TPU_NATIVE_SO override)
# plus the deliberately-broken scenario that proves the guard fires
# (see tools/natcheck/refguard.py).
# --chaos (or NATCHECK_CHAOS=1) runs the fixed-seed fault-injection soak
# (C smoke + pytest native matrix under the documented NAT_FAULT spec)
# and writes native/CHAOS.md (see tools/natcheck/chaos.py).
# --replay (or NATCHECK_REPLAY=1) runs the flight-recorder round-trip
# gate: capture a seeded native run, restart the server fresh, replay
# the capture through the native replay client — zero failed RPCs,
# response-count parity, Python-reader byte identity (see
# tools/natcheck/replay.py).
# --fleet (or NATCHECK_FLEET=1) runs the fleet-observatory round: a
# live 3-server group behind a file naming feed, real traffic, then
# wire-native builtin.stats scrape -> exact histogram merge -> fleet
# quantiles -> SLO engine, end to end (see tools/natcheck/fleet.py).
# --fuzz (or NATCHECK_FUZZ=1) runs the bounded deterministic parser
# fuzz lane: every native/fuzz target (ASan+UBSan, fixed seed) over its
# committed corpus + regress inputs for NATCHECK_FUZZ_MS (default
# 2000ms) each; any crash or sanitizer report fails (see
# tools/natcheck/fuzzlane.py).
# Exits nonzero on any finding.
set -u

cd "$(dirname "$0")/.."

PY="${PYTHON:-python3}"
RC=0

SOAK="${NATCHECK_SOAK:-0}"
CHAOS="${NATCHECK_CHAOS:-0}"
REFGUARD="${NATCHECK_REFGUARD:-0}"
REPLAY="${NATCHECK_REPLAY:-0}"
FLEET="${NATCHECK_FLEET:-0}"
FUZZ="${NATCHECK_FUZZ:-0}"
for arg in "$@"; do
    case "$arg" in
        --soak) SOAK=1 ;;
        --chaos) CHAOS=1 ;;
        --refguard) REFGUARD=1 ;;
        --replay) REPLAY=1 ;;
        --fleet) FLEET=1 ;;
        --fuzz) FUZZ=1 ;;
    esac
done

# static passes first: they need no toolchain and must report even when
# the compile below cannot run
if [ "$SOAK" = "1" ] || [ "${NATCHECK_SLOW:-0}" = "1" ]; then
    "$PY" -m tools.natcheck lint abi lockorder refown wiretrust model san || RC=1
else
    "$PY" -m tools.natcheck lint abi lockorder refown wiretrust || RC=1
fi

# lock-rank runtime validator: build + drive the smoke under it
if command -v g++ >/dev/null 2>&1; then
    if make -C native lockrank >/dev/null 2>&1 &&
           native/nat_smoke_lockrank >/dev/null; then
        echo "natcheck: lockrank: clean"
    else
        echo "natcheck: lockrank: FAILED (rank inversion or smoke error)"
        RC=1
    fi
else
    echo "natcheck: lockrank: skipped (no g++)"
fi

# refcount-contract runtime validator (refown's twin): the NAT_REFGUARD
# build of the .so driven by the smoke — an unbalanced tag pair aborts
if command -v g++ >/dev/null 2>&1; then
    if make -C native refguard >/dev/null 2>&1 &&
           native/nat_smoke_refguard >/dev/null; then
        echo "natcheck: refguard: clean"
    else
        echo "natcheck: refguard: FAILED (unbalanced ref contract or smoke error)"
        RC=1
    fi
else
    echo "natcheck: refguard: skipped (no g++)"
fi

# strict UBSan smoke: -fno-sanitize-recover build — any undefined
# behaviour aborts the smoke instead of printing and continuing
if command -v g++ >/dev/null 2>&1; then
    if make -C native ubsan >/dev/null 2>&1 &&
           UBSAN_OPTIONS=print_stacktrace=1 native/nat_smoke_ubsan >/dev/null; then
        echo "natcheck: ubsan: clean"
    else
        echo "natcheck: ubsan: FAILED (undefined behaviour or smoke error)"
        RC=1
    fi
else
    echo "natcheck: ubsan: skipped (no g++)"
fi

if [ "$REFGUARD" = "1" ]; then
    "$PY" - <<'PYRG' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, refguard
findings = refguard.run()
print("natcheck: refguard lane: %s"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
PYRG
fi

if [ "$REPLAY" = "1" ]; then
    JAX_PLATFORMS=cpu "$PY" - <<'PYRP' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, replay
findings = replay.run()
print("natcheck: replay lane: %s"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
PYRP
fi

if [ "$SOAK" = "1" ]; then
    "$PY" - <<'EOF' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, soak
findings = soak.run()
print("natcheck: soak: %s (log: native/SOAK.md)"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
EOF
fi

if [ "$FLEET" = "1" ]; then
    JAX_PLATFORMS=cpu "$PY" - <<'PYFL' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, fleet
findings = fleet.run()
print("natcheck: fleet: %s"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
PYFL
fi

if [ "$FUZZ" = "1" ]; then
    "$PY" - <<'PYFZ' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, fuzzlane
findings = fuzzlane.run()
print("natcheck: fuzz: %s"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
PYFZ
fi

if [ "$CHAOS" = "1" ]; then
    "$PY" - <<'EOF' || RC=1
import sys
sys.path.insert(0, ".")
from tools.natcheck import print_findings, chaos
findings = chaos.run()
print("natcheck: chaos: %s (log: native/CHAOS.md)"
      % ("clean" if not findings else "%d finding(s)" % len(findings)))
print_findings(findings)
sys.exit(1 if findings else 0)
EOF
fi

exit $RC
