"""natcheck — standing correctness tooling for the native runtime.

Six passes over the C++ core and its FFI boundary (see README.md here):

- ``abi``  — cross-checks the compiler-generated ABI manifest
  (native/nat_abi, built from nat_api.h) against the ctypes declarations
  in brpc_tpu/native/__init__.py and against ``nm -D`` of the built .so.
- ``lint`` — regex/clang-agnostic concurrency lint over native/src/
  enforcing repo invariants (explicit memory_order, no racy exit-time
  statics in thread-spawning files, seqlock readers re-check).
- ``lockorder`` — lock-rank verification: every mutex carries a declared
  rank (NatMutex<kLockRank...> / natcheck:rank comments); the static
  acquires-while-holding graph must be rank-monotone and no lock may be
  held across a fiber-switch/blocking point. Runtime twin: the
  NAT_LOCKRANK build (``make -C native lockrank``) asserts the same
  order on a TLS held-rank stack during nat_smoke runs.
- ``refown`` — declared ownership/refcount contracts: every add_ref/
  release goes through the NAT_REF_* macro grammar (nat_refown.h), the
  acquire/release/transfer graph per tag must balance (no unreleased
  acquires, no orphan releases, no early-return leaks, no borrows after
  release), deliberate leaks carry natcheck:leak declarations backing
  native/lsan.supp. Runtime twin: the NAT_REFGUARD build (``make -C
  native refguard``) asserts per-object per-tag balances at runtime.
- ``model`` — dsched deterministic interleaving checker (native/model/):
  exhaustive + seeded-random exploration of the lock-free primitives
  (wsq, descriptor ring, arena, butex protocol, EOWNERDEAD recovery)
  with stale-read weak-memory modeling; replayable failing schedules.
- ``san``  — builds the .so under ASan+UBSan and TSan and runs the native
  smoke driver under each; ``soak`` (tools/check.sh --soak) extends this
  to the full native matrix and logs native/SOAK.md.

Standing check.sh-only lanes: ``--refguard`` (the refown runtime twin
over the C smoke + pytest native matrix, refguard.py) and ``--chaos``
(fixed-seed fault-injection soak, chaos.py).

Entry points: ``python -m tools.natcheck`` or ``make -C native check``
(which delegates to tools/check.sh).
"""
from __future__ import annotations

import dataclasses
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass
class Finding:
    """One checker finding; `where` is file[:line], rule is a short slug."""

    pass_name: str   # "abi" | "lint" | "san"
    rule: str        # e.g. "atomic-order", "struct-layout"
    where: str       # "path" or "path:lineno"
    message: str

    def __str__(self) -> str:
        return f"{self.where}: [{self.pass_name}/{self.rule}] {self.message}"


def print_findings(findings, stream=None) -> int:
    """Print findings one per line; returns the count (0 = clean)."""
    import sys

    stream = stream or sys.stdout
    for f in findings:
        print(str(f), file=stream)
    return len(findings)
