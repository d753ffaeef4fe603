#!/usr/bin/env bash
# The one gate script: everything CI (or a pre-push hook) needs to trust a
# change.  Ordered cheap-to-expensive so the common failure is fast:
#
#   1. tpusnap lint            — project-invariant static analysis (always):
#                                the lexical rules plus the interprocedural
#                                family (collective-divergence,
#                                async-blocking-deep, lock-discipline,
#                                durability-flow, resource-leak) over the
#                                package-wide call graph.  For a fast local
#                                loop use `tpusnap lint --changed` (git-aware;
#                                the gate here always lints everything).
#   2. tpusnap lint --external — ruff + mypy when installed (skip = ok);
#                                mypy runs _analysis/ at non-lenient settings
#   3. tier-1 pytest           — the ROADMAP verify suite (not slow-marked)
#   4. sanitizer smoke         — TSAN race-regression legs, only when the
#                                toolchain can build+host the instrumented
#                                library (the suite itself skips otherwise)
#
# Speed is not judged here: the benchmark is BENCHMARK.json, run on the chip
# (chipbench/run.py), and its record is PERF_LEDGER.jsonl.
#
# Usage: tools/check.sh [--fast]   (--fast = lint only, no pytest)

set -u -o pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO_ROOT"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

fail=0
step() { printf '\n=== %s ===\n' "$*"; }

step "tpusnap lint"
python -m torchsnapshot_tpu lint "$REPO_ROOT" || fail=1

step "tpusnap lint --external (ruff + mypy; missing tools skip)"
python -m torchsnapshot_tpu lint "$REPO_ROOT" --external || fail=1

if [ "${1:-}" = "--fast" ]; then
  [ "$fail" -eq 0 ] && echo "check.sh --fast: OK" || echo "check.sh --fast: FAILED"
  exit "$fail"
fi

step "tier-1 pytest (-m 'not slow')"
timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider || fail=1

# Kill-chaos smoke: a rank SIGKILLed mid 2-rank take must abort the
# survivor fast (StorePeerError via lease expiry, wall << barrier
# timeout) and the retry must adopt the dead attempt's durable chunks.
# Also part of tier-1 above; its own gate line so a process-death
# regression is visible by name.
step "kill-chaos smoke (2-rank SIGKILL mid-take, fast variant)"
timeout -k 10 300 python -m pytest \
  tests/test_kill_chaos.py::test_sigkill_mid_take_fast -q \
  -p no:cacheprovider || fail=1

# Serve smoke: 2 concurrent restore processes through one shared host
# chunk cache (the fleet-serving read tier) — origin traffic must be
# ~one snapshot.  Also part of tier-1 above; called out here so a serving
# regression is visible as its own gate line.
step "serve smoke (2-worker concurrent restore through the chunk cache)"
timeout -k 10 300 python -m pytest \
  tests/test_serve.py::test_two_worker_concurrent_restore_fast -q \
  -p no:cacheprovider || fail=1

# Peer-serve smoke: 2 in-process peer daemons, digest-addressed range
# serving, and a fresh host restoring entirely peer-first (origin payload
# bytes == 0).  Also part of tier-1 above; its own gate line so a peer
# distribution regression is visible by name.
step "peer-serve smoke (2-daemon peer-first restore, zero origin bytes)"
timeout -k 10 300 python -m pytest \
  tests/test_peer.py::test_two_daemon_peer_first_restore_fast -q \
  -p no:cacheprovider || fail=1

# Serving-plane tracing smoke: the end-to-end distributed-trace proof —
# a 2-daemon peer-first restore under TPUSNAP_TRACE_DIR must yield ONE
# trace id spanning client peer_fetch spans and both daemons'
# peerd_handle spans, `trace --fleet` must merge them into a schema-valid
# timeline, and daemon access logs must validate.  The same file covers
# fault-injected span status, the peer scoreboard, and analyze --peer.
step "serving-plane tracing smoke (trace/access-log schema + fleet stitch)"
timeout -k 10 600 python -m pytest tests/test_peer_trace.py -q \
  -p no:cacheprovider || fail=1

# Shared-store chaos smoke: a writer SIGKILLed mid-take against the
# multi-tenant store must leave only debris a surviving tenant's sweep
# can reclaim — ledger/lease/quarantine invariants hold and the survivor
# still restores.  Also part of tier-1 above; its own gate line so a
# store-GC regression is visible by name.
step "shared-store chaos smoke (kill mid-take, survivor sweeps debris)"
timeout -k 10 300 python -m pytest \
  tests/test_store_chaos.py::test_kill_mid_take_debris_swept_by_survivor -q \
  -p no:cacheprovider || fail=1

# Postmortem smoke: the crash-forensics contract — a child killed
# mid-take by the crash fault must be NAMED by `tpusnap postmortem`
# (dead pid, op and phase at death, the injected kill point) from its
# flight-recorder ring, and the prescribed remediation must converge
# when applied.  Also covers the ring's crash-survival properties and
# the peerd ServerTracer idle-flush regression.
step "postmortem smoke (flight recorder + crash classification)"
timeout -k 10 300 python -m pytest tests/test_postmortem.py -q \
  -p no:cacheprovider || fail=1

# Profile smoke: the continuous-profiling contract — a profiled take
# writes schema-valid *.profile.json files (speedscope-loadable, tpusnap
# meta embedded) and `analyze --profile` folds them into the report and
# exits 0; also covers the <5% untagged-on-CPU attribution bar on a
# profiled fs take (the phase-inheriting executor regression test).
step "profile smoke (profiled take -> analyze --profile, schema valid)"
timeout -k 10 300 python -m pytest \
  tests/test_profiler.py::test_profile_smoke_gate \
  tests/test_profiler.py::test_untagged_share_under_5pct_on_profiled_fs_take \
  -q -p no:cacheprovider || fail=1

# Sanitizer smoke: only worth the build when the compiler supports
# -fsanitize=thread; the suite itself still skips per-test when the
# runtime can't host the instrumented library.
step "sanitizer smoke (tsan race-regression legs)"
if printf 'int main(){return 0;}' | g++ -x c++ -fsanitize=thread - -o /tmp/tsan_probe.$$ 2>/dev/null; then
  rm -f "/tmp/tsan_probe.$$"
  timeout -k 10 900 python -m pytest tests/test_native_sanitize.py -q \
    -p no:cacheprovider -k "tsan" || fail=1
else
  echo "toolchain lacks -fsanitize=thread; skipped"
fi

if [ "$fail" -eq 0 ]; then echo "check.sh: OK"; else echo "check.sh: FAILED"; fi
exit "$fail"
