#!/usr/bin/env bash
# Repo check pipeline: everything a PR must pass, in the order a human
# wants failures reported. Run from anywhere; works on the CPU backend.
#
#   scripts/ci_checks.sh            # lint + drift + tier-1 tests
#   scripts/ci_checks.sh --fast     # skip the pytest step (lint only)
#
# Steps:
#   1. graftlint  — JAX-serving-aware static analysis (trace purity,
#                   lock discipline + cross-thread races, thread
#                   hygiene, call-graph-inferred hot-path host-sync,
#                   atomic persistence, metrics contract, config
#                   drift, and the GL701-GL704 multihost collective-
#                   safety family: publish-before-launch dispatch
#                   inventory, fetch-seam enforcement, replay-
#                   divergence sources, rank-branched launches — all
#                   in this same single gating pass, so the SARIF
#                   artifact and --changed reverse-dependency scoping
#                   cover them for free);
#                   zero non-baselined findings required, and
#                   STALE baseline entries (fixed code) fail the step
#                   (--fail-stale) so the baseline shrinks over time.
#                   A SARIF artifact lands at build/lint.sarif for CI
#                   code-annotation upload.
#   2. ruff       — generic pycodestyle/pyflakes/bugbear subset
#                   (pyproject.toml [tool.ruff]); skipped with a notice
#                   when ruff isn't installed in the image.
#   3. config-docs drift — docs/configuration.md must match
#                   config/schema.py (scripts/gen_config_docs.py --check).
#   4. step-plan smoke — CPU gate for the composed fused+spec StepPlan
#                   path (scripts/smoke_plan_step.py: riders carry the
#                   whole prompt, tree drafts > 1 token/verify-step,
#                   byte-equality vs offline greedy).
#   5. router smoke — CPU gate for the 2-replica fleet
#                   (scripts/smoke_router.py: routed streams byte-
#                   identical to a single engine, prefix hit on turn 2,
#                   graceful drain finishes the in-flight stream).
#   6. tiered-ANN smoke — CPU gate for the demand-paged IVF index
#                   (scripts/smoke_tiered_ann.py: recall@4 > 0.8 with a
#                   forced tiny HBM budget so the pager actually pages,
#                   promotions observed, live writes race searches,
#                   tiered ids == plain-IVF ids).
#   7. QoS smoke  — CPU gate for the SLO-aware multi-tenant scheduler
#                   (scripts/smoke_qos.py: latency-tier goodput beats
#                   FIFO on a canned bursty trace, batch tier not
#                   starved, over-bound requests get a fast 429 +
#                   Retry-After instead of a hang).
#   8. KV-pager smoke — CPU gate for the session KV pager
#                   (scripts/smoke_kv_pager.py: sessions beyond pool
#                   capacity survive demotion at >= 4x the HBM-only
#                   count, warm resume from the host tier is
#                   byte-identical to never-demoted greedy,
#                   promotions observed).
#   9. chaos smoke — CPU gate for the elastic fleet's crash recovery
#                   (scripts/smoke_chaos.py: 2 replicas, seeded kill
#                   mid-burst — zero lost non-mid-stream requests,
#                   latency goodput >= 0.9x the no-fault baseline,
#                   kill counted + evicted + on the chaos timeline
#                   lane, zero zombie threads / stuck joins).
#  10. disagg smoke — CPU gate for disaggregated prefill/decode
#                   (scripts/smoke_disagg.py: prefill-role + decode-
#                   role pair, transferred-prefix streams byte-
#                   identical to colocated greedy, kv_transfer_pages
#                   > 0, prefill-role never decodes, broken-transfer
#                   fallback stays byte-identical and counted).
#  11. kernel smoke — CPU gate for the Pallas tree-attention kernels
#                   + fused sampling tail (scripts/smoke_kernels.py:
#                   interpret-mode kernels == XLA references, fused
#                   first-token tail == unfused sample bitwise, and
#                   reference-route vs forced-kernel engine streams
#                   byte-identical, bf16 and int8 pools).
#  12. flight smoke — CPU gate for the engine flight recorder
#                   (scripts/smoke_flight.py: recorder on by default,
#                   beat records >= decode_steps, recorder-on vs -off
#                   token streams byte-identical, timeline JSON loads
#                   and spans nest, analyzer attribution sums ~100%,
#                   overhead <= 1% on paired bursts).
#  13. multihost smoke — CPU gate for 2-process jax.distributed
#                   serving (scripts/smoke_multihost.py: config-driven
#                   distributed init, follower replay lockstep, streams
#                   byte-identical to a single-process TP=2 engine,
#                   planner-sized page pool + live gauges, stop record
#                   exits the follower cleanly; plus the features-on
#                   leg — speculative tree + step plans + fused
#                   prefill/sampling + prefix cache + kv pager all
#                   replaying byte-identically, warm-turn prefix hit,
#                   zero replay divergences on either rank).
#  14. tier-1 tests — the ROADMAP.md pytest gate.

set -u -o pipefail
cd "$(dirname "$0")/.."

fail=0
step() { echo; echo "== $* =="; }

step "graftlint (python -m generativeaiexamples_tpu.lint)"
# ONE pass: the gate (zero non-baselined findings + no stale baseline
# entries) and the SARIF annotation artifact come from the same run.
mkdir -p build
python -m generativeaiexamples_tpu.lint generativeaiexamples_tpu/ \
    --fail-stale --sarif-out build/lint.sarif || fail=1
if [ -s build/lint.sarif ]; then
    echo "wrote build/lint.sarif ($(wc -c < build/lint.sarif) bytes) — \
CI uploads this for inline code annotations"
else
    echo "build/lint.sarif missing/empty (lint crashed before emitting?)"
    fail=1
fi

step "ruff (scripts/lint.py --ruff; skips when absent)"
if command -v ruff >/dev/null 2>&1; then
    ruff check generativeaiexamples_tpu/ scripts/ tests/ || fail=1
else
    echo "ruff not installed — skipping"
fi

step "config docs drift (scripts/gen_config_docs.py --check)"
python scripts/gen_config_docs.py --check || fail=1

if [ "${1:-}" != "--fast" ]; then
    step "step-plan smoke (JAX_PLATFORMS=cpu scripts/smoke_plan_step.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_plan_step.py || fail=1

    step "router smoke (JAX_PLATFORMS=cpu scripts/smoke_router.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_router.py || fail=1

    step "tiered-ANN smoke (JAX_PLATFORMS=cpu scripts/smoke_tiered_ann.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_tiered_ann.py || fail=1

    step "QoS smoke (JAX_PLATFORMS=cpu scripts/smoke_qos.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_qos.py || fail=1

    step "KV-pager smoke (JAX_PLATFORMS=cpu scripts/smoke_kv_pager.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_kv_pager.py || fail=1

    step "chaos smoke (JAX_PLATFORMS=cpu scripts/smoke_chaos.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_chaos.py || fail=1

    step "disagg smoke (JAX_PLATFORMS=cpu scripts/smoke_disagg.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_disagg.py || fail=1

    step "kernel smoke (JAX_PLATFORMS=cpu scripts/smoke_kernels.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_kernels.py || fail=1

    step "flight smoke (JAX_PLATFORMS=cpu scripts/smoke_flight.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_flight.py || fail=1

    step "multihost smoke (JAX_PLATFORMS=cpu scripts/smoke_multihost.py)"
    JAX_PLATFORMS=cpu python scripts/smoke_multihost.py || fail=1

    step "tier-1 tests (JAX_PLATFORMS=cpu pytest -m 'not slow')"
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider || fail=1
fi

echo
if [ "$fail" -ne 0 ]; then
    echo "ci_checks: FAILED (one or more steps above)"
else
    echo "ci_checks: all steps passed"
fi
exit "$fail"
