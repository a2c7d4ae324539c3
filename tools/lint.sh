#!/bin/sh
# One-command contract lint for builder and hardware sessions: the
# tools/codelint static passes (lock order, blocking-under-lock,
# guarded-by, catalog drift, naked excepts) over the shipped package,
# exiting non-zero on any unbaselined finding or stale suppression.
#
#   tools/lint.sh                  # static passes only (<10s, jax-free)
#   tools/lint.sh --url http://127.0.0.1:9100/metrics --all
#                                  # + runtime exposition lint of a live
#                                  #   /metrics endpoint
#
# Extra arguments pass through to `python -m tools.codelint` (e.g.
# --json -, --pass catalog-drift, --write-baseline).
# The exec below propagates the lint's own exit code.
cd "$(dirname "$0")/.." || exit 1
. tools/_env.sh
exec python -m tools.codelint "$@"
