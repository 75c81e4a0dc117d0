# ruleset-analysis-tpu — developer targets.
#
# NOTE (tier-1 calibration, tests/conftest.py): NEVER run these targets
# concurrently with the tier-1 gate run on the 1-core container — a
# parallel python process starves the distributed rendezvous tests and
# fabricates failures.  Run `make lint`, THEN the gate.

.PHONY: lint lint-fast test chaos obs postmortem servescale epochstore

# Static program-invariant lint (DESIGN §18): abstract-eval traces of
# the full shipping step grid + the repo registry audit.  No device, no
# XLA compile — finishes in well under 60 s on one CPU core.
lint:
	JAX_PLATFORMS=cpu python tools/ralint.py

# The tier-1 representative subset (what tests/test_ralint.py runs).
lint-fast:
	JAX_PLATFORMS=cpu python tools/ralint.py --fast

# The tier-1 suite (see ROADMAP.md for the exact gate invocation).
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Seeded chaos subset (DESIGN §9/§19): the tier-1 fault schedules plus
# the transient retry-recovery schedules and the WAL/degraded-mode
# suites.  Exit-coded for CI; same 1-core caveat as the gate above.
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py tests/test_retry.py \
		tests/test_wal.py tests/test_failover.py -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Observability-plane subset (DESIGN §15/§20/§24): the obs timeline +
# flight-recorder + device-attribution suites plus the window-lineage /
# SLO burn-rate suite (lineage record identity under failover replay,
# ledger chaos, doctor join, burn-rate hysteresis, /metrics parity).
# Exit-coded for CI; same 1-core caveat as the gate above.
obs:
	JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py tests/test_flightrec.py \
		tests/test_devprof.py tests/test_lineage.py -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Multi-host serve acceptance (DESIGN §22): merged 2-host distributed
# windows and the cumulative report bit-identical to the single-host
# replay of the union with zero drops, and a whole-host kill that names
# the dead host in the window's incomplete marker and loses none of a
# survivor's lines.  Exit-coded; same 1-core caveat as the gate above.
servescale:
	JAX_PLATFORMS=cpu python -m pytest tests/test_distserve.py -q \
		-m 'not slow' --continue-on-collection-errors -p no:cacheprovider

# Durable epoch-store acceptance (DESIGN §25): segment-tree range
# queries bit-identical to a naive linear fold, a serve range report
# answered without replay, and a mid-compaction crash leaving a
# readable store with zero lost epochs.  Exit-coded; same 1-core caveat
# as the gate above.
epochstore:
	JAX_PLATFORMS=cpu python -m pytest tests/test_epochstore.py -q \
		-m 'not slow' --continue-on-collection-errors -p no:cacheprovider

# Doctor acceptance path (DESIGN §20): chaos-killed runs must leave a
# complete postmortem bundle the doctor can diagnose (failing stage +
# fired site), clean exits must leave none, and the serve /metrics
# latency histograms must agree between JSON and prom.  Exit-coded.
postmortem:
	JAX_PLATFORMS=cpu python -m pytest tests/test_flightrec.py -q \
		-m 'not slow' --continue-on-collection-errors -p no:cacheprovider
