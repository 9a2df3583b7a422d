# C11/C13 parity: canned topologies and dev targets (reference Makefile:1-38).
# The reference's 3-process PS topology on localhost keeps the same names:
#   make server / make first / make second  (world-size 3, rank 0 = server)
# plus `make launch` which runs all three in one command.

PY ?= python

# --- canned PS topology (reference Makefile:13-20) ---
# A chip belongs to one process, so the hand-launched ranks run on the CPU
# platform (same env that distributed_ml_pytorch_tpu.launch gives `make
# launch`); `launch --tpu` / `--tpu-worker` hand worker ranks a chip each.
PS_ENV = JAX_PLATFORMS=cpu

first:
	$(PS_ENV) $(PY) -m distributed_ml_pytorch_tpu.training.cli --mode ps --rank 1 --world-size 3

second:
	$(PS_ENV) $(PY) -m distributed_ml_pytorch_tpu.training.cli --mode ps --rank 2 --world-size 3

server:
	$(PS_ENV) $(PY) -m distributed_ml_pytorch_tpu.training.cli --mode ps --rank 0 --world-size 3 --server

launch:
	$(PY) -m distributed_ml_pytorch_tpu.launch --world-size 3

# sharded parameter server (DistBelief layout): 2 shard servers + 2 workers
sharded:
	$(PY) -m distributed_ml_pytorch_tpu.launch --world-size 4 --n-servers 2

# --- single-process baselines (reference Makefile:22-26; `gpu` → `tpu`) ---
single:
	$(PY) -m distributed_ml_pytorch_tpu.training.cli --no-distributed --backend cpu

tpu:
	$(PY) -m distributed_ml_pytorch_tpu.training.cli --no-distributed --backend tpu

gpu: tpu

# --- TPU-native extras ---
sync:
	$(PY) -m distributed_ml_pytorch_tpu.training.cli --mode sync

local-sgd:
	$(PY) -m distributed_ml_pytorch_tpu.training.cli --mode local-sgd

# two devices: real chips when the host has them, else the explicit CPU
# simulation (P2P_ENV="JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=2")
p2p:
	$(P2P_ENV) $(PY) -m distributed_ml_pytorch_tpu.parallel.p2p

# continuous-batching inference hub (serving/cli.py); CTRL-C prints the
# SLO summary. `make serve-demo` runs the self-contained in-process demo.
serve:
	$(PY) -m distributed_ml_pytorch_tpu.serving.cli

serve-demo:
	$(PY) -m distributed_ml_pytorch_tpu.serving.cli --demo 6

# fleet serving (serving/fleet.py): 3 engine replicas behind a FleetRouter
# — occupancy + session-affinity routing, stream migration across engine
# death, overload shed/brownout. CTRL-C prints the fleet summary.
serve-fleet:
	$(PY) -m distributed_ml_pytorch_tpu.serving.cli --fleet 3

serve-fleet-demo:
	$(PY) -m distributed_ml_pytorch_tpu.serving.cli --fleet 2 --demo 6

bench:
	$(PY) bench.py

# the quickest proof that the system still starts on the chip (needs a TPU;
# exits non-zero without one) — trainer, DownPour world, LM, engine
chip-smoke:
	$(PY) chip_smoke.py

# MFU regression gate (ISSUE 9): re-checks a bench record's per-leg MFU
# against the recorded floors in bench_floors.json and exits non-zero on
# a breach or a missing leg. The default target is the no-device smoke on
# a canned record (gate LOGIC is exercised; wired into `make test`);
# on a machine with the chip: python bench.py --gate
bench-gate:
	$(PY) bench.py --gate --json tests/data/bench_gate_smoke.json

# seeded fault-injection suite (utils/chaos.py + the reliability layer):
# deterministic drop/dup/corrupt/partition/crash scenarios on the PS and
# serving planes, soak variants included (they carry both markers)
chaos:
	$(PY) -m pytest tests/ -q -m chaos

# codec-plane suite (utils/codecs.py, ISSUE 18): WIRE_PLANES registry
# totality over the codec-id-bearing schemas, loss-contract numerics
# (int8 bound, tok16 exactness, delta-reply identity on the real server)
codec:
	$(PY) -m pytest tests/ -q -m codec

# elastic control-plane suite (coord/): membership + leases, coordinator-
# driven shard rebalancing (the join/crash acceptance scenario), straggler
# speculation with first-result-wins dedup, serving fleet hook
coord:
	$(PY) -m pytest tests/ -q -m coord

# disaster-recovery drill suite (coord/drill.py + utils/wal.py): snapshot
# barrier -> kill shard subsets mid-epoch -> restore from manifest + WAL
# with zero acked-update loss, byte-identical fault logs across repeats;
# soak variants additionally carry the slow marker
drill:
	$(PY) -m pytest tests/ -q -m drill

# one-command drill demo (prints MTTR + replayed counts + accounting)
drill-demo:
	$(PY) -m distributed_ml_pytorch_tpu.coord.cli --drill

# fleet-serving suite (serving/fleet.py): multi-engine routing, stream
# migration across engine death (token-identical, byte-identical chaos
# logs), overload shed/brownout, per-engine lease health
fleet:
	$(PY) -m pytest tests/ -q -m fleet

# overload soak (slow-marked): the fleet at 2x its sustainable arrival
# rate must shed/brownout instead of collapsing — goodput-under-SLO >= 80%
# of the 1x value and every shed request explicitly rejected
soak:
	$(PY) -m pytest tests/ -q -m soak

# numerical-health suite (ISSUE 8): admission gate + UpdateNack quarantine,
# SDC chaos (bit-perfect-on-the-wire payload corruption), worker reputation,
# and the coordinator auto-rollback barrier — the acceptance proves >=1
# automatic rollback under a seeded poisoned worker with byte-identical
# chaos logs and zero poison in any WAL
health:
	$(PY) -m pytest tests/ -q -m health

# one-command health demo (prints rollback MTTR, quarantine/nack counts,
# reputation revocations)
health-demo:
	$(PY) -m distributed_ml_pytorch_tpu.coord.cli --health

# MPMD pipeline-plane suite (ISSUE 10): stages as fleet members — per-stage
# compiled programs over the reliable wire, coordinator StagePlacement,
# stage kill -> lease-expiry detection -> checkpoint restart with
# watermark-bounded microbatch replay (byte-identical chaos logs 3x),
# stage speculation via standby takeover
mpmd:
	$(PY) -m pytest tests/ -q -m mpmd

# one-command MPMD demo (prints the loss trajectory, stage-restart MTTR,
# applied-microbatch accounting and chaos counts)
mpmd-demo:
	$(PY) -m distributed_ml_pytorch_tpu.coord.cli --mpmd

# timeline analyzer (ISSUE 12): merge a run's flight-recorder dumps and
# attribute each stage's wall clock (compute / wait-act / wait-grad /
# wire-blocked / ckpt) plus the wire's share (retransmits, credit-block,
# ack frames), and fail when a stage's exclusive states do not sum to its
# wall clock: make timeline TIMELINE_DIR=path/to/obs
timeline:
	@test -n "$(TIMELINE_DIR)" || (echo "pass TIMELINE_DIR=<dir of flight_*.jsonl dumps>"; exit 1)
	$(PY) -m distributed_ml_pytorch_tpu.analysis timeline $(TIMELINE_DIR)

# multi-tenant scheduler suite (ISSUE 16, coord/sched.py + coord/tenants.py):
# capacity ledger exclusivity, admit/pack/preempt/resume protocol against a
# real coordinator, autoscale actuation, and the park-and-restore drill
# (preempt a LIVE training shard at peak, resume bit-for-bit off-peak,
# byte-identical chaos logs 3x)
sched:
	$(PY) -m pytest tests/ -q -m sched

# one-command scheduler demo (prints preempt/resume MTTR, WAL replay and
# bit-identical restore proof, grants, decision log)
sched-demo:
	$(PY) -m distributed_ml_pytorch_tpu.coord.cli --sched-demo

# control-plane durability suite (ISSUE 17, coord/coordinator.py): the
# coordinator's own WAL+checkpoint restart, monotonic epoch fencing of
# every outbound control frame, the restart grace window, the coordfail
# distmodel plane, and the kill-the-coordinator drill (crash the arbiter
# mid-snapshot-barrier AND mid-preemption, restart, fleet re-attaches
# with nobody evicted and the parked member resumed bit-identically)
coordfail:
	$(PY) -m pytest tests/ -q -m coordfail

# adaptive-wire suite (ISSUE 7): RTT-driven retransmission, window/credit
# backpressure, circuit breakers, and seeded network weather (latency /
# jitter / bandwidth caps / one-way degradation) — the training acceptance
# proves graceful degradation with byte-identical chaos logs
netweather:
	$(PY) -m pytest tests/ -q -m netweather

# gray-failure plane (ISSUE 20, coord/grayhealth.py + utils/chaos.GrayRule):
# adaptive per-member/per-link suspicion on the LeaseRenew evidence tail,
# scheduled one-way partitions / lossy links / injected stalls, and the
# probation -> quarantine -> evict containment ladder; the drill acceptance
# runs a mid-training gray episode 3x with byte-identical chaos logs
gray:
	$(PY) -m pytest tests/ -q -m gray

# distcheck (analysis/): protocol / concurrency / tracing-hygiene static
# analysis over the whole package — exits non-zero on any unsuppressed
# finding that is not in the checked-in baseline. Regenerate the baseline
# (mirrors the slow_tests.txt workflow) with:
#   python tests/regen_distcheck_baseline.py
lint:
	$(PY) -m distributed_ml_pytorch_tpu.analysis --baseline tests/distcheck_baseline.txt

# interprocedural dataflow corpus (ISSUE 19, analysis/distflow.py): the
# DC501-504 seeded-bug/clean-twin tests plus the bounded-state runtime
# witness tests — the checks themselves run inside `make lint`
distflow:
	$(PY) -m pytest tests/ -q -m distflow

# bounded protocol model checker (ISSUE 13, analysis/distmodel.py):
# exhaustively explores small configurations of the extracted wire
# protocol (2 workers x 2 updates PS; 2-life lease plane; 2x2 MPMD
# hand-off) under drop/dup/reorder/crash/restart schedules and fails on
# any exactly-once / acked=>applied / lease-monotonicity /
# watermark-replay violation. Seconds on one core; counterexamples (from
# `--mutate <name>`) are written as ChaosPlan JSON + pytest repro stubs:
#   python -m distributed_ml_pytorch_tpu.analysis distmodel --mutate no_dedup --out /tmp/ce
distmodel:
	$(PY) -m distributed_ml_pytorch_tpu.analysis distmodel

# fast core signal: distcheck + the bounded model check + the MFU-gate
# smoke + everything that runs in-process (no subprocess worlds, no
# end-to-end example trainings) — minutes on one core
test: lint distmodel bench-gate
	$(PY) -m pytest tests/ -x -q -m "not slow"

# the whole suite, subprocess worlds included (tens of minutes on one core)
test-all:
	$(PY) -m pytest tests/ -x -q

# --- plots (reference Makefile:8-11) ---
graph:
	$(PY) -m distributed_ml_pytorch_tpu.graph
	mkdir -p docs && mv train_time.png test_time.png docs/

# --- packaging (reference Makefile:28-38) ---
install:
	pip install .

dist:
	$(PY) setup.py sdist bdist_wheel

.PHONY: chip-smoke first second server launch sharded single tpu gpu sync local-sgd p2p serve serve-demo serve-fleet serve-fleet-demo bench bench-gate timeline chaos codec coord coordfail distflow drill drill-demo fleet gray health health-demo mpmd mpmd-demo netweather sched sched-demo soak lint distmodel test test-all graph install dist
