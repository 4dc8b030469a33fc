# Evidence gates for run directories (sourced, not run).
#
#   source scripts/lib_gate.sh
#   fleet_gate <dir> <train args...> || echo "refused"
#
# Each ``*_gate <dir> <train args...>`` looks at the flags a run was
# trained with and, where they switch a subsystem on, refuses to bless the
# run directory unless that subsystem's determinism/anchor tests pass on
# this checkout (on the CPU: the anchors are bit-identity checks, not
# device measurements).  A passing verdict is stamped into the directory
# so a retry does not pay for the tests again; a run that does not use
# the subsystem passes through untouched.

# Pipelined evidence gate (ISSUE 2): a run dir trained with --pipeline 1
# may only be blessed (.done) if the pipeline=off determinism test passes
# on this checkout — proof the executor's schedule is still bit-faithful
# to the phase-locked trainer before any pipelined number becomes
# evidence (docs/PIPELINE.md "Determinism contract").  The verdict is
# stamped per run dir so retries (and the eval-only path) don't re-pay
# the ~2 min test; non-pipelined runs pass through untouched.
#   pipeline_gate <dir> <train args...>
pipeline_gate() {
  local dir=$1
  shift
  case " $* " in
    *" --pipeline 1 "*) ;;
    *) return 0 ;;  # not a pipelined run: nothing to gate
  esac
  if [ -f "$dir/.pipeline_determinism_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_pipeline.py -q -p no:cacheprovider \
         -k determinism \
       > "$dir/pipeline_gate.log" 2>&1; then
    touch "$dir/.pipeline_determinism_ok"
    return 0
  fi
  return 1
}

# Fleet evidence gate (ISSUE 4): a run dir trained with --actors N may
# only be blessed (.done) if the fleet=off determinism test passes on this
# checkout — proof that wiring the fleet subsystem into train.py left the
# default schedule bit-faithful to Trainer.run before any fleet number
# becomes evidence (docs/FLEET.md "Determinism anchor").  Same stamping
# discipline as pipeline_gate; non-fleet runs pass through untouched.
#   fleet_gate <dir> <train args...>
fleet_gate() {
  local dir=$1
  shift
  case " $* " in
    *" --actors "[1-9]*) ;;
    *) return 0 ;;  # not a fleet run (or --actors 0): nothing to gate
  esac
  # Record the NEGOTIATED wire lane in the evidence dir (ISSUE 5): a
  # fleet number's meaning depends on what crossed the wire (bf16 and
  # compressed lanes are different — equally valid — trajectories), so
  # the blessing stamps which lane produced it.  Defaults mirror
  # train.py's (--fleet-wire f32 --fleet-compress none --drain-coalesce 1).
  local _fw_enc=f32 _fw_comp=none _fw_coal=1 _fw_prev=""
  local _fw_arg
  for _fw_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_fw_arg" in
      --fleet-wire=*) _fw_enc=${_fw_arg#*=} ;;
      --fleet-compress=*) _fw_comp=${_fw_arg#*=} ;;
      --drain-coalesce=*) _fw_coal=${_fw_arg#*=} ;;
    esac
    case "$_fw_prev" in
      --fleet-wire) _fw_enc=$_fw_arg ;;
      --fleet-compress) _fw_comp=$_fw_arg ;;
      --drain-coalesce) _fw_coal=$_fw_arg ;;
    esac
    _fw_prev=$_fw_arg
  done
  printf 'encoding=%s compress=%s drain_coalesce=%s\n' \
    "$_fw_enc" "$_fw_comp" "$_fw_coal" > "$dir/fleet_wire.txt"
  if [ -f "$dir/.fleet_determinism_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_fleet.py -q -p no:cacheprovider \
         -k determinism \
       > "$dir/fleet_gate.log" 2>&1; then
    touch "$dir/.fleet_determinism_ok"
    return 0
  fi
  return 1
}

# Chaos drill gate (ISSUE 7): a run dir trained with --actors N may only
# be blessed (.done) if the non-slow chaos drills pass on this checkout —
# proof that every documented recovery path (heartbeat reap, CRC reject,
# reconnect, backoff restart, checkpoint/resume) still recovers before
# any fleet number becomes evidence (docs/FLEET.md "Failure modes &
# recovery").  The deterministic seeded single-fault drills only; the
# multi-fault subprocess soak stays a slow-marked pytest.  Same stamping
# discipline as fleet_gate; non-fleet runs pass through untouched.
#   chaos_gate <dir> <train args...>
chaos_gate() {
  local dir=$1
  shift
  case " $* " in
    *" --actors "[1-9]*) ;;
    *) return 0 ;;  # not a fleet run (or --actors 0): nothing to gate
  esac
  if [ -f "$dir/.chaos_drills_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_chaos.py -q -p no:cacheprovider \
         -m 'not slow' \
       > "$dir/chaos_gate.log" 2>&1; then
    touch "$dir/.chaos_drills_ok"
    return 0
  fi
  return 1
}

# Learner-dp evidence gate (ISSUE 9): a run dir trained with
# --learner-dp N may only be blessed (.done) if the dp determinism anchor
# passes on this checkout — proof the dp-mesh layout annotations change
# no bit of the trajectory before any multi-chip learner number becomes
# evidence (docs/FLEET.md "Multi-chip learner").  The resolved dp width
# is stamped into the evidence dir beside fleet_wire.txt either way, so
# a blessed number always says which mesh produced it.  Same stamping
# discipline as fleet_gate; non-dp runs pass through untouched.
#   learner_dp_gate <dir> <train args...>
learner_dp_gate() {
  local dir=$1
  shift
  local _dp="" _dp_prev=""
  local _dp_arg
  for _dp_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_dp_arg" in
      --learner-dp=*) _dp=${_dp_arg#*=} ;;
    esac
    case "$_dp_prev" in
      --learner-dp) _dp=$_dp_arg ;;
    esac
    _dp_prev=$_dp_arg
  done
  if [ -z "$_dp" ] || [ "$_dp" = 0 ]; then
    return 0  # not a dp-learner run: nothing to gate
  fi
  printf 'learner_dp=%s\n' "$_dp" > "$dir/learner_dp.txt"
  if [ -f "$dir/.learner_dp_determinism_ok" ]; then
    return 0
  fi
  # NB every gate pytest line clears XLA_FLAGS: a --learner-dp evidence
  # run exports --xla_force_host_platform_device_count=D, and an
  # inherited D != 8 fails tests/conftest.py's 8-device assert during
  # collection — the gate would loop "FAILED" forever on a healthy
  # anchor.
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_dp_learner.py -q -p no:cacheprovider \
         -k determinism \
       > "$dir/learner_dp_gate.log" 2>&1; then
    touch "$dir/.learner_dp_determinism_ok"
    return 0
  fi
  return 1
}

# Sampler evidence gate (ISSUE 10): a run dir trained with
# --replay-shards N may only be blessed (.done) if the in-network-
# sampling anchors pass on this checkout — the --replay-shards 1
# --actors 0 CLI path bit-identical to Trainer.run (wiring the knob
# changes no bit of the default schedule) AND the two-level sharded
# draw distribution-equivalent to central proportional sampling on
# exact-integer priorities (docs/REPLAY.md "Determinism anchor").  The
# resolved shard count is stamped into the evidence dir
# (replay_shards.txt) beside fleet_wire.txt, so a blessed number always
# says which replay topology produced it.  Same stamping discipline as
# fleet_gate; non-sharded runs pass through untouched.
#   sampler_gate <dir> <train args...>
sampler_gate() {
  local dir=$1
  shift
  local _rs="" _rs_prev=""
  local _rs_arg
  for _rs_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_rs_arg" in
      --replay-shards=*) _rs=${_rs_arg#*=} ;;
    esac
    case "$_rs_prev" in
      --replay-shards) _rs=$_rs_arg ;;
    esac
    _rs_prev=$_rs_arg
  done
  if [ -z "$_rs" ] || [ "$_rs" = 0 ]; then
    return 0  # not a sharded-replay run: nothing to gate
  fi
  printf 'replay_shards=%s\n' "$_rs" > "$dir/replay_shards.txt"
  if [ -f "$dir/.sampler_equivalence_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_sampler.py -q -p no:cacheprovider \
         -k 'determinism or equivalence' \
       > "$dir/sampler_gate.log" 2>&1; then
    touch "$dir/.sampler_equivalence_ok"
    return 0
  fi
  return 1
}

# Scrape-evidence check for --shard-procs dirs (ISSUE 13): every live
# shard 0..N-1 must have its labelled occupancy series in the run's
# final merged scrape, and every shard HOLDING data must have folded at
# least one TELEM snapshot (r2d2dpg_shard_telem_frames_total > 0).  The
# advert-mirror occupancy series alone is the learner talking to itself
# (RemoteShardSet registers it for every shard unconditionally), so it
# cannot distinguish an observability-dark shard proc from a healthy
# one — the TELEM counter only gets a labelled cell when a shard-proc
# snapshot actually crossed the wire and folded.  Idle shards (advert
# occupancy 0; the learner dials lazily, so an untrafficked shard never
# HELLOs and never pushes) are exempt — shard_skew is their signal.
# NB this means --shard-procs evidence must run the health plane
# (--obs-fleet 1 arms the shard-proc TELEM cadence).  Cheap (grep per
# shard), so it re-runs on every gate pass instead of hiding behind the
# anchor stamp.
#   shard_scrape_check <dir> <num_shards>
shard_scrape_check() {
  local dir=$1 n=$2 i occ prom
  prom=$dir/metrics_final.prom
  if [ ! -f "$prom" ]; then
    echo "$dir: shard_gate: metrics_final.prom missing — the run left no" \
         "final scrape to attribute the shard tier's numbers to"
    return 1
  fi
  for i in $(seq 0 $((n - 1))); do
    if ! grep -Eq "r2d2dpg_replay_shard_occupancy\{[^}]*shard=\"$i\"" \
         "$prom"; then
      echo "$dir: shard_gate: scrape lacks shard $i's labelled occupancy" \
         "series (metrics_final.prom) — an observability-dark shard" \
         "cannot be blessed as evidence"
      return 1
    fi
    # The advert-mirror series renders with shard= as its only label;
    # the TELEM-folded copy carries host= attribution.
    occ=$(grep -E "^r2d2dpg_replay_shard_occupancy\{shard=\"$i\"\} " \
            "$prom" | head -1 | awk '{print $2}')
    if [ -n "$occ" ] && awk -v o="$occ" 'BEGIN{exit !(o > 0)}'; then
      if ! grep -E \
           "^r2d2dpg_shard_telem_frames_total\{[^}]*shard=\"$i\"[^}]*\} " \
           "$prom" | awk '{s+=$2} END{exit !(s > 0)}'; then
        echo "$dir: shard_gate: shard $i holds data (advert occupancy" \
          "$occ) but folded no TELEM snapshot (metrics_final.prom has no" \
          "r2d2dpg_shard_telem_frames_total{shard=\"$i\"} > 0) — an" \
          "observability-dark shard proc cannot be blessed as evidence" \
          "(run with --obs-fleet 1)"
        return 1
      fi
    fi
  done
  return 0
}

# Standalone-shard-tier gate (ISSUE 12): a run dir trained with
# --shard-procs N may only be blessed (.done) if the shard-tier anchors
# pass on this checkout — the loopback-vs-out-of-process determinism
# anchor (a BATCH through a real socket decodes bit-identically to the
# in-learner loopback; plus the --shard-procs 0 off-setting riding the
# sampler CLI anchor) AND the non-slow kill_shard chaos drill (2 actors
# x 2 shard procs: run completes, quotas renormalize to the survivor,
# the restarted shard rejoins under a bumped epoch, stale-epoch frames
# fenced — docs/REPLAY.md "Standalone shard tier").  The resolved proc
# count is stamped into the evidence dir (shard_procs.txt) beside
# replay_shards.txt, so a blessed number always says where replay
# LIVED.  Same stamping discipline as fleet_gate; loopback runs pass
# through untouched.
#
# ISSUE 13 adds the scrape-evidence clause: the run's final merged
# scrape (metrics_final.prom, written by train.py's fleet teardown)
# must carry EVERY shard's labelled occupancy series — a shard that is
# observability-dark (its TELEM never folded, its advert mirror never
# registered) must not be blessed as evidence, because the numbers it
# contributed cannot be attributed on the one fleet /metrics page.
#
# ISSUE 17 adds the direct-data-plane clause: a run trained with
# --shard-direct 1 (actors pushing SEQS straight to shard procs,
# learner forward hop shed) may only be blessed if BOTH the
# -m shard_direct suite (assignment acks, K_STATS at-least-once
# accounting, per-plane byte separation, puller bit-determinism,
# coalesced PRIO golden) AND the partition_data_plane fallback drill
# (chaos e2e: dial refused mid-run -> loud fallback to the forwarded
# path, zero lost accounting) pass on this checkout, alongside the
# --shard-direct 0 bitwise CLI anchor that the 'determinism' -k
# selection already carries.  Direct evidence WITHOUT a passing
# fallback drill is refused outright: a data plane that has never
# demonstrated its escape hatch cannot be blessed.  The resolved flag
# is stamped (shard_direct.txt beside shard_procs.txt) so a blessed
# number always says which experience path produced it.
#   shard_gate <dir> <train args...>
shard_gate() {
  local dir=$1
  shift
  local _sp="" _rs="" _sd="" _sp_prev=""
  local _sp_arg
  for _sp_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_sp_arg" in
      --shard-procs=*) _sp=${_sp_arg#*=} ;;
      --replay-shards=*) _rs=${_sp_arg#*=} ;;
      --shard-direct=*) _sd=${_sp_arg#*=} ;;
    esac
    case "$_sp_prev" in
      --shard-procs) _sp=$_sp_arg ;;
      --replay-shards) _rs=$_sp_arg ;;
      --shard-direct) _sd=$_sp_arg ;;
    esac
    _sp_prev=$_sp_arg
  done
  if [ -z "$_sp" ] || [ "$_sp" = 0 ]; then
    return 0  # in-learner loopback (or no sampler path): nothing to gate
  fi
  printf 'shard_procs=%s\n' "$_sp" > "$dir/shard_procs.txt"
  printf 'shard_direct=%s\n' "${_sd:-0}" > "$dir/shard_direct.txt"
  if ! shard_scrape_check "$dir" "${_rs:-$_sp}"; then
    return 1
  fi
  if [ -n "$_sd" ] && [ "$_sd" != 0 ] \
     && ! [ -f "$dir/.shard_direct_ok" ]; then
    # Fallback drill + direct-plane suite, refused-not-skipped: every
    # test in the file carries the shard_direct mark, so -m shard_direct
    # deliberately includes the slow e2e pair (direct run + the
    # partition_data_plane fallback drill) — the drill is the point.
    if ! timeout --kill-after=30 900 \
         env JAX_PLATFORMS=cpu \
         R2D2DPG_PALLAS_INTERPRET=1 XLA_FLAGS= \
         python -m pytest tests/test_shard_direct.py \
           -q -p no:cacheprovider -m shard_direct \
         > "$dir/shard_direct_gate.log" 2>&1; then
      echo "$dir: shard_gate: --shard-direct evidence REFUSED — the" \
        "direct-plane suite or the partition_data_plane fallback drill" \
        "failed on this checkout (shard_direct_gate.log); a data plane" \
        "without a demonstrated escape hatch cannot be blessed"
      return 1
    fi
    touch "$dir/.shard_direct_ok"
  fi
  if [ -f "$dir/.shard_tier_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_shard.py tests/test_sampler.py \
         tests/test_shard_direct.py \
         -q -p no:cacheprovider -m 'not slow' \
         -k 'determinism or kill_shard or shard_direct or coalesce' \
       > "$dir/shard_gate.log" 2>&1; then
    touch "$dir/.shard_tier_ok"
    return 0
  fi
  return 1
}

# Composed-topology gate (ISSUE 11): a run dir trained with MORE THAN
# ONE scaling axis (--actors N plus --replay-shards N and/or
# --learner-dp N) may only be blessed (.done) if the per-pairing anchors
# pass on this checkout — the composed off-settings determinism anchor
# (--replay-shards 1 --learner-dp 1 --actors 0 bit-identical to
# Trainer.run through the CLI) and the sampler+dp bitwise learn anchor
# (tests/test_topology.py; docs/TOPOLOGY.md "Determinism anchors").  The
# resolved axis triple is stamped into the evidence dir (topology.txt,
# beside fleet_wire.txt/learner_dp.txt) for ANY multi-axis run, so a
# blessed number always says which composition produced it.  Single-axis
# runs pass through untouched — their own gates (fleet_gate,
# learner_dp_gate, sampler_gate) already cover them.
#   topology_gate <dir> <train args...>
topology_gate() {
  local dir=$1
  shift
  local _tg_actors=0 _tg_shards=0 _tg_dp=0 _tg_prev=""
  local _tg_arg
  for _tg_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_tg_arg" in
      --actors=*) _tg_actors=${_tg_arg#*=} ;;
      --replay-shards=*) _tg_shards=${_tg_arg#*=} ;;
      --learner-dp=*) _tg_dp=${_tg_arg#*=} ;;
    esac
    case "$_tg_prev" in
      --actors) _tg_actors=$_tg_arg ;;
      --replay-shards) _tg_shards=$_tg_arg ;;
      --learner-dp) _tg_dp=$_tg_arg ;;
    esac
    _tg_prev=$_tg_arg
  done
  local _tg_axes=0
  [ "${_tg_actors:-0}" != 0 ] && _tg_axes=$((_tg_axes + 1))
  [ "${_tg_shards:-0}" != 0 ] && _tg_axes=$((_tg_axes + 1))
  [ "${_tg_dp:-0}" != 0 ] && _tg_axes=$((_tg_axes + 1))
  if [ "$_tg_axes" -lt 2 ]; then
    return 0  # single-axis run: its own gate covers it
  fi
  # train.py already stamps the richer four-stage describe() line into
  # <logdir>/topology.txt (it contains the actors=/replay_shards=/
  # learner_dp= triple); only write the fallback triple when the run
  # predates that stamp or used a different logdir.
  if ! [ -f "$dir/topology.txt" ]; then
    printf 'actors=%s replay_shards=%s learner_dp=%s\n' \
      "$_tg_actors" "$_tg_shards" "$_tg_dp" > "$dir/topology.txt"
  fi
  if [ -f "$dir/.topology_anchors_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_topology.py -q -p no:cacheprovider \
         -k 'determinism or anchor' \
       > "$dir/topology_gate.log" 2>&1; then
    touch "$dir/.topology_anchors_ok"
    return 0
  fi
  return 1
}

# Device-plane gate (ISSUE 14): NO evidence dir may be blessed (.done)
# while any of its flight dumps carries a steady_recompile event — a
# learn/drain program whose avals re-keyed after warm-up recompiled
# mid-measurement, so every rate in the dir includes a silent multi-
# second stall the record doesn't explain (the exact bug class the
# PR 9/11 out_shardings pins exist to prevent; obs/device.py is the
# sentinel).  Applies to EVERY run shape — the phase-locked loop arms
# the sentinel too — and re-runs on every gate pass (a cheap grep; no
# stamp file to go stale).  The verdict is stamped device_obs.txt
# beside topology.txt either way, so a blessed number always says its
# steady window was compile-clean.  Runs predating the sentinel leave
# no flight dumps with the event and pass through unchanged.
#   device_gate <dir> <train args...>
device_gate() {
  local dir=$1
  shift
  local f n hits=0 dumps=0
  for f in "$dir"/flight*.jsonl; do
    [ -f "$f" ] || continue
    dumps=$((dumps + 1))
    n=$(grep -c '"kind": "steady_recompile"' "$f")
    hits=$((hits + ${n:-0}))
  done
  printf 'steady_recompiles=%s flight_dumps=%s\n' "$hits" "$dumps" \
    > "$dir/device_obs.txt"
  if [ "$hits" -gt 0 ]; then
    echo "$dir: device_gate: $hits steady_recompile event(s) in the" \
         "run's flight dumps — a learn/drain program re-keyed mid-run" \
         "(grep steady_recompile $dir/flight*.jsonl for the program" \
         "labels); compile-stalled rates cannot be blessed as evidence"
    return 1
  fi
  return 0
}

# Autoscale evidence gate (ISSUE 16): a run dir trained with
# --autoscale 1 may only be blessed (.done) if (a) the non-slow
# kill-drill recovery test passes on this checkout — proof the policy
# loop (not the backoff ladder) restores a killed actor, with zero
# crash-restarts and zero sheds (tests/test_autoscaler.py) — and (b)
# every autoscale_action event in the dir's flight dumps pairs with a
# LANDED origin="autoscale" spawn/retire actuation: an action the
# supervisor never executed is a policy engine claiming recoveries it
# didn't perform, and no rate measured under it can be blessed.  The
# resolved autoscale knobs are stamped into the evidence dir
# (autoscale.txt), so a blessed number always says which policy bounds
# governed its population.  --autoscale 0 runs pass through untouched
# (the mode is structurally inert there — topology determinism anchors
# cover it).  Metric names (r2d2dpg_autoscale_*) conform to the
# lint_obs.sh scheme check; no allowlist entry needed.
#   autoscale_gate <dir> <train args...>
autoscale_gate() {
  local dir=$1
  shift
  local _as="" _as_min="" _as_max="" _as_prev=""
  local _as_arg
  for _as_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_as_arg" in
      --autoscale=*) _as=${_as_arg#*=} ;;
      --autoscale-min=*) _as_min=${_as_arg#*=} ;;
      --autoscale-max=*) _as_max=${_as_arg#*=} ;;
    esac
    case "$_as_prev" in
      --autoscale) _as=$_as_arg ;;
      --autoscale-min) _as_min=$_as_arg ;;
      --autoscale-max) _as_max=$_as_arg ;;
    esac
    _as_prev=$_as_arg
  done
  if [ -z "$_as" ] || [ "$_as" = 0 ]; then
    return 0  # autoscale off: structurally inert, nothing to gate
  fi
  printf 'autoscale=%s min=%s max=%s\n' \
    "$_as" "${_as_min:-1}" "${_as_max:-actors}" > "$dir/autoscale.txt"
  # (b) action/actuation pairing over the run's own flight dumps — a
  # cheap scan, re-checked on every pass (no stamp to go stale).
  if ! python - "$dir"/flight*.jsonl <<'PYEOF'
import json
import sys

bad = False
for path in sys.argv[1:]:
    try:
        lines = open(path).read().splitlines()
    except OSError:
        continue
    actions = 0
    landed = 0
    for line in lines:
        try:
            e = json.loads(line)
        except ValueError:
            continue
        kind = e.get("kind", "")
        if kind == "autoscale_action":
            actions += 1
        elif (
            kind in ("actor_spawn", "actor_retire",
                     "shard_spawn", "shard_retire")
            and e.get("origin") == "autoscale"
        ):
            landed += 1
    if actions > landed:
        print(
            f"{path}: {actions} autoscale_action event(s) but only "
            f"{landed} landed origin=autoscale spawn/retire event(s) — "
            "the policy loop claimed an actuation the supervisor never "
            "executed"
        )
        bad = True
sys.exit(1 if bad else 0)
PYEOF
  then
    echo "$dir: autoscale_gate: flight dumps fail the action/actuation" \
         "pairing check (see lines above)"
    return 1
  fi
  # (a) the kill-drill recovery anchor, stamped per dir like the other
  # pytest-backed gates.
  if [ -f "$dir/.autoscale_recovery_ok" ]; then
    return 0
  fi
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_autoscaler.py -q -p no:cacheprovider \
         -m 'not slow' -k kill_drill \
       > "$dir/autoscale_gate.log" 2>&1; then
    touch "$dir/.autoscale_recovery_ok"
    return 0
  fi
  return 1
}

# Experience-quality gate (ISSUE 18): a fleet run (--actors N) with the
# obs plane armed (--obs-fleet 1) may only be blessed (.done) if its
# final merged scrape carries an ARMED policy-lag distribution — the
# r2d2dpg_quality_policy_lag series with count > 0.  On such a run every
# drained sequence carries wire provenance (the actor stamps its
# behavior param version at staging), so a scrape without the lag
# series means the quality plane went dark: the run's numbers cannot
# say how STALE the experience they trained on was, and a rate measured
# over unknown-staleness experience is not evidence (the failure mode
# the plane exists to expose — a fleet can be green on every liveness
# signal while training on garbage).  The verdict context is stamped
# quality.txt beside autoscale.txt either way — threshold + armed lag
# count — so a blessed number always says what staleness bound it was
# judged under.  Cheap (grep + awk), so it re-runs on every gate pass
# instead of hiding behind a stamp.  --actors 0 runs pass through
# untouched: no wire hop means no provenance and the lag axis stays
# structurally disarmed (docs/OBSERVABILITY.md "Experience-quality
# plane").
#   quality_gate <dir> <train args...>
quality_gate() {
  local dir=$1
  shift
  local _qa=0 _qo=0 _ql="" _q_prev=""
  local _q_arg
  for _q_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_q_arg" in
      --actors=*) _qa=${_q_arg#*=} ;;
      --obs-fleet=*) _qo=${_q_arg#*=} ;;
      --quality-max-lag=*) _ql=${_q_arg#*=} ;;
    esac
    case "$_q_prev" in
      --actors) _qa=$_q_arg ;;
      --obs-fleet) _qo=$_q_arg ;;
      --quality-max-lag) _ql=$_q_arg ;;
    esac
    _q_prev=$_q_arg
  done
  if [ "${_qa:-0}" = 0 ] || [ "${_qo:-0}" = 0 ]; then
    return 0  # no wire provenance or no obs plane: lag axis disarmed
  fi
  local prom=$dir/metrics_final.prom lag_count
  if [ ! -f "$prom" ]; then
    echo "$dir: quality_gate: metrics_final.prom missing — the run left" \
         "no final scrape to judge experience staleness from"
    return 1
  fi
  lag_count=$(grep -E '^r2d2dpg_quality_policy_lag_count' "$prom" \
                | awk '{s+=$2} END{print s+0}')
  printf 'quality_max_lag=%s policy_lag_count=%s\n' \
    "${_ql:-100.0}" "${lag_count:-0}" > "$dir/quality.txt"
  if ! awk -v c="${lag_count:-0}" 'BEGIN{exit !(c > 0)}'; then
    echo "$dir: quality_gate: metrics_final.prom lacks an armed" \
         "r2d2dpg_quality_policy_lag series (count=$lag_count) on an" \
         "--actors run with --obs-fleet 1 — the quality plane went dark" \
         "and the run cannot say how stale its trained experience was;" \
         "unknown-staleness rates cannot be blessed as evidence"
    return 1
  fi
  return 0
}

# Serving scale-out gate (ISSUE 20): an evidence dir produced with
# --serve-workers N (N >= 2, e.g. a routed serve deployment's obs
# capture) may only be blessed if the off-setting
# anchors pass on this checkout — the 1-worker router path bit-identical
# to the PR-1 PolicyService through the serve CLI, interleaved routed
# traffic bit-identical per session to sequential rollouts, and the
# rendezvous hash's determinism/coverage pins (docs/SERVING.md
# "Scale-out").  A routed p50/p99 number over traffic that silently lost
# a session's carry to an affinity bug is not evidence.  The resolved
# worker count is stamped into the evidence dir (serve_workers.txt)
# beside the other topology stamps, so a blessed number always says how
# many workers served it.  Same stamping discipline as fleet_gate;
# single-worker runs pass through untouched.
#   serve_gate <dir> <serve args...>
serve_gate() {
  local dir=$1
  shift
  local _sw="" _sw_prev=""
  local _sw_arg
  for _sw_arg in "$@"; do
    # Both argparse spellings: "--flag value" and "--flag=value".
    case "$_sw_arg" in
      --serve-workers=*) _sw=${_sw_arg#*=} ;;
    esac
    case "$_sw_prev" in
      --serve-workers) _sw=$_sw_arg ;;
    esac
    _sw_prev=$_sw_arg
  done
  if [ -z "$_sw" ] || [ "$_sw" = 0 ] || [ "$_sw" = 1 ]; then
    return 0  # single-worker (or non-serve) run: nothing to gate
  fi
  printf 'serve_workers=%s\n' "$_sw" > "$dir/serve_workers.txt"
  if [ -f "$dir/.serve_anchor_ok" ]; then
    return 0
  fi
  # XLA_FLAGS cleared like every gate pytest line: a serve evidence run
  # exports forced host devices, and an inherited count breaks
  # tests/conftest.py's device assert during collection.
  if timeout --kill-after=30 900 \
       env JAX_PLATFORMS=cpu R2D2DPG_PALLAS_INTERPRET=1 \
       XLA_FLAGS= \
       python -m pytest tests/test_serve_router.py tests/test_serve_cli.py \
         -q -p no:cacheprovider -m 'not slow' \
         -k 'bit_identical or affine or rendezvous' \
       > "$dir/serve_gate.log" 2>&1; then
    touch "$dir/.serve_anchor_ok"
    return 0
  fi
  return 1
}
