"""Client-scaling benchmark: round wall-clock vs N, sequential vs vectorized.

The paper's scalability claim is that CoRS cost does not blow up with the
number of users; the sequential simulation harness did (one Python dispatch
chain — relay, jitted update, EAGER upload computation — per client per
round). This measures the post-compile wall-clock of a full round (relay,
local updates, uploads, merge, eval) for both engines, weak-scaling: fixed
samples per client, so total work grows with N and a perfectly-scaling
engine has flat per-client cost.

Model choice matters for what you measure:
  - "mlp" (default): cheap per-client compute, so the number isolates the
    ENGINE overhead the vectorized path removes — this is where the
    >= 3x @ 32-clients acceptance bar applies.
  - "cnn": the paper's LeNet. On a few-core CPU its conv FLOPs saturate the
    machine under either engine, so the ratio measures compute batching
    (~1.1-1.6x here), not dispatch; on accelerators the batched path wins.

  PYTHONPATH=src python -m benchmarks.scaling_clients \
      [--clients 2,8,32,128] [--model mlp|cnn] [--rounds 3] \
      [--participation-sweep] [--participation-n 32] \
      [--hetero [--mix mlp:32,mlp:64] [--hetero-n 32]] \
      [--async-sweep [--async-n 32]] \
      [--download-lag [--download-lag-n 32]] \
      [--population-sweep [--populations 1000,...,1000000] \
          [--population-seats 8] [--population-shards 2]] \
      [--ci-gate [--out BENCH_ci.json] [--floor benchmarks/ci_floor.json]]

CSV to stdout: model,n_clients,engine,s_per_round,speedup_vs_seq.

--participation-sweep instead measures partial client rounds (the
relay/participation subsystem): at fixed N, k/N ∈ {0.25, 0.5, 1.0} clients
per round via the uniform_k schedule. The vectorized engine compacts the
round step to the k participants, so both wall-clock AND comm volume per
round should fall ≈ linearly with k/N.
CSV: model,n_clients,k,s_per_round,comm_mb_per_round,speedup_vs_full.

--hetero measures the BUCKETED engine on a mixed-architecture fleet
(`common.hetero_fleet` mix spec, clients assigned round-robin so buckets
interleave): one vmapped round step per bucket around the shared relay, vs
the sequential oracle stepping every client individually. Same weak-scaling
setup; the speedup column is the mixed-fleet vec-over-seq ratio.
CSV: mix,n_clients,n_buckets,engine,s_per_round,speedup_vs_seq.

--async-sweep measures the asynchronous event-ordered relay
(repro.relay.events + repro.sim clocks): at fixed N, a lognormal straggler
clock with D_max in {0, 1, 4} — D_max=0 is the synchronous fast path
(baseline), larger D_max pays for the pending-buffer commit inside the
jitted round step. The speedup column is vec-over-seq at the SAME D_max,
so it tracks whether the async engine keeps its vectorization win.
CSV: model,n_clients,d_max,engine,s_per_round,speedup_vs_seq.

--download-lag measures the download-lag relay history
(repro.relay.history + repro.sim download clocks): at fixed N, a lognormal
download clock with D_max in {0, 1, 4} — clients read stale snapshots from
a ring of H_max = D_max + 1 post-merge states. D_max=0 is the round-fresh
fast path (baseline); larger D_max pays for the in-step snapshot gather +
ring push, which should leave vec per-round cost ~flat in H_max.
CSV: model,n_clients,dl_max,engine,s_per_round,speedup_vs_seq.

--population-sweep measures the POPULATION-scale claim (cohort shards +
streaming arrivals, repro.relay.shards + repro.sim.population): hold the
active cohort (seats), participation (k) and relay shard count (S) fixed
while the total client population grows 10^3 -> 10^6. Per-round
wall-clock and resident state (relay shards + cohort seat table) must
stay flat — cost follows the cohort, never the id space — and the
per-shard occupancy/diversity report (repro.obs.shard_summary) surfaces
hash skew. CSV: model,population,seats,k,shards,s_per_round,state_mb.

--ci-gate is the CI benchmark-regression job (.github/workflows/ci.yml):
run the tiny committed configs from benchmarks/ci_floor.json (N=8 MLP
sync, an async lognormal entry, a download-lag entry, and the telemetry
on-vs-off overhead entry — repro.obs must stay within its committed
overhead ceiling when on), write the measurements to BENCH_ci.json plus
the telemetry run's BENCH_telemetry.jsonl / BENCH_trace.json (uploaded
as CI artifacts), and exit 1 if any vec-over-seq per-round speedup falls
below its committed floor or the telemetry overhead exceeds its ceiling.
Re-baselining is documented in ci_floor.json itself and ROADMAP.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import common
from repro.data import synthetic

PER_CLIENT = int(os.environ.get("REPRO_SCALE_PER_CLIENT", "64"))
N_TEST = int(os.environ.get("REPRO_SCALE_TEST", "1024"))
SEQ_MAX = int(os.environ.get("REPRO_SCALE_SEQ_MAX", "64"))


def _block_round_state(trainer):
    """Barrier on the trainer's device-side round outputs: relay state and
    client params (per bucket for hetero fleets; the oracle's per-client
    states otherwise). run_round returns after DISPATCH, so a timed loop
    without this would count Python dispatch and drop the last round's
    in-flight device work."""
    import jax
    targets = []
    if hasattr(trainer, "relay_state"):          # vectorized engine
        targets.append(trainer.relay_state)
        targets.append([b.params for b in trainer.buckets]
                       if trainer.hetero else trainer.params)
    else:                                        # sequential oracle
        targets.append(trainer.server.state)
        targets.append([c.params for c in trainer.clients])
    jax.block_until_ready(targets)


def time_rounds(trainer, rounds: int = 3) -> float:
    """Seconds per round, excluding the first round — the warm-up that
    absorbs jit tracing + compilation. Both the warm-up and the timed loop
    end on a `_block_round_state` barrier so the clock starts from an idle
    device and stops only when the last round's work actually finished."""
    trainer.run_round()
    _block_round_state(trainer)
    t0 = time.perf_counter()
    for _ in range(rounds):
        trainer.run_round()
    _block_round_state(trainer)
    return (time.perf_counter() - t0) / rounds


def bench(n_clients: int, engine: str, model: str, rounds: int,
          hetero: str = None, per_client: int = None,
          clock: str = None, download_clock: str = None,
          mesh_devices: int = 0, policy: str = None, arrivals: str = None,
          telemetry=None) -> float:
    pc = per_client or PER_CLIENT
    train = synthetic.class_images(pc * n_clients, seed=0, noise=0.8)
    test = synthetic.class_images(N_TEST, seed=99, noise=0.8)
    mesh = None
    if mesh_devices and engine == "vec":
        from repro import sharding
        mesh = sharding.client_mesh(mesh_devices)
    tr = common.make_trainer("cors", n_clients, engine=engine, model=model,
                             batch_size=16, train_data=train, test_data=test,
                             hetero=hetero, clock=clock,
                             download_clock=download_clock, mesh=mesh,
                             policy=policy, arrivals=arrivals,
                             telemetry=telemetry)
    return time_rounds(tr, rounds)


def async_sweep(n_clients: int = 32, rounds: int = 3, model: str = "mlp"):
    """Bounded-delay relay cost: vec vs seq per round at D_max in
    {0, 1, 4} under a lognormal straggler clock. D_max=0 routes to the
    synchronous fast path; D_max>0 runs the full-width async step with the
    (N, D_max, ...) pending buffer, so the column shows what event-ordered
    lateness costs and whether the vectorization win survives it."""
    print("model,n_clients,d_max,engine,s_per_round,speedup_vs_seq")
    results = {}
    for d_max in (0, 1, 4):
        clock = None if d_max == 0 else f"lognormal:{d_max}"
        t_vec = bench(n_clients, "vec", model, rounds, clock=clock)
        t_seq = bench(n_clients, "seq", model, rounds, clock=clock)
        results[d_max] = t_seq / t_vec
        print(f"{model},{n_clients},{d_max},seq,{t_seq:.4f},1.00")
        print(f"{model},{n_clients},{d_max},vec,{t_vec:.4f},"
              f"{results[d_max]:.2f}")
    return results


def download_lag_sweep(n_clients: int = 32, rounds: int = 3,
                       model: str = "mlp"):
    """Download-lag relay history cost: vec vs seq per round at download
    D_max in {0, 1, 4} (H_max = D_max + 1 retained snapshots) under a
    lognormal download clock. D_max=0 is the round-fresh fast path
    (baseline, no history machinery); D_max>0 threads the snapshot ring
    through the jitted step — per-client stale reads are one batched
    gather and the push one ring write, so vec per-round cost should stay
    ~flat in H_max while the seq oracle keeps paying its O(N) dispatch
    chain (mirroring the --async-sweep shape). The speedup column is
    vec-over-seq at the SAME D_max.
    CSV: model,n_clients,dl_max,engine,s_per_round,speedup_vs_seq."""
    print("model,n_clients,dl_max,engine,s_per_round,speedup_vs_seq")
    results = {}
    for dl_max in (0, 1, 4):
        dl = None if dl_max == 0 else f"lognormal:{dl_max}"
        t_vec = bench(n_clients, "vec", model, rounds, download_clock=dl)
        t_seq = bench(n_clients, "seq", model, rounds, download_clock=dl)
        results[dl_max] = t_seq / t_vec
        print(f"{model},{n_clients},{dl_max},seq,{t_seq:.4f},1.00")
        print(f"{model},{n_clients},{dl_max},vec,{t_vec:.4f},"
              f"{results[dl_max]:.2f}")
    return results


def hetero_sweep(n_clients: int = 32, rounds: int = 3,
                 mix: str = "mlp:32,mlp:64"):
    """Mixed-spec fleet: bucketed vectorized engine vs sequential oracle.

    The default mix keeps per-client compute cheap for the same reason the
    homogeneous sweep defaults to "mlp": the ratio then measures the ENGINE
    (O(N) Python dispatch vs one dispatch per bucket). Wider/conv mixes
    (e.g. "mlp:64,mlp:128" or "...,cnn:1") shift both engines toward the
    same compute and the ratio toward XLA's batching efficiency — measured
    ~3.7x for the default vs ~2.6x for "mlp:64,mlp:128" at N=32 on a
    2-core CPU."""
    n_buckets = len(mix.split(","))
    print("mix,n_clients,n_buckets,engine,s_per_round,speedup_vs_seq")
    t_vec = bench(n_clients, "vec", "mlp", rounds, hetero=mix)
    t_seq = bench(n_clients, "seq", "mlp", rounds, hetero=mix)
    speedup = t_seq / t_vec
    print(f"{mix},{n_clients},{n_buckets},seq,{t_seq:.4f},1.00")
    print(f"{mix},{n_clients},{n_buckets},vec,{t_vec:.4f},{speedup:.2f}")
    return speedup


def _population_trainer(engine: str, population: int, seats: int, k: int,
                        shards: int, model: str, per_client: int = None,
                        rate: float = 2.0, p_leave: float = 0.2):
    """A streaming cohort fleet: `seats` concurrently-resident clients
    drawn from a `population`-sized external id space, hashed onto
    `shards` relay shards. Compute, data and relay state are all sized by
    the SEATS — the population enters only through the id draws."""
    pc = per_client or PER_CLIENT
    train = synthetic.class_images(pc * seats, seed=0, noise=0.8)
    test = synthetic.class_images(N_TEST, seed=99, noise=0.8)
    return common.make_trainer(
        "cors", seats, engine=engine, model=model, batch_size=16,
        train_data=train, test_data=test,
        policy=f"sharded:flat,{shards}",
        arrivals=f"stream:{k},{rate},{p_leave},{population},0")


def _population_state_mb(tr) -> float:
    """Resident bytes that COULD scale with the population: the relay
    state (all shards) plus the cohort seat table."""
    import jax
    state = tr.relay_state if hasattr(tr, "relay_state") else tr.server.state
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    return (nbytes + tr._cohort.nbytes()) / 1e6


def population_sweep(populations=(10**3, 10**4, 10**5, 10**6),
                     seats: int = 8, k: int = 2, shards: int = 2,
                     rounds: int = 12, model: str = "mlp",
                     tolerance: float = 0.2, reps: int = 2):
    """The paper's N-independence claim at population scale: hold the
    active cohort (seats), participation (k) and shard count (S) fixed
    while the TOTAL population grows 10^3 -> 10^6. Per-round wall-clock
    and resident state must stay flat (within `tolerance`): cost follows
    the cohort, never the id space. Also prints the per-shard
    occupancy/diversity/commit-lag report (repro.obs.shard_summary) for
    the largest population — the observability surface for shard skew.
    Each point is the best of `reps` timed windows on the same compiled
    trainer: percent-level flatness needs sub-noise timings, and ~40ms
    rounds on a shared 2-core runner drift more than 20% run to run.
    CSV: model,population,seats,k,shards,s_per_round,state_mb."""
    from repro import obs
    print("model,population,seats,k,shards,s_per_round,state_mb")
    results, last = {}, None
    for pop in populations:
        tr = _population_trainer("vec", pop, seats, k, shards, model)
        t = min(time_rounds(tr, rounds) for _ in range(max(1, reps)))
        mb = _population_state_mb(tr)
        results[pop] = {"s_per_round": t, "state_mb": mb}
        print(f"{model},{pop},{seats},{k},{shards},{t:.4f},{mb:.3f}")
        last = tr
    times = [r["s_per_round"] for r in results.values()]
    spread = max(times) / min(times) - 1.0
    mbs = [r["state_mb"] for r in results.values()]
    mem_spread = max(mbs) / min(mbs) - 1.0
    flat = spread <= tolerance and mem_spread <= tolerance
    print(f"population-sweep: time spread {spread:.1%}, memory spread "
          f"{mem_spread:.1%} over N={populations[0]}..{populations[-1]} "
          f"[{'FLAT' if flat else 'NOT FLAT'}] (tolerance {tolerance:.0%})")
    shard_rep = obs.shard_summary(last.relay_state)
    print(f"per-shard occupancy {shard_rep['occupancy']}, owner diversity "
          f"{shard_rep['owner_diversity']}")
    return {"results": results, "time_spread": spread,
            "memory_spread": mem_spread, "flat": flat,
            "shards": shard_rep}


def _measure_entry(cfg) -> tuple:
    """(t_vec, t_seq) for one gate entry config. A "devices" key runs the
    vec side on a forced multi-device mesh (the placement path,
    repro.relay.placement); the seq oracle is meshless either way. A
    "policy"/"arrivals" pair runs the cohort-sharded streaming fleet
    (the population entry)."""
    kw = dict(per_client=cfg["per_client"], clock=cfg.get("clock"),
              download_clock=cfg.get("download_clock"),
              policy=cfg.get("policy"), arrivals=cfg.get("arrivals"))
    t_vec = bench(cfg["n_clients"], "vec", cfg["model"], cfg["rounds"],
                  mesh_devices=int(cfg.get("devices", 0)), **kw)
    t_seq = bench(cfg["n_clients"], "seq", cfg["model"], cfg["rounds"], **kw)
    return t_vec, t_seq


def _probe_subprocess(name: str, floor_path: str, devices: int) -> tuple:
    """Re-run ONE gate entry in a child interpreter with XLA forced to
    `devices` virtual host devices (the flag must be set before the first
    jax import, so the parent process cannot measure it itself)."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.scaling_clients",
         "--gate-probe", name, "--floor", floor_path],
        env=env, capture_output=True, text=True, check=True)
    probe = json.loads(out.stdout.strip().splitlines()[-1])
    return probe["t_vec"], probe["t_seq"]


def gate_probe(name: str, floor_path: str) -> int:
    """Child side of _probe_subprocess: measure one entry, print JSON."""
    with open(floor_path) as f:
        floor = json.load(f)
    cfg = (floor if name == "sync" else floor[name])["config"]
    t_vec, t_seq = _measure_entry(cfg)
    print(json.dumps({"t_vec": t_vec, "t_seq": t_seq}))
    return 0


def _measure_telemetry(cfg, jsonl_path: str, trace_path: str) -> tuple:
    """(t_off, t_on): vec per-round seconds with telemetry fully off vs
    fully ON (in-jit metrics + JSONL sink + span store — the whole
    opt-in surface, which also leaves the gate's artifacts behind for CI
    upload). Best of `reps` interleaved pairs, ALTERNATING which side of
    the pair runs first: machine drift within a pair (thermal, page
    cache) otherwise lands systematically on the second side and reads as
    fake overhead — measured ~5% of bias on a 2-core container, the same
    order as the real overhead this gate bounds."""
    from repro import obs
    kw = dict(per_client=cfg["per_client"])
    on_cfg = obs.TelemetryConfig(jsonl=jsonl_path)
    t_off = t_on = float("inf")
    for rep in range(int(cfg.get("reps", 4))):
        order = [(None, False), (on_cfg, True)]
        if rep % 2:
            order.reverse()
        for telem, is_on in order:
            # the engines record spans inside `run()` alone, and the
            # timing drives `run_round`: the on side records around it
            with obs.trace.record(trace_path if is_on else None):
                t = bench(cfg["n_clients"], "vec", cfg["model"],
                          cfg["rounds"], telemetry=telem, **kw)
            if is_on:
                t_on = min(t_on, t)
            else:
                t_off = min(t_off, t)
    return t_off, t_on


def ci_gate(out: str = "BENCH_ci.json",
            floor_path: str = "benchmarks/ci_floor.json") -> int:
    """The CI benchmark-regression gate. Measures every committed tiny
    config (the synchronous top-level entry plus any named extra entries,
    e.g. "async", or "mesh" — the placement path on forced virtual
    devices) and fails (exit 1) when any vec-over-seq speedup drops below
    its committed floor. A "telemetry" entry gates the observability
    layer's cost instead: vec rounds with the full telemetry surface on
    must stay within `max_overhead_on_over_off` of telemetry-off rounds
    (the "cheap when on" contract), and the measurement's JSONL/trace
    artifacts are written next to `out` for CI upload."""
    import jax
    with open(floor_path) as f:
        floor = json.load(f)
    entries = [("sync", floor)] + [
        (name, floor[name])
        for name in ("async", "download_lag", "mesh", "population")
        if name in floor]
    result, failed = {}, []
    for name, entry in entries:
        cfg = entry["config"]
        devices = int(cfg.get("devices", 0))
        if devices > jax.local_device_count():
            t_vec, t_seq = _probe_subprocess(name, floor_path, devices)
        else:
            t_vec, t_seq = _measure_entry(cfg)
        speedup = t_seq / t_vec
        min_speedup = entry["min_speedup_vec_over_seq"]
        ok = speedup >= min_speedup
        result[name] = {"config": cfg, "s_per_round_seq": t_seq,
                        "s_per_round_vec": t_vec,
                        "speedup_vec_over_seq": speedup,
                        "min_speedup_vec_over_seq": min_speedup,
                        "passed": ok}
        print(f"ci-gate[{name}]: vec {t_vec:.4f}s/round, seq "
              f"{t_seq:.4f}s/round -> {speedup:.2f}x (floor "
              f"{min_speedup}x) [{'PASS' if ok else 'FAIL'}]")
        if not ok:
            failed.append((name, f"vec-over-seq speedup {speedup:.2f}x is "
                                 f"below the committed floor {min_speedup}x"))
    if "population" in floor:
        # flatness artifact: a two-point population sweep (10^3 vs 10^6 at
        # the gate's seats/k/S) written next to `out` for CI upload; a
        # generous max_spread bounds wall-clock noise while still failing
        # a real O(population) regression (which shows up as ~10^3x).
        entry = floor["population"]
        cfg = entry["config"]
        sweep = population_sweep(
            populations=tuple(cfg.get("report_populations",
                                      (10**3, 10**6))),
            seats=cfg["n_clients"], k=int(cfg.get("k", 2)),
            shards=int(cfg.get("shards", 2)),
            rounds=int(cfg.get("report_rounds", 12)),
            model=cfg["model"], tolerance=entry.get("max_spread", 0.5))
        pop_out = os.path.join(os.path.dirname(os.path.abspath(out)),
                               "BENCH_population.json")
        with open(pop_out, "w") as f:
            json.dump(sweep, f, indent=2)
        result["population"]["sweep"] = pop_out
        result["population"]["time_spread"] = sweep["time_spread"]
        result["population"]["flat"] = sweep["flat"]
        if "max_spread" in entry and not sweep["flat"]:
            result["population"]["passed"] = False
            failed.append(
                ("population", f"per-round cost/memory is not flat in the "
                               f"population: time spread "
                               f"{sweep['time_spread']:.1%}, memory spread "
                               f"{sweep['memory_spread']:.1%} exceed "
                               f"max_spread {entry['max_spread']:.0%}"))
    if "telemetry" in floor:
        entry = floor["telemetry"]
        base = os.path.dirname(os.path.abspath(out))
        jsonl_path = os.path.join(base, "BENCH_telemetry.jsonl")
        trace_path = os.path.join(base, "BENCH_trace.json")
        t_off, t_on = _measure_telemetry(entry["config"], jsonl_path,
                                         trace_path)
        overhead = t_on / t_off
        max_over = entry["max_overhead_on_over_off"]
        ok = overhead <= max_over
        result["telemetry"] = {"config": entry["config"],
                               "s_per_round_off": t_off,
                               "s_per_round_on": t_on,
                               "overhead_on_over_off": overhead,
                               "max_overhead_on_over_off": max_over,
                               "jsonl": jsonl_path, "trace": trace_path,
                               "passed": ok}
        print(f"ci-gate[telemetry]: off {t_off:.4f}s/round, on "
              f"{t_on:.4f}s/round -> {overhead:.2f}x (ceiling "
              f"{max_over}x) [{'PASS' if ok else 'FAIL'}]")
        if not ok:
            failed.append(
                ("telemetry", f"telemetry-on rounds cost {overhead:.2f}x "
                              f"telemetry-off, above the committed ceiling "
                              f"{max_over}x"))
    result["passed"] = not failed
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"ci-gate: {'PASS' if not failed else 'FAIL'} -> {out}")
    for name, why in failed:
        print(f"ci-gate: FAIL[{name}] — {why} ({floor_path}). Either a "
              "perf regression, or the floor needs re-baselining (see "
              "that file).", file=sys.stderr)
    return 1 if failed else 0


def participation_sweep(n_clients: int = 32, rounds: int = 3,
                        model: str = "mlp", fractions=(0.25, 0.5, 1.0)):
    """Partial-round savings: s/round and comm/round vs participants k."""
    train = synthetic.class_images(PER_CLIENT * n_clients, seed=0, noise=0.8)
    test = synthetic.class_images(N_TEST, seed=99, noise=0.8)
    print("model,n_clients,k,s_per_round,comm_mb_per_round,speedup_vs_full")
    results = {}
    t_full = None
    for frac in sorted(fractions, reverse=True):     # full first (baseline)
        k = max(1, int(round(frac * n_clients)))
        tr = common.make_trainer(
            "cors", n_clients, engine="vec", model=model, batch_size=16,
            train_data=train, test_data=test,
            participation=f"uniform_k:{k}")
        t = time_rounds(tr, rounds)
        up, down = tr.ledger.by_round[-1]
        comm_mb = 4 * (up + down) / 1e6
        if t_full is None:
            t_full = t
        results[k] = (t, comm_mb, t_full / t)
        print(f"{model},{n_clients},{k},{t:.4f},{comm_mb:.4f},"
              f"{t_full / t:.2f}")
    return results


def main(clients=(2, 8, 32, 128), rounds: int = 3, model: str = "mlp"):
    print("model,n_clients,engine,s_per_round,speedup_vs_seq")
    results = {}
    for n in clients:
        t_vec = bench(n, "vec", model, rounds)
        if n <= SEQ_MAX:
            t_seq = bench(n, "seq", model, rounds)
            results[n] = t_seq / t_vec
            print(f"{model},{n},seq,{t_seq:.4f},1.00")
            print(f"{model},{n},vec,{t_vec:.4f},{results[n]:.2f}")
        else:
            results[n] = None
            print(f"{model},{n},seq,skipped,")
            print(f"{model},{n},vec,{t_vec:.4f},")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", default="2,8,32,128")
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--participation-sweep", action="store_true",
                    help="measure partial rounds (k/N in {0.25,0.5,1.0}) "
                         "instead of the seq-vs-vec engine scaling")
    ap.add_argument("--participation-n", type=int, default=32,
                    help="N for the participation sweep")
    ap.add_argument("--hetero", action="store_true",
                    help="measure a mixed-architecture fleet through the "
                         "bucketed engine vs the sequential oracle")
    ap.add_argument("--mix", default="mlp:32,mlp:64",
                    help="hetero mix spec (common.hetero_fleet), e.g. "
                         "mlp:32,mlp:64 or mlp:64,mlp:96,cnn:1")
    ap.add_argument("--hetero-n", type=int, default=32,
                    help="N for the hetero sweep")
    ap.add_argument("--async-sweep", action="store_true",
                    help="measure the asynchronous event-ordered relay "
                         "(lognormal straggler clock, D_max in {0,1,4}) "
                         "vec vs seq")
    ap.add_argument("--async-n", type=int, default=32,
                    help="N for the async sweep")
    ap.add_argument("--download-lag", action="store_true",
                    help="measure the download-lag history ring (lognormal "
                         "download clock, D_max in {0,1,4} i.e. H_max up "
                         "to 5) vec vs seq")
    ap.add_argument("--download-lag-n", type=int, default=32,
                    help="N for the download-lag sweep")
    ap.add_argument("--population-sweep", action="store_true",
                    help="hold seats/k/S fixed and grow the total "
                         "population 10^3 -> 10^6: per-round cost and "
                         "resident state must stay flat")
    ap.add_argument("--populations", default="1000,10000,100000,1000000",
                    help="population sizes for the population sweep")
    ap.add_argument("--population-seats", type=int, default=8,
                    help="active-cohort seats for the population sweep")
    ap.add_argument("--population-shards", type=int, default=2,
                    help="relay shard count for the population sweep")
    ap.add_argument("--ci-gate", action="store_true",
                    help="run the CI benchmark-regression gate (config + "
                         "floor from --floor; exit 1 below the floor)")
    ap.add_argument("--out", default="BENCH_ci.json",
                    help="ci-gate: where to write the measurement JSON")
    ap.add_argument("--floor", default="benchmarks/ci_floor.json",
                    help="ci-gate: committed config + speedup floor")
    ap.add_argument("--gate-probe", default=None, metavar="ENTRY",
                    help=argparse.SUPPRESS)   # ci_gate internal (subprocess)
    args = ap.parse_args()
    if args.gate_probe:
        sys.exit(gate_probe(args.gate_probe, args.floor))
    if args.ci_gate:
        sys.exit(ci_gate(args.out, args.floor))
    elif args.population_sweep:
        population_sweep(
            tuple(int(p) for p in args.populations.split(",")),
            seats=args.population_seats, shards=args.population_shards,
            rounds=args.rounds, model=args.model)
    elif args.download_lag:
        download_lag_sweep(args.download_lag_n, args.rounds, args.model)
    elif args.async_sweep:
        async_sweep(args.async_n, args.rounds, args.model)
    elif args.hetero:
        hetero_sweep(args.hetero_n, args.rounds, args.mix)
    elif args.participation_sweep:
        participation_sweep(args.participation_n, args.rounds, args.model)
    else:
        main(tuple(int(c) for c in args.clients.split(",")), args.rounds,
             args.model)
