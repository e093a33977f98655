"""Scale harness: golden determinism + incremental-vs-reference equivalence.

Two safety nets for the incremental-rate engine refactor:

  * *golden determinism* — the scale scenario run twice produces bit-identical
    event logs and makespans (the engine's (time, seq) + fid ordering);
  * *differential equivalence* — on a FaaSNet tree, a registry star and a
    Kraken mesh, the incremental engine's per-flow rate trajectories and
    completion times match the old full-recompute oracle
    (:class:`repro.sim.reference.ReferenceFlowSim`) to ±1e-9.

The full 2500-containers / 1000-VM burst is marked ``slow`` (run with
``--runslow``).
"""
import random
import time

import pytest

from repro.core import FunctionTree
from repro.core.topology import faasnet_plan, kraken_plan, on_demand_plan
from repro.sim import ScaleConfig, run_scale
from repro.sim.engine import FlowSim, SimConfig
from repro.sim.reference import ReferenceFlowSim

MB = 1e6

REL_TOL = 1e-9


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(1.0, abs(a), abs(b))


def _collapse(entries, t_done: float):
    """Reduce a per-flow [(t, rate), ...] log to its piecewise-constant form.

    The reference engine recomputes after *every* event, so a batch of
    same-timestamp completions logs several intermediate rates where the
    incremental engine logs one — including a zero-duration "bump" for a
    flow whose sibling finished at the very instant it did.  Keep only the
    final rate at each distinct timestamp, drop entries at/after the flow's
    own completion (they transport no bytes), then drop no-op repeats —
    what remains is the trajectory as a function of time, which both
    engines must agree on.
    """
    out = []
    for t, r in entries:
        if t >= t_done or _close(t, t_done):
            continue
        if out and _close(out[-1][0], t):
            out[-1] = (out[-1][0], r)
        else:
            out.append((t, r))
    dedup = []
    for t, r in out:
        if dedup and _close(dedup[-1][1], r):
            continue
        dedup.append((t, r))
    return dedup


def _assert_equivalent(plan, cfg: SimConfig, *, slow_vms=None):
    """Run one plan through both engines; rates and times must match."""
    sims = []
    for cls in (FlowSim, ReferenceFlowSim):
        sim = cls(cfg, record_rates=True)
        for vm, cap in (slow_vms or {}).items():
            sim.set_slow_vm(vm, cap)
        states = sim.add_plan(plan)
        sim.run()
        sims.append((sim, states))
    (inc, inc_states), (ref, ref_states) = sims
    assert _close(inc.now, ref.now), (inc.now, ref.now)
    assert len(inc_states) == len(ref_states)
    for a, b in zip(inc_states, ref_states):
        assert a.flow == b.flow
        assert a.done and b.done, (a.flow, a.done, b.done)
        assert _close(a.t_start, b.t_start), (a.flow, a.t_start, b.t_start)
        assert _close(a.t_done, b.t_done), (a.flow, a.t_done, b.t_done)
    # per-flow rate trajectories
    by_fid_inc: dict[int, list] = {}
    by_fid_ref: dict[int, list] = {}
    for t, fid, r in inc.rate_log:
        by_fid_inc.setdefault(fid, []).append((t, r))
    for t, fid, r in ref.rate_log:
        by_fid_ref.setdefault(fid, []).append((t, r))
    for fid in range(len(inc_states)):
        t_done = inc_states[fid].t_done
        ta = _collapse(by_fid_inc.get(fid, []), t_done)
        tb = _collapse(by_fid_ref.get(fid, []), t_done)
        assert len(ta) == len(tb), (fid, ta, tb)
        for (t1, r1), (t2, r2) in zip(ta, tb):
            assert _close(t1, t2), (fid, t1, t2)
            assert _close(r1, r2), (fid, r1, r2)


def _wave_simconfig(**kw) -> SimConfig:
    base = dict(
        per_stream_cap=30 * MB,
        hop_latency=0.2,
        registry_qps=1100.0,
    )
    base.update(kw)
    return SimConfig(**base)


# ----------------------------------------------------------------------
# Equivalence on the three canonical topologies
# ----------------------------------------------------------------------
def test_equivalence_faasnet_tree():
    ft = FunctionTree("f")
    for i in range(15):
        ft.insert(f"vm{i}")
    plan = faasnet_plan(ft, image_bytes=int(100 * MB), startup_fraction=0.2)
    _assert_equivalent(plan, _wave_simconfig())


def test_equivalence_faasnet_tree_with_straggler():
    ft = FunctionTree("f")
    for i in range(15):
        ft.insert(f"vm{i}")
    plan = faasnet_plan(ft, image_bytes=int(100 * MB), startup_fraction=0.2)
    _assert_equivalent(plan, _wave_simconfig(), slow_vms={"vm1": 2 * MB})


def test_equivalence_registry_star():
    plan = on_demand_plan(
        [f"vm{i}" for i in range(16)],
        image_bytes=int(100 * MB),
        startup_fraction=0.2,
    )
    _assert_equivalent(plan, _wave_simconfig())


def test_equivalence_kraken_mesh():
    plan = kraken_plan(
        [f"vm{i}" for i in range(12)],
        layer_bytes=[int(10 * MB)] * 4,
        origin="origin",
        seed=7,
    )
    _assert_equivalent(plan, _wave_simconfig(coordinator_cost_s=0.070))


# ----------------------------------------------------------------------
# Golden determinism of the scale scenario
# ----------------------------------------------------------------------
def _small_cfg(seed=3) -> ScaleConfig:
    return ScaleConfig(
        n_vms=32, n_functions=8, containers_per_function=8, churn_ops=10, seed=seed
    )


def test_scale_golden_determinism():
    """Two runs of the same config: bit-identical event logs and makespan."""
    a = run_scale(_small_cfg())
    b = run_scale(_small_cfg())
    assert a.trace == b.trace  # full (time, event) log, exact float equality
    assert a.makespan == b.makespan
    assert a.per_function == b.per_function
    assert a.events == b.events
    assert a.peak_registry_egress == b.peak_registry_egress


def test_scale_seed_changes_trace():
    """Different seeds genuinely change the scenario (no vacuous golden test)."""
    a = run_scale(_small_cfg(seed=3))
    b = run_scale(_small_cfg(seed=4))
    assert a.trace != b.trace


def test_scale_churn_fires_reparents_and_keeps_invariants():
    cfg = _small_cfg()
    res = run_scale(cfg)
    assert res.reparents > 0  # churn really exercised AVL repair
    assert res.n_containers == cfg.total_containers()
    for st in res.tree_stats.values():
        assert st["size"] == cfg.containers_per_function


def test_scale_all_functions_complete():
    res = run_scale(_small_cfg())
    assert set(res.per_function) == {f"fn{i}" for i in range(8)}
    assert all(t > 0 for t in res.per_function.values())
    assert res.provision_makespan > res.makespan


# ----------------------------------------------------------------------
# The paper-scale burst (gated: ~0.3 s today, but guards the perf budget)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_scale_1000_vm_burst_under_budget():
    """Paper §4.2 shape: 2500 containers / 1000 VMs, one CPU core, < 60 s."""
    t0 = time.perf_counter()
    res = run_scale(ScaleConfig(churn_ops=100))
    wall = time.perf_counter() - t0
    assert res.n_containers == 2500
    assert wall < 60.0, f"scale harness took {wall:.1f} s"
    # network fetch makespan in the provisioning regime the paper reports
    assert 4.0 < res.makespan < 30.0, res.makespan
    assert res.peak_registry_egress > 0


@pytest.mark.slow
def test_scale_1000_vm_deterministic():
    a = run_scale(ScaleConfig(churn_ops=50, seed=11))
    b = run_scale(ScaleConfig(churn_ops=50, seed=11))
    assert a.makespan == b.makespan
    assert a.trace == b.trace


# ----------------------------------------------------------------------
# The 10× mega-burst (PR 2): 10k VMs / 25 functions / 100k containers
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_scale_10k_mega_burst_under_budget():
    """10× paper scale end-to-end in < 30 s: the O(log n) control plane plus
    the incremental engine.  The seed BFS-scan control plane alone would
    blow this budget standing up the trees."""
    from repro.sim.scale import mega_burst_config

    t0 = time.perf_counter()
    res = run_scale(mega_burst_config())
    wall = time.perf_counter() - t0
    assert res.n_containers == 100_000
    assert wall < 30.0, f"mega burst took {wall:.1f} s"
    # control-plane build of 25 × 4000-node trees must stay sub-linear-ish
    assert res.build_s < 5.0, f"control-plane build took {res.build_s:.1f} s"
    assert res.churn_op_s < 0.001, f"churn op latency {res.churn_op_s*1e3:.2f} ms"
    assert res.reparents > 0
    # every tree is a 4000-node AVL: height must be logarithmic (<= 1.44 log2 n)
    for st in res.tree_stats.values():
        assert st["size"] == 4000
        assert st["height"] <= 18
    assert 4.0 < res.makespan < 120.0, res.makespan
    assert res.peak_registry_egress > 0


def test_mega_burst_config_shape():
    """Fast sanity: the mega config is 10× the paper's §4.2 burst."""
    from repro.sim.scale import mega_burst_config

    cfg = mega_burst_config()
    assert cfg.n_vms == 10_000
    assert cfg.total_containers() == 100_000
    assert cfg.max_functions_per_vm >= cfg.n_functions  # placement can't wedge


def test_scale_result_reports_control_plane_timings():
    res = run_scale(_small_cfg())
    assert res.build_s > 0.0
    assert res.churn_s > 0.0 and res.churn_op_s > 0.0


# ----------------------------------------------------------------------
# Incremental engine internals worth pinning
# ----------------------------------------------------------------------
def test_same_timestamp_completions_batched():
    """A symmetric star completes all flows in one settle pass."""
    from repro.core.topology import baseline_plan

    sim = FlowSim(SimConfig())
    plan = baseline_plan([f"vm{i}" for i in range(8)], image_bytes=10_000_000)
    sim.add_plan(plan)
    sim.run()
    done_times = {f.t_done for f in sim._flows}
    assert len(done_times) == 1  # all end at the same instant
    assert sim.events_processed == 8 + 8  # 8 starts + 8 completions


def test_registry_egress_peak_tracked():
    from repro.core.topology import baseline_plan

    sim = FlowSim(SimConfig(registry_out_cap=5 * 125e6))
    sim.add_plan(baseline_plan([f"vm{i}" for i in range(8)], image_bytes=10_000_000))
    sim.run()
    # 8 concurrent flows, each NIC-limited to 125 MB/s in, registry cap 625 MB/s
    assert sim.peak_registry_egress == pytest.approx(5 * 125e6, rel=1e-9)


def test_set_parent_mid_flight_applies_cap():
    """Attaching a parent to an already-started flow caps it immediately."""
    from repro.core.topology import REGISTRY, DistributionPlan, Flow

    results = []
    for cls in (FlowSim, ReferenceFlowSim):
        sim = cls(SimConfig(registry_out_cap=5e6))
        [p] = sim.add_plan(
            DistributionPlan(
                flows=[Flow(REGISTRY, "A", "img", 200_000_000)], streaming=False
            )
        )
        [c] = sim.add_plan(
            DistributionPlan(flows=[Flow("A", "B", "img", 125_000_000)], streaming=False)
        )
        sim.run(until=0.1)  # both flows start, uncapped
        sim.set_parent(c, p)  # the TraceReplay mid-flight attach path
        sim.run()
        results.append(c.t_done)
    inc, ref = results
    assert _close(inc, ref), (inc, ref)
    assert inc > 20.0  # capped at the parent's 5 MB/s, not B's NIC rate


def test_random_plan_fuzz_equivalence():
    """Seeded random flow graphs: both engines agree end-to-end."""
    from repro.core.topology import REGISTRY, DistributionPlan, Flow

    for seed in range(4):
        rng = random.Random(seed)
        nodes = [f"vm{i}" for i in range(10)]
        flows = []
        for i, n in enumerate(nodes):
            src = REGISTRY if i == 0 or rng.random() < 0.3 else nodes[rng.randrange(i)]
            flows.append(Flow(src, n, "img", rng.randrange(1_000_000, 50_000_000)))
        plan = DistributionPlan(
            flows=flows,
            control_latency={n: rng.random() * 0.05 for n in nodes},
            streaming=bool(seed % 2),
        )
        _assert_equivalent(plan, _wave_simconfig())
