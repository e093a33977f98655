"""VectorFlowSim: differential verification against the other engines.

The vector engine is the last member of the oracle chain (``reference`` →
``incremental`` → ``vector``, see ``repro.sim.engine.ENGINES``) and is held
to a *stricter* bar than the incremental engine was:

  * against the incremental engine it must be **bit-identical** — event
    logs compare equal as exact floats (run_scale trace, provision-wave
    latencies, TraceReplay TickStats) and peak-egress telemetry matches
    exactly;
  * against the reference oracle it must agree to ±1e-9 on completion
    times and peak egress, like the incremental engine does;
  * it must be cutoff-invariant: forcing every ready front down the wide
    vectorized path (``vector_engine.SCALAR_CUTOFF`` patched to 0) may not
    change a single bit.

Randomized plans + churn (seeded always; hypothesis variant when the
package is installed) drive the engines through the same scenarios,
including mid-flight ``set_parent`` and slow-VM re-rating.  The
``_done_heap`` compaction satellite is pinned here for both heap-based
engines; the wide-front dispatch telemetry (``dispatch_stats``) is fuzzed
for internal consistency against event counts.
"""
import random
from unittest import mock

import pytest

from repro.core import FunctionTree
from repro.core.topology import (
    REGISTRY,
    DistributionPlan,
    Flow,
    baseline_plan,
    faasnet_plan,
    kraken_plan,
    on_demand_plan,
)
from repro.sim import ScaleConfig, WaveConfig, provision_wave, run_scale
from repro.sim.engine import ENGINES, FlowSim, SimConfig, make_sim
from repro.sim import vector_engine
from repro.sim.reference import ReferenceFlowSim
from repro.sim.vector_engine import VectorFlowSim

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare interpreters
    HAVE_HYPOTHESIS = False

MB = 1e6
REL_TOL = 1e-9


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(1.0, abs(a), abs(b))


def _wave_simconfig(**kw) -> SimConfig:
    base = dict(per_stream_cap=30 * MB, hop_latency=0.2, registry_qps=1100.0)
    base.update(kw)
    return SimConfig(**base)


def _run_engine(cls, plan, cfg, *, slow_vms=None):
    sim = cls(cfg, record_rates=True)
    for vm, cap in (slow_vms or {}).items():
        sim.set_slow_vm(vm, cap)
    states = sim.add_plan(plan)
    sim.run()
    return sim, states


def _assert_bit_identical(inc, inc_states, other, other_states):
    """``other`` (a vector engine run) matches ``inc`` exactly."""
    assert other.now == inc.now
    assert other.trace == inc.trace
    assert other.events_processed == inc.events_processed
    assert other.completion_times() == inc.completion_times()
    assert other.peak_registry_egress == inc.peak_registry_egress
    assert other.peak_shard_egress == inc.peak_shard_egress
    assert other.peak_nic_utilization == inc.peak_nic_utilization
    for a, b in zip(other_states, inc_states):
        assert a.flow == b.flow
        assert a.t_start == b.t_start and a.t_done == b.t_done
        assert a.remaining == b.remaining and a.rate == b.rate


def _assert_three_way(plan, cfg: SimConfig, *, slow_vms=None):
    """One plan through all three engines: pairwise agreement.

    vector vs incremental is exact (same floats); vector vs reference is
    ±1e-9 — the reference engine re-rates after every single event, so a
    batch of same-instant completions can take a microscopically different
    arithmetic path.  The vector engine is also re-run with
    ``SCALAR_CUTOFF`` patched to 0 so every ready front takes the wide
    vectorized path — the front's width only picks between two paths that
    may not differ by a single bit.
    """
    inc, inc_states = _run_engine(FlowSim, plan, cfg, slow_vms=slow_vms)
    vec, vec_states = _run_engine(VectorFlowSim, plan, cfg, slow_vms=slow_vms)
    ref, ref_states = _run_engine(ReferenceFlowSim, plan, cfg, slow_vms=slow_vms)

    # vector vs incremental: bit-identical
    _assert_bit_identical(inc, inc_states, vec, vec_states)

    # cutoff invariance: every front forced down the wide path
    with mock.patch.object(vector_engine, "SCALAR_CUTOFF", 0):
        vec0, vec0_states = _run_engine(
            VectorFlowSim, plan, cfg, slow_vms=slow_vms
        )
    _assert_bit_identical(inc, inc_states, vec0, vec0_states)
    s, s0 = vec.dispatch_stats, vec0.dispatch_stats
    # the front decomposition is cutoff-independent; only the path differs
    assert s0["fronts_scalar"] == 0 and s0["flows_scalar"] == 0
    assert s0["fronts_vector"] == s["fronts_scalar"] + s["fronts_vector"]
    assert s0["front_width_hist"] == s["front_width_hist"]

    # vector vs reference: 1e-9 completion times + peak egress
    assert _close(vec.now, ref.now)
    for a, b in zip(vec_states, ref_states):
        assert a.flow == b.flow
        assert a.done and b.done
        assert _close(a.t_start, b.t_start), (a.flow, a.t_start, b.t_start)
        assert _close(a.t_done, b.t_done), (a.flow, a.t_done, b.t_done)
    assert _close(vec.peak_registry_egress, ref.peak_registry_egress)
    assert set(vec.peak_shard_egress) == set(ref.peak_shard_egress)
    for k, v in vec.peak_shard_egress.items():
        assert _close(v, ref.peak_shard_egress[k]), (k, v)
    return vec


# ----------------------------------------------------------------------
# Canonical topologies through all three engines
# ----------------------------------------------------------------------
def test_four_way_faasnet_tree():
    ft = FunctionTree("f")
    for i in range(15):
        ft.insert(f"vm{i}")
    plan = faasnet_plan(ft, image_bytes=int(100 * MB), startup_fraction=0.2)
    _assert_three_way(plan, _wave_simconfig())


def test_four_way_faasnet_tree_with_straggler():
    ft = FunctionTree("f")
    for i in range(15):
        ft.insert(f"vm{i}")
    plan = faasnet_plan(ft, image_bytes=int(100 * MB), startup_fraction=0.2)
    _assert_three_way(plan, _wave_simconfig(), slow_vms={"vm1": 2 * MB})


def test_four_way_registry_star():
    plan = on_demand_plan(
        [f"vm{i}" for i in range(16)],
        image_bytes=int(100 * MB),
        startup_fraction=0.2,
    )
    _assert_three_way(plan, _wave_simconfig())


def test_four_way_kraken_mesh():
    plan = kraken_plan(
        [f"vm{i}" for i in range(12)],
        layer_bytes=[int(10 * MB)] * 4,
        origin="origin",
        seed=7,
    )
    _assert_three_way(plan, _wave_simconfig(coordinator_cost_s=0.070))


def test_four_way_sharded_registry():
    from repro.core.registry import RegistrySpec

    spec = RegistrySpec(shards=3, egress_cap=2.0 * 125e6, qps=500.0)
    plan = on_demand_plan(
        [f"vm{i}" for i in range(18)],
        image_bytes=int(60 * MB),
        startup_fraction=0.25,
        registry=spec,
    )
    _assert_three_way(plan, _wave_simconfig(registry=spec))


# ----------------------------------------------------------------------
# Golden bit-identity with engine="vector" on the existing goldens
# ----------------------------------------------------------------------
def test_provision_wave_golden_all_systems():
    from repro.sim import SYSTEMS

    for system in SYSTEMS:
        a = provision_wave(system, 32, WaveConfig())
        b = provision_wave(system, 32, WaveConfig(engine="vector"))
        assert a == b, system


def test_run_scale_trace_sha_golden():
    """The pinned run_scale event-log SHA-256 holds under engine="vector"."""
    import hashlib

    cfg = ScaleConfig(
        n_vms=32,
        n_functions=4,
        containers_per_function=8,
        churn_ops=5,
        seed=3,
        wave=WaveConfig(engine="vector"),
    )
    res = run_scale(cfg)
    digest = hashlib.sha256(
        "\n".join(f"{t!r} {e}" for t, e in res.trace).encode()
    ).hexdigest()
    assert (
        digest == "bb5965a1fa885edd0aaf968dfec9bad59941edf5c13a367d869ed2eea7954c82"
    )
    assert res.engine == "vector"


def test_trace_replay_tickstats_identical():
    """TickStats bit-identical across engines on a short trace replay."""
    from repro.sim import ReplayConfig, TraceReplay
    from repro.sim.traces import iot_trace

    trace = iot_trace(scale=0.2)[: 4 * 60]
    out = {}
    for eng in ("incremental", "vector"):
        tl = TraceReplay(
            ReplayConfig(
                system="faasnet",
                idle_reclaim_s=120,
                vm_pool_size=60,
                wave=WaveConfig(engine=eng),
            )
        ).run(trace)
        out[eng] = [repr(ts) for ts in tl]
    assert out["incremental"] == out["vector"]


# ----------------------------------------------------------------------
# Engine selection seam
# ----------------------------------------------------------------------
def test_make_sim_selects_backend():
    assert isinstance(make_sim(SimConfig()), FlowSim)
    assert isinstance(make_sim(SimConfig(engine="vector")), VectorFlowSim)
    assert isinstance(make_sim(SimConfig(engine="reference")), ReferenceFlowSim)
    assert ENGINES == ("incremental", "vector", "reference")


@pytest.mark.parametrize("engine", ["nope", "vector_jax"])
def test_make_sim_rejects_unknown_engine(engine):
    """An unknown name, or a retired engine's in an old config, fails loudly."""
    with pytest.raises(ValueError, match="unknown engine"):
        make_sim(SimConfig(engine=engine))


def test_giga_burst_config_shape():
    """Fast sanity: the giga tier is 100× the paper's §4.2 burst."""
    from repro.sim import giga_burst_config

    cfg = giga_burst_config()
    assert cfg.n_vms == 100_000
    assert cfg.total_containers() == 1_000_000
    assert cfg.stagger_s > 0  # burst train, not one instant
    assert cfg.wave.engine == "vector"
    assert cfg.wave.record_trace is False
    assert cfg.max_functions_per_vm >= cfg.n_functions


# ----------------------------------------------------------------------
# Mid-flight mutation paths
# ----------------------------------------------------------------------
def test_set_parent_mid_flight_matches_incremental():
    results = []
    for cls in (FlowSim, VectorFlowSim, ReferenceFlowSim):
        sim = cls(SimConfig(registry_out_cap=5e6))
        [p] = sim.add_plan(
            DistributionPlan(
                flows=[Flow(REGISTRY, "A", "img", 200_000_000)], streaming=False
            )
        )
        [c] = sim.add_plan(
            DistributionPlan(
                flows=[Flow("A", "B", "img", 125_000_000)], streaming=False
            )
        )
        sim.run(until=0.1)  # both flows start, uncapped
        sim.set_parent(c, p)  # the TraceReplay mid-flight attach path
        sim.run()
        results.append(c.t_done)
    inc, vec, ref = results
    assert vec == inc  # bit-identical
    assert _close(vec, ref)
    assert vec > 20.0  # capped at the parent's 5 MB/s


def test_slow_vm_injected_mid_run_matches():
    """set_slow_vm / clear_slow_vm while flows are live re-rates identically."""
    ft = FunctionTree("f")
    for i in range(15):
        ft.insert(f"vm{i}")
    plan = faasnet_plan(ft, image_bytes=int(200 * MB), startup_fraction=0.2)
    times = {}
    for name, cls in (("inc", FlowSim), ("vec", VectorFlowSim)):
        sim = cls(_wave_simconfig())
        sim.add_plan(plan)
        sim.run(until=1.0)
        sim.set_slow_vm("vm0", 1 * MB)
        sim.run(until=2.0)
        sim.clear_slow_vm("vm0")
        sim.run()
        times[name] = (sim.now, sim.completion_times(), sim.trace)
    assert times["inc"] == times["vec"]


# ----------------------------------------------------------------------
# Satellite: _done_heap compaction under repeated re-rating
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cls", [FlowSim, VectorFlowSim])
def test_done_heap_stays_bounded_under_rerating(cls):
    """Churny rate flapping must not grow the completion heap unboundedly.

    Every re-rate pushes a fresh ``(t, fid, epoch)`` entry; before the
    compaction fix the stale ones survived until they surfaced at the heap
    head, so N re-rates of K flows held O(N*K) entries live.  Now the heap
    is compacted once stale entries exceed ~4x the live flows.
    """
    sim = cls(SimConfig())
    plan = baseline_plan([f"vm{i}" for i in range(32)], image_bytes=10**12)
    sim.add_plan(plan)
    sim.run(until=0.1)  # everything started, far from completion
    n_active = sum(1 for f in sim._flows if f.started and not f.done)
    assert n_active == 32
    for k in range(200):
        # flap the shared source: every flow re-rates twice per iteration
        sim.set_slow_vm("vm0", (1 + k % 7) * MB)
        sim.run(until=0.1 + (k + 1) * 1e-6)
    sim.clear_slow_vm("vm0")
    bound = max(64, 4 * n_active) + n_active  # one batch may land pre-compaction
    assert len(sim._done_heap) <= bound, (len(sim._done_heap), bound)
    sim.run()  # still terminates correctly
    assert all(f.done for f in sim._flows)


def test_done_heap_compaction_preserves_results():
    """Same flapping scenario: compacting engines agree with the reference."""
    plan = baseline_plan([f"vm{i}" for i in range(8)], image_bytes=int(50 * MB))
    ends = []
    for cls in (FlowSim, VectorFlowSim, ReferenceFlowSim):
        sim = cls(SimConfig())
        sim.add_plan(plan)
        for k in range(40):
            sim.run(until=0.01 * (k + 1))
            sim.set_slow_vm("vm0", (1 + k % 5) * 20 * MB)
        sim.clear_slow_vm("vm0")
        sim.run()
        ends.append((sim.now, sim.completion_times()))
    assert ends[0] == ends[1]  # incremental == vector, exact
    assert _close(ends[0][0], ends[2][0])


# ----------------------------------------------------------------------
# Event-queue internals: the bulk fold path
# ----------------------------------------------------------------------
def test_bulk_event_fold_matches_incremental():
    """>2048 scheduled starts exercise the sorted-snapshot fold path."""
    plan = baseline_plan([f"vm{i}" for i in range(2500)], image_bytes=int(5 * MB))
    out = []
    for cls in (FlowSim, VectorFlowSim):
        sim = cls(SimConfig())
        sim.add_plan(plan)
        sim.run()
        out.append((sim.now, sim.events_processed, sim.completion_times()))
    assert out[0] == out[1]


def test_interleaved_add_plan_and_run():
    """Waves added between runs land in heap + snapshot; order must hold."""
    out = []
    for cls in (FlowSim, VectorFlowSim):
        sim = cls(_wave_simconfig())
        for wave in range(3):
            ft = FunctionTree(f"f{wave}")
            for i in range(10):
                ft.insert(f"w{wave}vm{i}")
            plan = faasnet_plan(
                ft,
                image_bytes=int(40 * MB),
                startup_fraction=0.2,
                piece=f"f{wave}",
            )
            sim.add_plan(plan, t0=0.5 * wave)
            sim.run(until=0.5 * wave + 0.25)
        sim.run()
        out.append((sim.now, sim.trace, sim.completion_times()))
    assert out[0] == out[1]


# ----------------------------------------------------------------------
# Randomized differential suite (seeded always; hypothesis when present)
# ----------------------------------------------------------------------
def _random_plan(rng: random.Random, n_nodes: int) -> DistributionPlan:
    nodes = [f"vm{i}" for i in range(n_nodes)]
    flows = []
    for i, n in enumerate(nodes):
        src = REGISTRY if i == 0 or rng.random() < 0.3 else nodes[rng.randrange(i)]
        flows.append(Flow(src, n, "img", rng.randrange(1_000_000, 50_000_000)))
    return DistributionPlan(
        flows=flows,
        control_latency={n: rng.random() * 0.05 for n in nodes},
        streaming=bool(rng.getrandbits(1)),
    )


def _churned_run(cls, plan, cfg, churn_script):
    """Run a plan with a deterministic mid-flight churn script applied."""
    sim = cls(cfg)
    sim.add_plan(plan)
    for t, vm, cap in churn_script:
        sim.run(until=t)
        if cap is None:
            sim.clear_slow_vm(vm)
        else:
            sim.set_slow_vm(vm, cap)
    sim.run()
    return sim


def test_random_plan_churn_four_way_fuzz():
    for seed in range(6):
        rng = random.Random(1000 + seed)
        plan = _random_plan(rng, 12)
        churn = []
        for k in range(rng.randrange(4)):
            vm = f"vm{rng.randrange(12)}"
            cap = None if rng.random() < 0.3 else rng.uniform(1, 40) * MB
            churn.append((0.2 + 0.3 * k, vm, cap))
        cfg = _wave_simconfig()
        inc = _churned_run(FlowSim, plan, cfg, churn)
        vec = _churned_run(VectorFlowSim, plan, cfg, churn)
        ref = _churned_run(ReferenceFlowSim, plan, cfg, churn)
        assert vec.trace == inc.trace, seed
        assert vec.completion_times() == inc.completion_times(), seed
        assert vec.peak_shard_egress == inc.peak_shard_egress, seed
        ct_v, ct_r = vec.completion_times(), ref.completion_times()
        assert set(ct_v) == set(ct_r), seed
        for k, v in ct_v.items():
            assert _close(v, ct_r[k]), (seed, k, v, ct_r[k])
        assert _close(vec.peak_registry_egress, ref.peak_registry_egress), seed


def test_dispatch_telemetry_fuzz_consistency():
    """Seeded fuzz: dispatch telemetry is internally consistent and
    consistent with event counts on every scenario.

    Invariants pinned (see ``VectorFlowSim._recompute``):
      * every counted recompute processed at least one front, every front
        at least one flow;
      * fronts never exceed ``legacy_levels`` — the per-depth sweeps the
        retired algorithm would have dispatched on the same closures (that
        inequality *is* the wide-front claim);
      * the width histogram buckets (keyed by ``width.bit_length()``)
        account for every front and bound the flow totals;
      * recomputes are driven by events and churn only.
    """
    for seed in range(8):
        rng = random.Random(4242 + seed)
        n_nodes = rng.randrange(8, 40)
        plan = _random_plan(rng, n_nodes)
        churn = []
        for k in range(rng.randrange(4)):
            vm = f"vm{rng.randrange(n_nodes)}"
            cap = None if rng.random() < 0.3 else rng.uniform(1, 40) * MB
            churn.append((0.2 + 0.3 * k, vm, cap))
        cutoff = rng.choice([0, 2, 64])
        with mock.patch.object(vector_engine, "SCALAR_CUTOFF", cutoff):
            vec = _churned_run(VectorFlowSim, plan, _wave_simconfig(), churn)
        s = vec.dispatch_stats
        fronts = s["fronts_scalar"] + s["fronts_vector"]
        flows = s["flows_scalar"] + s["flows_vector"]
        assert s["recompute_calls"] >= 1, seed
        assert fronts >= s["recompute_calls"], seed
        assert flows >= fronts, seed
        assert s["legacy_levels"] >= fronts, seed  # the wide-front claim
        hist = s["front_width_hist"]
        assert sum(hist.values()) == fronts, seed
        assert all(b >= 1 for b in hist), seed  # fronts are never empty
        lo = sum(c * (1 << (b - 1) if b > 1 else 1) for b, c in hist.items())
        hi = sum(c * ((1 << b) - 1) for b, c in hist.items())
        assert lo <= flows <= hi, (seed, lo, flows, hi)
        if cutoff == 0:
            assert s["fronts_scalar"] == 0, seed
        # recomputes fire only after event batches, plan adds, or churn ops
        assert (
            s["recompute_calls"] <= vec.events_processed + len(churn) + 2
        ), (seed, s["recompute_calls"], vec.events_processed)


def test_blocks_on_warm_cache_four_way(monkeypatch):
    """Blocks-on provisioning with a warm block cache: the engines agree.

    Block-granular flows exercise the QPS-throttle leg of the cap chain
    (`block_size * qps / n_out`), and a warm cache makes the plans sparse
    and irregular — the worst case for front batching.
    """
    from repro.core import BlockCache, faasnet_block_plan, shared_base_images

    imgs = shared_base_images(6, 2, image_bytes=int(48 * MB))
    results = {}
    for name, cls in (("inc", FlowSim), ("vec", VectorFlowSim)):
        for cutoff in (0, 64):
            if name == "inc" and cutoff == 0:
                continue  # the cutoff only exists in the vector engine
            monkeypatch.setattr(vector_engine, "SCALAR_CUTOFF", cutoff)
            sim = cls(SimConfig(record_trace=True))
            cache = BlockCache()
            cache.add_image("seed", imgs[0])  # warm: base layers resident
            runnable, done = {}, {}
            for i, img in enumerate(imgs):
                ft = FunctionTree(img.name)
                for v in (f"f{i}a", f"f{i}b", f"f{i}c"):
                    ft.insert(v)
                sim.add_plan(
                    faasnet_block_plan(ft, image=img, cache=cache),
                    t0=0.01 * i,
                    on_node_done=lambda vm, t, i=i: done.__setitem__(
                        (i, vm), max(done.get((i, vm), 0.0), t)
                    ),
                    on_node_runnable=lambda vm, t, i=i: runnable.setdefault(
                        (i, vm), t
                    ),
                )
            sim.run()
            results[(name, cutoff)] = (
                runnable,
                done,
                sim.now,
                sim.events_processed,
                sim.trace,
            )
    base = results[("inc", 64)]
    for key, got in results.items():
        assert got == base, key


if HAVE_HYPOTHESIS:

    @settings(
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        n_nodes=st.integers(min_value=2, max_value=16),
        n_churn=st.integers(min_value=0, max_value=3),
    )
    def test_hypothesis_three_way_equivalence(seed, n_nodes, n_churn):
        rng = random.Random(seed)
        plan = _random_plan(rng, n_nodes)
        churn = []
        for k in range(n_churn):
            vm = f"vm{rng.randrange(n_nodes)}"
            cap = None if rng.random() < 0.3 else rng.uniform(1, 40) * MB
            churn.append((0.15 + 0.25 * k, vm, cap))
        cfg = _wave_simconfig()
        inc = _churned_run(FlowSim, plan, cfg, churn)
        vec = _churned_run(VectorFlowSim, plan, cfg, churn)
        ref = _churned_run(ReferenceFlowSim, plan, cfg, churn)
        assert vec.trace == inc.trace
        assert vec.completion_times() == inc.completion_times()
        assert vec.peak_registry_egress == inc.peak_registry_egress
        assert vec.peak_shard_egress == inc.peak_shard_egress
        ct_v, ct_r = vec.completion_times(), ref.completion_times()
        assert set(ct_v) == set(ct_r)
        for k, v in ct_v.items():
            assert _close(v, ct_r[k])
