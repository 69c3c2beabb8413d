"""Fast simulation core: bit-exactness vs the reference engine.

The vectorized slot pipeline (pre-drawn Poisson arrivals, scalar channel
fast path, idle short-circuits) and the idle-slot fast-forward must leave
fixed-seed results *bit-identical* to the reference draw-per-slot engine —
same RNG stream, same event ordering, same float trajectories. These tests
pin that contract across all three schemes x {classic, batched} nodes, for
the single-cell and multi-cell simulators, plus the parallel-vs-serial
sweep equality.
"""

import math
import sys

import numpy as np
import pytest

from repro.batching import BatchedComputeNode
from repro.core.capacity import mean_over_seeds, network_sweep, sweep, sweep_generic
from repro.core.channel import ChannelConfig, UplinkChannel
from repro.core.latency_model import (
    GH200_NVL2,
    L4,
    LLAMA2_7B,
    LatencyModel,
    ModelService,
)
from repro.core.parallel import parallel_map
from repro.core.simulator import SCHEMES, SimConfig, SimResult, SlotEngine, simulate
from repro.network import NetSimConfig, SCENARIOS, simulate_network, three_cell_hetero

SVC = ModelService(GH200_NVL2.scaled(2), LLAMA2_7B)


def _job_key(j):
    return (
        j.uid, j.ue, j.cell, j.route, j.t_gen, j.bits, j.dropped,
        j.t_compute_arrival, j.t_complete, j.t_first_token,
    )


def assert_results_equal(a, b):
    """Exact SimResult equality, treating NaN == NaN (empty-window means)."""
    import dataclasses

    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, float) and math.isnan(va):
            assert isinstance(vb, float) and math.isnan(vb), f.name
        else:
            assert va == vb, (f.name, va, vb)


def assert_jobs_identical(jobs_a, jobs_b):
    """Full-timeline equality, NaN-aware (exact floats, exact ordering)."""
    assert len(jobs_a) == len(jobs_b)
    for a, b in zip(jobs_a, jobs_b):
        ka, kb = _job_key(a), _job_key(b)
        for va, vb in zip(ka, kb):
            if isinstance(va, float) and math.isnan(va):
                assert isinstance(vb, float) and math.isnan(vb), (ka, kb)
            else:
                assert va == vb, (ka, kb)


class TestSingleCellBitExact:
    @pytest.mark.parametrize("scheme", sorted(SCHEMES))
    def test_classic_node_all_schemes(self, scheme):
        cfg = SimConfig(n_ues=25, sim_time=5.0, seed=11)
        ref = simulate(SCHEMES[scheme], cfg, SVC, fast=False)
        fast = simulate(SCHEMES[scheme], cfg, SVC, fast=True)
        assert_results_equal(ref, fast)

    @pytest.mark.parametrize("scheme", sorted(SCHEMES))
    def test_batched_node_all_schemes(self, scheme):
        lm = LatencyModel(L4, LLAMA2_7B, fidelity="extended")
        sch = SCHEMES[scheme]

        def factory():
            return BatchedComputeNode(
                lm, max_batch=4, policy=sch.compute_policy,
                drop_infeasible=sch.drop_infeasible,
            )

        cfg = SimConfig(n_ues=12, sim_time=5.0, seed=3)
        ref = simulate(sch, cfg, node_factory=factory, fast=False)
        fast = simulate(sch, cfg, node_factory=factory, fast=True)
        assert_results_equal(ref, fast)

    def test_job_timelines_identical(self):
        """Beyond the aggregate SimResult: every job's full timeline."""
        cfg = SimConfig(n_ues=30, sim_time=4.0, seed=5)
        engines = {}
        for fast in (False, True):
            rng = np.random.default_rng(cfg.seed)
            from repro.core.scheduler import ComputeNode

            node = ComputeNode(SVC, policy="priority", drop_infeasible=True)
            eng = SlotEngine(
                cfg, rng, packet_priority=True,
                wireline=lambda job, t: 0.005, deliver=node.submit, fast=fast,
            )
            s = 0
            while s < eng.n_slots:
                if eng.can_skip():
                    nxt = eng.next_arrival_at_or_after(s)
                    if nxt > s:
                        eng.skip_slots(s, min(nxt, eng.n_slots))
                        s = nxt
                        continue
                node.run_until(eng.step(s))
                s += 1
            node.run_until(float("inf"))
            engines[fast] = eng
        assert_jobs_identical(engines[False].jobs, engines[True].jobs)


class TestSaturatedCellArrayMode:
    @pytest.mark.parametrize("scheme", ["icc", "disjoint_mec"])
    def test_busy_cell_crosses_into_array_mode(self, scheme):
        """Large prompts (rag-style 2k-token bursts) keep >scalar_cutoff UEs
        holding grants at once, so the channel must hop into (and back out
        of) native array mode — with the trajectory still bit-identical to
        the reference."""
        from repro.core.scheduler import ComputeNode

        cfg = SimConfig(n_ues=120, lam_per_ue=0.5, n_input=2048,
                        sim_time=1.5, seed=4,
                        channel=ChannelConfig(bytes_per_token=16.0))
        engines = {}
        for fast in (False, True):
            rng = np.random.default_rng(cfg.seed)
            node = ComputeNode(SVC, policy="priority", drop_infeasible=True)
            eng = SlotEngine(
                cfg, rng, packet_priority=(scheme == "icc"),
                wireline=lambda job, t: 0.005, deliver=node.submit, fast=fast,
            )
            s = 0
            while s < eng.n_slots:
                if eng.can_skip():
                    nxt = eng.next_arrival_at_or_after(s)
                    if nxt > s:
                        eng.skip_slots(s, min(nxt, eng.n_slots))
                        s = nxt
                        continue
                node.run_until(eng.step(s))
                s += 1
            node.run_until(float("inf"))
            engines[fast] = eng
        if scheme == "icc":
            # prioritized grants pile up grant holders under this load: the
            # fast engine must actually have exercised the array-mode hop
            # (FIFO shares grants with background and stays scalar here)
            assert engines[True].channel.array_mode_switches > 0
        assert_jobs_identical(engines[False].jobs, engines[True].jobs)


class TestNetworkBitExact:
    @pytest.mark.parametrize("policy", ["slack_aware", "least_loaded", "mec_only"])
    def test_policies(self, policy):
        cfg = NetSimConfig(topology=three_cell_hetero(), sim_time=2.5,
                           warmup=0.5, seed=9)
        ref = simulate_network(cfg, policy, fast=False)
        fast = simulate_network(cfg, policy, fast=True)
        assert_results_equal(ref.total, fast.total)
        for k in ref.per_cell:
            assert_results_equal(ref.per_cell[k], fast.per_cell[k])
        assert ref.route_share == fast.route_share

    def test_batched_fleet(self):
        cfg = NetSimConfig(topology=three_cell_hetero(), sim_time=2.5,
                           warmup=0.5, seed=2, node_kind="batched", max_batch=4)
        ref = simulate_network(cfg, "slack_aware", fast=False)
        fast = simulate_network(cfg, "slack_aware", fast=True)
        assert_results_equal(ref.total, fast.total)
        assert ref.route_share == fast.route_share


class TestIdleSlotFastForward:
    def test_sparse_arrivals_skip_and_match(self):
        """At sparse load the fast path must actually fast-forward, with job
        timelines identical to the reference stepped engine."""
        sc = SCENARIOS["rag_doc_qa"]
        cfg = SimConfig(
            n_ues=2, lam_per_ue=sc.lam_per_ue, n_input=sc.n_input,
            n_output=sc.n_output, b_total=sc.b_total, sim_time=6.0,
            warmup=0.5, seed=1,
            channel=ChannelConfig(bytes_per_token=sc.bytes_per_token),
        )
        lm = LatencyModel(L4, LLAMA2_7B, fidelity="extended")

        def factory():
            return BatchedComputeNode(lm, max_batch=4, policy="priority",
                                      drop_infeasible=True)

        ref = simulate(SCHEMES["icc"], cfg, node_factory=factory, fast=False)
        fast = simulate(SCHEMES["icc"], cfg, node_factory=factory, fast=True)
        assert_results_equal(ref, fast)

    def test_skip_counter_increments(self):
        from repro.core.scheduler import ComputeNode

        cfg = SimConfig(n_ues=1, lam_per_ue=0.2, sim_time=4.0, seed=0)
        rng = np.random.default_rng(cfg.seed)
        node = ComputeNode(SVC)
        eng = SlotEngine(cfg, rng, packet_priority=True,
                         wireline=lambda j, t: 0.005, deliver=node.submit)
        s = 0
        while s < eng.n_slots:
            if eng.can_skip():
                nxt = eng.next_arrival_at_or_after(s)
                if nxt > s:
                    eng.skip_slots(s, min(nxt, eng.n_slots))
                    s = nxt
                    continue
            node.run_until(eng.step(s))
            s += 1
        # a near-empty cell spends most slots idle: the jump must be real
        assert eng.slots_skipped > eng.n_slots // 2

    def test_fast_forward_disabled_still_matches(self):
        cfg = SimConfig(n_ues=2, lam_per_ue=0.3, sim_time=4.0, seed=6)
        results = {}
        for ff in (False, True):
            from repro.core.scheduler import ComputeNode

            rng = np.random.default_rng(cfg.seed)
            node = ComputeNode(SVC)
            eng = SlotEngine(cfg, rng, packet_priority=True,
                             wireline=lambda j, t: 0.005,
                             deliver=node.submit, fast_forward=ff)
            s = 0
            while s < eng.n_slots:
                if eng.can_skip():
                    nxt = eng.next_arrival_at_or_after(s)
                    if nxt > s:
                        eng.skip_slots(s, min(nxt, eng.n_slots))
                        s = nxt
                        continue
                node.run_until(eng.step(s))
                s += 1
            node.run_until(float("inf"))
            results[ff] = eng
        assert results[True].slots_skipped > 0
        assert results[False].slots_skipped == 0
        assert_jobs_identical(results[False].jobs, results[True].jobs)


class TestChannelScalarVsArray:
    def test_state_trajectories_identical(self):
        """Drive two channels with the same RNG through both step APIs."""
        cfg = ChannelConfig()
        ch_ref = UplinkChannel(cfg, 10, np.random.default_rng(4))
        ch_fast = UplinkChannel(cfg, 10, np.random.default_rng(4))
        bits = 15 * cfg.bytes_per_token * 8.0
        now = 0.0
        for s in range(800):
            # identical rng state in both channels -> identical draws
            ch_ref.add_background(now)
            ch_fast.add_background(now)
            if s % 37 == 0:
                ch_ref.add_job_bits(s % 10, bits, now)
                ch_fast.add_job_bits(s % 10, bits, now)
            drained_ref = ch_ref.step(now, prioritize_jobs=(s % 2 == 0))
            drained_fast = ch_fast.step_drain(now, prioritize_jobs=(s % 2 == 0))
            dense = np.zeros(10)
            for ue, d in drained_fast:
                dense[ue] = d
            np.testing.assert_array_equal(drained_ref, dense)
            now += cfg.slot_s
        np.testing.assert_array_equal(ch_ref.job_bits, ch_fast.job_bits)
        np.testing.assert_array_equal(ch_ref.bg_bits, ch_fast.bg_bits)
        np.testing.assert_array_equal(ch_ref.job_granted, ch_fast.job_granted)
        np.testing.assert_array_equal(ch_ref.bg_granted, ch_fast.bg_granted)


def _sat_point(lam: float, seed_idx: int) -> SimResult:
    cfg = SimConfig(n_ues=max(1, int(round(lam))), sim_time=3.0,
                    seed=1000 * seed_idx)
    return simulate(SCHEMES["icc"], cfg, SVC)


def _jax_backend_inherited() -> bool:
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()


class TestParallelSweeps:
    @pytest.mark.parametrize(
        "kw", [{}, {"heartbeat_s": 5.0}, {"task_timeout_s": 60.0}],
        ids=["pooled", "monitored", "resilient"],
    )
    def test_accelerator_holder_does_not_fork(self, monkeypatch, kw):
        """A caller holding an accelerator (on a TPU host: the chip) gets
        workers started fresh, not forks that inherit its JAX runtime."""
        import jax

        import repro.core.parallel as par

        jax.devices()
        assert _jax_backend_inherited()
        assert not par._holds_accelerator()  # the CPU backend holds no chip
        monkeypatch.setattr(par, "_holds_accelerator", lambda: True)
        got = parallel_map(_jax_backend_inherited, [()] * 2, workers=2, **kw)
        assert got == [False, False]

    def test_parallel_equals_serial_generic(self):
        rates = [5.0, 20.0]
        serial = sweep_generic(rates, _sat_point, n_seeds=2, workers=0)
        parallel = sweep_generic(rates, _sat_point, n_seeds=2, workers=2)
        assert serial == parallel

    def test_parallel_equals_serial_sweep(self):
        rates = [5.0, 15.0]
        base = SimConfig(sim_time=3.0)
        serial = sweep(SCHEMES["icc"], base, rates, SVC, n_seeds=2, workers=0)
        parallel = sweep(SCHEMES["icc"], base, rates, SVC, n_seeds=2, workers=2)
        assert serial == parallel

    def test_parallel_equals_serial_network(self):
        rates = [30.0, 60.0]
        topo = three_cell_hetero()
        serial = network_sweep(topo, "slack_aware", rates, sim_time=2.0,
                               warmup=0.5, n_seeds=2, workers=0)
        parallel = network_sweep(topo, "slack_aware", rates, sim_time=2.0,
                                 warmup=0.5, n_seeds=2, workers=2)
        assert serial == parallel

    def test_mean_over_seeds_optional_fields(self):
        a = SimResult("x", 10, 1.0, 0.0, 1.0, 2.0, 3.0, 4.0,
                      p95_e2e=0.5, avg_ttft=None)
        b = SimResult("x", 20, 0.5, 0.1, 2.0, 3.0, 4.0, 5.0,
                      p95_e2e=None, avg_ttft=0.2)
        m = mean_over_seeds([a, b])
        assert m.scheme == "x" and m.n_jobs == 30
        assert m.satisfaction == pytest.approx(0.75)
        assert m.p95_e2e == pytest.approx(0.5)  # only seed a produced it
        assert m.avg_ttft == pytest.approx(0.2)  # only seed b produced it


class TestBatchedAwarePrediction:
    def test_in_transit_amortized_on_batched_fleet(self):
        """The old estimate charged a batched node the *serial* sum of its
        in-transit commitments plus a whole-job solo service; a node serving
        `max_batch` sequences per iteration absorbs that backlog
        concurrently, so slack_aware systematically over-estimated batched
        fleets and misrouted (ROADMAP item)."""
        from repro.network.fleet import build_fleet_node
        from repro.core.scheduler import Job

        fn = build_fleet_node("ran:x", "ran", "h100", node_kind="batched",
                              max_batch=8)
        job = Job(uid=0, ue=0, t_gen=0.0, n_input=15, n_output=15,
                  b_total=0.080)
        job.t_compute_arrival = 0.005
        for k in range(6):  # six jobs already routed here, still in transit
            j = Job(uid=10 + k, ue=0, t_gen=0.0, n_input=15, n_output=15,
                    b_total=0.080)
            fn.commit(j)
        assert fn.in_transit_s > 0
        naive = (
            max(fn.node.estimated_free_at(0.0) + fn.in_transit_s, 0.005)
            + fn.service_time(job)
        )
        pred = fn.predict_finish(job, t_arrival=0.005, now=0.0)
        assert pred < naive  # backlog amortized across the batch width

    def test_predicted_service_uses_iteration_model(self):
        """With residents in the batch, the own-service quote comes from
        the per-iteration latency model, not the solo whole-job latency."""
        import math as _math

        from repro.network.fleet import build_fleet_node
        from repro.core.scheduler import Job

        fn = build_fleet_node("ran:x", "ran", "h100", node_kind="batched",
                              max_batch=8)
        node = fn.node
        warm = Job(uid=1, ue=0, t_gen=0.0, n_input=15, n_output=500,
                   b_total=10.0)
        warm.t_compute_arrival = 0.0
        node.submit(warm)
        node.run_until(0.004)
        assert len(node._running) >= 1
        job = Job(uid=0, ue=0, t_gen=0.0, n_input=15, n_output=15,
                  b_total=0.080)
        iters = 15 + _math.ceil(15 / node.prefill_chunk)
        ctx = sum(r.context for r in node._running) + 15
        expected = iters * node.lm.iteration_latency(0, 2, ctx)
        assert node.predicted_service(job) == pytest.approx(expected)

    def test_classic_node_unchanged(self):
        from repro.network.fleet import build_fleet_node
        from repro.core.scheduler import Job

        fn = build_fleet_node("ran:y", "ran", "h100", node_kind="classic")
        job = Job(uid=0, ue=0, t_gen=0.0, n_input=15, n_output=15,
                  b_total=0.080)
        job.t_compute_arrival = 0.005
        finish = fn.predict_finish(job, t_arrival=0.005, now=0.0)
        assert finish == pytest.approx(
            max(fn.node.estimated_free_at(0.0), 0.005) + fn.service_time(job)
        )
